"""Op registry + lowerings. Importing this package registers all ops."""

from . import registry
from . import core_ops  # noqa: F401 — registration side effects
from . import sequence_ops  # noqa: F401 — registration side effects
from . import parallel_ops  # noqa: F401 — registration side effects
from . import sparse_ops  # noqa: F401 — registration side effects (after core/parallel: attaches lookup grad makers)
from . import control_flow_ops  # noqa: F401 — registration side effects
from . import loss_ops  # noqa: F401 — registration side effects
from . import decode_ops  # noqa: F401 — registration side effects
from . import detection_ops  # noqa: F401 — registration side effects
from . import dist_ops  # noqa: F401 — registration side effects
from . import quant_ops  # noqa: F401 — registration side effects
from . import nn_extra_ops  # noqa: F401 — registration side effects
from . import compose_ops  # noqa: F401 — registration side effects
from . import frame_ops  # noqa: F401 — registration side effects
from . import pallas_kernels  # noqa: F401 — registration side effects
from . import generation_ops  # noqa: F401 — registration side effects
from . import decoder_ops  # noqa: F401 — registration side effects
from .registry import OPS, get, is_registered, register
