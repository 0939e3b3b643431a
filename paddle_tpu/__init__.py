"""paddle_tpu — a TPU-native deep-learning framework with the capabilities of
PaddlePaddle Fluid (reference: baobrian/Paddle, see SURVEY.md).

Define-then-run Program IR built by a fluid-compatible Python frontend, lowered
whole-block to XLA via JAX instead of per-op kernel dispatch; SPMD data
parallelism via jax.sharding instead of NCCL; Pallas kernels where XLA fusion
isn't enough.

`import paddle_tpu.fluid as fluid` is a drop-in for `import paddle.fluid`.
"""

from . import platform_setup

platform_setup.place_compile_cache()

from . import (  # noqa: E402
    backward,
    clip,
    dataset,
    framework,
    initializer,
    io,
    layers,
    metrics,
    nets,
    optimizer,
    param_attr,
    reader,
    regularizer,
    transpiler,
    unique_name,
)
from . import distributed  # noqa: F401
from . import observability  # noqa: F401
from . import resilience  # noqa: F401
from . import profiler  # noqa: F401
from . import imperative  # noqa: F401
from . import debugger  # noqa: F401
from . import average  # noqa: F401
from . import evaluator  # noqa: F401
from . import lod_tensor  # noqa: F401
from . import contrib  # noqa: F401
from . import inference  # noqa: F401
from . import serving  # noqa: F401
from . import embedding  # noqa: F401
from . import flags  # noqa: F401
from .flags import get_flags, set_flags
from .lod_tensor import create_lod_tensor, create_random_int_lodtensor
from . import native  # noqa: F401
from .batch import batch
from .data_feeder import DataFeeder
from .py_reader import EOFException
from .backward import append_backward
from .executor import Executor, Scope, global_scope, scope_guard
from .async_executor import AsyncExecutor
from .data_feed_desc import DataFeedDesc
from .framework import (
    Program,
    Variable,
    default_main_program,
    default_startup_program,
    device_guard,
    name_scope,
    program_guard,
)
from .parallel_executor import BuildStrategy, ExecutionStrategy, ParallelExecutor
from .param_attr import ParamAttr, WeightNormParamAttr
from .place import (
    CPUPlace,
    CUDAPinnedPlace,
    CUDAPlace,
    TPUPlace,
    is_compiled_with_cuda,
)

__version__ = "0.1.0"
