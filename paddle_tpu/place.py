"""Places (reference paddle/fluid/platform/place.h:26-99 — CPUPlace,
CUDAPlace, CUDAPinnedPlace). The TPU build adds TPUPlace — SURVEY.md's north
star — and keeps CUDAPlace as a compatibility alias so unchanged fluid scripts
run on the chip."""

import jax

__all__ = ["CPUPlace", "CUDAPlace", "CUDAPinnedPlace", "TPUPlace", "is_compiled_with_cuda"]


class Place:
    def __eq__(self, other):
        return type(self) is type(other) and getattr(self, "device_id", 0) == getattr(
            other, "device_id", 0
        )

    def __hash__(self):
        return hash((type(self).__name__, getattr(self, "device_id", 0)))


class CPUPlace(Place):
    """"Wherever JAX runs by default": an Executor built on it pins nothing,
    so under the tests' JAX_PLATFORMS=cpu it is the host and on a machine
    with a chip it is the chip. Only host-side staging asks it for a
    device."""

    def jax_device(self):
        return jax.devices("cpu")[0]

    def __repr__(self):
        return "CPUPlace"


class TPUPlace(Place):
    """Chip `device_id` of this process's TPU backend — that chip or an
    error, never another device in its place."""

    def __init__(self, device_id=0):
        self.device_id = device_id

    def jax_device(self):
        try:
            devs = jax.devices("tpu")
        except RuntimeError as e:
            raise RuntimeError(
                "%r needs a TPU and JAX has none: the default backend is %r (%s)"
                % (self, jax.default_backend(), e)
            ) from e
        if not 0 <= self.device_id < len(devs):
            raise RuntimeError(
                "%r: this process sees %d TPU device(s)" % (self, len(devs))
            )
        return devs[self.device_id]

    def __repr__(self):
        return "TPUPlace(%d)" % self.device_id


class CUDAPlace(TPUPlace):
    """Compatibility alias: fluid scripts that say CUDAPlace(0) run on the
    TPU chip instead — the drop-in promise of BASELINE.json's north star."""

    def __repr__(self):
        return "CUDAPlace(%d)->TPU" % self.device_id


class CUDAPinnedPlace(CPUPlace):
    def __repr__(self):
        return "CUDAPinnedPlace"


def is_compiled_with_cuda():
    return False
