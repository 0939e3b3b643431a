"""Process-level JAX set-up: the virtual-CPU-device bootstrap shared by
tests/conftest.py and the driver's dryrun path
(__graft_entry__.dryrun_multichip), and the one decision about where XLA's
persistent compilation cache lives.
"""

import os
import re


def force_virtual_cpu_devices(n_devices):
    """Ensure jax will expose >= n_devices virtual CPU devices.

    Must be called before the first jax backend use (jax.devices() etc.):
    sets XLA_FLAGS for the device count and pins jax_platforms to cpu.
    Returns the exception raised by the platform flip, or None on success —
    callers can fold it into their own error messages.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%d" % n_devices
        ).strip()
    elif int(m.group(1)) < n_devices:
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), "--xla_force_host_platform_device_count=%d" % n_devices
        )

    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception as e:  # backend already initialized on another platform
        return e
    return None


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def place_compile_cache():
    """Decide where XLA's persistent compilation cache lives; called once,
    when the package is imported, and by nothing else. Where
    JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and no directory is
    set in code. Otherwise the cache is `<checkout>/.jax_cache`: a fixed path,
    never a temporary name, a pid or a time, because the directory is part of
    what a later process must repeat to hit. Returns the directory."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if d:
        return d
    import jax

    d = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    return d
