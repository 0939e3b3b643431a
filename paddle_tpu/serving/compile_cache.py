"""Persistent on-disk compile cache for the serving runtime.

A serving replica must cold-start in seconds, not re-pay one trace + XLA
compile per (model, bucket) variant on every boot. Two layers make that
true:

1. **Artifact cache** (this module's CompileCache, under `cache_dir` /
   FLAGS_serving_cache_dir): serialized `jax.export` artifacts keyed by
   (program fingerprint, feed avals, fetch names, jax/jaxlib version,
   backend platform, the Pallas kernels' revision). A hit skips the
   Python-side program lowering and StableHLO trace entirely — the replica
   deserializes and calls. The keys hold no path, so the directory may
   move.
2. **XLA executable cache**: JAX's own persistent compilation cache, in the
   one process-wide directory the environment places
   (platform_setup.place_compile_cache), so the StableHLO→executable
   compile of each deserialized artifact is also a disk hit on second boot.
   This module only zeroes its size/time thresholds.

Cache writes are atomic (tmp + os.replace) and keyed content-addressed, so
concurrent replicas sharing a cache directory race only to write identical
bytes. Hit/miss counts ride the PR 4 metric registry
(`serving/compile_cache/{hits,misses}`), which is how the tests assert
"zero compilations after warmup".

This module also owns the `export_compiled` artifact layout (an .npz holding
the serialized StableHLO plus parameters), folded in from inference.py so
the offline-export and serving paths share one format.
"""

import hashlib
import json
import os

import numpy as np

__all__ = [
    "CompileCache",
    "variant_key",
    "write_artifact",
    "read_artifact",
    "enable_xla_executable_cache",
]

ARTIFACT_SUFFIX = ".npz"


def _versions():
    import jax
    import jaxlib

    return jax.__version__, jaxlib.__version__, jax.default_backend()


def variant_key(fingerprint, feed_avals, fetch_names, state_avals=None,
                geometry=None):
    """Content key for one compiled serving variant.

    `feed_avals` is {name: (shape tuple, dtype str)} for the PADDED bucket
    shapes. The jax/jaxlib versions and backend platform are folded in
    because a serialized artifact is only replayable on a compatible stack —
    a version bump misses cleanly instead of deserializing garbage.

    Stateful (generation) variants must also pass `state_avals` — the
    decode-state dict's {name: (shape, dtype)}, i.e. the KV pool tensors —
    and `geometry`, the engine's page layout (page_size, pool_pages,
    max_slots, ...). Both change the compiled gather/scatter indexing
    without necessarily changing any feed shape, so leaving them out of the
    key would let a config flip replay a stale executable against a
    differently-shaped pool.

    Parameter VALUES are deliberately absent: the key hashes the program
    fingerprint and avals only. That asymmetry is the hot-swap contract
    (docs/online.md) — ServingEngine.set_params replaces param values with
    same-aval arrays, so every cached variant (and the in-process compiled
    set) stays valid across an online-learning swap; only a geometry/program
    change misses.

    `ops.pallas_kernels.KERNELS_REVISION` is folded in because none of the
    above sees a lowering: an exported module embeds the Mosaic kernels it
    was lowered with, so without it a cache filled before a kernel changed
    would replay the old kernel after the upgrade.
    """
    from ..ops import pallas_kernels

    jax_v, jaxlib_v, platform = _versions()
    doc = {
        "fingerprint": fingerprint,
        "feeds": sorted(
            (n, list(shape), str(dtype)) for n, (shape, dtype) in feed_avals.items()
        ),
        "fetches": list(fetch_names),
        "jax": jax_v,
        "jaxlib": jaxlib_v,
        "platform": platform,
        "kernels": pallas_kernels.KERNELS_REVISION,
    }
    if state_avals:
        doc["state"] = sorted(
            (n, list(shape), str(dtype))
            for n, (shape, dtype) in state_avals.items()
        )
    if geometry:
        doc["geometry"] = {k: geometry[k] for k in sorted(geometry)}
    return hashlib.sha256(json.dumps(doc, sort_keys=True).encode()).hexdigest()


def enable_xla_executable_cache():
    """Let JAX's persistent compilation cache keep every serving compile, so
    the StableHLO→executable compile of each deserialized artifact is a disk
    hit on later boots: the thresholds are zeroed because serving variants
    are small models whose compiles would otherwise fall under the default
    1s/min-size cutoffs. Where that cache lives is not decided here
    (platform_setup.place_compile_cache: JAX_COMPILATION_CACHE_DIR, else
    `<checkout>/.jax_cache`)."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _atomic_write_bytes(path, blob):
    tmp = "%s.tmp.%d" % (path, os.getpid())
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, path)


class CompileCache:
    """Keyed blob store for serialized jax.export artifacts.

    Layout: `<dir>/<key>.stablehlo` (the serialized artifact) plus
    `<key>.json` (human-readable meta: model name, feed avals, versions —
    never read back for correctness, the key IS the identity).
    """

    def __init__(self, cache_dir, enable_xla_cache=True):
        self.dir = cache_dir
        os.makedirs(cache_dir, exist_ok=True)
        if enable_xla_cache:
            enable_xla_executable_cache()
        from ..observability import registry as _registry

        reg = _registry.default_registry()
        self._hits = reg.counter(
            "serving/compile_cache/hits",
            "serving variants served from the persistent compile cache",
        )
        self._misses = reg.counter(
            "serving/compile_cache/misses",
            "serving variants traced+compiled because the cache had no entry",
        )

    def _path(self, key):
        return os.path.join(self.dir, key + ".stablehlo")

    def get(self, key):
        """Deserialized jax.export Exported for `key`, or None. Counts a
        hit/miss on the registry either way."""
        from jax import export as jax_export

        path = self._path(key)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            self._misses.inc()
            return None
        try:
            exported = jax_export.deserialize(blob)
        except Exception:
            # torn/incompatible entry: treat as a miss and let the caller
            # rebuild + overwrite it
            self._misses.inc()
            return None
        self._hits.inc()
        return exported

    def put(self, key, exported, meta=None):
        """Serialize + store atomically; concurrent writers of the same key
        write identical bytes, so last-rename-wins is safe."""
        _atomic_write_bytes(self._path(key), exported.serialize())
        doc = dict(meta or {})
        jax_v, jaxlib_v, platform = _versions()
        doc.update({"jax": jax_v, "jaxlib": jaxlib_v, "platform": platform})
        _atomic_write_bytes(
            os.path.join(self.dir, key + ".json"),
            json.dumps(doc, sort_keys=True, indent=1).encode(),
        )

    def get_or_build(self, key, build, meta=None):
        """(exported, hit). `build()` runs only on a miss; its result is
        stored before returning."""
        exported = self.get(key)
        if exported is not None:
            return exported, True
        exported = build()
        self.put(key, exported, meta=meta)
        return exported, False

    def stats(self):
        return {
            "hits": int(self._hits.value()),
            "misses": int(self._misses.value()),
        }


# ---------------------------------------------------------------------------
# export_compiled artifact layout (one .npz: StableHLO + parameters).
# Folded in from inference.py so the offline-export deliverable and the
# serving cache share one serializer.
# ---------------------------------------------------------------------------

def artifact_path(out_path):
    """The path np.savez actually writes for `out_path` (it appends `.npz`
    when missing — the export_compiled return-path bug this normalizes)."""
    return out_path if out_path.endswith(ARTIFACT_SUFFIX) else out_path + ARTIFACT_SUFFIX


def write_artifact(out_path, blob, feed_names, fetch_names, ro, mut):
    """Write one export_compiled artifact; returns the ACTUAL written path.

    bf16 parameters are stored as f32 with a dtype record (np.savez cannot
    serialize ml_dtypes arrays — the same constraint io._bf16_safe_save
    handles for checkpoints) and restored to bf16 by read_artifact so the
    deserialized computation sees the avals it was traced with."""
    from .. import io as _io

    path = artifact_path(out_path)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    params = {}
    param_dtypes = {}
    for prefix, group in (("ro:", ro), ("mut:", mut)):
        for k, v in group.items():
            arr, orig_dtype = _io._bf16_safe_save(v)
            params[prefix + k] = arr
            if orig_dtype:
                param_dtypes[prefix + k] = orig_dtype
    np.savez(
        path,
        __stablehlo__=np.frombuffer(blob, np.uint8),
        __feed_names__=np.array(list(feed_names)),
        __fetch_names__=np.array(list(fetch_names)),
        __param_dtypes__=np.array(json.dumps(param_dtypes)),
        **params,
    )
    return path


def read_artifact(path):
    """Inverse of write_artifact: {exported, feed_names, fetch_names, ro,
    mut} with parameters as jax arrays."""
    from jax import export as jax_export
    import jax.numpy as jnp

    data = np.load(artifact_path(path))
    dtypes = {}
    if "__param_dtypes__" in data.files:
        dtypes = json.loads(str(data["__param_dtypes__"]))

    def _param(k):
        arr = jnp.asarray(data[k])
        if dtypes.get(k) == "bfloat16":
            arr = arr.astype(jnp.bfloat16)
        return arr

    return {
        "exported": jax_export.deserialize(data["__stablehlo__"].tobytes()),
        "feed_names": [str(s) for s in data["__feed_names__"]],
        "fetch_names": [str(s) for s in data["__fetch_names__"]],
        "ro": {k[3:]: _param(k) for k in data.files if k.startswith("ro:")},
        "mut": {k[4:]: _param(k) for k in data.files if k.startswith("mut:")},
    }
