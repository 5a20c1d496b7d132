"""Ahead-of-time compiled serving executables with a cross-process cache.

The fleet's pre-ISSUE-14 bucket warmup *traced* the jitted predict once
per padded bucket at every swap — correct (no post-swap compile lands
mid-traffic) but the swap itself still paid the full XLA compile bill,
in-process, every time.  This layer replaces the warmup with real AOT:

    jax.jit(step).lower(abstract_params, abstract_bucket).compile()

once per padded bucket shape, serialized via
``jax.experimental.serialize_executable`` into an on-disk cache keyed by
the PR 6 canonical fingerprint of

    (payload content hash, bucket signature, serving dtype, device kind,
     endpoint, jax version)

so the NEXT process to swap in the same payload — a fleet restart, a
canary on another replica host, the Rewriter pre-warming at export time
— deserializes executables instead of compiling, and the PR 12
``compiles_after_warm == 0`` contract holds by construction: every
bucket shape traffic can pose is in the loaded model's
:class:`~tpu_pipelines.trainer.export.AotDispatch` table before the
version becomes eligible.

Knobs:

  TPP_AOT=0          disable the executable table AND the disk cache
                     (warmup degrades to the legacy once-per-bucket
                     trace — still no mid-traffic compiles)
  TPP_AOT_CACHE=dir  cache location (default ``<cache root>/aot``, the
                     root of utils/compile_cache.py)

Cache entries are written atomically (tmp + rename) and read
tolerantly: a torn/corrupt/version-skewed entry is a cache miss that
recompiles and rewrites, never an error.
"""

from __future__ import annotations

import logging
import os
import pickle
import tempfile
import time
from typing import Any, Dict, Optional

import numpy as np

from tpu_pipelines.utils.compile_cache import cache_root
from tpu_pipelines.utils.fingerprint import fingerprint_dir, fingerprint_json

log = logging.getLogger("tpu_pipelines.serving")

ENV_AOT = "TPP_AOT"
ENV_AOT_CACHE = "TPP_AOT_CACHE"

# Payload entries whose bytes define the compiled program (the Rewriter's
# `variants/` subtree and report json deliberately excluded: the root
# payload of a Rewriter artifact must key identically to the same bytes
# pushed as a bare version dir).
_PAYLOAD_ENTRIES = (
    "model_spec.json", "module_copy.py", "checkpoint", "transform_graph",
)


def aot_enabled() -> bool:
    return os.environ.get(ENV_AOT, "1").strip() != "0"


def cache_dir() -> str:
    return os.environ.get(ENV_AOT_CACHE, "").strip() or os.path.join(
        cache_root(), "aot"
    )


def payload_fingerprint(uri: str) -> str:
    """Content hash of the payload files that define the served program.

    Byte-identical payloads (a Pusher copy, a Rewriter hardlink) key
    identically across processes and hosts; the hash cost is one read of
    the checkpoint, paid once per swap."""
    import hashlib

    h = hashlib.sha256()
    for entry in _PAYLOAD_ENTRIES:
        path = os.path.join(uri, entry)
        if os.path.exists(path):
            h.update(entry.encode())
            h.update(fingerprint_dir(path).encode())
    return h.hexdigest()


def cache_key(
    payload_fp: str,
    bucket: int,
    dtype: str,
    device_kind: str,
    endpoint: str,
    signature: tuple,
) -> str:
    import jax

    return fingerprint_json({
        "payload": payload_fp,
        "bucket": int(bucket),
        "dtype": dtype,
        "device_kind": device_kind,
        "endpoint": endpoint,
        "signature": [list(map(str, entry)) for entry in signature],
        "jax": jax.__version__,
    })


def _cache_path(key: str) -> str:
    return os.path.join(cache_dir(), f"{key}.aotexe")


def _execution_devices(params: Any) -> Optional[list]:
    """The devices an executable lowered against ``params`` runs on, in
    assignment order — what ``deserialize_and_load`` must be told, or it
    loads for EVERY device of the backend and the first call on a
    multi-device host dies on a shard-count mismatch."""
    import jax

    for leaf in jax.tree_util.tree_leaves(params):
        sharding = getattr(leaf, "sharding", None)
        if sharding is None:
            continue
        mesh = getattr(sharding, "mesh", None)
        if mesh is not None:
            return list(mesh.devices.flat)
        return sorted(sharding.device_set, key=lambda d: d.id)
    return None


def _load_cached(path: str, devices: Optional[list]) -> Optional[Any]:
    """Deserialize a cached executable onto ``devices``; None on any
    failure (miss)."""
    if not os.path.exists(path):
        return None
    try:
        from jax.experimental import serialize_executable

        with open(path, "rb") as f:
            payload, in_tree, out_tree = pickle.load(f)
        return serialize_executable.deserialize_and_load(
            payload, in_tree, out_tree, execution_devices=devices
        )
    except Exception as e:  # noqa: BLE001 — torn/skewed entry = miss
        log.warning("aot: unreadable cache entry %s (%s)", path, e)
        return None


def _store_cached(path: str, compiled: Any) -> bool:
    """Serialize + atomically write an executable; False on any failure
    (serialization is platform-dependent — degrade to in-process AOT)."""
    try:
        from jax.experimental import serialize_executable

        payload, in_tree, out_tree = serialize_executable.serialize(
            compiled
        )
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fd, tmp = tempfile.mkstemp(
            dir=os.path.dirname(path), suffix=".tmp"
        )
        try:
            with os.fdopen(fd, "wb") as f:
                pickle.dump((payload, in_tree, out_tree), f)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return True
    except Exception as e:  # noqa: BLE001 — cache is an optimization
        log.warning("aot: could not persist executable to %s (%s)", path, e)
        return False


def _abstract_tree(tree: Any):
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype),
        tree,
    )


def _abstract_params(tree: Any):
    """Abstract params that PRESERVE each leaf's live sharding.

    An AOT executable is compiled for concrete input placements; lowering
    with bare shape/dtype assumes default single-device placement, and a
    payload whose restore produced committed/NamedSharding params (e.g.
    a checkpoint saved under a training mesh whose metadata could not be
    re-targeted) would then fail EVERY post-swap call with a sharding
    mismatch — the jit fallback path re-infers placement and hides the
    drift, the AOT path must bake it in."""
    import jax

    def leaf(x):
        sharding = getattr(x, "sharding", None)
        return jax.ShapeDtypeStruct(
            np.shape(x), np.asarray(x).dtype, sharding=sharding
        )

    return jax.tree.map(leaf, tree)


def _params_placement_token(tree: Any) -> str:
    """Stable digest of the params tree's shardings — part of the cache
    key, so an executable compiled for one placement/device set is never
    deserialized into another (where its baked-in shardings would refuse
    the live arrays)."""
    import jax

    return fingerprint_json({
        "device_count": jax.device_count(),
        "local_devices": [str(d) for d in jax.local_devices()],
        "shardings": [
            str(getattr(leaf, "sharding", None))
            for leaf in jax.tree_util.tree_leaves(tree)
        ],
    })


def warm_loaded(
    loaded: Any,
    batch: Dict[str, Any],
    max_batch_size: int,
    *,
    raw: bool = True,
    use_cache: Optional[bool] = None,
    devices: Optional[list] = None,
) -> Dict[str, Any]:
    """AOT-compile every padded bucket shape for a loaded payload.

    One lowered computation per bucket, compiled from the single device
    step the serving path dispatches (raw endpoint: host preprocess +
    fused transform-and-forward; transformed endpoint: the bare forward)
    — NOT one trace per (bucket, endpoint) through the whole predict
    closure.  Executables land in ``loaded.aot`` keyed by the exact
    padded batch signature the replica batchers will pose, and in the
    disk cache for the next process.

    ``devices``: the devices the payload will be asked to compute on (a
    fleet passes one per replica); every bucket is compiled — or loaded
    from the cache — for each, against the params copy resident there
    (``loaded.params_on``).  None = the payload's home device only.

    Stub payloads (tests) and disabled AOT degrade to the legacy
    once-per-bucket call through the predict path, so the no-mid-traffic-
    compile guarantee holds everywhere; only its cost model changes.

    Returns ``{"buckets", "compiled", "cache_hits", "seconds",
    "fallback_warm", "cached_to_disk", "load_failed"}``.
    """
    from tpu_pipelines.serving.batching import bucket_sizes

    t0 = time.monotonic()
    buckets = bucket_sizes(max_batch_size)
    row = {k: np.asarray(v)[:1] for k, v in batch.items()}
    endpoint = "raw" if raw else "transformed"
    dispatch = getattr(loaded, "aot", None)
    step = getattr(
        loaded, "device_step" if raw else "forward_step", None
    )
    stats = {
        "buckets": list(buckets), "compiled": 0, "cache_hits": 0,
        "fallback_warm": False, "cached_to_disk": 0, "load_failed": 0,
        "seconds": 0.0,
    }
    if (
        not aot_enabled()
        or dispatch is None
        or step is None
        or not hasattr(step, "lower")
    ):
        # Legacy warm: trace the predict path once per bucket (stubs,
        # TPP_AOT=0, hand-built payloads without the jit step handle).
        fn = loaded.predict if raw else loaded.predict_transformed
        for bucket in buckets:
            fn({k: np.repeat(v, bucket, axis=0) for k, v in row.items()})
        stats["fallback_warm"] = True
        stats["seconds"] = round(time.monotonic() - t0, 6)
        return stats

    import jax

    from tpu_pipelines.trainer.export import AotDispatch

    host = loaded.host_preprocess if raw else (lambda b: b)
    if host is None:
        host = lambda b: b  # noqa: E731
    uri = getattr(loaded, "uri", "") or ""
    cacheable = use_cache if use_cache is not None else bool(uri)
    payload_fp = payload_fingerprint(uri) if cacheable else ""
    device_kind = jax.devices()[0].device_kind
    dtype = getattr(loaded, "dtype", "float32")
    has_transform = getattr(loaded, "transform", None) is not None
    # Without a transform, raw and transformed dispatch the SAME
    # computation — one canonical cache key serves both, so a payload
    # prewarmed through either endpoint (Rewriter at export time, fleet
    # at swap time) hits the other's cache.
    key_endpoint = endpoint if has_transform else "step"
    params_on = getattr(loaded, "params_on", None)
    # Host preprocessing and the batch signature do not depend on the
    # device: once per bucket, not once per (device, bucket).
    posed = []
    for bucket in buckets:
        device_batch = host(
            {k: np.repeat(v, bucket, axis=0) for k, v in row.items()}
        )
        posed.append(
            (bucket, device_batch, AotDispatch.signature(device_batch))
        )

    for device in dict.fromkeys(devices or [None]):
        params = (
            loaded.params if device is None or params_on is None
            else params_on(device)
        )
        params_abs = _abstract_params(params)
        exec_devices = _execution_devices(params)
        placement_fp = (
            payload_fp + ":" + _params_placement_token(params)
            if cacheable else ""
        )
        for bucket, device_batch, sig in posed:
            exe = None
            path = ""
            if cacheable:
                key = cache_key(
                    placement_fp, bucket, dtype, device_kind, key_endpoint,
                    sig,
                )
                path = _cache_path(key)
                exe = _load_cached(path, exec_devices)
            if exe is not None:
                # A deserialized executable is proven by one real call
                # HERE, at the swap gate: an entry that loads but cannot
                # run (built for another device set) is a counted, logged
                # load failure and a recompile — never the first
                # request's surprise.
                try:
                    jax.block_until_ready(exe(params, device_batch))
                except Exception as e:  # noqa: BLE001 — any refusal
                    log.warning(
                        "aot: cache entry %s loaded but failed to run "
                        "(%s); recompiling", path, e,
                    )
                    stats["load_failed"] += 1
                    exe = None
            if exe is not None:
                stats["cache_hits"] += 1
            else:
                compiled = step.lower(
                    params_abs, _abstract_tree(device_batch)
                ).compile()
                stats["compiled"] += 1
                if cacheable and _store_cached(path, compiled):
                    stats["cached_to_disk"] += 1
                exe = compiled
            dispatch.install(endpoint, sig, exe, device)
            if not has_transform:
                # Without a transform both endpoints dispatch the same
                # computation — one lowering serves predict AND
                # predict_transformed.
                dispatch.install(
                    "transformed" if raw else "raw", sig, exe, device
                )
    stats["seconds"] = round(time.monotonic() - t0, 6)
    return stats
