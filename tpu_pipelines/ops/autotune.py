"""Kernel autotuning: measured block configs + the flash/dense crossover.

``flash_attention``'s hard-coded ``block_q=128``/``block_k=128`` were
never tuned per shape or device, and ``attn_impl="auto"`` picked
flash-vs-dense on memory feasibility alone, never consulting a
measurement.  This module closes both gaps (what either is worth on the
chip is in PERF.md; until it is measured there, it is "not measured"):

  * per ``(op, shape-bucket, dtype, causal, device_kind)`` key, sweep a
    candidate grid of ``(block_q, block_k)`` configurations (constrained
    to TPU-valid tilings and L-divisibility; forward and backward tuned
    independently — their arithmetic-intensity profiles differ), time
    them with dispatch-overhead amortization (compile once, chain
    iterations through the device, one host read at the end), and
    persist the winner in an on-disk table;
  * per ``device_kind``, store the measured flash-vs-dense *crossover*
    sequence length, which upgrades ``attn_impl="auto"`` (see
    ``models/transformer.py choose_attn_impl``) from memory-fit-only to
    a measurement: dense below the crossover, flash at/above it, with
    ``dense_attn_fits`` demoted to the OOM guard it always really was.

Storage (multi-process safe — PR 7's ``atomic_write_json`` under a
``FileLock``, tolerant reads via ``load_json_tolerant``; keys via PR 6's
canonical ``fingerprint_json`` so two fresh processes derive the SAME key
for the same shape):

  * user cache:  ``<cache root>/autotune/<device_kind>.json`` (the root
    of ``utils/compile_cache.py``; ``TPP_AUTOTUNE_CACHE`` overrides the
    directory), written by sweeps;
  * committed table: ``tpu_pipelines/ops/autotune_table.json`` — winners
    promoted into the repo so fresh checkouts start tuned.  User-cache
    entries shadow committed ones.

``TPP_AUTOTUNE`` controls behavior:

  * ``cache-only`` (default) — consult the table, NEVER time anything.
    ``flash_attention`` is consulted at jit-trace time, and timing inside
    a trace would hang the trace on real work; cache-only makes the
    trace-time path a pure dict lookup.
  * ``sweep`` — on a table miss (and only outside a trace), run the sweep
    and persist the winner.
  * ``0`` / ``off`` — bypass the table entirely (hard-coded defaults).

Cache traffic is counted in the PR 5 metrics registry:
``autotune_cache_hits_total`` / ``autotune_cache_misses_total`` /
``autotune_sweeps_total`` (all labeled by op) and the
``autotune_sweep_latency_seconds`` histogram.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from tpu_pipelines.robustness.atomic import (
    FileLock,
    atomic_write_json,
    load_json_tolerant,
)
from tpu_pipelines.utils.compile_cache import cache_root
from tpu_pipelines.utils.fingerprint import fingerprint_json

ENV_MODE = "TPP_AUTOTUNE"
ENV_CACHE_DIR = "TPP_AUTOTUNE_CACHE"
ENV_BLOCKS = "TPP_AUTOTUNE_BLOCKS"      # "128x128,256x256" candidate override
ENV_ITERS = "TPP_AUTOTUNE_ITERS"

MODE_OFF = "off"
MODE_CACHE_ONLY = "cache-only"
MODE_SWEEP = "sweep"

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128

# Candidate block edges (before L-divisibility / tiling / VMEM filters).
# 64 is below one MXU tile but wins at short L where fewer, fuller grid
# steps beat pipeline depth; 512 amortizes per-block overhead at long L.
_CANDIDATE_EDGES = (64, 128, 256, 512)

# VMEM working-set budget for a candidate: the fwd kernel holds one Q
# block, one K and one V block, the [bq, bk] score tile and the f32
# accumulator/rowstat scratch.  16 MB/core on current TPUs; leave half
# for the compiler's own double-buffering.
_VMEM_BUDGET_BYTES = 8 * 1024 * 1024

_TABLE_VERSION = 1
_COMMITTED_TABLE = os.path.join(os.path.dirname(__file__), "autotune_table.json")

# Minimum second-to-last-dim tile per dtype (pallas_guide.md): f32 tiles
# (8, 128), bf16 (16, 128), int8/fp8 (32, 128).
_MIN_SUBLANE = {2: 16, 4: 8, 1: 32}


def _min_sublane(itemsize: int) -> int:
    return _MIN_SUBLANE.get(int(itemsize), 8)


# ------------------------------------------------------------------- keys


def autotune_mode() -> str:
    """Effective mode from ``TPP_AUTOTUNE`` (unset => cache-only)."""
    raw = os.environ.get(ENV_MODE, MODE_CACHE_ONLY).strip().lower()
    if raw in ("0", "off", "false", "none"):
        return MODE_OFF
    if raw == MODE_SWEEP:
        return MODE_SWEEP
    return MODE_CACHE_ONLY


def current_device_kind() -> str:
    """The accelerator kind tables are keyed by ("TPU v5 lite", "cpu"...)."""
    try:
        import jax

        return str(jax.devices()[0].device_kind)
    except Exception:
        return "unknown"


def _next_pow2(n: int) -> int:
    n = max(1, int(n))
    return 1 << (n - 1).bit_length()


def make_key(
    op: str,
    batch: int,
    heads: int,
    seq_len: int,
    head_dim: int,
    dtype: str,
    causal: bool,
    device_kind: Optional[str] = None,
) -> Dict[str, Any]:
    """Canonical lookup key for one tuned kernel instance.

    ``batch*heads`` is bucketed to the next power of two: it only sets the
    embarrassingly-parallel first grid dimension, so nearby sizes share a
    winner — while ``seq_len`` stays exact because block validity
    (L-divisibility) and the compute/bandwidth balance both hinge on it.
    """
    return {
        "op": str(op),
        "bh_bucket": _next_pow2(batch * heads),
        "seq_len": int(seq_len),
        "head_dim": int(head_dim),
        "dtype": str(dtype),
        "causal": bool(causal),
        "device_kind": device_kind or current_device_kind(),
    }


def key_id(key: Dict[str, Any]) -> str:
    """Process-stable table key — PR 6's canonical JSON encoding hashed,
    so two fresh interpreters derive byte-identical ids for one shape."""
    return fingerprint_json(key)[:16]


# ------------------------------------------------------------------ tables


def cache_dir() -> str:
    env = os.environ.get(ENV_CACHE_DIR)
    if env:
        return env
    return os.path.join(cache_root(), "autotune")


def cache_path(device_kind: Optional[str] = None) -> str:
    kind = (device_kind or current_device_kind()).replace(" ", "_")
    return os.path.join(cache_dir(), f"{kind}.json")


_table_memo: Dict[str, Tuple[Tuple[float, int], Dict[str, Any]]] = {}


def _load_table(path: str) -> Dict[str, Any]:
    """Tolerant, mtime-memoized table read ({} for absent/corrupt/torn —
    a damaged cache must never take down a training run)."""
    try:
        st = os.stat(path)
        stamp = (st.st_mtime, st.st_size)
    except OSError:
        return {}
    memo = _table_memo.get(path)
    if memo is not None and memo[0] == stamp:
        return memo[1]
    data = load_json_tolerant(path)
    if not isinstance(data, dict):
        data = {}
    _table_memo[path] = (stamp, data)
    return data


def clear_memo() -> None:
    """Drop in-process table memos (tests repoint the cache dir)."""
    _table_memo.clear()


def _lookup_entry(
    kid: str, device_kind: Optional[str] = None
) -> Optional[Dict[str, Any]]:
    """User cache first (freshly swept winners shadow the committed table),
    then the repo-committed table."""
    for path in (cache_path(device_kind), _COMMITTED_TABLE):
        entry = (_load_table(path).get("entries") or {}).get(kid)
        if isinstance(entry, dict):
            return entry
    return None


def _update_table(path: str, mutate) -> None:
    """Read-modify-write under the cross-process lock, atomically."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with FileLock(path + ".lock"):
        table = load_json_tolerant(path)
        if not isinstance(table, dict):
            table = {}
        table.setdefault("version", _TABLE_VERSION)
        table.setdefault("entries", {})
        table.setdefault("crossover", {})
        mutate(table)
        atomic_write_json(path, table)
    _table_memo.pop(path, None)


def record_entry(
    key: Dict[str, Any],
    block_q: int,
    block_k: int,
    ms: float,
    swept: Optional[Sequence[Dict[str, Any]]] = None,
    source: str = "sweep",
) -> str:
    """Persist one winner into the user cache; returns its table id."""
    kid = key_id(key)

    def mutate(table):
        table["entries"][kid] = {
            "key": key,
            "block_q": int(block_q),
            "block_k": int(block_k),
            "ms": round(float(ms), 4),
            "swept": list(swept or []),
            "source": source,
        }

    _update_table(cache_path(key.get("device_kind")), mutate)
    return kid


# -------------------------------------------------------------- crossover


def record_crossover(
    device_kind: str,
    crossover_seq_len: Optional[int],
    geometry: Optional[Dict[str, Any]] = None,
    source: str = "measured",
) -> None:
    """Store the measured flash-vs-dense crossover for one device kind.

    ``None`` means "dense won at every measured length where it fits" —
    recorded explicitly so ``auto`` can distinguish *measured-no-crossover*
    from *never measured*.
    """

    def mutate(table):
        table["crossover"][device_kind] = {
            "crossover_seq_len": (
                int(crossover_seq_len)
                if crossover_seq_len is not None else None
            ),
            "geometry": geometry or {},
            "source": source,
        }

    _update_table(cache_path(device_kind), mutate)


def lookup_crossover(device_kind: Optional[str] = None) -> Optional[int]:
    """Measured crossover seq length for this device, or None when no
    measurement exists (or dense won everywhere measured)."""
    kind = device_kind or current_device_kind()
    for path in (cache_path(kind), _COMMITTED_TABLE):
        rec = (_load_table(path).get("crossover") or {}).get(kind)
        if isinstance(rec, dict):
            v = rec.get("crossover_seq_len")
            return int(v) if v is not None else None
    return None


def record_decode_crossover(
    device_kind: str,
    crossover_kv_len: Optional[int],
    geometry: Optional[Dict[str, Any]] = None,
    source: str = "measured",
) -> None:
    """Store the measured flash-decode-vs-dense crossover CACHE length.

    The decode regime (single-query attention against the KV cache during
    autoregressive generation) is bandwidth-bound on streaming the cache,
    a different balance from the training shapes — so it carries its own
    crossover, consulted by ``models/transformer.py choose_decode_impl``
    (nothing records one since the leg that swept it went: ROADMAP D6).  ``None`` means "dense
    won at every measured cache length" (measured-no-crossover, distinct
    from never-measured)."""

    def mutate(table):
        table.setdefault("decode_crossover", {})[device_kind] = {
            "crossover_kv_len": (
                int(crossover_kv_len)
                if crossover_kv_len is not None else None
            ),
            "geometry": geometry or {},
            "source": source,
        }

    _update_table(cache_path(device_kind), mutate)


def lookup_decode_crossover(device_kind: Optional[str] = None) -> Optional[int]:
    """Measured decode-regime crossover KV length for this device, or
    None when no measurement exists (or dense won everywhere measured)."""
    kind = device_kind or current_device_kind()
    for path in (cache_path(kind), _COMMITTED_TABLE):
        rec = (_load_table(path).get("decode_crossover") or {}).get(kind)
        if isinstance(rec, dict):
            v = rec.get("crossover_kv_len")
            return int(v) if v is not None else None
    return None


def committed_crossovers() -> Dict[str, int]:
    """device_kind -> crossover from the REPO-COMMITTED table only (what
    the TPP208 lint rule consults: reviewable, versioned evidence)."""
    out: Dict[str, int] = {}
    for kind, rec in (_load_table(_COMMITTED_TABLE).get("crossover") or {}).items():
        if isinstance(rec, dict) and rec.get("crossover_seq_len") is not None:
            out[str(kind)] = int(rec["crossover_seq_len"])
    return out


# ----------------------------------------------------------------- metrics


def _metrics():
    from tpu_pipelines.observability.metrics import default_registry

    reg = default_registry()
    return (
        reg.counter(
            "autotune_cache_hits_total",
            "Autotune table lookups answered from cache", ("op",),
        ),
        reg.counter(
            "autotune_cache_misses_total",
            "Autotune table lookups with no stored winner", ("op",),
        ),
        reg.counter(
            "autotune_sweeps_total",
            "Candidate-grid sweeps executed (timed on device)", ("op",),
        ),
        reg.histogram(
            "autotune_sweep_latency_seconds",
            "Wall-clock cost of one candidate-grid sweep", ("op",),
        ),
    )


# -------------------------------------------------------------- candidates


def valid_blocks(seq_len: int, itemsize: int) -> List[int]:
    """Block sizes a [seq_len] axis can tile into on TPU: must divide L
    (the kernels' grid is ``L // block``) and be a multiple of the dtype's
    minimum sublane tile — or be L itself (a single whole-axis block is
    always exactly the array's own shape)."""
    sub = _min_sublane(itemsize)
    out = [
        c for c in _CANDIDATE_EDGES
        if c <= seq_len and seq_len % c == 0 and c % sub == 0
    ]
    if seq_len not in out and seq_len <= max(_CANDIDATE_EDGES):
        out.append(seq_len)
    return sorted(set(out))


def clamp_block(
    seq_len: int, requested: int, itemsize: int, what: str = "block"
) -> int:
    """Largest valid block <= ``requested`` for this axis.

    ``flash_attention`` used to require ``L % block == 0`` implicitly (the
    grid was ``l // block``) and mis-tiled opaquely otherwise; this
    validates up front.  Raises with the valid choices listed when nothing
    <= ``requested`` works (rather than an inscrutable Mosaic error).
    """
    requested = int(requested)
    sub = _min_sublane(itemsize)
    best = 0
    for c in range(min(requested, seq_len), 0, -1):
        if seq_len % c == 0 and (c % sub == 0 or c == seq_len):
            best = c
            break
    if best <= 0:
        valid = sorted(
            {
                c for c in range(1, seq_len + 1)
                if seq_len % c == 0 and (c % sub == 0 or c == seq_len)
            }
        )
        raise ValueError(
            f"flash_attention: no valid {what} <= {requested} for "
            f"seq_len={seq_len} (blocks must divide the sequence and tile "
            f"to a multiple of {sub} for this dtype; valid: {valid})"
        )
    return best


def candidate_pairs(
    seq_len: int, head_dim: int, itemsize: int
) -> List[Tuple[int, int]]:
    """(block_q, block_k) grid for one shape: TPU-valid, L-divisible, and
    within the VMEM working-set budget.  ``TPP_AUTOTUNE_BLOCKS`` (e.g.
    ``"128x128,256x128"``) overrides — tests and constrained sweeps."""
    env = os.environ.get(ENV_BLOCKS)
    if env:
        pairs = []
        for tok in env.split(","):
            tok = tok.strip().lower()
            if not tok:
                continue
            bq_s, _, bk_s = tok.partition("x")
            pairs.append((int(bq_s), int(bk_s or bq_s)))
        return pairs
    blocks = valid_blocks(seq_len, itemsize)
    out = []
    for bq in blocks:
        for bk in blocks:
            # fwd working set: Q + K + V blocks at itemsize, score tile +
            # accumulator + rowstats in f32.
            vmem = (
                (bq + 2 * bk) * head_dim * itemsize
                + (bq * bk + bq * head_dim + 2 * bq * 128) * 4
            )
            if vmem <= _VMEM_BUDGET_BYTES:
                out.append((bq, bk))
    return out or [(min(blocks), min(blocks))] if blocks else []


# ------------------------------------------------------------------ timing


def time_compiled(compiled, args, iters: int) -> float:
    """ms per call with dispatch overhead amortized: the compiled
    executable is warmed, then ``iters`` calls are chained by feeding the
    first output back in (same shape/dtype => executable reused), with ONE
    device->host read at the end proving every call executed."""
    import numpy as np

    out = compiled(*args)
    first = out[0] if isinstance(out, (tuple, list)) else out
    np.asarray(first).ravel()[:1]  # warm-up fence
    cur = list(args)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = compiled(*cur)
        first = out[0] if isinstance(out, (tuple, list)) else out
        if first.shape == cur[0].shape and first.dtype == cur[0].dtype:
            cur[0] = first
    np.asarray(first).ravel()[:1]
    return (time.perf_counter() - t0) / max(1, iters) * 1e3


def _sweep_iters() -> int:
    try:
        return max(1, int(os.environ.get(ENV_ITERS, "10")))
    except ValueError:
        return 10


def _best_of(
    op: str, swept: List[Dict[str, Any]], interpret: bool
) -> Optional[Dict[str, Any]]:
    """Fastest timed candidate.  One refused tiling is what a sweep is
    for; EVERY candidate refused by the device's compiler means the kernel
    does not build there, and that raises rather than falling to the
    untuned defaults (which would fail the same way, later and further
    from the cause)."""
    timed = [r for r in swept if "ms" in r]
    if timed:
        return min(timed, key=lambda r: r["ms"])
    if swept and not interpret:
        raise RuntimeError(
            f"autotune: no {op} candidate compiled on this device: "
            + "; ".join(
                f"{r['block_q']}x{r['block_k']}: {r.get('error')}"
                for r in swept
            )
        )
    return None


def sweep_flash(
    batch: int,
    heads: int,
    seq_len: int,
    head_dim: int,
    dtype: Any,
    causal: bool,
    interpret: bool,
    pairs: Optional[Sequence[Tuple[int, int]]] = None,
    iters: Optional[int] = None,
) -> Dict[str, Dict[str, Any]]:
    """Time every candidate (block_q, block_k) for the flash forward AND
    backward independently; returns ``{"flash_fwd": {...}, "flash_bwd":
    {...}}`` with the winner and the full swept grid in each.

    Forward and backward are tuned separately because their balance
    differs: the backward runs two extra matmuls per block and streams dO,
    so its best tile is routinely smaller than the forward's.
    """
    import importlib

    import jax
    import jax.numpy as jnp

    # sys.modules lookup: the package __init__ re-exports a same-named
    # function that shadows attribute-style module imports.
    fa = importlib.import_module("tpu_pipelines.ops.flash_attention")

    jdt = jnp.dtype(dtype)
    itemsize = jdt.itemsize
    if pairs is None:
        pairs = candidate_pairs(seq_len, head_dim, itemsize)
    iters = iters if iters is not None else _sweep_iters()

    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    shape = (batch, seq_len, heads, head_dim)
    q = jax.random.normal(kq, shape, jdt)
    k = jax.random.normal(kk, shape, jdt)
    v = jax.random.normal(kv, shape, jdt)

    def fwd_fn(bq, bk):
        def f(q, k, v):
            return fa.flash_attention(
                q, k, v, causal=causal, block_q=bq, block_k=bk,
                interpret=interpret,
            )
        return f

    def bwd_fn(bq, bk):
        def loss(q, k, v):
            # Fixed fwd blocks: only the bwd tiling varies across this leg.
            return fa.flash_attention(
                q, k, v, causal=causal,
                block_q=min(DEFAULT_BLOCK_Q, seq_len),
                block_k=min(DEFAULT_BLOCK_K, seq_len),
                bwd_block_q=bq, bwd_block_k=bk, interpret=interpret,
            ).astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))

    results: Dict[str, Dict[str, Any]] = {}
    for op, make in (("flash_fwd", fwd_fn), ("flash_bwd", bwd_fn)):
        swept = []
        for bq, bk in pairs:
            row: Dict[str, Any] = {"block_q": bq, "block_k": bk}
            try:
                compiled = jax.jit(make(bq, bk)).lower(q, k, v).compile()
                row["ms"] = round(time_compiled(compiled, (q, k, v), iters), 4)
            except Exception as e:  # invalid tiling for this backend
                row["error"] = str(e).splitlines()[0][:160]
            swept.append(row)
        results[op] = {"best": _best_of(op, swept, interpret), "swept": swept}
    return results


def sweep_decode(
    batch: int,
    heads: int,
    kv_len: int,
    head_dim: int,
    dtype: Any,
    interpret: bool,
    pairs: Optional[Sequence[Tuple[int, int]]] = None,
    iters: Optional[int] = None,
) -> Dict[str, Dict[str, Any]]:
    """Time candidate ``block_k`` values for the single-query flash-decode
    kernel (ops/flash_attention.py ``flash_decode_attention``); returns
    ``{"flash_decode": {best, swept}}``.

    ``block_q`` is not tuned — the one query row is replicated to the
    dtype's sublane tile, a constant — so the grid here is 1-D over
    ``block_k``: the knob that sets how the KV cache streams through
    VMEM, which is everything in the bandwidth-bound decode regime.
    """
    import importlib

    import jax
    import jax.numpy as jnp

    fa = importlib.import_module("tpu_pipelines.ops.flash_attention")

    jdt = jnp.dtype(dtype)
    itemsize = jdt.itemsize
    qrows = fa._DECODE_QROWS.get(int(itemsize), 8)
    if pairs is None:
        env = os.environ.get(ENV_BLOCKS)
        if env:
            pairs = [(qrows, bk) for _, bk in candidate_pairs(
                kv_len, head_dim, itemsize
            )]
        else:
            pairs = [(qrows, bk) for bk in valid_blocks(kv_len, itemsize)]
    iters = iters if iters is not None else _sweep_iters()

    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, (batch, 1, heads, head_dim), jdt)
    k = jax.random.normal(kk, (batch, kv_len, heads, head_dim), jdt)
    v = jax.random.normal(kv, (batch, kv_len, heads, head_dim), jdt)

    swept: List[Dict[str, Any]] = []
    for _, bk in pairs:
        row: Dict[str, Any] = {"block_q": qrows, "block_k": bk}
        try:
            def f(q, k, v, _bk=bk):
                return fa.flash_decode_attention(
                    q, k, v, block_k=_bk, interpret=interpret
                )

            compiled = jax.jit(f).lower(q, k, v).compile()
            row["ms"] = round(time_compiled(compiled, (q, k, v), iters), 4)
        except Exception as e:  # invalid tiling for this backend
            row["error"] = str(e).splitlines()[0][:160]
        swept.append(row)
    return {"flash_decode": {
        "best": _best_of("flash_decode", swept, interpret), "swept": swept,
    }}


# ---------------------------------------------------------------- dispatch


def get_block_config(
    op: str,
    batch: int,
    heads: int,
    seq_len: int,
    head_dim: int,
    dtype: Any,
    causal: bool,
    interpret: bool = False,
    allow_sweep: bool = True,
) -> Optional[Tuple[int, int]]:
    """The tuned (block_q, block_k) for one kernel instance, or None when
    the caller should fall back to its defaults.

    Consulted by ``flash_attention`` on first trace.  ``allow_sweep=False``
    (set under a jit trace) means a miss can never time anything — in
    sweep mode the sweep only runs from concrete (non-traced) call sites.
    """
    mode = autotune_mode()
    if mode == MODE_OFF:
        return None
    hits, misses, sweeps, latency = _metrics()
    key = make_key(
        op, batch, heads, seq_len, head_dim, str(dtype), causal
    )
    entry = _lookup_entry(key_id(key), key["device_kind"])
    if entry is not None:
        hits.labels(op).inc()
        return int(entry["block_q"]), int(entry["block_k"])
    misses.labels(op).inc()
    if mode != MODE_SWEEP or not allow_sweep:
        return None
    t0 = time.perf_counter()
    if op == "flash_decode":
        swept = sweep_decode(
            batch, heads, seq_len, head_dim, dtype, interpret
        )
    else:
        swept = sweep_flash(
            batch, heads, seq_len, head_dim, dtype, causal, interpret
        )
    elapsed = time.perf_counter() - t0
    out: Optional[Tuple[int, int]] = None
    for swept_op, res in swept.items():
        best = res.get("best")
        if best is None:
            continue
        swept_key = make_key(
            swept_op, batch, heads, seq_len, head_dim, str(dtype), causal
        )
        record_entry(
            swept_key, best["block_q"], best["block_k"], best["ms"],
            swept=res["swept"],
        )
        sweeps.labels(swept_op).inc()
        latency.labels(swept_op).observe(elapsed)
        if swept_op == op:
            out = (int(best["block_q"]), int(best["block_k"]))
    return out
