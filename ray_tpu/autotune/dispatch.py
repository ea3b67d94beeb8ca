"""Measured kernel-variant dispatch for attention.

``attention(q, k, v, ...)`` picks flash vs ring vs dense (vs splash when
the shape and jax build admit it) per shape from MEASURED timings, not
heuristics: ``tune_attention`` times every applicable variant (each with
its own tuned config) and persists the winner as an ``attention_variant``
record in the autotune cache; ``attention`` consults that record — via a
process-local L1 memo so the cache is touched once per shape — and runs
the winning kernel.

On a cache miss the behavior is configurable (``RT_AUTOTUNE_ON_MISS``):

* ``default`` (the default): fall back to the static heuristic the
  models used before the subsystem existed (flash when profitable,
  dense otherwise) — zero added latency, the miss is counted so the
  operator sees the cold cache in /api/metrics;
* ``inline``: tune on first use, under a budget
  (``RT_AUTOTUNE_BUDGET_S``, default 30 s per shape), then persist —
  the second process to hit the shape reads the first one's answer;
* offline: run ``scripts/autotune_sweep.py`` once per fleet and ship
  the cache file.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.autotune import metrics as _am
from ray_tpu.autotune.cache import (attention_key, backend_fingerprint,
                                    canon_dtype, get_cache)

VARIANT_OP = "attention_variant"

# Variant op-name in the cache, per selectable variant.
_VARIANT_OPS = {"flash": "flash_attention", "dense": "dense_attention",
                "ring": "ring_attention", "splash": "splash_attention"}

# L1 memo: (backend, key, allowed) -> chosen variant str or None (miss).
_MEMO: Dict[Tuple[str, str, tuple], Optional[str]] = {}
_memo_lock = threading.Lock()


def on_miss_mode() -> str:
    return os.environ.get("RT_AUTOTUNE_ON_MISS", "default").strip().lower()


def _budget_s() -> float:
    try:
        return float(os.environ.get("RT_AUTOTUNE_BUDGET_S", "30"))
    except ValueError:
        return 30.0


def clear_memo() -> None:
    """Test hook: drop the process-local variant memo."""
    with _memo_lock:
        _MEMO.clear()


# -------------------------------------------------------- applicability

def _flash_ok(S: int, interpret: bool) -> bool:
    from ray_tpu.autotune.search import valid_blocks
    if interpret:
        return S >= 2
    return bool(valid_blocks(S) or valid_blocks(S, (8, 16, 32, 64)))


def applicable_variants(kd: dict, interpret: bool,
                        mesh=None) -> List[str]:
    """Which variants can legally run at this shape/runtime.  Order is
    the tie-break preference (earlier wins on equal timings)."""
    from ray_tpu.autotune.search import splash_supported
    out = ["dense"]
    if _flash_ok(kd["S"], interpret):
        out.insert(0, "flash")
    if splash_supported(kd):
        out.insert(0, "splash")
    if mesh is not None and kd.get("causal", True) and _ring_ok(kd, mesh):
        out.append("ring")
    return out


def _ring_ok(kd: dict, mesh) -> bool:
    try:
        sp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("sp", 1)
    except Exception:
        return False
    return sp > 1 and kd["S"] % sp == 0


# --------------------------------------------------------------- choice

def choose_variant_from_timings(timings: Dict[str, Optional[float]],
                                allowed: Optional[Tuple[str, ...]] = None
                                ) -> Optional[str]:
    """Pure crossover policy: cheapest measured variant wins; variants
    that failed to run (None/inf) never win; ``allowed`` filters.  Used
    directly by tests with synthetic timings."""
    best, best_ms = None, float("inf")
    for v, ms in timings.items():
        if allowed is not None and v not in allowed:
            continue
        if ms is None or ms != ms or ms == float("inf"):
            continue
        if ms < best_ms:
            best, best_ms = v, ms
    return best


def _heuristic_variant(S: int, allowed: Tuple[str, ...]) -> str:
    """The pre-autotune static policy (mirrors models' _flash_profitable):
    flash once the sequence is long and lane-aligned, else dense."""
    import jax
    if ("flash" in allowed and S >= 1024 and S % 128 == 0
            and jax.default_backend() != "cpu"):
        return "flash"
    return "dense" if "dense" in allowed else allowed[0]


def choose(B: int, S: int, N: int, H: int, dtype: Any, causal: bool = True,
           allowed: Optional[Tuple[str, ...]] = None, mesh=None,
           interpret: Optional[bool] = None) -> Tuple[str, Optional[dict]]:
    """Pick the attention variant for a shape.

    Returns (variant, variant_record_or_None).  Consults the L1 memo,
    then the persistent cache's ``attention_variant`` record, then the
    on-miss policy."""
    import jax
    interp = (jax.default_backend() != "tpu") if interpret is None \
        else interpret
    kd = {"B": B, "S": S, "N": N, "H": H,
          "dtype": canon_dtype(dtype), "causal": bool(causal)}
    avail = applicable_variants(kd, interp, mesh=mesh)
    if allowed is not None:
        avail = [v for v in avail if v in allowed]
    if not avail:
        return "dense", None
    allowed_t = tuple(avail)
    key = attention_key(B, S, N, H, dtype, causal)
    backend = backend_fingerprint()
    memo_key = (backend, key, allowed_t)
    with _memo_lock:
        hit = _MEMO.get(memo_key, _MEMO)       # sentinel: _MEMO itself
    cache = get_cache()
    if hit is not _MEMO:
        if hit is not None:
            return hit, cache.lookup(VARIANT_OP, key, count=False)
    else:
        rec = cache.lookup(VARIANT_OP, key)
        variant = None
        if rec is not None:
            v = (rec.get("config") or {}).get("variant")
            if v in allowed_t:
                variant = v
        if variant is None and on_miss_mode() == "inline":
            rec = tune_attention(B, S, N, H, dtype, causal,
                                 variants=allowed_t, mesh=mesh,
                                 interpret=interp,
                                 budget_s=_budget_s())
            if rec is not None:
                v = (rec.get("config") or {}).get("variant")
                if v in allowed_t:
                    variant = v
        with _memo_lock:
            _MEMO[memo_key] = variant
        if variant is not None:
            return variant, rec
    # Miss (or memoized miss): inherit the pre-subsystem heuristic.
    return _heuristic_variant(S, allowed_t), None


# --------------------------------------------------------------- tuning

def tune_attention(B: int, S: int, N: int, H: int, dtype: Any,
                   causal: bool = True,
                   variants: Optional[Tuple[str, ...]] = None,
                   mesh=None, interpret: Optional[bool] = None,
                   budget_s: Optional[float] = None,
                   force: bool = False) -> Optional[dict]:
    """Time every applicable variant (tuning each variant's own config
    first) and persist the crossover winner as an ``attention_variant``
    record.  Returns the record, or None when nothing ran."""
    import time as _time

    from ray_tpu.autotune import search as _search
    import jax
    interp = (jax.default_backend() != "tpu") if interpret is None \
        else interpret
    key = attention_key(B, S, N, H, dtype, causal)
    kd = _search.parse_key(key)
    cache = get_cache()
    if not force:
        rec = cache.lookup(VARIANT_OP, key, count=False)
        if rec is not None:
            return rec
    avail = applicable_variants(kd, interp, mesh=mesh)
    if variants is not None:
        avail = [v for v in avail if v in variants]
    t0 = _time.perf_counter()
    timings: Dict[str, Optional[float]] = {}
    per_budget = None
    if budget_s is not None and avail:
        per_budget = budget_s / len(avail)
    context = {"mesh": mesh} if mesh is not None else None
    for v in avail:
        rec = _search.tune(_VARIANT_OPS[v], key, interpret=interp,
                           budget_s=per_budget, context=context,
                           force=force)
        timings[v] = rec.get("ms") if rec else None
    _am.bump("autotune_tune_ms", (_time.perf_counter() - t0) * 1e3)
    winner = choose_variant_from_timings(timings)
    if winner is None:
        return None
    return cache.put(VARIANT_OP, key, {"variant": winner},
                     timings[winner], meta={"timings": timings})


# ------------------------------------------------------------ execution

def make_splash_kernel(N: int, S: int, cfg: Optional[dict],
                       interpret: bool):
    """Build a causal splash-MHA callable over [N, S, H] (vmap it over
    batch; caller pre-scales q).  cfg carries the block knobs from the
    autotune sweep; None uses 128s (the minimum this jax build accepts)."""
    from jax.experimental.pallas.ops.tpu import splash_attention as spl
    cfg = cfg or {}
    fwd = int(cfg.get("block_q", 128))
    fkv = int(cfg.get("block_kv", fwd))
    bq = int(cfg.get("block_q_bwd", fwd))
    bkv = int(cfg.get("block_kv_bwd", fkv))
    sizes = spl.BlockSizes(
        block_q=fwd, block_kv=fkv, block_kv_compute=fkv,
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkv,
        block_q_dq=bq, block_kv_dq=bkv)
    mask = spl.MultiHeadMask(
        [spl.CausalMask((S, S)) for _ in range(N)])
    return spl.make_splash_mha(mask, head_shards=1, q_seq_shards=1,
                               block_sizes=sizes, interpret=interpret)


def _run_variant(variant: str, q, k, v, causal: bool, sm_scale, interp:
                 bool, layout: str, mesh, config: Optional[dict]):
    import jax
    import jax.numpy as jnp
    if variant == "flash":
        from ray_tpu.ops.flash_attention import flash_attention
        cfg = config or {}
        return flash_attention(q, k, v, causal,
                               cfg.get("block_q"), cfg.get("block_k"),
                               sm_scale, interp, layout)
    if variant == "ring":
        from ray_tpu.ops.ring_attention import ring_attention
        if layout == "bnsh":
            q, k, v = (x.swapaxes(1, 2) for x in (q, k, v))
        o = ring_attention(q, k, v, mesh)
        return o.swapaxes(1, 2) if layout == "bnsh" else o
    if variant == "splash":
        if layout != "bnsh":
            q, k, v = (x.swapaxes(1, 2) for x in (q, k, v))
        N, S, H = q.shape[1], q.shape[2], q.shape[3]
        scale = sm_scale if sm_scale is not None else H ** -0.5
        kern = make_splash_kernel(N, S, config, interp)
        o = jax.vmap(lambda q, k, v: kern(q * scale, k, v))(q, k, v)
        o = o.astype(q.dtype)
        return o if layout == "bnsh" else o.swapaxes(1, 2)
    from ray_tpu.ops.flash_attention import _dense_reference
    if layout == "bnsh":
        q, k, v = (x.swapaxes(1, 2) for x in (q, k, v))
    o = _dense_reference(q, k, v, causal, sm_scale)
    return o.swapaxes(1, 2) if layout == "bnsh" else o


def attention(q, k, v, causal: bool = True, sm_scale=None,
              variant: Optional[str] = None, mesh=None,
              interpret: Optional[bool] = None, layout: str = "bsnh"):
    """Dispatched multi-head attention.

    q, k, v: [B, S, N, H] ("bsnh", default) or [B, N, S, H] ("bnsh").
    ``variant`` forces a kernel ("flash"/"dense"/"ring"/"splash");
    None consults the autotune cache (measured crossover) with the
    on-miss policy.  ``mesh`` enables the ring variant (sequence
    sharded over its "sp" axis)."""
    import jax
    interp = (jax.default_backend() != "tpu") if interpret is None \
        else interpret
    if layout == "bnsh":
        B, N, S, H = q.shape
    else:
        B, S, N, H = q.shape
    if variant is None:
        variant, _rec = choose(B, S, N, H, q.dtype, causal, mesh=mesh,
                               interpret=interp)
    cfg = None
    if variant in ("flash", "splash"):
        rec = get_cache().lookup(_VARIANT_OPS[variant],
                                 attention_key(B, S, N, H, q.dtype,
                                               causal), count=False)
        cfg = rec.get("config") if rec else None
    return _run_variant(variant, q, k, v, causal, sm_scale, interp,
                        layout, mesh, cfg)


__all__ = ["attention", "choose", "tune_attention",
           "choose_variant_from_timings", "applicable_variants",
           "make_splash_kernel", "clear_memo", "on_miss_mode",
           "VARIANT_OP"]
