"""Block-config search engine: benchmark candidate configs for a
registered op under a warmup + best-of-N timing harness.

Ops register a candidate generator and a builder; the builder returns a
zero-arg callable that runs ONE timed step (fwd+bwd for training kernels)
and blocks until the device has finished before returning.  The harness
is interpret-mode-aware: on CPU the Pallas kernels run interpreted, so
candidate sets shrink to tiny blocks and one repeat, which keeps the
end-to-end tune testable in CI seconds while the same code path sweeps
the real grid on TPU.

Candidate pruning encodes the Mosaic tiling rules the kernels live
under: blocks divide S, blocks >= 8 sublanes (the TPU compiler rejects
sub-tile blocks), and the f32 probability tile block_q x block_k must
fit VMEM (~16 MB/core; we cap the tile at 8 MB to leave room for the
operand tiles and accumulators).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ray_tpu.autotune import metrics as _am
from ray_tpu.autotune.cache import (attention_key, backend_fingerprint,
                                    canon_dtype, get_cache, norm_batch)

# f32 probability-tile VMEM budget for a (block_q, block_k) pair.
_VMEM_TILE_BYTES = 8 * 1024 * 1024

# Sublane minimum: Mosaic rejects blocks under 8 rows on real TPU.
_MIN_BLOCK = 8


class OpSpec:
    def __init__(self, name: str,
                 candidates: Callable[[dict, bool], List[dict]],
                 build: Callable[..., Callable[[], Any]]):
        self.name = name
        self.candidates = candidates
        self.build = build


_OPS: Dict[str, OpSpec] = {}


def register_op(name: str, candidates, build) -> OpSpec:
    spec = OpSpec(name, candidates, build)
    _OPS[name] = spec
    return spec


def get_op(name: str) -> OpSpec:
    return _OPS[name]


def parse_key(key: str) -> dict:
    """Inverse of cache.attention_key: "B=2|S=4096|..." -> typed dict."""
    out: dict = {}
    for part in key.split("|"):
        k, v = part.split("=", 1)
        out[k] = v if k == "dtype" else int(v)
    out["causal"] = bool(out.get("causal", 1))
    return out


# ------------------------------------------------------------------ timing

def time_fn(fn: Callable[[], Any], iters: int = 3, repeats: int = 2,
            warmup: int = 1) -> float:
    """Best-of-``repeats`` mean wall-clock ms per call.  ``warmup`` calls
    absorb compilation; ``fn`` must synchronize internally."""
    for _ in range(max(1, warmup)):
        fn()
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        for _ in range(max(1, iters)):
            fn()
        best = min(best, (time.perf_counter() - t0) / max(1, iters))
    return best * 1e3


def _is_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    import jax
    return jax.default_backend() != "tpu"


def search_op(op: str, key: str, candidates: Optional[List[dict]] = None,
              interpret: Optional[bool] = None, budget_s: Optional[float]
              = None, iters: Optional[int] = None,
              context: Optional[dict] = None
              ) -> Tuple[Optional[dict], float, List[Tuple[dict, float]]]:
    """Benchmark every candidate config for ``op`` at ``key``.

    Returns (best_config, best_ms, [(config, ms), ...]).  A candidate
    that fails to build or run (compile rejection, OOM) costs itself,
    not the sweep.  ``budget_s`` stops the sweep once exceeded, provided
    at least one candidate finished."""
    spec = get_op(op)
    interp = _is_interpret(interpret)
    kd = parse_key(key)
    cands = candidates if candidates is not None else spec.candidates(
        kd, interp)
    if iters is None:
        iters = 1 if interp else 3
    results: List[Tuple[dict, float]] = []
    t_start = time.perf_counter()
    for cfg in cands:
        if (budget_s is not None and results
                and time.perf_counter() - t_start > budget_s):
            break
        try:
            fn = spec.build(kd, cfg, interpret=interp,
                            context=context or {})
            ms = time_fn(fn, iters=iters, repeats=1 if interp else 2)
        except Exception:
            continue
        results.append((cfg, ms))
    if not results:
        return None, float("inf"), results
    best_cfg, best_ms = min(results, key=lambda r: r[1])
    return best_cfg, best_ms, results


def tune(op: str, key: str, force: bool = False, **search_kw
         ) -> Optional[dict]:
    """Cache-aware tune: return the cached record for (op, backend, key)
    or run the sweep, persist the winner, and return the new record.
    Returns None when no candidate survived (op unsupported at this
    shape/backend)."""
    cache = get_cache()
    if not force:
        rec = cache.lookup(op, key)
        if rec is not None:
            return rec
    else:
        _am.bump("autotune_cache_misses")
    t0 = time.perf_counter()
    best_cfg, best_ms, results = search_op(op, key, **search_kw)
    _am.bump("autotune_tune_ms", (time.perf_counter() - t0) * 1e3)
    if best_cfg is None:
        return None
    meta = {"swept": len(results),
            "results": [[c, round(ms, 4)] for c, ms in results[:32]]}
    return cache.put(op, key, best_cfg, best_ms, meta=meta)


# --------------------------------------------------------- block helpers

def valid_blocks(S: int, values=(128, 256, 512, 1024)) -> List[int]:
    return [v for v in values if v <= S and S % v == 0 and v >= _MIN_BLOCK]


def suggest_blocks(S: int) -> Tuple[int, int, int]:
    """For an S no TPU-legal block divides, suggest the nearest padded
    sequence length and a block pair for it: (padded_S, block_q,
    block_k).  Used by the strict-mode divisibility error path."""
    pad = 128 if S > 16 else 8
    S_pad = ((int(S) + pad - 1) // pad) * pad
    cands = valid_blocks(S_pad) or [pad]
    b = max(cands)
    return S_pad, b, b


def flash_candidates(kd: dict, interpret: bool) -> List[dict]:
    """Pruned (block_q, block_k) sweep under the Mosaic rules."""
    S = kd["S"]
    if interpret:
        vals = [v for v in (8, 16, 32, 64, 128) if v <= S and S % v == 0]
        vals = vals[-2:] or [S]        # tiny CI shapes: 2 candidates max
    else:
        vals = valid_blocks(S)
        if not vals:
            vals = valid_blocks(S, (8, 16, 32, 64)) or [S]
    out = []
    for bq in vals:
        for bk in vals:
            if bq * bk * 4 > _VMEM_TILE_BYTES:
                continue
            out.append({"block_q": bq, "block_k": bk})
    return out


def _qkv_for(kd: dict, layout: str = "bsnh"):
    import jax.numpy as jnp
    import numpy as np
    B, S, N, H = kd["B"], kd["S"], kd["N"], kd["H"]
    dtype = jnp.dtype(kd["dtype"])
    shape = (B, N, S, H) if layout == "bnsh" else (B, S, N, H)
    rng = np.random.default_rng(0)
    return tuple(jnp.asarray(rng.standard_normal(shape), dtype)
                 for _ in range(3))


def _fwdbwd_timed(loss_fn, q, k, v):
    """Jitted grad-of-loss wrapped as a self-syncing zero-arg callable."""
    import jax
    f = jax.jit(jax.grad(loss_fn, argnums=(0, 1, 2)))

    def run():
        return jax.block_until_ready(f(q, k, v))
    return run


def flash_build(kd: dict, cfg: dict, interpret: bool, context: dict):
    import jax.numpy as jnp
    from ray_tpu.ops.flash_attention import flash_attention
    q, k, v = _qkv_for(kd)
    bq, bk = int(cfg["block_q"]), int(cfg["block_k"])
    causal = kd["causal"]

    def loss(q, k, v):
        return flash_attention(q, k, v, causal, bq, bk, None,
                               interpret).astype(jnp.float32).sum()
    return _fwdbwd_timed(loss, q, k, v)


def dense_build(kd: dict, cfg: dict, interpret: bool, context: dict):
    import jax.numpy as jnp
    from ray_tpu.ops.flash_attention import _dense_reference
    q, k, v = _qkv_for(kd)
    causal = kd["causal"]

    def loss(q, k, v):
        return _dense_reference(q, k, v, causal,
                                None).astype(jnp.float32).sum()
    return _fwdbwd_timed(loss, q, k, v)


def ring_build(kd: dict, cfg: dict, interpret: bool, context: dict):
    """Ring attention needs a mesh with an sp axis — supplied via
    ``context={"mesh": mesh}`` (mesh topology is runtime state, not part
    of the shape key; the backend fingerprint carries device count)."""
    import jax.numpy as jnp
    from ray_tpu.ops.ring_attention import ring_attention
    mesh = context.get("mesh")
    if mesh is None:
        raise ValueError("ring_attention tuning requires context['mesh']")
    if not kd["causal"]:
        raise ValueError("ring_attention is causal-only")
    q, k, v = _qkv_for(kd)

    def loss(q, k, v):
        return ring_attention(q, k, v, mesh).astype(jnp.float32).sum()
    return _fwdbwd_timed(loss, q, k, v)


def splash_supported(kd: dict) -> bool:
    """jax's splash kernels require head_dim and seq multiples of 128
    (this jax version), and blocks of 128."""
    try:
        from jax.experimental.pallas.ops.tpu import splash_attention  # noqa
    except Exception:
        return False
    return (kd["H"] % 128 == 0 and kd["S"] % 128 == 0
            and kd.get("causal", True))


def splash_candidates(kd: dict, interpret: bool) -> List[dict]:
    """The splash BlockSizes surface: eight knobs (fwd q/kv/kv_compute,
    dkv q/kv/kv_compute, dq q/kv), all multiples of 128.  Pruned: compute
    blocks ride their parent kv block, dkv/dq sweep jointly — the
    remaining grid is fwd x bwd block sizes."""
    if not splash_supported(kd):
        return []
    S = kd["S"]
    vals = [v for v in (128, 256, 512) if v <= S and S % v == 0]
    if interpret:
        vals = vals[:1]
    out = []
    for fwd in vals:
        for bwd in vals:
            out.append({"block_q": fwd, "block_kv": fwd,
                        "block_q_bwd": bwd, "block_kv_bwd": bwd})
    return out


def splash_build(kd: dict, cfg: dict, interpret: bool, context: dict):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.autotune.dispatch import make_splash_kernel
    kern = make_splash_kernel(kd["N"], kd["S"], cfg, interpret)
    q, k, v = _qkv_for(kd, layout="bnsh")
    scale = 1.0 / np.sqrt(kd["H"])

    def loss(q, k, v):
        out = jax.vmap(lambda q, k, v: kern(q * scale, k, v))(q, k, v)
        return out.astype(jnp.float32).sum()
    return _fwdbwd_timed(loss, q, k, v)


register_op("flash_attention", flash_candidates, flash_build)
register_op("dense_attention", lambda kd, interp: [{}], dense_build)
register_op("ring_attention", lambda kd, interp: [{}], ring_build)
register_op("splash_attention", splash_candidates, splash_build)


def tune_flash(B: int, S: int, N: int, H: int, dtype: Any = "bfloat16",
               causal: bool = True, candidates: Optional[List[dict]] = None,
               interpret: Optional[bool] = None, force: bool = False,
               budget_s: Optional[float] = None) -> Optional[dict]:
    """Convenience wrapper: tune flash block sizes for one shape and
    persist the winner.  Returns the cache record."""
    key = attention_key(B, S, N, H, canon_dtype(dtype), causal)
    return tune("flash_attention", key, force=force, candidates=candidates,
                interpret=interpret, budget_s=budget_s)


__all__ = ["register_op", "get_op", "search_op", "tune", "tune_flash",
           "time_fn", "suggest_blocks", "valid_blocks", "flash_candidates",
           "splash_candidates", "splash_supported", "parse_key",
           "attention_key", "backend_fingerprint", "norm_batch"]
