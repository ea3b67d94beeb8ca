"""Headline benchmark: GPT-2-small training throughput + MFU on one chip.

Prints ONE JSON line on stdout:
  {"metric", "value", "unit", "vs_baseline", "mfu", "platform",
   "device_kind", "device_count", ...}
All diagnostics go to stderr.

Runs in one process, on the chip JAX finds.  Where JAX finds no TPU it
exits non-zero and prints no result: a number from a CPU run is not a
measurement of this system.

Baseline: the reference's north-star is GPT-2 DDP samples/sec/chip on
A100+NCCL (BASELINE.json); a 124M-param GPT-2 at seq 1024 trains at roughly
18 samples/s/A100 under torch DDP in the reference's release setup
(release/air_tests/air_benchmarks/workloads/torch_benchmark.py equivalent).
vs_baseline = ours / 18.0 — >1.0 means we beat the per-chip baseline.
"""

from __future__ import annotations

import json
import os
import sys
import time

BASELINE_SAMPLES_PER_SEC_PER_CHIP = 18.0

# Peak bf16 FLOP/s per chip by TPU generation (public spec sheet numbers).
# Both marketing names (v5e) and JAX device_kind forms ("TPU v5 lite" ->
# "tpuv5lite") are keyed; longest match wins so "v5litepod" etc. resolve.
PEAK_FLOPS = {
    "v2": 45e12, "v3": 123e12, "v4": 275e12,
    "v5e": 197e12, "v5lite": 197e12,
    "v5p": 459e12,
    "v6e": 918e12, "v6lite": 918e12,
}


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _peak_flops(kind: str) -> float:
    flat = kind.lower().replace(" ", "")
    matches = [gen for gen in PEAK_FLOPS if gen in flat]
    if not matches:
        raise SystemExit(f"bench: no peak FLOP/s known for device kind "
                         f"{kind!r}; add it to PEAK_FLOPS with its source")
    return PEAK_FLOPS[max(matches, key=len)]


def main() -> None:
    from ray_tpu._private.jaxutil import place_compile_cache
    place_compile_cache(os.environ)

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.gpt import (GPTConfig, gpt_init, gpt_param_axes,
                                    make_train_step)
    from ray_tpu.parallel import LogicalAxisRules, MeshSpec
    from ray_tpu.parallel.sharding import shard_params

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found "
                         f"{devices[0].platform!r}")
    kind = devices[0].device_kind
    peak = _peak_flops(kind)
    batch, seq = 32, 1024
    batch = int(os.environ.get("RT_BENCH_BATCH", 0)) or batch
    # Flash attention (round-3 Pallas kernels with the real FA2 backward)
    # beats XLA dense at bench scale: 20.9 vs 28.8 ms fwd+bwd per attention
    # pass at B=32 S=1024 on v5e.  RT_BENCH_* envs let perf experiments
    # flip the knobs without editing the file.
    attn = os.environ.get("RT_BENCH_ATTN", "flash")
    remat = os.environ.get("RT_BENCH_REMAT", "1") == "1"
    # "dots" measured best on v5e at B=32 S=1024: 93.3 samples/s (MFU
    # 0.417) vs 91.4 full / 92.0 attn / 90.4 attn_dots; B=48+ OOMs, B=40
    # regresses (fragmentation), remat off OOMs at any useful batch.
    policy = os.environ.get("RT_BENCH_REMAT_POLICY", "dots")
    # Blocked CE head (r5): head matmul + CE per 256-token chunk, never
    # materializing [B,S,V].  RT_BENCH_CE_BLOCK=0 restores the full head.
    ce_block = int(os.environ.get("RT_BENCH_CE_BLOCK", 256))
    cfg = GPTConfig(max_seq_len=seq, attention=attn, remat=remat,
                    remat_policy=policy, ce_block=ce_block)

    n = len(devices)
    spec = MeshSpec.for_devices(n)
    mesh = spec.build()
    rules = LogicalAxisRules.for_transformer(spec)

    with jax.sharding.set_mesh(mesh):
        params = gpt_init(jax.random.PRNGKey(0), cfg)
        n_params = sum(int(p.size) for p in jax.tree.leaves(params))
        params = shard_params(params, mesh, rules, gpt_param_axes(cfg))
        # RT_BENCH_MU_DTYPE=bfloat16 stores the first moment in bf16
        # (halves its HBM traffic; v is kept f32 for numerics).
        mu_dtype = getattr(jnp, os.environ.get("RT_BENCH_MU_DTYPE", ""),
                           None)
        tx = optax.adamw(3e-4, b2=0.95, mu_dtype=mu_dtype)
        opt_state = tx.init(params)
        step = make_train_step(cfg, tx, rules)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size,
            jnp.int32)
        batch_dict = {"tokens": tokens}

        for _ in range(2):   # warmup / compile
            params, opt_state, m = step(params, opt_state, batch_dict)
        jax.block_until_ready(m["loss"])
        _log(f"bench: compiled; n_params={n_params / 1e6:.1f}M "
             f"platform={devices[0].platform} n={n}")

        iters = int(os.environ.get("RT_BENCH_ITERS", 0)) or 10
        t0 = time.perf_counter()
        for _ in range(iters):
            params, opt_state, m = step(params, opt_state, batch_dict)
        jax.block_until_ready(m["loss"])
        dt = time.perf_counter() - t0

    samples_per_sec = iters * batch / dt
    per_chip = samples_per_sec / n
    # Training FLOPs/token ≈ 6*N (fwd+bwd matmuls) + attention
    # 12*L*S*E (score + weighted-value matmuls, fwd+bwd).
    flops_per_token = (6.0 * n_params
                       + 12.0 * cfg.num_layers * seq * cfg.embed_dim)
    tokens_per_sec = samples_per_sec * seq
    result = {
        "metric": "gpt2_small_train_samples_per_sec_per_chip",
        "value": round(per_chip, 3),
        "unit": "samples/s/chip",
        "vs_baseline": round(per_chip / BASELINE_SAMPLES_PER_SEC_PER_CHIP, 3),
        "mfu": round(flops_per_token * tokens_per_sec / (n * peak), 4),
        "platform": devices[0].platform,
        "device_kind": kind,
        "device_count": n,
        "tokens_per_sec_per_chip": round(tokens_per_sec / n, 1),
    }
    if os.environ.get("RT_BENCH_LONGCTX", "1") == "1":
        result.update(_longctx_curve())
    if os.environ.get("RT_BENCH_LLAMA", "1") == "1":
        result.update(_llama_point(n, peak))
    print(json.dumps(result))


def _llama_point(n_chips: int, peak: float, B: int = 32, S: int = 1024,
                 iters: int = 8) -> dict:
    """Second model family on the same chip: LLaMA-125M-class (RoPE,
    RMSNorm, SwiGLU, GQA 12q/4kv) train samples/s + MFU."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.llama import (LlamaConfig, llama_init,
                                      llama_param_axes, make_train_step)
    from ray_tpu.parallel import LogicalAxisRules, MeshSpec
    from ray_tpu.parallel.sharding import shard_params

    cfg = LlamaConfig(max_seq_len=S, remat=True, remat_policy="dots",
                      attention="flash",
                      ce_block=int(os.environ.get("RT_BENCH_CE_BLOCK", 256)))
    spec = MeshSpec.for_devices(len(jax.devices()))
    mesh = spec.build()
    rules = LogicalAxisRules.for_transformer(spec)
    with jax.sharding.set_mesh(mesh):
        params = llama_init(jax.random.PRNGKey(0), cfg)
        n_params = sum(int(p.size) for p in jax.tree.leaves(params))
        params = shard_params(params, mesh, rules, llama_param_axes(cfg))
        tx = optax.adamw(3e-4, b2=0.95)
        opt_state = tx.init(params)
        step = make_train_step(cfg, tx, rules)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0,
                                    cfg.vocab_size, jnp.int32)
        batch = {"tokens": tokens}
        for _ in range(2):
            params, opt_state, m = step(params, opt_state, batch)
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        for _ in range(iters):
            params, opt_state, m = step(params, opt_state, batch)
        jax.block_until_ready(m["loss"])
        dt = time.perf_counter() - t0
    sps = iters * B / dt
    flops_per_token = (6.0 * n_params
                       + 12.0 * cfg.num_layers * S * cfg.embed_dim)
    return {
        "llama_samples_per_sec_per_chip": round(sps / n_chips, 3),
        "llama_mfu": round(flops_per_token * sps * S / (n_chips * peak),
                           4),
        "llama_n_params_m": round(n_params / 1e6, 1),
    }


def _longctx_one(S, B, N, H, iters) -> dict:
    """One curve point: flash / dense / ring fwd+bwd ms at [B, S, N, H]
    bf16, plus the cheapest variant.  A variant that cannot run at the
    point by a rule stated here (dense's score tensor against device
    memory, ring on one chip) is null; one that fails is an error.
    Timings are recorded into the autotune cache so a bench run doubles as
    a cache seed for the same shapes at train time."""
    import jax
    import jax.numpy as jnp
    import numpy as np_

    from ray_tpu.autotune import attention_key, get_cache
    from ray_tpu.autotune.dispatch import (VARIANT_OP,
                                           choose_variant_from_timings)
    from ray_tpu.ops.flash_attention import _dense_reference, flash_attention
    from ray_tpu.ops.ring_attention import make_ring_attention_fn
    from ray_tpu.parallel import MeshSpec

    rng = np_.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, N, H)), jnp.bfloat16)
               for _ in range(3))

    def timed(fn):
        f = jax.jit(jax.grad(
            lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))
        jax.block_until_ready(f(q, k, v))
        t0 = time.perf_counter()
        for _ in range(iters):
            r = f(q, k, v)
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / iters * 1e3

    timings = {"flash": timed(
        lambda q, k, v: flash_attention(q, k, v, True)),
        "dense": None, "ring": None}
    # Dense keeps the [B, N, S, S] scores in f32 and their probabilities
    # and gradients beside them: ~48 GB at S=32768.
    limit = jax.devices()[0].memory_stats()["bytes_limit"]
    if 3 * 4 * B * N * S * S < limit:
        timings["dense"] = timed(
            lambda q, k, v: _dense_reference(q, k, v, True, None))
    n = len(jax.devices())
    if n > 1 and S % n == 0:
        timings["ring"] = timed(
            make_ring_attention_fn(MeshSpec(sp=n).build()))

    variant = choose_variant_from_timings(timings)
    # seed the autotune cache: this measurement IS a tune result
    cache = get_cache()
    key = attention_key(B, S, N, H, "bfloat16", True)
    for name, op in (("flash", "flash_attention"),
                     ("dense", "dense_attention"),
                     ("ring", "ring_attention")):
        if timings[name] is not None:
            cache.put(op, key, {}, timings[name], meta={"source": "bench"})
    cache.put(VARIANT_OP, key, {"variant": variant}, timings[variant],
              meta={"timings": {k: (round(t, 3) if t else None)
                                for k, t in timings.items()},
                    "source": "bench"})
    out = {"seq": S, "batch": B, "variant": variant}
    for name, t in timings.items():
        out[f"{name}_ms"] = round(t, 2) if t is not None else None
    return out


def _longctx_curve(seqs=(4096, 8192, 16384, 32768), iters: int = 5) -> dict:
    """Long-sequence attention fwd+bwd CURVE: per-seq flash / dense / ring
    wall time and the cheapest variant from 4096 to 32768 at GPT-2-small's
    heads.  Emits ``longctx_curve`` plus the legacy single-point longctx_*
    keys (from the first point) so downstream result diffing keeps
    working."""
    N, H = 12, 64
    curve = [_longctx_one(S, max(1, 8192 // S), N, H,
                          iters if S < 16384 else max(1, iters // 2))
             for S in seqs]
    p0 = curve[0]
    out = {"longctx_curve": curve, "longctx_seq": p0["seq"],
           "longctx_flash_fwdbwd_ms": p0["flash_ms"]}
    if p0["dense_ms"] is not None:
        out["longctx_dense_fwdbwd_ms"] = p0["dense_ms"]
        out["longctx_flash_speedup"] = round(
            p0["dense_ms"] / p0["flash_ms"], 2)
    return out


if __name__ == "__main__":
    main()
