"""Serving benchmark: GPT-2 generation through a ray_tpu.serve replica.

Design analog: reference ``release/serve_tests/`` (serve throughput +
latency percentiles release jobs).  A single replica holds the model and a
jitted greedy-decode step; requests batch through ``@serve.batch``; the
driver fires concurrent requests via the DeploymentHandle router and
reports tokens/s plus p50/p99 end-to-end latency.

Where the cluster advertises a ``TPU`` the replica leases it and runs
GPT-2-small on the chip (the runtime keeps every other worker on CPU —
only one process may hold the chip); on a CPU-only cluster it runs the
tiny config under ``*_cpu_smoke`` metric names.

Emits JSON lines:
  {"metric": "serve_gpt2_tokens_per_sec", "value": ..., "p50_ms": ...,
   "p99_ms": ..., "vs_baseline": null}

A second phase benchmarks the STREAMING path (paged KV-cache continuous
batching through ``handle.remote_stream``): per-token timestamps give
p50 time-to-first-token and mean inter-token latency at 1, 4, and 16
concurrent sessions against one replica — the scaling curve shows
iteration-level batching absorbing concurrency (TTFT grows far slower
than linearly).  One JSON line per session count:
  {"metric": "serve_stream_...", "sessions": N, "ttft_p50_ms": ...,
   "inter_token_mean_ms": ..., "tokens_per_sec": ...}
"""

from __future__ import annotations

import os
import sys

# Runnable as `python release/<script>.py`: python puts the SCRIPT's dir
# on sys.path, not the repo root where ray_tpu lives.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json
import statistics
import time

from ray_tpu import serve as _serve_mod

# A replica on the chip leases it from the raylet like any other resource.
_TPU_REPLICA = {"resources": {"TPU": 1}}


class GPTGenerator:
    """Serve replica: jitted greedy decoder over a fixed-length prompt.

    Batched via serve.batch so concurrent HTTP/handle requests share one
    MXU dispatch (the TPU-first analog of the reference's
    @serve.batch-wrapped torch model replicas)."""

    PROMPT_LEN = 64
    GEN_TOKENS = 32
    MAX_BATCH = 8   # shared by the batch queue, pad buffer, and warmup

    @_serve_mod.batch(max_batch_size=MAX_BATCH, batch_wait_timeout_s=0.02)
    async def _batched(self, prompts):
        return self._decode_batch(prompts)

    def __init__(self, on_tpu: bool):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.gpt import GPTConfig, gpt_forward, gpt_init

        cfg = GPTConfig.gpt2_small() if on_tpu else GPTConfig.tiny()
        cfg = type(cfg)(**{**cfg.__dict__,
                           "max_seq_len": self.PROMPT_LEN
                           + self.GEN_TOKENS})
        self.cfg = cfg
        self.params = gpt_init(jax.random.PRNGKey(0), cfg)

        def gen(params, tokens):
            def body(toks, i):
                logits = gpt_forward(params, toks, cfg)
                pos = self.PROMPT_LEN - 1 + i
                nxt = jnp.argmax(logits[:, pos, :], axis=-1)
                toks = jax.lax.dynamic_update_slice_in_dim(
                    toks, nxt[:, None], pos + 1, axis=1)
                return toks, None

            toks, _ = jax.lax.scan(body, tokens,
                                   jnp.arange(self.GEN_TOKENS))
            return toks

        self._gen = jax.jit(gen)
        import numpy as np
        warm = np.zeros((self.MAX_BATCH,
                         self.PROMPT_LEN + self.GEN_TOKENS), np.int32)
        jax.block_until_ready(self._gen(self.params, warm))   # compile

    def _decode_batch(self, prompts):
        import numpy as np
        # Pad to the max batch size so every flush hits ONE compiled
        # shape (a fresh jit compile inside the timed loop would
        # dominate p99).
        toks = np.zeros((self.MAX_BATCH,
                         self.PROMPT_LEN + self.GEN_TOKENS), np.int32)
        for i, p in enumerate(prompts):
            ids = (p if isinstance(p, list)
                   else [ord(c) % 255 for c in str(p)])
            ids = ids[:self.PROMPT_LEN]
            toks[i, :len(ids)] = ids
        out = self._gen(self.params, toks)
        return np.asarray(out[:len(prompts), self.PROMPT_LEN:]).tolist()

    async def __call__(self, prompt):
        return await self._batched(prompt)


def _stream_session(handle, payload):
    """Consume one streamed generation, timestamping every token as its
    ref resolves.  Runs in a driver thread (stream_next blocks off-loop)."""
    import ray_tpu
    t0 = time.perf_counter()
    stamps = []
    for ref in handle.remote_stream(payload):
        ray_tpu.get(ref, timeout=600)
        stamps.append(time.perf_counter())
    return t0, stamps


def run_streaming_bench(on_tpu: bool) -> None:
    """Paged-KV continuous-batching streaming: p50 TTFT and inter-token
    latency at 1/4/16 concurrent sessions against ONE replica."""
    import concurrent.futures

    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.serve.engine import EngineConfig, LLMServer

    if on_tpu:
        mc = GPTConfig.gpt2_small()
        mc = type(mc)(**{**mc.__dict__, "max_seq_len": 128})
    else:
        mc = GPTConfig(vocab_size=97, max_seq_len=96, num_layers=2,
                       num_heads=4, embed_dim=32, dtype=jnp.float32,
                       attention="dense", remat=False)
    gen_tokens = 24
    ecfg = EngineConfig(model="gpt", model_config=mc, page_size=8,
                        num_pages=128, max_batch=16, max_prompt_len=32,
                        max_new_tokens=gen_tokens)
    dep = serve.deployment(
        name="llm_stream", max_concurrent_queries=32,
        ray_actor_options=_TPU_REPLICA if on_tpu else {},
    )(LLMServer)
    handle = serve.run(dep.bind(ecfg))
    payload = {"tokens": list(range(1, 17)), "max_new_tokens": gen_tokens}
    _stream_session(handle, payload)   # warmup: compiles prefill + decode

    metric = ("serve_stream" if on_tpu else "serve_stream_cpu_smoke")
    for sessions in (1, 4, 16):
        with concurrent.futures.ThreadPoolExecutor(sessions) as pool:
            t_wall = time.perf_counter()
            futs = [pool.submit(_stream_session, handle, payload)
                    for _ in range(sessions)]
            outs = [f.result(timeout=600) for f in futs]
            wall = time.perf_counter() - t_wall
        ttfts, gaps, n_tokens = [], [], 0
        for t0, stamps in outs:
            assert len(stamps) == gen_tokens, len(stamps)
            ttfts.append(stamps[0] - t0)
            gaps.extend(b - a for a, b in zip(stamps, stamps[1:]))
            n_tokens += len(stamps)
        # One metric name per session count so the release harness
        # (run_release_suite.py keys records by "metric") keeps the whole
        # scaling curve; "value" is tokens/s, the scaling signal.
        print(json.dumps({
            "metric": f"{metric}_{sessions}_sessions",
            "value": round(n_tokens / wall, 2),
            "unit": "tokens/s",
            "sessions": sessions,
            "ttft_p50_ms": round(
                statistics.median(sorted(ttfts)) * 1000, 1),
            "inter_token_mean_ms": round(
                statistics.mean(gaps) * 1000, 2) if gaps else None,
            "gen_tokens": gen_tokens,
            "vs_baseline": None,
        }), flush=True)


def main() -> None:
    import ray_tpu
    from ray_tpu import serve
    ray_tpu.init(num_cpus=4, log_level="ERROR")
    on_tpu = ray_tpu.cluster_resources().get("TPU", 0) >= 1 and \
        os.environ.get("RT_SERVE_BENCH_CPU") != "1"
    n_requests = int(os.environ.get("RT_SERVE_BENCH_REQUESTS",
                                    96 if on_tpu else 32))
    concurrency = 16
    try:
        dep = serve.deployment(
            name="gpt_gen",
            max_concurrent_queries=32,
            ray_actor_options=_TPU_REPLICA if on_tpu else {},
        )(GPTGenerator)
        handle = serve.run(dep.bind(on_tpu))

        prompt = list(range(GPTGenerator.PROMPT_LEN))
        # warmup through the full path
        ray_tpu.get(handle.remote(prompt), timeout=600)

        lat: list = []
        t0 = time.perf_counter()
        pending = []
        sent = 0
        while sent < n_requests or pending:
            while sent < n_requests and len(pending) < concurrency:
                pending.append((time.perf_counter(),
                                handle.remote(prompt)))
                sent += 1
            start, ref = pending.pop(0)
            ray_tpu.get(ref, timeout=600)
            lat.append(time.perf_counter() - start)
        wall = time.perf_counter() - t0

        toks = n_requests * GPTGenerator.GEN_TOKENS
        lat_sorted = sorted(lat)
        result = {
            "metric": ("serve_gpt2_tokens_per_sec" if on_tpu
                       else "serve_gpt2_cpu_smoke_tokens_per_sec"),
            "value": round(toks / wall, 2),
            "unit": "tokens/s",
            "requests_per_sec": round(n_requests / wall, 2),
            "p50_ms": round(
                statistics.median(lat_sorted) * 1000, 1),
            "p99_ms": round(   # nearest-rank p99
                lat_sorted[max(0, -(-99 * len(lat_sorted) // 100) - 1)]
                * 1000, 1),
            "n_requests": n_requests,
            "vs_baseline": None,
        }
        print(json.dumps(result), flush=True)

        # One chip, one holder: the batch replica must be gone before the
        # streaming replica can lease the TPU.
        serve.delete("gpt_gen")
        run_streaming_bench(on_tpu)
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()


if __name__ == "__main__":
    main()
