"""A tiny engine decoding two sequences under the JAX profiler: the trace
that ``test_tracing_regions.py`` checks region by region and that
``tests/benchmark/test_host_regions.py`` feeds to the benchmark's reader.
No cluster; one run a process, whoever asks first.

The second prompt arrives when the first sequence's first token does, so
the trace holds both orders of the engine's loop: the first decode step is
dispatched alone and, because somebody then waits for a prefill, fetched
with nothing behind it (a drain: the order every step had before the loop
ran ahead); from the second on each step is dispatched before the one
before it is fetched, until the last token drains the pipe again.  The
first sequence takes decode steps 1-5, the second steps 2-4."""

import asyncio
import functools
import glob
import os
import tempfile
import time

PROMPTS = ([5, 17, 3, 88, 41], [7, 8, 9])
NEW_TOKENS = (6, 4)
WARM_PROMPT, WARM_NEW = [1, 2, 3], 2
MAX_PROMPT_LEN, MAX_BATCH, PAGE = 16, 4, 8


@functools.lru_cache(maxsize=None)
def run() -> dict:
    """``{"path": the .xplane.pb, "stats": the engine's stats() at the
    end, "stats_before": those when the trace began (the engine idle after
    its warm-up), "tokens": what each sequence generated, "wall_s": the engine's
    lifetime}``.  The traced
    stretch begins with one forced full pass of the collector."""
    import gc

    import jax
    import jax.numpy as jnp
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    trace_dir = tempfile.mkdtemp(prefix="rt_engine_trace_")
    model = GPTConfig(vocab_size=97, max_seq_len=32, num_layers=2,
                      num_heads=4, embed_dim=32, dtype=jnp.float32,
                      attention="dense", remat=False)
    config = EngineConfig(model="gpt", model_config=model, page_size=PAGE,
                          num_pages=32, max_batch=MAX_BATCH,
                          max_prompt_len=MAX_PROMPT_LEN, max_new_tokens=8)

    async def go():
        born = time.perf_counter()
        engine = InferenceEngine(config)

        async def consume(prompt, new):
            return [t async for t in engine.generate(prompt, new)]

        # both programs compile outside the trace
        await consume(WARM_PROMPT, WARM_NEW)
        before = engine.stats()
        jax.profiler.start_trace(trace_dir)
        try:
            gc.collect()
            first = engine.generate(PROMPTS[0], NEW_TOKENS[0])
            head = await first.__anext__()
            second = asyncio.ensure_future(
                consume(PROMPTS[1], NEW_TOKENS[1]))
            tokens = [[head] + [t async for t in first], await second]
        finally:
            jax.profiler.stop_trace()
        stats = engine.stats()
        engine.close()
        return tokens, before, stats, time.perf_counter() - born

    tokens, before, stats, wall_s = asyncio.run(go())
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return {"path": path, "stats": stats, "stats_before": before,
            "tokens": tokens, "wall_s": wall_s}
