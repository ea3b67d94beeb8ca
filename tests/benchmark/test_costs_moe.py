"""The grouped matmuls' operations and bytes, and the readers built on
them, on made-up runs."""

import pytest

from benchmark import costs, costs_moe, moe_scopes, spec

OLMOE = spec.load_json("configs", "olmoe-1b-7b-0125-4l.json")


def test_grouped_matmuls_count_assignments_and_touched_experts():
    cost = costs_moe.grouped_matmuls(128, 56, 2048, 1024, 4)
    assert cost["flops"] == 128 * 6 * 2048 * 1024
    assert cost["bytes"] == 56 * 3 * 2048 * 1024 * 4
    assert costs_moe.grouped_matmuls(128, 56, 2048, 1024, 2)["bytes"] == \
        cost["bytes"] / 2
    assert (OLMOE["hidden_size"], OLMOE["intermediate_size"]) == (2048, 1024)


def test_a_decode_step_is_bound_by_bytes_and_a_prefill_by_operations():
    peaks = spec.peaks_for("TPU v5 lite")
    step = costs_moe.grouped_matmuls(16 * 8, 56, 2048, 1024, 4)
    assert costs.least_seconds(step, peaks) == \
        step["bytes"] / peaks["hbm_bytes_per_s"]
    prefill = costs_moe.grouped_matmuls(512 * 8 * 64, 64, 2048, 1024, 4)
    assert costs.least_seconds(prefill, peaks) == \
        prefill["flops"] / peaks["bf16_flops_per_s"]


@pytest.mark.parametrize("instruction,op_name,scope", [
    ("%fusion.3 = f32[16,64] fusion(...)",
     "jit(_decode)/while/body/closed_call/moe_router/dot_general",
     "moe_router"),
    ("%ragged-dot-none.2 = bf16[128,2048] custom-call(...)",
     "ragged-dot-none", "moe_experts"),
    ("%ragged-dot-metadata = (s32[65]) custom-call(...)",
     "ragged-dot-metadata", "moe_experts"),
    ("%convert.61 = bf16[4,64,2,2048,1024] convert(...)",
     "jit(_decode)/while/body/closed_call/moe_experts/convert_element_type",
     "moe_experts"),
    ("%gather.1 = bf16[128,2048] gather(...)",
     "jit(_decode)/while/body/closed_call/moe_combine/gather",
     "moe_combine"),
    ("%fusion.9 = bf16[16,2048] fusion(...)",
     "jit(_decode)/while/body/closed_call/paged_read/dot_general", None),
    ("%fusion.9 = bf16[16,2048] fusion(...)", "", None),
])
def test_scope_of(instruction, op_name, scope):
    assert moe_scopes.scope_of(instruction, op_name) == scope


def test_readers_find_nothing_in_a_run_without_a_trace():
    run = {"trace": {}, "cell": {"name": "x", "config": OLMOE},
           "peaks": spec.peaks_for("TPU v5 lite")}
    for name in ("moe_router_device_ms", "moe_dispatch_combine_device_ms",
                 "moe_experts_device_ms", "moe_experts_roofline",
                 "moe_experts_hit_share", "moe_load_max_over_mean"):
        assert spec.metric_reader(name)(run) is None


def test_routing_metrics_from_the_steps_regions(monkeypatch):
    """Two steps of 16 live tokens on 4 layers of 64 experts."""
    steps = [{"assignments": 512, "experts_hit": 224, "load_max": 16,
              "weight_itemsize": 4},
             {"assignments": 512, "experts_hit": 216, "load_max": 20,
              "weight_itemsize": 4}]
    monkeypatch.setattr(moe_scopes.host_regions, "rows",
                        lambda run, region: steps
                        if region == "engine.decode.moe" else None)
    monkeypatch.setattr(moe_scopes, "decode_scope_ms",
                        lambda run, scopes: 10.0)
    run = {"trace": {"programs": {}}, "cell": {"name": "x", "config": OLMOE},
           "peaks": spec.peaks_for("TPU v5 lite")}
    assert spec.metric_reader("moe_experts_hit_share")(run) == \
        pytest.approx(100 * 440 / (2 * 4 * 64))
    assert spec.metric_reader("moe_load_max_over_mean")(run) == \
        pytest.approx(36 * 64 / 1024)
    least = 220 * 3 * 2048 * 1024 * 4 / 819e9   # a step, by stored bytes
    assert spec.metric_reader("moe_experts_roofline")(run) == \
        pytest.approx(100 * least / 10e-3, rel=1e-3)


def test_routing_is_read_from_a_profile_of_the_engine(monkeypatch, tmp_path):
    """A tiny expert model decoding under the profiler: the regions the
    readers find say what the engine's always-on counters say."""
    import asyncio
    import glob
    import os

    import jax
    import jax.numpy as jnp
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    from benchmark import host_regions
    model = LlamaConfig(vocab_size=97, max_seq_len=32, num_layers=2,
                        num_heads=4, num_kv_heads=4, embed_dim=32,
                        mlp_dim=16, num_experts=8, experts_per_token=3,
                        qk_norm=True, dtype=jnp.float32, attention="dense",
                        remat=False)
    config = EngineConfig(model="llama", model_config=model, page_size=8,
                          num_pages=16, max_batch=4, max_prompt_len=16,
                          max_new_tokens=8)

    async def go():
        engine = InferenceEngine(config)

        async def consume(prompt, new):
            return [t async for t in engine.generate(prompt, new)]
        await consume([1, 2, 3], 2)           # compile outside the trace
        before = engine.stats()
        jax.profiler.start_trace(str(tmp_path))
        try:
            await asyncio.gather(consume([5, 17, 3, 88, 41], 6),
                                 consume([7, 8, 9], 4))
        finally:
            jax.profiler.stop_trace()
        after = engine.stats()
        engine.close()
        return before, after

    before, after = asyncio.run(go())
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    monkeypatch.setattr(host_regions, "profile",
                        lambda run: host_regions.read_profile(path))
    run = {"trace": {}, "cell": {"name": "x", "config": {
        "num_experts": 8, "num_hidden_layers": 2}}}
    routing = moe_scopes.decode_routing(run)
    prefills = host_regions.rows(run, "engine.prefill.moe")
    assert routing["steps"] == after["steps"] - before["steps"]
    assert len(prefills) == 2
    for key in ("assignments", "experts_hit", "load_max"):
        assert routing[key] + sum(p[key] for p in prefills) == \
            after["moe_" + key] - before["moe_" + key]
    assert routing["assignments"] == 3 * 2 * (5 + 3)   # k x L x tokens
    assert routing["weight_itemsize"] == 4             # f32, as stored
    share = spec.metric_reader("moe_experts_hit_share")(run)
    assert 100 * 3 / 8 <= share <= 100
    assert 1 <= spec.metric_reader("moe_load_max_over_mean")(run) <= 8 / 3
