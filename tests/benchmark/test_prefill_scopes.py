"""``benchmark/prefill_scopes.py`` (own device time of named scopes inside
``jit__prefill`` programs, the engine's prefill regions) and the readers of
ISSUE 55 that stand on it (``benchmark/metrics/unlisted/``): on rows written
by hand, on nothing at all (the parent's trace, a CPU rehearsal), and on the
engine's own recorded trace."""

import os

import pytest

from benchmark import host_regions as hr
from benchmark import prefill_scopes, spec
from benchmark.tools import read_profile

CELL = "serve-lfm2-longprompt-wide"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("conv_op_prefill_ms", "conv_op_decode_ms", "moe_prefill_experts_ms",
       "moe_prefill_dispatch_combine_ms", "moe_prefill_roofline",
       "moe_prefill_load_max_over_mean", "prefill_mfu")
# what the issue names with ``.lfm2`` and an accepted reader reads by stem
BY_STEM = ("prefill_device_ms", "prefill_useful_share", "prefill_flash_share",
           "engine_queue_wait_ms", "decode_device_ms",
           "decode_batch_occupancy", "decode_ahead_share", "host_loop_cpu_ms",
           "host_loop_busy_share", "paged_kv_device_ms", "paged_kernel_share",
           "paged_read_roofline", "moe_experts_device_ms",
           "moe_routed_hit_share")
P, M = "rt:engine.prefill", "rt:engine.prefill.moe"


def run_of(trace="two prefills"):
    return {"cell": spec.load_cell(spec.load_benchmark(), CELL),
            "peaks": PEAKS,
            "trace": {"window_s": 5.0, "programs": {
                "jit__prefill": {"calls": 2, "device_s": 0.1},
                "jit__decode": {"calls": 10, "device_s": 0.15}}}
            if trace == "two prefills" else trace}


def regions(lengths=(1500, 4000), rungs=(2048, 4096)):
    """Two prefills and what each said of its eight expert layers."""
    out = []
    for i, (n, rung) in enumerate(zip(lengths, rungs)):
        out.append((P, i * 0.1, i * 0.1 + 0.05, {
            "prompt_len": n, "padded_len": rung, "attention": "flash",
            "waited_us": 1000}))
        out.append((M, i * 0.1 + 0.05, i * 0.1 + 0.05, {
            "assignments": n * 4 * 8, "assignments_made": n * 4 * 8,
            "experts_hit": 8 * 64, "load_max": 8 * n // 12,
            "weight_itemsize": 2}))
    return out


OPS = ((30e-3, "jit(_prefill)/conv_in/dot_general"),
       (4e-3, "jit(_prefill)/conv_mix/mul"),
       (6e-3, "jit(_prefill)/conv_out/dot_general"),
       (50e-3, "jit(_prefill)/moe_experts/grouped_matmul/pallas_call"),
       (7e-3, "jit(_prefill)/moe_dispatch/gather"),
       (3e-3, "jit(_prefill)/moe_combine/reduce"),
       (9e-3, "jit(_prefill)/flash_fwd/pallas_call"))


@pytest.fixture
def traced(monkeypatch):
    """A window of two prefills whose operations are ``OPS`` (own seconds by
    ``op_name``, as ``prefill_ops`` lists them) and ten decode steps."""
    from benchmark import decode_scopes, replica
    monkeypatch.setattr(hr, "profile", lambda run: {"regions": regions()})
    monkeypatch.setattr(replica, "find_xplane", lambda folder: "a.xplane.pb")
    monkeypatch.setattr(prefill_scopes, "prefill_ops", lambda path: OPS)
    monkeypatch.setattr(decode_scopes, "decode_ops", lambda path: (
        (20e-3, "jit(_decode)/conv_in/dot_general"),
        (5e-3, "jit(_decode)/conv_mix/mul"),
        (5e-3, "jit(_decode)/conv_out/dot_general"),
        (70e-3, "jit(_decode)/moe_experts/grouped_matmul/pallas_call")))
    return run_of()


def test_the_scopes_time_is_per_prefill_call(traced):
    read = {name: read_profile.reader(name + ".lfm2")(traced) for name in NEW}
    assert read["conv_op_prefill_ms"] == pytest.approx(40 / 2)
    assert read["conv_op_decode_ms"] == pytest.approx(30 / 10)
    assert read["moe_prefill_experts_ms"] == pytest.approx(50 / 2)
    assert read["moe_prefill_dispatch_combine_ms"] == pytest.approx(10 / 2)
    assert prefill_scopes.prefill_device_ms(traced) == pytest.approx(50.0)
    assert prefill_scopes.prefill_scope_ms(traced, ("no_such",)) is None
    # largest loads 8 x (1500 + 4000) / 12 over a mean of 4 / 64 a token
    assert read["moe_prefill_load_max_over_mean"] == pytest.approx(
        (8 * 1500 // 12 + 8 * 4000 // 12) * 64 / (5500 * 32))


def test_the_rooflines_count_the_real_positions(traced, monkeypatch):
    """The experts' share: each prefill's assignments at the peak (the
    larger side: the bytes of 512 touched experts of 18.9 MB at 1,500
    positions, 94 rows an expert, and the operations at 4,000, 250 rows, past
    the v5e's ridge of 240) over 25 ms a call; the whole prefill's: a mean
    prefill's model operations over 50 ms a call."""
    from benchmark import costs, costs_moe, costs_prefill
    family = spec.load_part("families", "lfm2_moe")
    config = traced["cell"]["config"]
    least = [costs.least_seconds(costs_moe.grouped_matmuls(
        n * 32, 512, 2048, 1536, 2), PEAKS) for n in (1500, 4000)]
    assert least[0] == 512 * 3 * 2048 * 1536 * 2 / 819e9     # the bytes
    assert least[1] == 4000 * 32 * 6 * 2048 * 1536 / 197e12  # the products
    got = read_profile.reader("moe_prefill_roofline.lfm2")(traced)
    assert got == pytest.approx(100 * (sum(least) / 2) / 25e-3)
    assert 0 < got < 100
    shape = family.prefill_shape(config)
    operations = (costs_prefill.model_operations(1500, 1500 * 32, **shape)
                  + costs_prefill.model_operations(4000, 4000 * 32, **shape)
                  ) / 2
    mfu = read_profile.reader("prefill_mfu.lfm2")(traced)
    assert mfu == pytest.approx(100 * operations / (197e12 * 50e-3))
    assert 0 < mfu < 100
    # the rung does not count: the same prompts on wider rungs, the same
    monkeypatch.setattr(hr, "profile", lambda run: {
        "regions": regions(rungs=(4096, 4096))})
    assert read_profile.reader("prefill_mfu.lfm2")(traced) == mfu


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_gives_none_and_does_not_raise(monkeypatch, name):
    """No trace at all; a trace without a prefill in its window; a program
    without the regions (the parent's)."""
    for trace in ({}, None):
        assert read_profile.reader(name + ".lfm2")(run_of(trace)) is None
    monkeypatch.setattr(hr, "profile", lambda run: {"regions": []})
    monkeypatch.setattr(prefill_scopes, "prefill_ops", lambda path: ())
    from benchmark import decode_scopes, replica
    monkeypatch.setattr(decode_scopes, "decode_ops", lambda path: ())
    monkeypatch.setattr(replica, "find_xplane", lambda folder: "a.xplane.pb")
    assert read_profile.reader(name + ".lfm2")(run_of()) is None
    assert read_profile.reader(name + ".lfm2")(
        run_of({"window_s": 5.0, "programs": {}})) is None


def test_the_readers_are_files_with_no_entry_and_the_tool_finds_them():
    """``per_layer`` is at the contract's 128, so ISSUE 55's new readers are
    files of ``metrics/unlisted/`` as PR 53's are, and what it names with
    ``.lfm2`` for a quantity that has an accepted reader is read by stem.
    (``test_loop_split.py::test_the_unlisted_readers_are_in_no_entry_and_
    all_are_found`` pins the folder's files as PR 53 left them and is marked
    stale; its other assertions run here.)"""
    import test_loop_split
    bench = spec.load_benchmark()
    assert len(bench["per_layer"]) == 128
    names = {m["name"] for kind in ("end_to_end", "per_layer")
             for m in bench[kind]}
    files = {f[:-3] for f in os.listdir(read_profile.UNLISTED)
             if f.endswith(".py")}
    assert files == set(test_loop_split.UNLISTED) | set(NEW)
    assert not files & (names | {n.split(".")[0] for n in names})
    for name in sorted(names | files):
        assert callable(read_profile.reader(name)), name
    for stem in (*NEW, *BY_STEM):
        assert callable(read_profile.reader(stem + ".lfm2")), stem
    with pytest.raises(SystemExit, match="no reader"):
        read_profile.reader("host_loop_nothing_ms")


def test_the_engines_own_trace_has_a_prefill_to_read(monkeypatch):
    """A recorded span of the tiny GPT engine (``tests/engine_trace.py``):
    its two prefills' regions are read; on the CPU no device plane holds a
    ``jit__prefill``, so the scopes' time is None."""
    import engine_trace
    profile = hr.read_profile(engine_trace.run()["path"])
    monkeypatch.setattr(hr, "profile", lambda run: profile)
    found = prefill_scopes.prefill_regions(run_of())
    assert len(found["prefills"]) == 2 and found["routing"] == []
    assert all(p["prompt_len"] <= p["padded_len"] for p in found["prefills"])
    assert prefill_scopes.prefill_ops(engine_trace.run()["path"]) == ()
