"""The two readers ISSUE 49 adds for the token step's paged read:
``paged_kernel_share`` (what the window's decode steps read their pages
with: ``paged_read`` on ``rt:engine.decode.dispatch``) for chat and the
hybrid cell, and ``paged_read_roofline`` (the live positions' K and V at the
memory's rate over the time under the scope ``paged_read``) for the hybrid
and the Ouro cells."""

import pytest

from benchmark import decode_scopes, host_regions as hr
from benchmark import replica, spec

from test_decode_ahead_share import run_of   # (and tests/ on sys.path)

SHARES = {"paged_kernel_share": ("serve-chat-steady", "itl_mean_ms"),
          "paged_kernel_share.hybrid": ("serve-olmo-hybrid-decode-wide",
                                        "served_tokens_per_s")}
ROOFLINES = {"paged_read_roofline.hybrid": "serve-olmo-hybrid-decode-wide",
             "paged_read_roofline.ouro": "serve-ouro-cot-batch"}
# positions a step's sequences hold: 48 slots of ~420, 12 slots of ~40
LIVE = {"paged_read_roofline.hybrid": 20000, "paged_read_roofline.ouro": 500}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
D = "rt:engine.decode.dispatch"


def dispatches(reads, live=20000, **attrs):
    """Decode dispatches 10 ms apart, the second holding 2% more positions
    than the first; ``None`` leaves ``paged_read`` out (the parent's
    regions)."""
    return [(D, i * 0.01, i * 0.01 + 0.001,
             {"active": 2, "live_tokens": live + live // 50 * i, **attrs,
              **({} if r is None else {"paged_read": r})})
            for i, r in enumerate(reads)]


@pytest.mark.parametrize("name", SHARES)
@pytest.mark.parametrize("reads, want", [
    (["kernel"] * 4, 100.0), (["gather"] * 3, 0.0),
    (["gather", "kernel", "gather", "gather"], 25.0), ([], None),
    # the parent's steps say nothing of their read: nothing to read
    ([None, None], None)])
def test_known_rows_give_the_known_share(monkeypatch, name, reads, want):
    monkeypatch.setattr(hr, "profile",
                        lambda run: {"regions": dispatches(reads)})
    assert spec.metric_reader(name)(run_of(name)) == want


@pytest.mark.parametrize("name", [*SHARES, *ROOFLINES])
def test_no_trace_gives_none(name):
    for trace in ({}, None):
        run = run_of(name)
        run["trace"] = trace
        assert spec.metric_reader(name)(run) is None


def test_the_engines_own_trace_reads_five_gathering_steps(monkeypatch):
    """A recorded span: the tiny GPT engine of ``tests/engine_trace.py``
    has heads of 8 on the CPU, so its steps gather."""
    import engine_trace
    profile = hr.read_profile(engine_trace.run()["path"])
    monkeypatch.setattr(hr, "profile", lambda run: profile)
    rows = hr.rows(run_of("paged_kernel_share"), "engine.decode.dispatch")
    assert [r["paged_read"] for r in rows] == ["gather"] * 5
    for name in SHARES:
        assert spec.metric_reader(name)(run_of(name)) == 0.0


@pytest.mark.parametrize("name", ROOFLINES)
def test_the_roofline_is_the_live_bytes_over_the_scopes_time(monkeypatch,
                                                             name):
    """Two token steps of ``LIVE`` positions and 2% more whose operations
    under ``paged_read`` (the kernel's custom call keeps the scope in its
    ``op_name``) took 3 ms in all; what lies under ``paged_append`` or
    outside is not the read's.  It cannot pass 100 while the time holds the
    live bytes' copies; a program without the scope, or a cell whose steps
    are block steps, gives nothing."""
    ops = ((2e-3, "jit(_decode)/while/body/closed_call/paged_read/"
                  "paged_read/pallas_call"),
           (1e-3, "jit(_decode)/while/body/closed_call/paged_read/pad"),
           (7e-3, "jit(_decode)/while/body/closed_call/paged_append/scatter"),
           (9e-3, "jit(_decode)/while/body/dot_general"))
    monkeypatch.setattr(decode_scopes, "decode_ops", lambda path: ops)
    monkeypatch.setattr(replica, "find_xplane", lambda folder: "a.pb")
    monkeypatch.setattr(hr, "profile", lambda run: {
        "regions": dispatches(["kernel", "kernel"], LIVE[name])})
    run = run_of(name, {"programs": {"jit__decode": {"calls": 2,
                                                     "device_s": 0.04}}})
    run["peaks"] = PEAKS
    config = run["cell"]["config"]
    family = spec.load_part("families", config["family"])
    least = 1.01 * LIVE[name] * family.kv_bytes_per_token(config) / 819e9
    got = spec.metric_reader(name)(run)
    assert got == pytest.approx(100 * least / 1.5e-3, rel=1e-6)
    assert 0 < got < 100
    monkeypatch.setattr(decode_scopes, "decode_ops", lambda path: ops[2:])
    assert spec.metric_reader(name)(run) is None
    monkeypatch.setattr(decode_scopes, "decode_ops", lambda path: ops)
    monkeypatch.setattr(hr, "profile", lambda run: {
        "regions": dispatches(["gather"] * 2, block_len=4)})
    assert spec.metric_reader(name)(run) is None


def test_a_position_of_the_two_cells_is_the_pools_row():
    """``kv_bytes_per_token``: the hybrid's 3 full layers of 30 heads of
    128, K and V, bf16; Ouro's 48 layers of 16 heads, a pool layer a pass."""
    for name, want in (("paged_read_roofline.hybrid", 3 * 2 * 3840 * 2),
                       ("paged_read_roofline.ouro", 4 * 48 * 2 * 2048 * 2)):
        config = run_of(name)["cell"]["config"]
        assert spec.load_part("families", config["family"]) \
            .kv_bytes_per_token(config) == want


def test_the_benchmark_lists_them_last():
    listed = spec.load_benchmark()["per_layer"]
    entries = {m["name"]: m for m in listed}
    assert [m["name"] for m in listed[-4:]] == [
        "paged_read_roofline.hybrid", "paged_read_roofline.ouro",
        "paged_kernel_share.hybrid", "paged_kernel_share"]
    for name, (cell, moves) in SHARES.items():
        entry = entries[name]
        assert entry["workloads"] == [cell] and entry["moves"] == moves
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"]) == ("%", "higher", "program_counter",
                                    "ops kernels")
    for name, cell in ROOFLINES.items():
        entry = entries[name]
        assert entry["workloads"] == [cell]
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"], entry["moves"]) == (
            "%", "higher", "device_trace", "ops kernels",
            "served_tokens_per_s")


def test_chats_layers_still_move_the_mean():
    """``test_prefill_flash_share.py::test_chats_layers_still_move_the_mean``
    with the one name this PR added (that file is the benchmark's, and lists
    chat's per-layer metrics as PR 46 left them)."""
    import test_itl_mean
    mine = {m["name"]: m["moves"] for m in spec.metrics_of(
        spec.load_benchmark(), "per_layer", test_itl_mean.CHAT)}
    assert set(mine) == test_itl_mean.LAYERS | {
        "itl_p99_ms", "prefill_flash_share.chat", "paged_kernel_share"}
    assert set(mine.values()) == {"itl_mean_ms"}


def test_what_the_hybrid_cell_added_still_stands():
    """``test_spec_olmo_hybrid.py::test_what_the_cell_adds_to_the_lists``
    less its place at the list's end and its count of the cell's entries as
    PR 48 left them (13), which no PR that adds a metric to the cell can
    keep: ``tests/conftest.py`` marks that test as expected to fail, and its
    other assertions run here, the cell's entries now 15."""
    import test_spec_olmo_hybrid as hybrid
    bench = spec.load_benchmark()
    assert len(bench["workloads"]) == 9 and len(bench["configs"]) == 8
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert bench["workloads"][-1] == {
        "name": hybrid.CELL, "config": "olmo-hybrid-7b-12l",
        "traffic": "decode-heavy-wide", "chips": 1,
        "why": bench["workloads"][-1]["why"]}
    assert bench["configs"][-1]["reduced"] == hybrid.REDUCED
    served, = [m for m in bench["end_to_end"]
               if m["name"] == "served_tokens_per_s"]
    assert served["workloads"][-1] == hybrid.CELL \
        and len(served["workloads"]) == 6
    mine = [m for m in bench["per_layer"]
            if m.get("workloads") == [hybrid.CELL]]
    assert all(m["name"].endswith(".hybrid")
               and m["moves"] == "served_tokens_per_s"
               and set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"} for m in mine)
    names = [m["name"][:-len(".hybrid")] for m in mine]
    assert len(names) == 15 and names[-2:] == ["paged_read_roofline",
                                               "paged_kernel_share"]
    assert not set(names) & {"prefill_device_ms", "linear_scan_roofline"}
    assert not [m["name"] for m in bench["per_layer"]
                if hybrid.CELL in m.get("workloads", []) and m not in mine]
    for metric in mine:
        spec.metric_reader(metric["name"])
        if "roofline" in metric["name"]:
            assert metric["unit"] == "%" and metric["better"] == "higher"
