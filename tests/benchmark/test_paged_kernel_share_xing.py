"""``paged_kernel_share.xing`` (ISSUE 50): what the Xing cell's decode steps
read their latent pages with, by the reader ISSUE 49 added for chat and the
hybrid cell (``benchmark/metrics/paged_kernel_share.py``, found by the
name's stem), and the two tests of the benchmark's files that listed the
entries as they stood before it."""

import pytest

from benchmark import host_regions as hr
from benchmark import spec

import test_paged_read_metrics as reads
import test_spec_sdar
from test_decode_ahead_share import run_of   # (and tests/ on sys.path)

NAME, CELL = "paged_kernel_share.xing", "serve-xing-reasoning-batch"


@pytest.mark.parametrize("kinds, want", [
    (["kernel"] * 4, 100.0), (["gather"] * 3, 0.0),
    (["kernel", "gather", "kernel", "kernel"], 75.0), ([], None),
    ([None, None], None)])
def test_known_rows_give_the_known_share(monkeypatch, kinds, want):
    """PR 49's regions carry ``paged_read`` ("gather" for latent pages), so
    the parent reads 0 here; regions without it give nothing."""
    monkeypatch.setattr(hr, "profile",
                        lambda run: {"regions": reads.dispatches(kinds)})
    assert spec.metric_reader(NAME)(run_of(NAME)) == want


def test_no_trace_gives_none():
    for trace in ({}, None):
        run = run_of(NAME)
        run["trace"] = trace
        assert spec.metric_reader(NAME)(run) is None


def test_the_benchmark_lists_it_last_and_the_others_before_it():
    """``test_paged_read_metrics.py::test_the_benchmark_lists_them_last``
    with the list's end as this PR leaves it (``tests/conftest.py`` marks
    that test as expected to fail)."""
    listed = spec.load_benchmark()["per_layer"]
    entries = {m["name"]: m for m in listed}
    assert [m["name"] for m in listed[-5:]] == [
        "paged_read_roofline.hybrid", "paged_read_roofline.ouro",
        "paged_kernel_share.hybrid", "paged_kernel_share", NAME]
    shares = {**reads.SHARES, NAME: (CELL, "served_tokens_per_s")}
    for name, (cell, moves) in shares.items():
        entry = entries[name]
        assert entry["workloads"] == [cell] and entry["moves"] == moves
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"]) == ("%", "higher", "program_counter",
                                    "ops kernels")
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
    for name, cell in reads.ROOFLINES.items():
        entry = entries[name]
        assert entry["workloads"] == [cell]
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"], entry["moves"]) == (
            "%", "higher", "device_trace", "ops kernels",
            "served_tokens_per_s")


def test_published_widths_of_xing_hold_with_the_cells_fifteen():
    """``test_spec_sdar.py::test_published_widths_of_xing_still_hold`` less
    its count of the cell's own per-layer entries as PR 40 found them (14;
    marked as expected to fail): they are 15, all ``.xing``, and every one
    of the cell's listed metrics has a reader."""
    bench = spec.load_benchmark()
    xing = spec.load_json("configs", "xing4.0-29b-a4b-6l.json")
    reduced = ["num_hidden_layers", "first_k_dense_replace",
               "num_nextn_predict_layers"]
    published = test_spec_sdar.XING_PUBLISHED
    assert xing["reduced"] == reduced
    assert {k: xing[k] for k in reduced} == {
        "num_hidden_layers": 6, "first_k_dense_replace": 1,
        "num_nextn_predict_layers": 0}
    assert xing["published"] == {k: published[k] for k in reduced}
    assert {k: v for k, v in xing.items() if k in published
            and k not in reduced} == \
        {k: v for k, v in published.items() if k not in reduced}
    assert xing["engine"]["num_pages"] == 8193
    assert 0 < xing["numerics"]["logits_rtol"] < 0.05
    mine = [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]
    assert len(mine) == 15 and all(n.endswith(".xing") for n in mine)
    assert mine[-1] == NAME
    for metric in spec.metrics_of(bench, "per_layer", CELL):
        assert callable(spec.metric_reader(metric["name"]))
