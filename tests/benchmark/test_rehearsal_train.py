"""Whole runs of ``run.py`` on the CPU at tiny sizes: the train generator,
on one device and on a mesh of four, and a cell whose family, generator,
traffic, configuration and per-layer metric were all added as new files."""

import pytest

import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def check_line(line, chips, names):
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": chips, "memory_peak_bytes": 0}
    assert set(line["metrics"]) == set(names)
    for metric in line["metrics"].values():
        assert isinstance(metric["value"], (int, float)) and metric["unit"]


@pytest.mark.parametrize("workload,chips", [("tiny-train", 1),
                                            ("tiny-train-mesh", 4)])
def test_train_cell_end_to_end(root, workload, chips):
    rc, line, err = tiny.run_cell(root, workload, 0, chips=chips)
    assert rc == 0, err[-3000:]
    check_line(line, chips, ["train_tokens_per_s_chip", "setup_s"])
    assert line["metrics"]["train_tokens_per_s_chip"]["value"] > 0
    assert "loss_check" in err


def test_a_cell_made_of_added_files_runs_traced(root):
    """The CPU has no device plane, so the trace's readers find nothing and
    are left out of the line; the added reader and the MFU are there."""
    rc, line, err = tiny.run_cell(root, "tiny-train-added", 1)
    assert rc == 0, err[-3000:]
    check_line(line, 1, ["train_mfu", "added_steps"])
    assert line["metrics"]["added_steps"] == {
        "value": line["attempted"], "unit": "steps"}


def test_no_result_without_a_tpu(root):
    """The node advertises no chip: non-zero, and no result line."""
    rc, line, err = tiny.run_cell(root, "tiny-train", 0, chips=0)
    assert rc != 0 and line is None
    assert "advertises" in err


def test_no_result_on_a_device_the_table_of_peaks_lacks(root, tmp_path):
    """The real table lists no CPU: a run on one is refused."""
    import json
    import os
    import shutil
    copy = str(tmp_path / "copy")
    shutil.copytree(root, copy, ignore=shutil.ignore_patterns("cache"))
    path = os.path.join(copy, "benchmark", "peaks.json")
    peaks = json.load(open(path))
    del peaks["cpu"]
    json.dump(peaks, open(path, "w"))
    rc, line, err = tiny.run_cell(copy, "tiny-train", 0, seconds=1)
    assert rc != 0 and line is None
    assert "peaks.json" in err
