"""Whole runs of ``run.py`` on the CPU at tiny sizes: the open-loop and the
closed-loop serve generators through serve.run, the HTTP ingress and SSE."""

import os
import shutil
import subprocess
import sys

import pytest

import tiny
from test_rehearsal_train import check_line


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def test_open_loop_cell_end_to_end(root):
    rc, line, err = tiny.run_cell(root, "tiny-serve-chat", 0)
    assert rc == 0, err[-3000:]
    check_line(line, 1, ["itl_mean_ms", "setup_s"])
    assert line["attempted"] == 12        # round(4.0 requests/s * 3 s)
    assert "logits_rel_err" in err


def test_open_loop_cell_traced(root):
    rc, line, err = tiny.run_cell(root, "tiny-serve-chat", 1)
    assert rc == 0, err[-3000:]
    names = set(line["metrics"])
    assert {"gen_late_p99_ms", "ingress_ttft_overhead_ms",
            "ttft_p50_ms.chat", "engine_waiting_mean"} <= names
    # the trace's readers found no device plane on the CPU
    assert not names & {"prefill_device_ms.chat", "decode_device_ms.chat",
                        "decode_hbm_roofline"}
    assert line["metrics"]["ingress_ttft_overhead_ms"]["value"] > 0
    # the profile was reduced by run.py, not inside the replica
    assert '"reduce_profile_s"' in err and '"collect_s"' in err


def test_closed_loop_cell_end_to_end(root):
    rc, line, err = tiny.run_cell(root, "tiny-serve-batch", 0)
    assert rc == 0, err[-3000:]
    check_line(line, 1, ["served_tokens_per_s", "setup_s"])
    assert line["attempted"] >= 6


def test_no_result_where_only_the_benchmark_is(tmp_path):
    """BENCHMARK.json and the files under ``paths``, and no program."""
    for path in ("benchmark", "tests/benchmark"):
        shutil.copytree(os.path.join(tiny.REPO, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "serve-chat-steady", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=120)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
