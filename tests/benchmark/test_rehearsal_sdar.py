"""Whole runs of ``run.py`` on the CPU of the SDAR cell at a tiny size:
through serve.run, the HTTP ingress and SSE, untraced and traced, with the
replica whose check drives blocks; what the ``*.sdar`` readers find in the
traced run's regions; and what happens where the program cannot take the
configuration (the parent of the PR that taught it blocks)."""

import os
import time

import pytest

import tiny
import tiny_sdar
from test_rehearsal_train import check_line


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_sdar.make_root(str(tmp_path_factory.mktemp("bench")))


def test_sdar_cell_end_to_end(root):
    rc, line, err = tiny.run_cell(root, tiny_sdar.CELL, 0)
    assert rc == 0, err[-3000:]
    check_line(line, 1, ["served_tokens_per_s", "setup_s"])
    assert line["attempted"] >= 6 and line["failed"] == 0
    # the block replica's check: 2 sequences x 3 blocks x 3 passes x 4 rows
    assert '"positions": 36' in err and "logits_rel_err" in err
    assert line["checks"]["logits_rel_err"][0] < 0.03


def test_sdar_cell_traced_and_its_readers(root, monkeypatch):
    """The CPU has no device plane, so nothing is reduced from the trace
    and the line carries the poll of ``stats()`` alone; the engine's regions
    are in the profile all the same, and the readers that need only them
    read the static schedule there: 4 denoise passes and a commit a block."""
    rc, line, err = tiny.run_cell(root, tiny_sdar.CELL, 1)
    assert rc == 0, err[-3000:]
    assert set(line["metrics"]) == {"decode_batch_occupancy.sdar"}
    assert 0 < line["metrics"]["decode_batch_occupancy.sdar"]["value"] <= 100
    from benchmark import host_regions, replica, spec
    profile = host_regions.read_profile(replica.find_xplane(os.path.join(
        root, ".bench_trace", tiny_sdar.CELL)))
    monkeypatch.setattr(host_regions, "profile", lambda run: profile)
    run = {"trace": {"window_s": 1.0}, "cell": {
        "name": tiny_sdar.CELL, "config": tiny_sdar.TINY_SDAR}}
    read = {name: spec.metric_reader(name + ".sdar")(run) for name in (
        "block_tokens_per_slot_step", "block_dropped_share",
        "paged_read_useful_share", "decode_ahead_share")}
    # 0.8 less the dropped tails and the prompts' tails in first blocks
    assert 0.3 < read["block_tokens_per_slot_step"] <= 0.8
    assert 0 <= read["block_dropped_share"] < 60
    assert 0 < read["paged_read_useful_share"] <= 100
    assert 0 < read["decode_ahead_share"] <= 100
    steps = [attrs for name, _, _, attrs in profile["regions"]
             if name == "rt:engine.decode.dispatch"]
    assert steps and all(s["block_len"] == 4 for s in steps)
    # what needs the device's plane gives None, and does not raise
    for name in ("block_unmask_device_ms", "lm_head_device_ms",
                 "block_read_roofline", "block_step_hbm_roofline",
                 "decode_device_ms", "moe_routed_roofline"):
        assert spec.metric_reader(name + ".sdar")(
            {**run, "trace": {}, "peaks": {}}) is None


def test_a_configuration_the_family_refuses_fails_at_once(root):
    import json
    refused = {**tiny_sdar.TINY_SDAR, "mlp_only_layers": [0]}
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-sdar.json"), "w") as f:
        json.dump(refused, f)
    try:
        started = time.monotonic()
        rc, line, err = tiny.run_cell(root, tiny_sdar.CELL, 0)
    finally:
        with open(os.path.join(root, "benchmark", "configs",
                               "tiny-sdar.json"), "w") as f:
            json.dump(tiny_sdar.TINY_SDAR, f)
    assert rc != 0 and line is None
    assert "mlp_only_layers" in err
    assert time.monotonic() - started < 60


def test_a_program_without_blocks_fails_at_once(root, tmp_path, monkeypatch):
    """On the parent of the PR that added generation by blocks
    ``LlamaConfig`` has no ``block_length``: the generator's check meets the
    dataclass's own TypeError in ``run.py``'s process, before any replica is
    deployed.  The parent is stood in for by a ``sitecustomize`` that takes
    the new fields off the dataclass as soon as the module is imported."""
    (tmp_path / "sitecustomize.py").write_text(
        "import dataclasses, importlib.abc, importlib.util, sys\n"
        "NEW = ('head_size', 'qk_norm_per_head', 'block_length',\n"
        "       'denoise_steps', 'confidence_threshold', 'mask_token')\n"
        "class Strip(importlib.abc.MetaPathFinder):\n"
        "    def find_spec(self, name, path, target=None):\n"
        "        if name != 'ray_tpu.models.llama':\n"
        "            return None\n"
        "        sys.meta_path.remove(self)\n"
        "        spec = importlib.util.find_spec(name)\n"
        "        run = spec.loader.exec_module\n"
        "        def exec_module(module):\n"
        "            run(module)\n"
        "            old = module.LlamaConfig\n"
        "            module.LlamaConfig = dataclasses.make_dataclass(\n"
        "                'LlamaConfig', [(f.name, f.type, f) for f in\n"
        "                dataclasses.fields(old) if f.name not in NEW],\n"
        "                frozen=True)\n"
        "        spec.loader.exec_module = exec_module\n"
        "        return spec\n"
        "sys.meta_path.insert(0, Strip())\n")
    started = time.monotonic()
    # run_cell puts tiny.REPO on the run's PYTHONPATH: the stand-in rides
    # in front of the checkout there
    monkeypatch.setattr(tiny, "REPO",
                        os.pathsep.join([str(tmp_path), tiny.REPO]))
    rc, line, err = tiny.run_cell(root, tiny_sdar.CELL, 0)
    assert rc != 0 and line is None
    assert "TypeError" in err and ("head_size" in err or "block_length" in err)
    assert time.monotonic() - started < 60
