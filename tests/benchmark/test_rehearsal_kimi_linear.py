"""Whole runs of ``run.py`` on the CPU of the Kimi Linear cell at a tiny size:
through serve.run, the HTTP ingress and SSE, untraced and traced, with the
replica's generic check (prefill into slot 0, decode in row 0); what the
``*.kimi`` readers find in the traced run's regions; and what happens where
the program cannot take the configuration (the parent of the PR that taught it
a decay a key channel and held experts)."""

import os
import time

import pytest

import tiny
import tiny_kimi_linear
from test_rehearsal_train import check_line


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_kimi_linear.make_root(str(tmp_path_factory.mktemp("bench")))


def test_kimi_cell_end_to_end(root):
    rc, line, err = tiny.run_cell(root, tiny_kimi_linear.CELL, 0)
    assert rc == 0, err[-3000:]
    check_line(line, 1, ["served_tokens_per_s", "setup_s"])
    assert line["attempted"] >= 6 and line["failed"] == 0
    assert line["correct"] is True
    # prefill and 8 decode positions of two sequences; bfloat16 at 64 wide
    assert '"positions": 9' in err and "logits_rel_err" in err
    assert 0 < line["checks"]["logits_rel_err"][0] < 0.5


def test_kimi_cell_traced_and_its_readers(root, monkeypatch):
    """The CPU has no device plane, so nothing is reduced from the trace
    and the line carries the poll of ``stats()`` alone; the engine's regions
    are in the profile all the same, and the readers that need only them
    read there."""
    rc, line, err = tiny.run_cell(root, tiny_kimi_linear.CELL, 1)
    assert rc == 0, err[-3000:]
    assert set(line["metrics"]) == {"decode_batch_occupancy.kimi"}
    assert 0 < line["metrics"]["decode_batch_occupancy.kimi"]["value"] <= 100
    from benchmark import host_regions, replica, spec
    profile = host_regions.read_profile(replica.find_xplane(os.path.join(
        root, ".bench_trace", tiny_kimi_linear.CELL)))
    monkeypatch.setattr(host_regions, "profile", lambda run: profile)
    run = {"trace": {"window_s": 1.0}, "cell": {
        "name": tiny_kimi_linear.CELL,
        "config": tiny_kimi_linear.TINY_KIMI}}
    read = {name: spec.metric_reader(name + ".kimi")(run) for name in (
        "paged_read_useful_share", "decode_ahead_share",
        "moe_kept_assignment_share", "moe_routed_hit_share",
        "moe_routed_load_max_over_mean", "paged_kernel_share")}
    assert 0 < read["paged_read_useful_share"] <= 100
    assert 0 < read["decode_ahead_share"] <= 100
    # one share of four: about a quarter of the assignments fall here
    assert 10 < read["moe_kept_assignment_share"] < 45
    assert 0 < read["moe_routed_hit_share"] <= 100       # of the 8 held
    assert read["moe_routed_load_max_over_mean"] >= 1
    assert read["paged_kernel_share"] == 0               # the CPU gathers
    steps = [attrs for name, _, _, attrs in profile["regions"]
             if name == "rt:engine.decode.dispatch"]
    assert steps and all(1 <= s["active"] <= 4 for s in steps)
    moe = [attrs for name, _, _, attrs in profile["regions"]
           if name == "rt:engine.decode.moe"]
    assert moe and all(
        0 <= s["assignments"] <= s["assignments_made"]
        and s["assignments_made"] % (7 * 4) == 0
        and s["experts_hit"] <= 7 * 8 for s in moe)
    # what needs the device's plane gives None, and does not raise
    for name in ("linear_state_device_ms", "linear_conv_device_ms",
                 "linear_gate_norm_device_ms", "kda_gate_device_ms",
                 "linear_state_roofline", "latent_kv_device_ms",
                 "latent_read_roofline", "moe_experts_device_ms",
                 "moe_routed_roofline", "kimi_step_hbm_roofline",
                 "decode_device_ms"):
        assert spec.metric_reader(name + ".kimi")(
            {**run, "trace": {}, "peaks": {}}) is None


def test_a_program_without_a_decay_a_channel_fails_at_once(root, tmp_path,
                                                           monkeypatch):
    """On the parent of the PR that added them ``LlamaConfig`` has no
    ``linear_gate_rank`` and no ``expert_share``: the generator's check
    meets the dataclass's own TypeError in ``run.py``'s process, before any
    replica is deployed.  The parent is stood in for by a ``sitecustomize``
    that takes the new fields off the dataclass as soon as the module is
    imported."""
    (tmp_path / "sitecustomize.py").write_text(
        "import dataclasses, importlib.abc, importlib.util, sys\n"
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
        "                dataclasses.fields(old) if f.name not in\n"
        "                ('linear_gate_rank', 'expert_share')],\n"
        "                frozen=True)\n"
        "        spec.loader.exec_module = exec_module\n"
        "        return spec\n"
        "sys.meta_path.insert(0, Strip())\n")
    started = time.monotonic()
    monkeypatch.setattr(tiny, "REPO",
                        os.pathsep.join([str(tmp_path), tiny.REPO]))
    rc, line, err = tiny.run_cell(root, tiny_kimi_linear.CELL, 0)
    assert rc != 0 and line is None
    assert "TypeError" in err and ("linear_gate_rank" in err
                                   or "expert_share" in err)
    assert time.monotonic() - started < 60
