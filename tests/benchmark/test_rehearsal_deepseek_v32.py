"""A whole run of ``run.py`` on the CPU of the DeepSeek-V3.2-Exp cell at a
tiny size: through serve.run, the HTTP ingress and SSE, traced, prompts in
chunks through the scheduler, with the replica's own check (two chunks and
more past the selection's size, eight token steps, the share of selected
positions common to program and reference); what the readers find in the
traced run's regions; and what happens where the program cannot take the
configuration (the parent of the PR that taught it the indexer)."""

import os
import time

import pytest

import tiny
import tiny_deepseek_v32


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_deepseek_v32.make_root(
        str(tmp_path_factory.mktemp("bench")))


def test_the_cell_traced_and_its_readers(root, monkeypatch):
    """The CPU has no device plane, so nothing is reduced from the trace and
    the line carries the poll of ``stats()`` alone; the engine's regions are
    in the profile all the same, and the readers that need only them read
    there."""
    rc, line, err = tiny.run_cell(root, tiny_deepseek_v32.CELL, 1)
    assert rc == 0, err[-3000:]
    assert line["attempted"] >= 6 and line["failed"] == 0
    assert line["correct"] is True
    checks = line["checks"]
    assert 0 < checks["logits_rel_err"][0] < checks["logits_rel_err"][1]
    selecting, limit = checks["logits_rel_err_selecting"]
    assert 0 < selecting < limit
    share, least = checks["dsa_selection_common_share"]
    assert least <= share <= 1.0
    mine, wanted = checks["dsa_selected_positions"]
    assert mine == wanted == 8 * 3 * 8
    assert '"prompt_lengths": [45, 7]' in err and '"positions": 9' in err
    assert set(line["metrics"]) == {"decode_batch_occupancy"}
    from benchmark import host_regions, replica
    from benchmark.tools import read_profile
    profile = host_regions.read_profile(replica.find_xplane(os.path.join(
        root, ".bench_trace", tiny_deepseek_v32.CELL)))
    monkeypatch.setattr(host_regions, "profile", lambda run: profile)
    run = {"trace": {"window_s": 1.0}, "peaks": {}, "cell": {
        "name": tiny_deepseek_v32.CELL,
        "config": tiny_deepseek_v32.TINY_DSV32}}
    read = {name: read_profile.reader(name + ".dsv32")(run) for name in (
        "prefill_useful_share", "engine_queue_wait_ms", "dsa_selected_share")}
    # chunks of 16 and prompts of 8-48: the padding is the last chunk's
    assert 50 <= read["prefill_useful_share"] <= 100
    assert read["engine_queue_wait_ms"] >= 0
    # a sequence of 8-64 positions keeps 8
    assert 10 < read["dsa_selected_share"] < 100
    chunks = host_regions.rows(run, "engine.prefill")
    assert chunks and all(
        c["prompt_len"] == c["width"] <= c["rung"] == c["padded_len"] == 16
        and c["start"] % 16 == 0 for c in chunks)
    assert any(c["start"] for c in chunks)
    steps = host_regions.rows(run, "engine.decode.dispatch")
    assert steps and all(0 < s["selected"] <= 8 * s["active"]
                         and s["live"] == s["live_tokens"] for s in steps)
    # what needs the device's plane gives None, and does not raise
    for name in ("dsa_index_decode_ms", "dsa_read_prefill_ms",
                 "dsa_read_roofline", "dsv32_step_hbm_roofline",
                 "prefill_device_ms", "moe_experts_device_ms"):
        assert read_profile.reader(name + ".dsv32")(
            {**run, "trace": {}}) is None


def test_a_program_without_the_indexer_fails_at_once(root, tmp_path,
                                                     monkeypatch):
    """On the parent of the PR that added them ``LlamaConfig`` has no
    ``index_heads`` and no ``expert_groups``: the generator's check meets the
    dataclass's own TypeError in ``run.py``'s process, before any replica is
    deployed.  The parent is stood in for by a ``sitecustomize`` that takes
    the new fields off the dataclass as soon as the module is imported."""
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
        "                ('index_heads', 'index_head_dim', 'index_topk',\n"
        "                 'expert_groups')], frozen=True)\n"
        "        spec.loader.exec_module = exec_module\n"
        "        return spec\n"
        "sys.meta_path.insert(0, Strip())\n")
    started = time.monotonic()
    monkeypatch.setattr(tiny, "REPO",
                        os.pathsep.join([str(tmp_path), tiny.REPO]))
    rc, line, err = tiny.run_cell(root, tiny_deepseek_v32.CELL, 0)
    assert rc != 0 and line is None
    assert "TypeError" in err and ("index_heads" in err
                                   or "expert_groups" in err)
    assert time.monotonic() - started < 60
