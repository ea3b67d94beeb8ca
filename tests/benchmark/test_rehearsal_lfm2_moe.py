"""A whole run of ``run.py`` on the CPU of the LFM2 cell at a tiny size:
through serve.run, the HTTP ingress and SSE, traced, with the replica's
generic check (prefill into slot 0, decode in row 0); what the readers find
in the traced run's regions; and what happens where the program cannot take
the configuration (the parent of the PR that taught it the conv kind)."""

import os
import time

import pytest

import tiny
import tiny_lfm2_moe
from test_rehearsal_train import check_line


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_lfm2_moe.make_root(str(tmp_path_factory.mktemp("bench")))


def test_lfm2_cell_traced_and_its_readers(root, monkeypatch):
    """The CPU has no device plane, so nothing is reduced from the trace and
    the line carries the poll of ``stats()`` alone; the engine's regions are
    in the profile all the same, and the readers that need only them read
    there."""
    rc, line, err = tiny.run_cell(root, tiny_lfm2_moe.CELL, 1)
    assert rc == 0, err[-3000:]
    assert line["attempted"] >= 6 and line["failed"] == 0
    assert line["correct"] is True
    # prefill and 8 decode positions of two sequences; bfloat16 at 64 wide
    assert '"positions": 9' in err and "logits_rel_err" in err
    assert 0 < line["checks"]["logits_rel_err"][0] < 0.5
    assert set(line["metrics"]) == {"decode_batch_occupancy"}
    assert 0 < line["metrics"]["decode_batch_occupancy"]["value"] <= 100
    from benchmark import host_regions, prefill_scopes, replica
    from benchmark.tools import read_profile
    profile = host_regions.read_profile(replica.find_xplane(os.path.join(
        root, ".bench_trace", tiny_lfm2_moe.CELL)))
    monkeypatch.setattr(host_regions, "profile", lambda run: profile)
    run = {"trace": {"window_s": 1.0}, "peaks": {}, "cell": {
        "name": tiny_lfm2_moe.CELL, "config": tiny_lfm2_moe.TINY_LFM2}}
    read = {name: read_profile.reader(name + ".lfm2")(run) for name in (
        "prefill_useful_share", "prefill_flash_share", "paged_kernel_share",
        "paged_read_useful_share", "decode_ahead_share",
        "moe_routed_hit_share", "moe_prefill_load_max_over_mean")}
    assert 25 <= read["prefill_useful_share"] <= 100
    assert read["prefill_flash_share"] == 0          # the CPU's is dense
    assert read["paged_kernel_share"] == 0           # and it gathers
    assert 0 < read["paged_read_useful_share"] <= 100
    assert 0 < read["decode_ahead_share"] <= 100
    assert 0 < read["moe_routed_hit_share"] <= 100
    assert read["moe_prefill_load_max_over_mean"] >= 1
    found = prefill_scopes.prefill_regions(run)
    # one of each a prefill, but for a prefill that straddles an end of the
    # profiled seconds
    assert found["prefills"] and abs(
        len(found["routing"]) - len(found["prefills"])) <= 1
    # every real position makes 4 assignments in each of the 5 expert layers
    lengths = {prefill["prompt_len"] for prefill in found["prefills"]}
    for routing in found["routing"][1:-1]:
        assert routing["assignments"] == routing["assignments_made"]
        assert routing["assignments"] // (5 * 4) in lengths
        assert routing["experts_hit"] <= 5 * 8
    for prefill in found["prefills"]:
        assert 8 <= prefill["prompt_len"] <= prefill["padded_len"] <= 32
    # what needs the device's plane gives None, and does not raise
    for name in ("conv_op_prefill_ms", "conv_op_decode_ms",
                 "moe_prefill_experts_ms", "moe_prefill_dispatch_combine_ms",
                 "moe_prefill_roofline", "prefill_mfu", "prefill_device_ms",
                 "paged_read_roofline", "moe_experts_device_ms"):
        assert read_profile.reader(name + ".lfm2")(
            {**run, "trace": {}}) is None


def test_a_program_without_the_conv_kind_fails_at_once(root, tmp_path,
                                                       monkeypatch):
    """On the parent of the PR that added them ``LlamaConfig`` has no
    ``tie_embeddings`` and no ``router_norm_eps``: the generator's check
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
        "                ('tie_embeddings', 'router_norm_eps')],\n"
        "                frozen=True)\n"
        "        spec.loader.exec_module = exec_module\n"
        "        return spec\n"
        "sys.meta_path.insert(0, Strip())\n")
    started = time.monotonic()
    monkeypatch.setattr(tiny, "REPO",
                        os.pathsep.join([str(tmp_path), tiny.REPO]))
    rc, line, err = tiny.run_cell(root, tiny_lfm2_moe.CELL, 0)
    assert rc != 0 and line is None
    assert "TypeError" in err and ("tie_embeddings" in err
                                   or "router_norm_eps" in err)
    assert time.monotonic() - started < 60
