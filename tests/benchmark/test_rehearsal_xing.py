"""Whole runs of ``run.py`` on the CPU of the Xing cell at a tiny size:
through serve.run, the HTTP ingress and SSE, untraced and traced; what the
cell's traffic file holds; and what happens where the program cannot take
the configuration (the parent of the PR that taught it latent pages)."""

import itertools
import os
import time

import pytest

import tiny
import tiny_xing
from test_rehearsal_train import check_line


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_xing.make_root(str(tmp_path_factory.mktemp("bench")))


def test_xing_cell_end_to_end(root):
    rc, line, err = tiny.run_cell(root, tiny_xing.CELL, 0)
    assert rc == 0, err[-3000:]
    check_line(line, 1, ["served_tokens_per_s", "setup_s"])
    assert line["attempted"] >= 6
    assert "logits_rel_err" in err


def test_xing_cell_traced(root):
    """The CPU has no device plane, so nothing is reduced from the trace
    and its readers (device times, the rooflines, the steps' regions) are
    left out of the line; the poll of the engine's ``stats()`` is there."""
    rc, line, err = tiny.run_cell(root, tiny_xing.CELL, 1)
    assert rc == 0, err[-3000:]
    assert set(line["metrics"]) == {"decode_batch_occupancy.xing"}
    assert 0 < line["metrics"]["decode_batch_occupancy.xing"]["value"] <= 100


def test_a_configuration_the_family_refuses_fails_at_once(root):
    started = time.monotonic()
    rc, line, err = tiny.run_cell(root, tiny_xing.REFUSED, 0)
    assert rc != 0 and line is None
    assert "num_nextn_predict_layers" in err
    assert time.monotonic() - started < 60


def test_a_program_without_latent_attention_fails_at_once(root, tmp_path,
                                                          monkeypatch):
    """On the parent of the PR that added latent pages ``LlamaConfig`` has
    no ``kv_lora_rank``: the generator's check meets the dataclass's own
    TypeError in ``run.py``'s process, before any replica is deployed.  The
    parent is stood in for by a ``sitecustomize`` that takes the new fields
    off the dataclass as soon as the module is imported."""
    (tmp_path / "sitecustomize.py").write_text(
        "import dataclasses, importlib.abc, importlib.util, sys\n"
        "NEW = ('kv_lora_rank', 'q_lora_rank', 'qk_nope_dim',\n"
        "       'qk_rope_dim', 'v_head_dim', 'rope_yarn',\n"
        "       'first_dense_layers', 'dense_mlp_dim', 'shared_experts',\n"
        "       'router_scoring', 'router_bias', 'routed_scaling',\n"
        "       'hc_mult', 'hc_sinkhorn_iters', 'hc_eps', 'hc_clamp')\n"
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
    rc, line, err = tiny.run_cell(root, tiny_xing.CELL, 0)
    assert rc != 0 and line is None
    assert "kv_lora_rank" in err and "TypeError" in err
    assert time.monotonic() - started < 60


def test_the_cells_traffic_is_the_issues():
    """40 callers for 32 slots, prompts uniform 256-1024, outputs uniform
    1024-3072, in blocks of 40 that the run's seed shuffles."""
    from benchmark import spec
    from benchmark.generators import closed_loop_serve
    traffic = spec.load_json("traffic", "reasoning-long-batch.json")
    assert set(traffic) == set(
        spec.load_json("traffic", "decode-heavy-batch.json"))
    assert traffic["generator"] == "closed_loop_serve_checked"
    assert (traffic["clients"], traffic["block"]) == (40, 40)
    assert traffic["prompt_tokens"] == {"distribution": "uniform",
                                        "min": 256, "max": 1024}
    assert traffic["output_tokens"] == {"distribution": "uniform",
                                        "min": 1024, "max": 3072}
    block = list(itertools.islice(closed_loop_serve.plan(traffic, 3), 40))
    other = list(itertools.islice(closed_loop_serve.plan(traffic, 7), 40))
    assert block != other                  # the seed orders the lengths
    assert sorted(p for p, _ in block) == sorted(p for p, _ in other)
    assert sorted(o for _, o in block) == sorted(o for _, o in other)
    engine = spec.load_json("configs", "xing4.0-29b-a4b-6l.json")["engine"]
    assert max(p for p, _ in block) <= engine["max_prompt_len"] == 1024
    assert max(o for _, o in block) <= engine["max_new_tokens"] == 3072
    assert min(p for p, _ in block) >= 256 and min(o for _, o in block) >= 1024
    # a sequence's worst case is 256 pages, and every slot has them
    assert engine["num_pages"] == engine["max_batch"] * 256 + 1 == 8193
    bench = spec.load_benchmark()
    cell = spec.load_cell(bench, "serve-xing-reasoning-batch")
    assert cell["traffic"]["name"] == "reasoning-long-batch"
    assert cell["chips"] == 1
