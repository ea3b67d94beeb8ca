"""Whole runs of ``run.py`` on the CPU of the Ouro cell at a tiny size:
through serve.run, the HTTP ingress and SSE, untraced and traced; what the
cell's traffic file holds; and what happens where the program cannot take
the configuration (the parent of the PR that taught it ``ut_steps``)."""

import itertools
import os
import time

import pytest

import tiny
import tiny_ouro
from test_rehearsal_train import check_line


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_ouro.make_root(str(tmp_path_factory.mktemp("bench")))


def test_ouro_cell_end_to_end(root):
    rc, line, err = tiny.run_cell(root, tiny_ouro.CELL, 0)
    assert rc == 0, err[-3000:]
    check_line(line, 1, ["served_tokens_per_s", "setup_s"])
    assert line["attempted"] >= 6
    assert "logits_rel_err" in err


def test_ouro_cell_traced(root):
    """The CPU has no device plane, so nothing is reduced from the trace
    and its readers (device times, the roofline, the steps' regions) are
    left out of the line; the poll of the engine's ``stats()`` is there."""
    rc, line, err = tiny.run_cell(root, tiny_ouro.CELL, 1)
    assert rc == 0, err[-3000:]
    assert set(line["metrics"]) == {"decode_batch_occupancy.ouro"}
    assert 0 < line["metrics"]["decode_batch_occupancy.ouro"]["value"] <= 100


def test_a_configuration_the_family_refuses_fails_at_once(root):
    started = time.monotonic()
    rc, line, err = tiny.run_cell(root, tiny_ouro.REFUSED, 0)
    assert rc != 0 and line is None
    assert "early_exit_threshold" in err
    assert time.monotonic() - started < 60


def test_a_program_without_ut_steps_fails_at_once(root, tmp_path,
                                                  monkeypatch):
    """On the parent of the PR that added the pass loop ``LlamaConfig`` has
    no ``ut_steps``: the generator's check meets the dataclass's own
    TypeError in ``run.py``'s process, before any replica is deployed.  The
    parent is stood in for by a ``sitecustomize`` that takes the two fields
    off the dataclass as soon as the module is imported."""
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
        "                ('ut_steps', 'post_norm')], frozen=True)\n"
        "        spec.loader.exec_module = exec_module\n"
        "        return spec\n"
        "sys.meta_path.insert(0, Strip())\n")
    started = time.monotonic()
    # run_cell puts tiny.REPO on the run's PYTHONPATH: the stand-in rides
    # in front of the checkout there
    monkeypatch.setattr(tiny, "REPO",
                        os.pathsep.join([str(tmp_path), tiny.REPO]))
    rc, line, err = tiny.run_cell(root, tiny_ouro.CELL, 0)
    assert rc != 0 and line is None
    assert "ut_steps" in err and "TypeError" in err
    assert time.monotonic() - started < 60


def test_the_cells_traffic_is_the_issues():
    """18 callers for 12 slots, prompts uniform 32-128, outputs uniform
    96-192, in blocks of 18 that the run's seed shuffles."""
    from benchmark import spec
    from benchmark.generators import closed_loop_serve
    traffic = spec.load_json("traffic", "cot-short-batch.json")
    assert set(traffic) == set(
        spec.load_json("traffic", "decode-heavy-batch.json"))
    assert traffic["generator"] == "closed_loop_serve_checked"
    assert (traffic["clients"], traffic["block"]) == (18, 18)
    assert traffic["prompt_tokens"] == {"distribution": "uniform",
                                        "min": 32, "max": 128}
    assert traffic["output_tokens"] == {"distribution": "uniform",
                                        "min": 96, "max": 192}
    block = list(itertools.islice(closed_loop_serve.plan(traffic, 3), 18))
    other = list(itertools.islice(closed_loop_serve.plan(traffic, 7), 18))
    assert block != other                  # the seed orders the lengths
    assert sorted(p for p, _ in block) == sorted(p for p, _ in other)
    assert sorted(o for _, o in block) == sorted(o for _, o in other)
    engine = spec.load_json("configs", "ouro-2.6b.json")["engine"]
    assert max(p for p, _ in block) <= engine["max_prompt_len"] == 128
    assert max(o for _, o in block) <= engine["max_new_tokens"] == 192
    assert min(p for p, _ in block) >= 32 and min(o for _, o in block) >= 96
    # a sequence's worst case is 20 pages, and every slot has them
    assert engine["num_pages"] == engine["max_batch"] * 20 + 1 == 241
