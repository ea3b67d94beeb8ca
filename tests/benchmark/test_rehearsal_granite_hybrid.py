"""A whole run of ``run.py`` on the CPU of the Granite 4.0-H cell at a tiny
size: through serve.run, the HTTP ingress and SSE, with the replica whose
check compares the slot's state rows and tail as well as the logits
(``replica_states.py``, bound by ``closed_loop_serve_states``); and what
happens where the program cannot take the configuration (the parent of the PR
that taught it the layer)."""

import os
import time

import pytest

import tiny
import tiny_granite_hybrid
from test_rehearsal_train import check_line


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_granite_hybrid.make_root(
        str(tmp_path_factory.mktemp("bench")))


def test_granite_cell_end_to_end(root):
    rc, line, err = tiny.run_cell(root, tiny_granite_hybrid.CELL, 0)
    assert rc == 0, err[-3000:]
    check_line(line, 1, ["served_tokens_per_s", "setup_s"])
    assert line["attempted"] >= 6 and line["failed"] == 0
    assert line["correct"] is True
    # prefill and 8 decode positions of two sequences; bfloat16 at 64 wide
    assert '"positions": 9' in err and "state_rel_err_by_layer" in err
    checks = line["checks"]
    assert 0 < checks["logits_rel_err"][0] < checks["logits_rel_err"][1]
    assert 0 < checks["state_rel_err"][0] < checks["state_rel_err"][1]
    assert 0 < checks["tail_rel_err"][0] < checks["tail_rel_err"][1]
    assert checks["state_pool_itemsize"] == [4, 4]


def test_a_program_without_the_layer_fails_at_once(root, tmp_path,
                                                   monkeypatch):
    """On the parent ``LlamaConfig`` has none of the four multipliers: the
    generator's check meets the dataclass's own TypeError in ``run.py``'s
    process, before any replica is deployed.  The parent is stood in for by
    a ``sitecustomize`` that takes the new fields off the dataclass as soon
    as the module is imported."""
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
        "                ('embedding_multiplier', 'residual_multiplier',\n"
        "                 'attention_multiplier', 'logits_scaling')],\n"
        "                frozen=True)\n"
        "        spec.loader.exec_module = exec_module\n"
        "        return spec\n"
        "sys.meta_path.insert(0, Strip())\n")
    started = time.monotonic()
    monkeypatch.setattr(tiny, "REPO",
                        os.pathsep.join([str(tmp_path), tiny.REPO]))
    rc, line, err = tiny.run_cell(root, tiny_granite_hybrid.CELL, 0)
    assert rc != 0 and line is None
    assert "TypeError" in err and "multiplier" in err
    assert time.monotonic() - started < 60
