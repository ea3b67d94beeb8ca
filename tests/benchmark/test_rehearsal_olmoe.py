"""Whole runs of ``run.py`` on the CPU of the OLMoE cell at a tiny size:
through serve.run, the HTTP ingress and SSE, untraced and traced, and what
happens where the program cannot take the configuration."""

import time

import pytest

import tiny
import tiny_olmoe
from test_rehearsal_train import check_line


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_olmoe.make_root(str(tmp_path_factory.mktemp("bench")))


def test_olmoe_cell_end_to_end(root):
    rc, line, err = tiny.run_cell(root, tiny_olmoe.CELL, 0)
    assert rc == 0, err[-3000:]
    check_line(line, 1, ["served_tokens_per_s", "setup_s"])
    assert line["attempted"] >= 6
    assert "logits_rel_err" in err


def test_olmoe_cell_traced(root):
    """The CPU has no device plane, so nothing is reduced from the trace
    and its readers (device times, the roofline, the steps' regions) are
    left out of the line; the poll of the engine's ``stats()`` is there."""
    rc, line, err = tiny.run_cell(root, tiny_olmoe.CELL, 1)
    assert rc == 0, err[-3000:]
    assert set(line["metrics"]) == {"decode_batch_occupancy.olmoe"}
    assert 0 < line["metrics"]["decode_batch_occupancy.olmoe"]["value"] <= 100


def test_a_configuration_the_program_cannot_take_fails_at_once(root):
    """The generator's check runs in ``run.py``'s own process before any
    replica is deployed: a configuration that the family refuses (or, on the
    parent of the PR that added the expert fields, that ``LlamaConfig``
    does) ends the run in seconds with that error, where the replica's
    constructor would be retried by the readiness probe for minutes."""
    started = time.monotonic()
    rc, line, err = tiny.run_cell(root, tiny_olmoe.REFUSED, 0)
    assert rc != 0 and line is None
    assert "attention_bias" in err
    assert time.monotonic() - started < 60


def test_the_cells_traffic_is_the_issues():
    """24 callers for 16 slots, prompts uniform 64-512, outputs uniform
    256-1024, in blocks of 24 that the run's seed shuffles: what
    ``closed_loop_serve`` does with ``longprompt-batch``, after the check."""
    import itertools

    from benchmark import spec
    from benchmark.generators import closed_loop_serve
    traffic = spec.load_json("traffic", "decode-heavy-batch.json")
    assert set(traffic) == set(
        spec.load_json("traffic", "longprompt-batch.json"))
    assert traffic["generator"] == "closed_loop_serve_checked"
    checked = spec.load_part("generators", traffic["generator"])
    assert checked.closed_loop_serve is closed_loop_serve
    assert (traffic["clients"], traffic["block"]) == (24, 24)
    assert traffic["prompt_tokens"] == {"distribution": "uniform",
                                        "min": 64, "max": 512}
    assert traffic["output_tokens"] == {"distribution": "uniform",
                                        "min": 256, "max": 1024}
    block = list(itertools.islice(closed_loop_serve.plan(traffic, 3), 24))
    other = list(itertools.islice(closed_loop_serve.plan(traffic, 7), 24))
    assert block != other                  # the seed orders the lengths
    assert sorted(p for p, _ in block) == sorted(p for p, _ in other)
    assert sorted(o for _, o in block) == sorted(o for _, o in other)
    assert min(p for p, _ in block) >= 64 and max(p for p, _ in block) <= 512
    assert min(o for _, o in block) >= 256 \
        and max(o for _, o in block) <= 1024


def test_the_checked_generator_hands_the_run_to_closed_loop_serve(
        monkeypatch):
    """After the family has taken the configuration the run is
    ``closed_loop_serve``'s, context and all; a refused one never gets
    there."""
    from benchmark import spec
    from benchmark.generators import closed_loop_serve
    checked = spec.load_part("generators", "closed_loop_serve_checked")
    handed = []
    monkeypatch.setattr(closed_loop_serve, "run",
                        lambda ctx: handed.append(ctx) or {"ok": True})
    ctx = {"cell": {"config": tiny_olmoe.TINY_OLMOE,
                    "traffic": tiny_olmoe.TRAFFIC}, "seed": 5, "seconds": 1}
    assert checked.run(ctx) == {"ok": True} and handed == [ctx]
    refused = {**ctx, "cell": {**ctx["cell"], "config": {
        **tiny_olmoe.TINY_OLMOE, "attention_bias": True}}}
    with pytest.raises(ValueError, match="attention_bias"):
        checked.run(refused)
    assert handed == [ctx]
