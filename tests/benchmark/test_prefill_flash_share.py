"""``prefill_flash_share``: the share of the window's prefills whose rung ran
the flash forward kernel (``attention`` on ``rt:engine.prefill``, PR 46), read
by ``benchmark/metrics/prefill_flash_share.py`` for the long-prompt batch
cell (``.batch``) and for chat (``.chat``)."""

import pytest

from benchmark import host_regions as hr
from benchmark import spec

from test_decode_ahead_share import run_of   # (and tests/ on sys.path)

NAMES = {"prefill_flash_share.batch": ("serve-longprompt-batch",
                                       "served_tokens_per_s"),
         "prefill_flash_share.chat": ("serve-chat-steady", "itl_mean_ms")}
P = "rt:engine.prefill"


def prefills(attention):
    """Prefills 100 ms apart; ``None`` leaves the attribute out (the
    parent's regions)."""
    return [(P, i * 0.1, i * 0.1 + 0.05,
             {"prompt_len": 1500, "padded_len": 2048,
              **({} if a is None else {"attention": a})})
            for i, a in enumerate(attention)]


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("attention, want", [
    (["flash"] * 4, 100.0), (["dense", "flash", "dense", "dense"], 25.0),
    (["dense"] * 3, 0.0), ([], None),
    # the parent's prefills say nothing of their attention: nothing to read
    ([None, None], None)])
def test_known_rows_give_the_known_share(monkeypatch, name, attention, want):
    monkeypatch.setattr(hr, "profile",
                        lambda run: {"regions": prefills(attention)})
    assert spec.metric_reader(name)(run_of(name)) == want


@pytest.mark.parametrize("name", NAMES)
def test_no_trace_gives_none(name):
    for trace in ({}, None):
        run = run_of(name)
        run["trace"] = trace
        assert spec.metric_reader(name)(run) is None


def test_the_engines_own_trace_reads_two_dense_prefills(monkeypatch):
    """A recorded span: the tiny GPT engine of ``tests/engine_trace.py``
    prefills its two prompts dense (that family's prefill has no kernel)."""
    import engine_trace
    profile = hr.read_profile(engine_trace.run()["path"])
    monkeypatch.setattr(hr, "profile", lambda run: profile)
    rows = hr.rows(run_of("prefill_flash_share.chat"), "engine.prefill")
    assert [r["attention"] for r in rows] == ["dense", "dense"]
    for name in NAMES:
        assert spec.metric_reader(name)(run_of(name)) == 0.0


def test_the_benchmark_lists_it_for_the_two_mistral_cells():
    entries = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    for name, (cell, moves) in NAMES.items():
        entry = entries[name]
        assert entry["workloads"] == [cell] and entry["moves"] == moves
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"]) == ("%", "higher", "program_counter",
                                    "serve/engine scheduler")


def test_chats_layers_still_move_the_mean():
    """``test_itl_mean.py::test_chats_layers_move_the_mean`` with the one
    name this PR added (that file is the benchmark's, and lists chat's
    per-layer metrics as PR 44 left them)."""
    import test_itl_mean
    mine = {m["name"]: m["moves"] for m in spec.metrics_of(
        spec.load_benchmark(), "per_layer", test_itl_mean.CHAT)}
    assert set(mine) == test_itl_mean.LAYERS | {
        "itl_p99_ms", "prefill_flash_share.chat"}
    assert set(mine.values()) == {"itl_mean_ms"}
