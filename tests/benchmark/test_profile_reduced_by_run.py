"""The replica hands back where its profile lies and ``run.py`` reduces it:
read inside the replica, the profile of a fast cell held the process for
longer than the serve controller waits for a ping, and the controller
killed the replica under ``collect`` (PR 44's refused check, Xing traced)."""

import pytest

from benchmark import replica, trace_reduce


class Engine:
    class config:
        max_batch = 4

    def stats(self):
        return {"steps": 9}


def server(cls, traced):
    made = object.__new__(cls)           # no engine, no device
    made._engine, made._trace_dir = Engine(), "somewhere"
    made._seen = {0: [1.0, 1.5], 1: [2.0, None], None: [0.0, 0.1]}
    made._polls, made._phases = [(3, 0)], {"replica_init_s": 1.0}
    made._traced, made._steps_at_start = traced, 2
    return made


def classes():
    from benchmark.replica_blocks import BlockBenchLLMServer
    return [replica.BenchLLMServer, BlockBenchLLMServer]


@pytest.mark.parametrize("cls", classes(), ids=lambda c: c.__name__)
@pytest.mark.parametrize("traced", [False, True])
def test_collect_reads_no_profile(monkeypatch, cls, traced):
    assert cls.collect is replica.BenchLLMServer.collect
    monkeypatch.setattr(replica, "memory_peak_bytes", lambda: 5)
    monkeypatch.setattr(replica, "find_xplane",
                        lambda folder: folder + "/a.xplane.pb")

    def refuse(*a, **kw):
        raise AssertionError("the replica read its profile")
    monkeypatch.setattr(trace_reduce, "read_xplane", refuse)
    monkeypatch.setattr(trace_reduce, "reduce_events", refuse)
    got = server(cls, traced).collect()
    assert got["profile"] == ("somewhere/a.xplane.pb" if traced else None)
    assert "trace" not in got
    assert got["decode_steps"] == 7 and got["max_batch"] == 4
    assert got["replica_ttft_s"] == {0: 0.5} and got["polls"] == [(3, 0)]


def test_an_instruction_is_shortened_once():
    text = ("%fusion.1 = bf16[2,16]{1,0:T(8,128)} fusion(bf16[2,16]{1,0} "
            "%p), kind=kLoop")
    plain = trace_reduce.short_op.__wrapped__
    before = trace_reduce.short_op.cache_info().hits
    assert trace_reduce.short_op(text) == plain(text) == \
        "fusion.1 bf16[2,16] fusion"
    assert trace_reduce.short_op(text) == plain(text)
    assert trace_reduce.short_op.cache_info().hits > before
