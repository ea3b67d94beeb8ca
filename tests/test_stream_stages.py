"""A streamed yield's way between two processes of one host, stage by stage
and message by message (ISSUE 53): the producer's ``rt:stream.yield`` with
its stages and what the owner's ack told, the always-on sums behind them,
the transport's counts (by kind and type under a profiler session), and a
serving replica's decode steps carrying what the streams and the transport
cost its loop.  And who holds a yield (ISSUE 54): its owner and whoever
took its reference from the stream; the producer only names it."""

import asyncio
import glob
import os
import time

import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu._private.core_worker import INLINE_MAX
from ray_tpu._private.ids import ObjectID, TaskID
from ray_tpu._private.worker import get_core

N = 24


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=8, _worker_env={"JAX_PLATFORMS": "cpu"})
    yield
    serve.shutdown()
    ray_tpu.shutdown()


@ray_tpu.remote
class Producer:
    """Streams whole numbers to whoever calls ``count``; everything else
    reads this process's instruments."""

    def __init__(self):
        self.seen = []

    async def count(self, n):
        from ray_tpu.util import tracing
        self.seen = []
        for i in range(n):
            # while the body runs for yield i + 1, the ``after`` of yield
            # i has been added and nothing of yield i + 1 has
            self.seen.append(tracing.sums("stream."))
            yield i

    async def gated(self, n, gate, early=1, size=0):
        """``early`` yields, then the rest once the file ``gate`` is there:
        the consumer decides how long the stream runs.  ``held`` is what
        this process counted and borrowed when the body ran for each
        yield; ``closed`` says the body's ``finally`` ran."""
        self.held, self.closed = [], False
        try:
            for i in range(n):
                while i >= early and not os.path.exists(gate):
                    await asyncio.sleep(0.005)
                self.held.append(self._held())
                yield bytes(size) if size else i
        finally:
            self.closed = True

    @staticmethod
    def _held():
        core = get_core()
        return len(core._borrowing), len(core._local_refs)

    def told(self):
        """After a stream (the call waits its turn behind it): what the
        body saw at each yield, what is held now, whether it was closed."""
        return self.held, self._held(), self.closed

    def sums(self):
        from ray_tpu.util import tracing
        return tracing.sums()

    def start_trace(self, log_dir):
        import jax
        jax.profiler.start_trace(log_dir)

    def stop_trace(self, log_dir):
        """The session's ``rt:stream.yield`` regions' attributes, in order,
        and what the body saw of the sums at each step."""
        import jax
        from jax.profiler import ProfileData
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        plane, = [p for p in ProfileData.from_file(path).planes
                  if p.name == "/host:CPU"]
        events = sorted((e.start_ns, dict(e.stats)) for line in plane.lines
                        for e in line.events if e.name == "rt:stream.yield")
        return [stats for _, stats in events], self.seen

    def owners_are_remote(self):
        """Stamp every connection this worker holds as one to another
        host: its next yields go unstamped."""
        from ray_tpu._private.worker import get_core
        conns = get_core()._worker_conns.values()
        for conn in conns:
            conn.peer_is_local = False
        return len(conns)


def _stream(producer, n=N):
    refs = list(producer.count.options(num_returns="streaming").remote(n))
    assert [ray_tpu.get(r, timeout=60) for r in refs] == list(range(n))


def _grown(producer, before, wanted, timeout=30):
    """The growth of the producer's sums once ``wanted(growth)`` holds:
    a reply is counted when its frame is parsed, which the stream's
    consumer does not wait for."""
    deadline = time.monotonic() + timeout
    while True:
        after = ray_tpu.get(producer.sums.remote(), timeout=60)
        grown = {k: v - before.get(k, 0) for k, v in after.items()
                 if v != before.get(k, 0)}
        if wanted(grown) or time.monotonic() > deadline:
            return grown
        time.sleep(0.1)


def test_a_yields_stages_ride_on_its_region_and_add_up_to_the_sums(
        cluster, tmp_path):
    producer = Producer.remote()
    _stream(producer, 2)          # the connection to the owner is made
    before = ray_tpu.get(producer.sums.remote(), timeout=60)
    ray_tpu.get(producer.start_trace.remote(str(tmp_path)), timeout=120)
    _stream(producer)
    yields, seen = ray_tpu.get(producer.stop_trace.remote(str(tmp_path)),
                               timeout=120)
    grown = _grown(producer, before, lambda g: g.get("stream.yields") == N)
    assert len(yields) == N and grown["stream.yields"] == N
    for stats in yields:
        assert set(stats) == {"wait_us", "store_us", "ack_us", "after_us",
                              "out_us", "in_us", "held_us"}, stats
        assert all(isinstance(v, int) and v >= 0 for v in stats.values())
        # the outbox's wait and what the owner did with the yield lie
        # inside the ack's wait, one after the other
        assert stats["out_us"] + stats["in_us"] + stats["held_us"] \
            <= stats["ack_us"], stats
    # the first yield of a stream has no yield before it
    assert yields[0]["after_us"] == 0
    # a stage's attributes are its sum's growth, rounded down a region
    for stage in ("wait", "store", "ack"):
        carried = sum(stats[stage + "_us"] for stats in yields)
        assert 0 <= grown[f"stream.{stage}_s"] * 1e6 - carried <= N, stage
    # ``after`` rides on the next yield: what yields 2..N carry is what
    # the sum had grown by when the body ran for the last of them
    carried = sum(stats["after_us"] for stats in yields)
    told = (seen[-1]["after_s"] - seen[0].get("after_s", 0)) * 1e6
    assert 0 <= told - carried <= N
    assert grown["stream.after_s"] * 1e6 >= told
    assert "stream.store_aside_s" not in grown     # a number goes inline


def test_a_stream_of_n_yields_is_counted_message_by_message(cluster,
                                                            tmp_path):
    """N yields between two local processes: N ``stream_yield`` requests out
    and their N replies in, and nothing else a token.  Until ISSUE 54 each
    token also cost a ``borrow_add`` and, at the stream's end, a
    ``borrow_remove`` (the producer kept a counted reference to an object
    the consumer owns): three requests and three replies.  The producer
    now only names its yields.  The totals are always on; the kinds and
    types are told apart while a profiler session records."""
    producer = Producer.remote()
    _stream(producer, 2)
    unrecorded = ray_tpu.get(producer.sums.remote(), timeout=60)
    assert unrecorded["rpc.msgs_out"] >= 2 and \
        not any(key.startswith("msgs.") for key in unrecorded)
    ray_tpu.get(producer.start_trace.remote(str(tmp_path)), timeout=120)
    before = ray_tpu.get(producer.sums.remote(), timeout=60)
    _stream(producer)
    grown = _grown(producer, before,
                   lambda g: g.get("msgs.in.reply", 0) >= N)
    ray_tpu.get(producer.stop_trace.remote(str(tmp_path)), timeout=120)
    assert grown["msgs.out.request.stream_yield"] == N
    assert not any("borrow" in key for key in grown), grown
    # the acks, and whatever else this worker asked of the cluster
    # meanwhile
    assert grown["msgs.in.reply"] >= N
    # the yields, the answers to the calls that read these very sums and
    # started the stream, a heartbeat: well under a second message a token
    assert N <= grown["rpc.msgs_out"] < 2 * N and \
        grown["rpc.msgs_in"] >= grown["msgs.in.reply"]
    # a frame holds at least one message, and packing it takes time
    assert 0 < grown["rpc.frames_out"] <= grown["rpc.msgs_out"]
    assert 0 < grown["rpc.frames_in"] <= grown["rpc.msgs_in"]
    assert grown["rpc.out_s"] > 0 and grown["rpc.in_s"] > 0
    assert grown["rpc.bytes_out"] > grown["rpc.frames_out"] * 4


def test_an_owner_on_another_host_is_not_asked_for_its_clock(cluster,
                                                             tmp_path):
    """``time.perf_counter`` means nothing between hosts: a yield to a
    connection whose peer is not this host carries no stamp, and its ack
    and region no ``out_us`` / ``in_us`` / ``held_us``."""
    producer = Producer.remote()
    _stream(producer, 2)
    assert ray_tpu.get(producer.owners_are_remote.remote(), timeout=60) >= 1
    ray_tpu.get(producer.start_trace.remote(str(tmp_path)), timeout=120)
    _stream(producer, 4)
    yields, _ = ray_tpu.get(producer.stop_trace.remote(str(tmp_path)),
                            timeout=120)
    assert len(yields) == 4
    for stats in yields:
        assert set(stats) == {"wait_us", "store_us", "ack_us", "after_us"}


# ------------------------------------------------------ who holds a yield

def _settled():
    """Once the owner's loop (this process's) has run what was queued on it
    so far: a dropped reference's free is queued there by the drop."""
    core = get_core()
    asyncio.run_coroutine_threadsafe(asyncio.sleep(0), core.loop).result(60)
    return core


def _ids(gen, n):
    """The ids of a streaming call's returns: 0 the final list's, 1..n the
    yields'."""
    task = TaskID(bytes.fromhex(gen.task_id()))
    return [ObjectID.for_task_return(task, i).hex() for i in range(n + 1)]


def _kept(core, ids):
    return [h for h in ids if h in core.owned or h in core.memory_store]


def _arrived(core, oid_hex, timeout=60):
    deadline = time.monotonic() + timeout
    while oid_hex not in core.owned:
        assert time.monotonic() < deadline, "the yield never arrived"
        time.sleep(0.005)


def _told_once_closed(producer, timeout=60):
    """``told()`` once the body's ``finally`` has run.  A cancel ends the
    stream's turn on the producer before the cancelled body is closed, so a
    call right behind it can be answered in between: ask until ``closed``."""
    deadline = time.monotonic() + timeout
    while True:
        told = ray_tpu.get(producer.told.remote(), timeout=60)
        if told[2] or time.monotonic() > deadline:
            return told
        time.sleep(0.005)


def test_the_producer_counts_and_borrows_none_of_its_yields(cluster,
                                                            tmp_path):
    producer = Producer.remote()
    _stream(producer, 2)
    gen = producer.gated.options(num_returns="streaming").remote(
        N, str(tmp_path / "gate"), early=N)
    refs = list(gen)
    held, now, closed = ray_tpu.get(producer.told.remote(), timeout=60)
    assert len(held) == N and closed
    # what the body saw with i yields behind it is what it saw with none
    assert {borrowed for borrowed, _ in held} == {0}
    assert {counted for _, counted in held} == {held[0][1]}
    assert now == held[0]
    assert [ray_tpu.get(r, timeout=60) for r in refs] == list(range(N))


def test_a_dropped_yield_is_freed_while_its_stream_runs(cluster, tmp_path):
    core = get_core()
    producer = Producer.remote()
    gate = tmp_path / "gate"
    gen = producer.gated.options(num_returns="streaming").remote(
        4, str(gate), early=2)
    final, first, second, *_ = _ids(gen, 4)
    kept = next(gen)
    dropped = next(gen)
    assert [kept.hex(), dropped.hex()] == [first, second]
    assert ray_tpu.get(dropped, timeout=60) == 1
    assert second in core.owned and second in core.memory_store
    del dropped
    _settled()
    assert _kept(core, [second]) == []
    # the stream has not ended: its final list is not here, the body waits
    assert final not in core.memory_store
    assert _kept(core, [first]) == [first]
    gate.touch()
    assert [ray_tpu.get(r, timeout=60) for r in gen] == [2, 3]
    assert ray_tpu.get(kept, timeout=60) == 0


def test_a_held_yield_outlives_its_stream_and_the_list_names_them_all(
        cluster, tmp_path):
    producer = Producer.remote()
    gen = producer.gated.options(num_returns="streaming").remote(
        N, str(tmp_path / "gate"), early=N)
    refs = list(gen)
    yielded = [r.hex() for r in refs]
    assert yielded == _ids(gen, N)[1:]
    kept, gone = refs[3], yielded[4]
    del refs
    core = _settled()
    listed = list(ray_tpu.get(gen.completed(), timeout=60))
    assert [r.hex() for r in listed] == yielded
    # naming a yield does not hold it, and does not bring it back
    assert _kept(core, yielded) == [kept.hex()]
    assert ray_tpu.get(kept, timeout=60) == 3
    assert ray_tpu.get(listed[3], timeout=60) == 3
    with pytest.raises(ray_tpu.exceptions.ObjectLostError, match="freed"):
        ray_tpu.get(listed[4], timeout=60)
    assert listed[4].hex() == gone
    del listed, kept
    assert _kept(_settled(), yielded) == []


def test_a_cancelled_stream_runs_the_bodys_finally_and_leaves_nothing_owned(
        cluster, tmp_path):
    core = get_core()
    producer = Producer.remote()
    gate = tmp_path / "gate"
    gen = producer.gated.options(num_returns="streaming").remote(
        N, str(gate), early=4)
    ids = _ids(gen, N)
    taken = next(gen)
    assert ray_tpu.get(taken, timeout=60) == 0
    _arrived(core, ids[4])        # three more wait on the stream's queue
    gen.cancel()
    gate.touch()
    held, _, closed = _told_once_closed(producer)
    assert closed and 4 <= len(held) < N
    del taken, gen
    _settled()
    assert _kept(core, ids) == [] and ids[0] not in core._streams
    assert not any(core._borrowers.get(h) for h in ids)


def test_a_yield_too_large_to_go_inline_is_read_while_held_and_freed_after(
        cluster, tmp_path):
    core = get_core()
    size = 2 * INLINE_MAX()
    producer = Producer.remote()
    before = ray_tpu.get(producer.sums.remote(), timeout=60)
    gate = tmp_path / "gate"
    gen = producer.gated.options(num_returns="streaming").remote(
        2, str(gate), early=1, size=size)
    large = next(gen)
    oid = large.id
    # the owner holds the name; the copy is in the producer's node's store
    assert core.memory_store[large.hex()] == ("plasma", None)
    assert ray_tpu.get(large, timeout=60) == bytes(size)
    assert core.plasma.contains(oid)
    del large
    _settled()
    # freed with the stream still running, the copy with it
    assert _kept(core, [oid.hex()]) == [] and not core.plasma.contains(oid)
    gate.touch()
    assert [len(ray_tpu.get(r, timeout=60)) for r in gen] == [size]
    grown = _grown(producer, before,
                   lambda g: g.get("stream.store_aside_s", 0) > 0)
    assert grown["stream.yields"] == 2 and grown["stream.store_aside_s"] > 0


# ------------------------------------------------------ a serving replica

def test_a_replicas_steps_carry_what_streams_and_transport_cost_its_loop(
        cluster, tmp_path):
    """Four streams through a replica under the profiler: each decode
    step's region carries the yields, messages and frames since the step
    before and the walls of the streams' and the transport's synchronous
    sections, which with the engine's own regions on that thread lie
    within the step's wall."""
    from jax.profiler import ProfileData
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.serve.engine import EngineConfig, LLMServer
    from ray_tpu.serve.engine.engine import _BESIDE

    model = GPTConfig(vocab_size=97, max_seq_len=96, num_layers=2,
                      num_heads=4, embed_dim=32, dtype=jnp.float32,
                      attention="dense", remat=False)
    ecfg = EngineConfig(model="gpt", model_config=model, page_size=8,
                        num_pages=64, max_batch=8, max_prompt_len=32,
                        max_new_tokens=32)
    dep = serve.deployment(name="llm_split", max_concurrent_queries=16,
                           ray_actor_options={"num_cpus": 0.1})(LLMServer)
    handle = serve.run(dep.bind(ecfg))
    payload = {"tokens": [5, 17, 3], "max_new_tokens": 24}
    warm = [ray_tpu.get(r) for r in handle.remote_stream(payload)]
    before = ray_tpu.get(handle.method("stats").remote(), timeout=60)
    assert before["rpc"]["out"] == {}    # by kind and type: a session's
    trace = handle.method("profile").remote(str(tmp_path), 4.0)
    # the session records once the process tells its messages apart (the
    # answers to these very questions), however loaded the machine is
    deadline = time.monotonic() + 60
    while not ray_tpu.get(handle.method("stats").remote(),
                          timeout=60)["rpc"]["out"]:
        assert time.monotonic() < deadline, "no profiler session began"
        time.sleep(0.05)
    streams = [handle.remote_stream(payload) for _ in range(4)]
    for stream in streams:
        assert [ray_tpu.get(r) for r in stream] == warm
    path = ray_tpu.get(trace, timeout=120)
    after = ray_tpu.get(handle.method("stats").remote(), timeout=60)
    plane, = [p for p in ProfileData.from_file(path).planes
              if p.name == "/host:CPU"]
    regions = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name,
                      dict(e.stats)) for line in plane.lines
                     for e in line.events if e.name.startswith("rt:"))
    steps = [stats for _, _, name, stats in regions
             if name == "rt:engine.decode.dispatch"]
    assert len(steps) >= len(warm) // 2
    for attr in _BESIDE:
        assert all(isinstance(stats[attr], int) and stats[attr] >= 0
                   for stats in steps), attr
    # the regions carry no more than the always-on twins grew by
    for attr, key in _BESIDE.items():
        table, name = key.split(".")
        twin = (after[table][name] - before[table].get(name, 0)) \
            * (1e6 if attr.endswith("_us") else 1)
        assert sum(stats[attr] for stats in steps) <= twin, attr
    carried = {attr: sum(stats[attr] for stats in steps)
               for attr in _BESIDE}
    yields = [stats for _, _, name, stats in regions
              if name == "rt:stream.yield"]
    streamed = after["stream"]["yields"] - before["stream"]["yields"]
    assert streamed == 4 * len(warm)           # always on
    # what the session saw (all of it, unless the machine was so loaded
    # that the four seconds ended first): at least one stream's worth
    assert len(warm) <= len(yields) <= streamed
    # by kind and type only under the session: a yield is counted where
    # its frame is packed, its region made when its ack is back
    told = after["rpc"]["out"]["request.stream_yield"]
    assert abs(told - len(yields)) <= len(streams)
    # a good part of them was streamed between two steps of a stretch
    # (a tiny model's loop goes idle between tokens where the machine is
    # loaded, and a waking starts the count anew)
    assert carried["yields"] >= len(warm)
    assert carried["msgs_out"] >= carried["yields"]
    assert carried["frames_out"] <= carried["msgs_out"]
    assert carried["stream_after_us"] > 0 and carried["rpc_out_us"] > 0
    # A step's named sections lie within it, held to what no stall of a
    # thread can break.  The streams' and the transport's sections are
    # walls inside the very interval of ``step_us``, on its clock.  With
    # the engine's own regions they lie between the end of the
    # ``rt:engine.schedule`` that preceded the step before (nothing else
    # runs on the loop from there to that step's submission) and the end
    # of the one that preceded this step: all on the loop thread, one
    # after the other.
    schedules = [end for _, end, name, _ in regions
                 if name == "rt:engine.schedule"]
    scheduled = [max(end for end in schedules if end <= start)
                 for start, _, name, _ in regions
                 if name == "rt:engine.decode.dispatch"]
    for begun, until, stats in zip(scheduled, scheduled[1:], steps[1:]):
        engine = sum(end - start for start, end, name, _ in regions
                     if name in ("rt:engine.deliver", "rt:engine.schedule")
                     and begun <= start and end <= until)
        beside = sum(stats[attr] for attr in (
            "stream_store_us", "stream_after_us", "rpc_out_us", "rpc_in_us"))
        assert beside <= stats["step_us"] + 1, stats
        assert engine * 1e-3 + beside <= (until - begun) * 1e-3 + 1, stats
    assert all(y["out_us"] + y["in_us"] + y["held_us"] <= y["ack_us"]
               for y in yields)
