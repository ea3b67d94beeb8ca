"""A streamed yield's way between two processes of one host, stage by stage
and message by message (ISSUE 53): the producer's ``rt:stream.yield`` with
its stages and what the owner's ack told, the always-on sums behind them,
the transport's counts (by kind and type under a profiler session), and a
serving replica's decode steps carrying what the streams and the transport
cost its loop."""

import glob
import os
import time

import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu import serve

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
    del refs                      # the borrows go with the references


def _grown(producer, before, wanted, timeout=30):
    """The growth of the producer's sums once ``wanted(growth)`` holds:
    what follows a stream's end (the borrows' removal) is not awaited by
    the stream."""
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
    and their N replies in.  TODAY each token also costs a ``borrow_add``
    and, when the consumer lets go of it, a ``borrow_remove`` (the producer
    keeps a counted reference to an object the consumer owns): three
    requests and three replies a token.  Pinned here so that the change
    which sheds them shows.  The totals are always on; the kinds and types
    are told apart while a profiler session records."""
    producer = Producer.remote()
    _stream(producer, 2)
    unrecorded = ray_tpu.get(producer.sums.remote(), timeout=60)
    assert unrecorded["rpc.msgs_out"] >= 6 and \
        not any(key.startswith("msgs.") for key in unrecorded)
    ray_tpu.get(producer.start_trace.remote(str(tmp_path)), timeout=120)
    before = ray_tpu.get(producer.sums.remote(), timeout=60)
    _stream(producer)
    removed = "msgs.out.request.borrow_remove"
    grown = _grown(producer, before, lambda g: g.get(removed) == N
                   and g.get("msgs.in.reply", 0) >= 3 * N)
    ray_tpu.get(producer.stop_trace.remote(str(tmp_path)), timeout=120)
    assert grown["msgs.out.request.stream_yield"] == N
    assert grown["msgs.out.request.borrow_add"] == N
    assert grown[removed] == N
    # the acks, the borrows' answers, and whatever else this worker asked
    # of the cluster meanwhile
    assert grown["msgs.in.reply"] >= 3 * N
    assert grown["rpc.msgs_out"] >= 3 * N and \
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
