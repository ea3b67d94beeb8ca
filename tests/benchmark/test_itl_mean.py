"""What PR 44 made of ``serve-chat-steady``'s judged numbers: the mean gap
between streamed tokens, the 99th percentile among the per-layer metrics,
the bounds, what each per-layer metric of the cell
moves, a reader for every entry and an entry for every reader, and a
profile taken without the Python tracer."""

import asyncio
import os

import pytest

from benchmark import spec, stats

CHAT = "serve-chat-steady"
GAPS = ("itl_mean_ms", "itl_p99_ms")
# every per-layer metric of chat but the demoted percentile itself
LAYERS = {
    "decode_device_ms.chat", "decode_hbm_roofline", "paged_kv_device_ms",
    "host_dispatch_blocked_ms", "host_dispatch_loop_cpu_ms",
    "host_resume_loop_cpu_ms", "host_loop_cpu_ms", "host_loop_busy_share",
    "host_loop_hidden_share", "gc_pause_share", "decode_ahead_share",
    "stream_yield_ack_ms", "loop_lag_max_ms.serve",
    "prefill_device_ms.chat", "prefill_useful_share.chat", "gc_pause_max_ms",
    "engine_waiting_mean", "ttft_p50_ms.chat", "ingress_ttft_overhead_ms",
    "gen_late_p99_ms"}


def request(arrivals, error=None):
    return {"prompt_tokens": 8, "asked": 4, "due": 0.0, "sent": 0.0,
            "arrivals": arrivals, "error": error}


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


@pytest.fixture(scope="module")
def mean():
    return spec.metric_reader("itl_mean_ms")


# ------------------------------------------------------------- the reader

def test_the_mean_is_over_all_gaps_of_all_requests(mean):
    # three gaps of 10 ms in one stream, one of 50 ms in another: the mean
    # of the four, not of the two streams' means (10 and 50)
    run = {"requests": [request([1.00, 1.01, 1.02, 1.03]),
                        request([2.00, 2.05])]}
    assert mean(run) == pytest.approx(20.0)


def test_a_request_with_one_token_gives_no_gap(mean):
    run = {"requests": [request([1.0]), request([2.00, 2.03]), request([])]}
    assert mean(run) == pytest.approx(30.0)
    assert mean({"requests": [request([1.0])]}) is None


def test_an_empty_run_gives_none(mean):
    assert mean({"requests": []}) is None


@pytest.mark.parametrize("name", GAPS)
def test_a_failed_requests_arrivals_count_as_far_as_they_came(name):
    """Both readers take ``stats.token_gaps_ms`` of every record: the
    tokens a stream delivered before it broke were felt by its reader."""
    whole = request([1.00, 1.01, 1.02])
    broken = request([3.00, 3.04], error="RuntimeError('stream closed')")
    read = spec.metric_reader(name)
    both = read({"requests": [whole, broken]})
    gaps = stats.token_gaps_ms([whole, broken])
    assert sorted(gaps) == pytest.approx([10.0, 10.0, 40.0])
    assert both == pytest.approx({
        "itl_mean_ms": 20.0,
        "itl_p99_ms": stats.percentile(gaps, 99)}[name])
    assert both != read({"requests": [whole]})


def test_the_mean_carries_the_stalls_and_the_percentile_is_one():
    """98 gaps of a step and two behind a prefill: the mean is a tenth over
    the step, the 99th percentile the stall."""
    arrivals = [0.0]
    for i in range(100):
        arrivals.append(arrivals[-1] + (0.040 if i % 40 == 39 else 0.0075))
    run = {"requests": [request(arrivals)]}
    assert spec.metric_reader("itl_mean_ms")(run) == pytest.approx(
        (98 * 7.5 + 2 * 40.0) / 100)
    assert spec.metric_reader("itl_p99_ms")(run) == pytest.approx(
        40.0, rel=0.01)
    assert spec.metric_reader("itl_p99_ms")({"requests": []}) is None


# --------------------------------------------------------- BENCHMARK.json

def test_chat_is_judged_by_the_mean_and_the_setup(bench):
    mine = spec.metrics_of(bench, "end_to_end", CHAT)
    assert [m["name"] for m in mine] == ["itl_mean_ms", "setup_s"]
    mean_, _ = mine
    assert mean_["workloads"] == [CHAT]
    assert (mean_["unit"], mean_["better"], mean_["source"]) == \
        ("ms", "lower", "host_clock")
    # five times the widest spread of two sets of six is over the
    # contract's cap (PERF.md section 2)
    assert mean_["bound"] == 0.1


def test_the_percentile_is_a_per_layer_metric_of_the_client(bench):
    """Its sets of six spread 16% and 27% in two groups (PERF.md section
    2): no bound holds it, so it is read and not judged."""
    assert "itl_p99_ms" not in {m["name"] for m in bench["end_to_end"]}
    tail, = [m for m in bench["per_layer"] if m["name"] == "itl_p99_ms"]
    assert tail == {"name": "itl_p99_ms", "unit": "ms", "better": "lower",
                    "source": "host_clock", "layer": "client (benchmark)",
                    "moves": "itl_mean_ms", "workloads": [CHAT]}


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_no_other_cell_reports_chats_numbers(bench, kind):
    for w in bench["workloads"]:
        names = {m["name"] for m in spec.metrics_of(bench, kind, w["name"])}
        if w["name"] != CHAT:
            assert not names & (set(GAPS) | LAYERS), w["name"]


def test_every_bound_is_inside_the_contract(bench):
    for metric in bench["end_to_end"]:
        assert 0.01 <= metric["bound"] <= 0.1, metric["name"]
    setup, = [m for m in bench["end_to_end"] if m["name"] == "setup_s"]
    assert setup["bound"] == 0.1 and "workloads" not in setup


def test_chats_layers_move_the_mean(bench):
    """With the percentile out of ``end_to_end``, what made the stalls and
    what every step pays move the one judged number that holds both."""
    mine = {m["name"]: m["moves"]
            for m in spec.metrics_of(bench, "per_layer", CHAT)}
    assert set(mine) == LAYERS | {"itl_p99_ms"}
    assert set(mine.values()) == {"itl_mean_ms"}


@pytest.mark.parametrize("workload", [
    w["name"] for w in spec.load_benchmark()["workloads"]])
def test_every_moves_names_a_metric_its_cells_report(bench, workload):
    judged = {m["name"] for m in spec.metrics_of(bench, "end_to_end",
                                                 workload)}
    for metric in spec.metrics_of(bench, "per_layer", workload):
        assert metric["moves"] in judged - {"setup_s"}, metric["name"]


def test_a_reader_for_every_entry_and_an_entry_for_every_reader(bench):
    names = {m["name"] for kind in ("end_to_end", "per_layer")
             for m in bench[kind]}
    folder = os.path.join(spec.BENCH_DIR, "metrics")
    files = {f[:-3] for f in os.listdir(folder) if f.endswith(".py")}
    # an entry ``<quantity>.<cells>`` is read by ``metrics/<quantity>.py``
    stems = {n if n in files else n.split(".")[0] for n in names}
    assert stems <= files, sorted(stems - files)
    assert files <= stems, sorted(files - stems)
    for name in names:
        assert callable(spec.metric_reader(name))


# ------------------------------------------------------------ the profile

class Engine:
    def stats(self):
        return {"steps": 7, "active": 3, "waiting": 1}


@pytest.mark.parametrize("module, cls", [
    ("benchmark.replica", "BenchLLMServer"),
    ("benchmark.replica_blocks", "BlockBenchLLMServer")])
def test_observe_profiles_without_the_python_tracer(monkeypatch, tmp_path,
                                                    module, cls):
    """``observe`` hands ``jax.profiler.start_trace`` options whose
    ``python_tracer_level`` is 0, as ``LLMServer.profile`` does; the block
    cell's replica inherits it."""
    import importlib

    import jax
    server_cls = getattr(importlib.import_module(module), cls)
    from benchmark.replica import BenchLLMServer
    assert server_cls.observe is BenchLLMServer.observe
    server = object.__new__(server_cls)     # no engine, no device
    server._engine, server._trace_dir = Engine(), str(tmp_path)
    server._polls, server._traced, server._steps_at_start = [], False, 0
    calls = []
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda log_dir, **kw: calls.append(("start", log_dir, kw)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    asyncio.run(server.observe(0.5, 0.0, 0.1))
    assert [c[0] for c in calls] == ["start", "stop"]
    _, log_dir, kw = calls[0]
    assert log_dir == str(tmp_path) and set(kw) == {"profiler_options"}
    options = kw["profiler_options"]
    assert isinstance(options, jax.profiler.ProfileOptions)
    assert options.python_tracer_level == 0
    # the host tracer stays: the ``rt:`` regions are its events
    assert options.host_tracer_level == \
        jax.profiler.ProfileOptions().host_tracer_level > 0
    assert server._traced and server._steps_at_start == 7
    assert server._polls and set(server._polls) == {(3, 1)}
