"""Test configuration.

JAX runs on CPU with 8 virtual devices so multi-chip sharding logic is
exercised without TPU hardware (the driver separately dry-runs the multichip
path).  Must be set before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import contextlib  # noqa: E402
import faulthandler  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import tempfile  # noqa: E402

import pytest  # noqa: E402

if os.environ.get("RT_TEST_LOG_LEVEL"):
    import logging
    logging.basicConfig(level=os.environ["RT_TEST_LOG_LEVEL"])
    logging.getLogger("jax").setLevel(logging.WARNING)

# Every test's setup, call and teardown each get this long (the longest
# test takes 105 s on an idle 8-core machine); a test that truly needs
# more says so with @pytest.mark.timeout_s(n).
TEST_LIMIT_S = 300
# A main thread that sits in a C call never runs the alarm's handler, and
# a finaliser or a bare `except` can swallow what it raises: where the
# phase has not ended this long after the limit, the worker dumps its
# stacks and exits, and xdist replaces it (the run loses that test, not
# its end).
KILL_GRACE_S = 15

_stacks_key = pytest.StashKey()


def _stacks_file(config):
    """This process's stack-dump file under the pytest temp root (with
    xdist each worker's root is its own popen-gw<N>/), opened once."""
    f = config.stash.get(_stacks_key, None)
    if f is None:
        path = config._tmp_path_factory.getbasetemp() / "timeout_stacks.txt"
        f = config.stash[_stacks_key] = open(path, "a+")
    return f


def _in_flight(config):
    """Where the xdist workers of one run each name the test they are in.
    `--dist loadfile` hands a lost worker's file out again from the test it
    died in, so whoever gets it has to know not to run that test again."""
    shared = config._tmp_path_factory.getbasetemp().parent / "in_flight"
    shared.mkdir(exist_ok=True)
    return shared


@contextlib.contextmanager
def _time_limit(item, phase):
    marker = item.get_closest_marker("timeout_s")
    limit = marker.args[0] if marker else TEST_LIMIT_S
    stacks = _stacks_file(item.config)
    stacks.write(f"\n=== {item.nodeid} ({phase}), limit {limit} s\n")
    stacks.flush()
    at = stacks.tell()
    mine = None
    if "PYTEST_XDIST_WORKER" in os.environ:
        mine = _in_flight(item.config) / str(os.getpid())
        mine.write_text(item.nodeid)

    def cut(signum, frame):
        faulthandler.dump_traceback(stacks, all_threads=True)
        stacks.seek(at)
        pytest.fail(
            f"{item.nodeid} ({phase}) was cut at its time limit of "
            f"{limit} s; every thread's stack then (kept in "
            f"{stacks.name}):\n{stacks.read()}", pytrace=False)

    faulthandler.dump_traceback_later(limit + KILL_GRACE_S, exit=True,
                                      file=stacks)
    before = signal.signal(signal.SIGALRM, cut)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)
        faulthandler.cancel_dump_traceback_later()
        if mine is not None:
            mine.write_text("")   # not unlinked: others are reading the files


# Tests that hold a snapshot no later PR can keep, in files this repository's
# PRs may not edit (``tests/benchmark`` is one of BENCHMARK.json's ``paths``:
# only a ``benchmark`` PR edits a file there).  Expected to fail, with the
# reason, until such a PR repairs the test and drops its line here.
STALE_SNAPSHOTS = {
    "test_spec_xing.py::test_published_widths_of_xing":
        "counts the benchmark's cells (7) and configurations (6) as PR 34 "
        "left them; PR 40 added one of each.  Its other assertions run as "
        "test_spec_sdar.py::test_published_widths_of_xing_still_hold",
    "test_itl_mean.py::test_chats_layers_move_the_mean":
        "lists chat's per-layer metrics as PR 44 left them; PR 46 added "
        "prefill_flash_share.chat.  Its assertions run, with that name, as "
        "test_prefill_flash_share.py::test_chats_layers_still_move_the_mean",
    "test_prefill_flash_share.py::test_chats_layers_still_move_the_mean":
        "lists chat's per-layer metrics as PR 46 left them; PR 49 added "
        "paged_kernel_share.  Its assertions run, with that name, as "
        "test_paged_read_metrics.py::test_chats_layers_still_move_the_mean",
    "test_spec_sdar.py::test_what_the_cell_adds_to_the_lists":
        "counts the benchmark's cells (8) and configurations (7) and takes "
        "the lists' last entries as PR 40 left them; PR 48 added one of "
        "each.  Its other assertions run as test_spec_olmo_hybrid.py::"
        "test_what_the_sdar_cell_added_still_stands",
    "test_spec_olmo_hybrid.py::test_what_the_cell_adds_to_the_lists":
        "counts the hybrid cell's per-layer entries (13) and takes the "
        "list's last entries as PR 48 left them; PR 49 added two to the "
        "cell and two to others.  Its other assertions run as "
        "test_paged_read_metrics.py::"
        "test_what_the_hybrid_cell_added_still_stands",
    "test_paged_read_metrics.py::test_the_benchmark_lists_them_last":
        "takes the per-layer list's last four entries as PR 49 left them; PR "
        "50 added paged_kernel_share.xing after them.  Its other assertions "
        "run as test_paged_kernel_share_xing.py::"
        "test_the_benchmark_lists_it_last_and_the_others_before_it",
    "test_spec_sdar.py::test_published_widths_of_xing_still_hold":
        "counts the Xing cell's own per-layer entries (14) as PR 40 found "
        "them; PR 50 added paged_kernel_share.xing.  Its other assertions "
        "run as test_paged_kernel_share_xing.py::"
        "test_published_widths_of_xing_hold_with_the_cells_fifteen",
    "test_xing_reference.py::test_absorbed_decode_is_expanded_decode":
        "asserts the latent pool's folded shape [L, P, page * W] as PR 34 "
        "left it; PR 50 stores a page [page, Wp] in whole lane tiles.  Its "
        "other assertions run, on that shape, as test_xing_latent_pool.py::"
        "test_absorbed_decode_is_expanded_decode_on_padded_pages",
    "test_paged_read_metrics.py::test_what_the_hybrid_cell_added_still_stands":
        "counts the benchmark's cells (9) and configurations (8) and takes "
        "the lists' last entries as PR 49 left them; PR 51 added one of "
        "each.  Its other assertions run as test_spec_kimi_linear.py::"
        "test_what_the_hybrid_cell_added_still_stands",
    "test_paged_kernel_share_xing.py::"
    "test_the_benchmark_lists_it_last_and_the_others_before_it":
        "takes the per-layer list's last five entries as PR 50 left them; PR "
        "51 added the Kimi cell's after them.  Its other assertions run as "
        "test_spec_kimi_linear.py::test_the_paged_read_entries_still_stand",
    "test_spec_kimi_linear.py::test_what_the_hybrid_cell_added_still_stands":
        "takes served_tokens_per_s's cells past the hybrid's as PR 51 left "
        "them (the Kimi cell alone); PR 55 appended "
        "serve-lfm2-longprompt-wide.  Its other assertions run as "
        "test_spec_lfm2_moe.py::test_what_the_hybrid_cell_added_still_stands",
    "test_loop_split.py::"
    "test_the_unlisted_readers_are_in_no_entry_and_all_are_found":
        "takes the files of benchmark/metrics/unlisted/ as PR 53 left them "
        "(eleven); PR 55 added seven readers of the prefill's scopes there, "
        "per_layer still being full.  Its other assertions run as "
        "test_prefill_scopes.py::"
        "test_the_readers_are_files_with_no_entry_and_the_tool_finds_them",
    "test_prefill_scopes.py::"
    "test_the_readers_are_files_with_no_entry_and_the_tool_finds_them":
        "takes the files of benchmark/metrics/unlisted/ as PR 55 left them "
        "(eighteen); PR 57 added nine readers of the sparse attention's "
        "scopes and regions there, per_layer still being full.  Its other "
        "assertions run as test_spec_deepseek_v32.py::"
        "test_the_unlisted_readers_are_files_with_no_entry",
}


def pytest_configure(config):
    """One persistent compile cache for the whole run, in a directory of its
    own under the run's ``TMPDIR``, made here and removed when the run ends:
    the xdist workers (which inherit this process's environment), the tests
    of a file and the processes a test starts compile many of the same tiny
    programs (an engine's two of one tiny model in a dozen files, a
    replica's beside its twin's), and a program one of them has compiled the
    others read back.  A run starts with the cache empty, so nothing is
    carried from an earlier tree.  A directory the environment already names
    stands, and so does one a test sets itself (``tests/benchmark/tiny.py``,
    ``test_chip_smoke.py``).  CHANGES.md, PR 58, has the suite's times with
    and without, file by file."""
    if "JAX_COMPILATION_CACHE_DIR" in os.environ:
        return
    cache = tempfile.mkdtemp(prefix="rt_jax_cache_")
    config.add_cleanup(lambda: shutil.rmtree(cache, ignore_errors=True))
    os.environ.update(JAX_COMPILATION_CACHE_DIR=cache,
                      JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0.3",
                      JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")


# The files that take one worker longest and stand last in the alphabet, in
# the order they are handed out.  ``--dist loadfile`` hands a file to the next
# free worker in collection order, so a long file that starts last runs alone
# at the end while five workers idle: test_tpu_compile.py began ~1,170 s into
# a run of 1,416 s and took 433 s of one worker (PR 59's whole run; the other
# four 195-217 s each).  They start once ``tests/benchmark/`` has been handed
# out, as it always was first (its rehearsals trace a second and a half of a
# three-second window, so they keep the company they have passed in), and the
# run ends when the work does: 1,416 -> 1,325 s in the same hour.
# PR 61: every file outside ``tests/benchmark/`` that took 130 s or more of
# one worker in a whole run of six (the junit file's times), longest first: a
# file of 150-250 s that starts in the run's last minutes idles five workers
# as surely as one of 430 s (the run with PR 61's files was 8,350 s of work,
# 1,392 s a worker, and ended at 1,447 s: 55 s of idle workers at its end).
LONG_FILES = (
    "tests/test_tpu_compile_models.py", "tests/test_models.py",
    "tests/test_serve_resilience.py", "tests/test_linear_attention.py",
    "tests/test_kda.py", "tests/test_tpu_compile_kimi_linear.py",
    "tests/test_pipeline.py", "tests/test_llama_kimi_linear.py",
    "tests/test_engine_kimi_linear.py", "tests/test_tpu_compile.py",
    "tests/test_gbdt_sklearn_trainer.py", "tests/test_moe_dead_rows.py",
    "tests/test_ops.py", "tests/test_paged_attention_kernel.py",
    "tests/test_moe_dropless.py", "tests/test_serve_streaming.py",
)


def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(LONG_FILES)}

    def place(item):                    # the sort is stable: the rest stay
        path = item.nodeid.split("::", 1)[0]
        return (-1 if path.startswith("tests/benchmark/")
                else rank.get(path, len(rank)))
    items.sort(key=place)
    for item in items:
        for tail, reason in STALE_SNAPSHOTS.items():
            if item.nodeid.endswith(tail):
                item.add_marker(pytest.mark.xfail(reason=reason))


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_setup(item):
    with _time_limit(item, "setup"):
        return (yield)


@pytest.hookimpl(specname="pytest_runtest_setup", tryfirst=True)
def pytest_refuse_a_test_that_lost_a_worker(item):
    # Not in the wrapper above: the other plug-ins' wrappers have to be
    # entered before a setup may fail, or their teardowns fail too.
    if "PYTEST_XDIST_WORKER" not in os.environ:
        return
    shared = _in_flight(item.config)
    if any(p.read_text() == item.nodeid for p in shared.iterdir()
           if p.name != str(os.getpid())):
        pytest.fail(
            f"{item.nodeid} already cost the run a worker, which sat deaf "
            f"to the alarm {KILL_GRACE_S} s past the time limit; its "
            f"stacks are in a timeout_stacks.txt under {shared.parent}",
            pytrace=False)


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_call(item):
    with _time_limit(item, "call"):
        return (yield)


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_teardown(item):
    with _time_limit(item, "teardown"):
        return (yield)


def _stop_cluster():
    """ray_tpu.shutdown(); where the time limit cuts it (or it raises),
    kill the daemons, so that the next file on this worker starts clean:
    the raylet's workers leave with it, and with the daemons gone a
    second shutdown() has nothing to wait for and resets the driver."""
    import ray_tpu
    try:
        ray_tpu.shutdown()
    except BaseException:
        import psutil
        for proc in psutil.Process().children(recursive=True):
            with contextlib.suppress(psutil.NoSuchProcess):
                proc.kill()
        ray_tpu.shutdown()
        raise


@pytest.fixture(scope="module")
def ray_start():
    """Module-scoped local cluster with 4 CPUs (reference: ray_start_regular)."""
    import ray_tpu
    # Generous CPU count: module-scoped tests accumulate long-lived actors.
    ray_tpu.init(num_cpus=16, _worker_env={"JAX_PLATFORMS": "cpu"},
                 log_level=os.environ.get("RT_TEST_LOG_LEVEL", "WARNING"))
    yield
    _stop_cluster()


@pytest.fixture
def ray_start_fresh():
    """Function-scoped cluster for tests that mutate cluster state."""
    import ray_tpu
    ray_tpu.init(num_cpus=4, _worker_env={"JAX_PLATFORMS": "cpu"})
    yield
    _stop_cluster()
