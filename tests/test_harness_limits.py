"""The per-test time limit of tests/conftest.py, tried on throw-away test
files in a pytest of their own: a test or a fixture that blocks costs the
run that test and its limit in seconds, never the run's end."""

import os
import re
import subprocess
import sys
import textwrap

import pytest

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))

BLOCKING_TESTS = """
    import time

    import pytest


    @pytest.mark.timeout_s(2)
    def test_sleeps_past_its_limit():
        time.sleep(600)


    def test_after_the_cut_one():
        assert True


    @pytest.fixture
    def blocks_in_teardown():
        yield
        time.sleep(600)


    @pytest.mark.timeout_s(2)
    def test_whose_fixture_blocks(blocks_in_teardown):
        assert True


    @pytest.mark.timeout_s(2)
    def test_inside_its_limit():
        time.sleep(0.2)
"""

# With SIGALRM blocked the handler never runs, as under a main thread that
# sits in a C call: only the watchdog is left.
DEAF_TESTS = """
    import signal
    import time

    import pytest


    @pytest.mark.timeout_s(1)
    def test_deaf_to_the_alarm():
        signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
        time.sleep(600)


    def test_after_the_lost_worker():
        assert True
"""

# The cluster fixtures under the limit: a get() that cannot end, then a
# shutdown() that does not return.
CLUSTER_TESTS = """
    import time

    import psutil
    import pytest

    import ray_tpu


    @ray_tpu.remote
    def never():
        time.sleep(600)


    @pytest.mark.timeout_s(5)
    def test_get_blocks(ray_start_fresh):
        ray_tpu.get(never.remote())


    @pytest.mark.timeout_s(5)
    def test_shutdown_blocks(monkeypatch, ray_start_fresh):
        shutdown, calls = ray_tpu.shutdown, []

        def first_call_blocks():
            calls.append(1)
            if len(calls) == 1:
                time.sleep(600)
            shutdown()

        monkeypatch.setattr(ray_tpu, "shutdown", first_call_blocks)


    def test_next_cluster_starts_clean():
        assert not ray_tpu.is_initialized()
        deadline = time.monotonic() + 30   # daemons leave when their head does
        while psutil.Process().children() and time.monotonic() < deadline:
            time.sleep(0.1)
        assert psutil.Process().children() == []
        ray_tpu.init(num_cpus=1)
        try:
            assert ray_tpu.get(ray_tpu.put(7), timeout=60) == 7
        finally:
            ray_tpu.shutdown()
"""


def _run_pytest(tmp_path, source, *options):
    """Run a pytest of its own on `source` under tests/conftest.py (loaded
    as a plug-in: the file lies outside tests/) and the repo's pytest.ini;
    returns its exit code, its output and what its stack files hold."""
    (tmp_path / "test_throwaway.py").write_text(textwrap.dedent(source))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [TESTS_DIR, os.path.dirname(TESTS_DIR),
         os.environ.get("PYTHONPATH", "")]))
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "test_throwaway.py", "-v",
         "-c", os.path.join(os.path.dirname(TESTS_DIR), "pytest.ini"),
         "--rootdir", str(tmp_path), "-p", "conftest",
         "-p", "no:cacheprovider", "--basetemp", str(tmp_path / "tmp"),
         *options],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=240)
    stacks = "".join(p.read_text() for p in
                     sorted((tmp_path / "tmp").rglob("timeout_stacks.txt")))
    return done.returncode, done.stdout + done.stderr, stacks


@pytest.fixture(scope="module")
def blocking_run(tmp_path_factory):
    return _run_pytest(tmp_path_factory.mktemp("blocking"), BLOCKING_TESTS)


def test_run_with_blocking_tests_reaches_its_end(blocking_run):
    rc, out, _ = blocking_run
    assert rc == 1, out
    assert re.search(r"1 failed, 3 passed, 1 error in", out), out


def test_cut_test_is_named_with_every_threads_stack(blocking_run):
    _, out, stacks = blocking_run
    assert re.search(r"test_throwaway.py::test_sleeps_past_its_limit "
                     r"\(call\) was cut at its time limit of 2 s", out), out
    # faulthandler's dump of the main thread, in the report and in the file
    for text in (out, stacks):
        assert "most recent call first" in text
        assert re.search(r'line \d+ in test_sleeps_past_its_limit', text)
    assert "=== test_throwaway.py::test_sleeps_past_its_limit (call), " \
        "limit 2 s" in stacks


def test_test_after_a_cut_one_still_passes(blocking_run):
    _, out, _ = blocking_run
    assert re.search(r"::test_after_the_cut_one PASSED", out), out


def test_blocking_fixture_teardown_is_cut(blocking_run):
    _, out, stacks = blocking_run
    assert re.search(r"::test_whose_fixture_blocks PASSED", out), out
    assert re.search(r"ERROR at teardown of test_whose_fixture_blocks", out)
    assert re.search(r"test_whose_fixture_blocks \(teardown\) was cut at "
                     r"its time limit of 2 s", out), out
    assert re.search(r"line \d+ in blocks_in_teardown", stacks)


def test_test_inside_its_limit_is_untouched(blocking_run):
    _, out, stacks = blocking_run
    assert re.search(r"::test_inside_its_limit PASSED", out), out
    after = stacks.split("::test_inside_its_limit (call)")[1]
    assert "most recent call first" not in after.split("===")[0]


def test_worker_deaf_to_the_alarm_is_replaced_and_the_run_ends(tmp_path):
    rc, out, stacks = _run_pytest(tmp_path, DEAF_TESTS, "-p", "xdist",
                                  "-n", "1", "--dist", "loadfile")
    assert rc == 1, out
    assert re.search(r"node down", out), out
    assert re.search(r"crashed while running "
                     r"'test_throwaway.py::test_deaf_to_the_alarm'", out), out
    # xdist hands the file out again from the test the worker died in
    assert re.search(r"test_deaf_to_the_alarm already cost the run a "
                     r"worker", out), out
    assert re.search(r"1 failed, 1 passed, 1 error in", out), out
    cut = stacks.split("::test_deaf_to_the_alarm (call), limit 1 s")[1]
    assert "most recent call first" in cut
    assert re.search(r"line \d+ in test_deaf_to_the_alarm", cut)


def test_cut_cluster_tests_leave_the_next_a_clean_start(tmp_path):
    rc, out, stacks = _run_pytest(tmp_path, CLUSTER_TESTS)
    assert rc == 1, out
    assert re.search(r"test_get_blocks \(call\) was cut at its time limit "
                     r"of 5 s", out), out
    assert re.search(r"test_shutdown_blocks \(teardown\) was cut at its "
                     r"time limit of 5 s", out), out
    assert re.search(r"line \d+ in first_call_blocks", stacks)
    assert re.search(r"::test_next_cluster_starts_clean PASSED", out), out
    assert re.search(r"1 failed, 2 passed, 1 error in", out), out
