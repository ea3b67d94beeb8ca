"""Release-suite harness: yaml-subset loader + criteria evaluation.

Reference analog: release/release_tests.yaml + ray_release runner (success
criteria with hard pass/fail per workload).
"""

import importlib.util
import json
import os
import shlex
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "release"))

from run_release_suite import load_suite, run_test  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUITE = load_suite(os.path.join(REPO, "release", "release_tests.yaml"))


def test_load_suite_parses_entries():
    tests = SUITE
    names = {t["name"] for t in tests}
    assert {"microbenchmark", "train_ckpt_async_bench",
            "multichip_dryrun"} <= names
    mb = next(t for t in tests if t["name"] == "microbenchmark")
    assert "smoke" in mb["suite"]
    assert mb["timeout_s"] == 420
    assert mb["success_criteria"]["1_1_actor_calls_sync"]["min"] == 1500


@pytest.mark.parametrize("row", SUITE, ids=[t["name"] for t in SUITE])
def test_entrypoint_exists(row):
    """What a row runs is in the tree: the script, or the ``-m`` module,
    and every ``.py`` file handed to it (a row left behind by a deleted
    benchmark or test file fails here, not in the nightly)."""
    argv = shlex.split(row["entrypoint"])
    assert argv[0] == "python", row["entrypoint"]
    if argv[1] == "-m":
        assert importlib.util.find_spec(argv[2]) is not None, argv[2]
    files = [a for a in argv[1:] if a.endswith(".py")]
    assert files or argv[1] == "-m", row["entrypoint"]
    for path in files:
        assert os.path.isfile(os.path.join(REPO, path)), path


def test_run_test_evaluates_criteria(tmp_path):
    script = tmp_path / "emit.py"
    script.write_text(
        "import json\n"
        "print(json.dumps({'metric': 'speed', 'value': 10.0}))\n"
        "print(json.dumps({'metric': 'mem', 'value': 3.0}))\n")
    base = {"name": "t", "entrypoint": f"{sys.executable} {script}",
            "timeout_s": 60}
    ok = run_test({**base, "success_criteria": {
        "speed": {"min": 5}, "mem": {"max": 4}}})
    assert ok["passed"], ok["failures"]
    assert ok["metrics"]["speed"]["value"] == 10.0

    bad = run_test({**base, "success_criteria": {"speed": {"min": 50}}})
    assert not bad["passed"]
    assert "speed=10.0 < min 50" in bad["failures"][0]

    missing = run_test({**base, "success_criteria": {"nope": {"min": 1}}})
    assert not missing["passed"]


def test_run_test_fails_on_nonzero_exit(tmp_path):
    script = tmp_path / "boom.py"
    script.write_text("import sys; sys.exit(3)\n")
    r = run_test({"name": "t", "entrypoint": f"{sys.executable} {script}",
                  "timeout_s": 60, "success_criteria": {}})
    assert not r["passed"]
    assert "exit code 3" in r["failures"][0]
