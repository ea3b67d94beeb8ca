"""rtlint static-analyzer tests: per-rule positive/negative fixtures,
suppression + baseline semantics, CLI smoke, and the repo-clean gate."""

import json
import os
import textwrap

import pytest

from ray_tpu.tools.rtlint import LintConfig, lint_paths
from ray_tpu.tools.rtlint.engine import load_baseline, write_baseline

pytestmark = pytest.mark.lint


def _write(root, rel, src):
    path = root / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(src))
    return path


def _lint(root, **kw):
    return lint_paths([str(root)], **kw)


def _rules_hit(result):
    return {f.rule for f in result.findings}


# ------------------------------------------------------ blocking-in-loop

def test_blocking_in_loop_positive(tmp_path):
    _write(tmp_path / "proj", "a.py", """
        import time
        async def loop_body():
            time.sleep(1)
    """)
    res = _lint(tmp_path / "proj")
    assert [f.rule for f in res.findings] == ["blocking-in-loop"]
    assert "time.sleep" in res.findings[0].message


def test_blocking_in_loop_open_and_subprocess(tmp_path):
    _write(tmp_path / "proj", "a.py", """
        import subprocess
        async def h():
            with open("/tmp/x") as f:
                f.read()
            subprocess.run(["true"])
    """)
    res = _lint(tmp_path / "proj")
    assert len(res.findings) == 2
    assert all(f.rule == "blocking-in-loop" for f in res.findings)


def test_blocking_in_loop_negative_nested_and_await(tmp_path):
    _write(tmp_path / "proj", "a.py", """
        import asyncio, time
        async def h():
            def executor_target():
                time.sleep(1)          # runs on the executor, fine
            await asyncio.sleep(0.1)   # async sleep, fine
            await asyncio.get_running_loop().run_in_executor(
                None, executor_target)
    """)
    assert _lint(tmp_path / "proj").findings == []


def test_blocking_in_loop_sync_helper_expansion(tmp_path):
    _write(tmp_path / "proj", "a.py", """
        class A:
            def _helper(self):
                with open("/tmp/x") as f:
                    return f.read()
            async def h(self):
                return self._helper()
    """)
    res = _lint(tmp_path / "proj")
    assert [f.rule for f in res.findings] == ["blocking-in-loop"]
    assert "_helper" in res.findings[0].message


def test_blocking_in_loop_cloudpickle_only_on_loop_modules(tmp_path):
    src = """
        import cloudpickle
        async def h(msg):
            return cloudpickle.loads(msg)
    """
    _write(tmp_path / "proj", "elsewhere.py", src)
    _write(tmp_path / "proj", "_private/gcs.py", src)
    res = _lint(tmp_path / "proj")
    assert [f.path for f in res.findings] == ["proj/_private/gcs.py"]


def test_blocking_in_loop_cross_module_helper(tmp_path):
    # v2: the project index widens helper expansion one hop across
    # modules — a sync helper imported from another file is seen through.
    _write(tmp_path / "proj", "helpers.py", """
        def read_config(path):
            with open(path) as f:
                return f.read()
    """)
    _write(tmp_path / "proj", "a.py", """
        from helpers import read_config
        async def h():
            return read_config("/etc/rt.json")
    """)
    res = _lint(tmp_path / "proj")
    assert [f.rule for f in res.findings] == ["blocking-in-loop"]
    assert "helpers.py" in res.findings[0].message


# ---------------------------------------------------- pickle-fast-lane

def test_pickle_fast_lane_positive(tmp_path):
    _write(tmp_path / "proj", "_private/protocol.py", """
        import pickle
        class Conn:
            def _flush_outbox_v2(self):
                return pickle.dumps({"x": 1})
    """)
    res = _lint(tmp_path / "proj")
    assert "pickle-fast-lane" in _rules_hit(res)


def test_pickle_fast_lane_ignores_slow_path(tmp_path):
    _write(tmp_path / "proj", "_private/protocol.py", """
        import pickle
        class Conn:
            def _flush_outbox(self):     # legacy v1 path — allowed
                return pickle.dumps({"x": 1})
    """)
    assert "pickle-fast-lane" not in _rules_hit(_lint(tmp_path / "proj"))


def test_pickle_fast_lane_sees_nested_defs(tmp_path):
    _write(tmp_path / "proj", "_private/worker_main.py", """
        import pickle
        class T:
            def fast_actor_call(self, msg):
                def done(fut):
                    return pickle.dumps(fut.result())
                return done
    """)
    assert "pickle-fast-lane" in _rules_hit(_lint(tmp_path / "proj"))


# --------------------------------------------------------- orphan-task

def test_orphan_create_task_positive(tmp_path):
    _write(tmp_path / "proj", "a.py", """
        import asyncio
        async def h():
            asyncio.get_running_loop().create_task(work())
        async def work():
            pass
    """)
    res = _lint(tmp_path / "proj")
    assert [f.rule for f in res.findings] == ["orphan-task"]


def test_orphan_task_tracked_is_clean(tmp_path):
    _write(tmp_path / "proj", "a.py", """
        import asyncio
        async def work():
            pass
        async def h():
            t = asyncio.get_running_loop().create_task(work())
            return t
        async def h2(tasks):
            tasks.append(asyncio.ensure_future(work()))
        async def h3():
            asyncio.get_running_loop().create_task(
                work()).add_done_callback(print)
    """)
    assert _lint(tmp_path / "proj").findings == []


def test_orphan_spawn_helper_is_clean(tmp_path):
    _write(tmp_path / "proj", "a.py", """
        from ray_tpu._private.async_utils import spawn
        async def work():
            pass
        async def h():
            spawn(work(), name="w")
    """)
    assert _lint(tmp_path / "proj").findings == []


def test_unawaited_coroutine_positive(tmp_path):
    _write(tmp_path / "proj", "a.py", """
        async def work():
            pass
        async def h():
            work()          # missing await: never runs
        async def ok():
            await work()
    """)
    res = _lint(tmp_path / "proj")
    assert len(res.findings) == 1
    assert "never awaited" in res.findings[0].message


# --------------------------------------------------- cross-thread-state

_CROSS_SRC = """
    import threading
    class C:
        def __init__(self):
            self.n = 0
            self.lock = threading.Lock()
            threading.Thread(target=self._worker).start()
        def _worker(self):
            {exec_write}
        async def on_loop(self):
            {loop_write}
"""


def test_cross_thread_unlocked_write_flagged(tmp_path):
    _write(tmp_path / "proj", "a.py", _CROSS_SRC.format(
        exec_write="self.n += 1", loop_write="self.n = 0"))
    res = _lint(tmp_path / "proj")
    assert [f.rule for f in res.findings] == ["cross-thread-state"]
    assert "self.n" in res.findings[0].message


def test_cross_thread_locked_write_clean(tmp_path):
    _write(tmp_path / "proj", "a.py", _CROSS_SRC.format(
        exec_write="\n".join(["with self.lock:",
                              "                self.n += 1"]),
        loop_write="\n".join(["with self.lock:",
                              "                self.n = 0"])))
    assert _lint(tmp_path / "proj").findings == []


def test_cross_thread_one_side_only_clean(tmp_path):
    _write(tmp_path / "proj", "a.py", _CROSS_SRC.format(
        exec_write="self.exec_only = 1", loop_write="self.loop_only = 2"))
    assert _lint(tmp_path / "proj").findings == []


def test_cross_thread_annotation_marks_exec_side(tmp_path):
    _write(tmp_path / "proj", "a.py", """
        class C:
            def pumped_externally(self):  # rtlint: thread=exec
                self.shared = 1
            async def on_loop(self):
                self.shared = 2
    """)
    res = _lint(tmp_path / "proj")
    assert [f.rule for f in res.findings] == ["cross-thread-state"]


# ----------------------------------------------------------- jit-purity

def test_jit_purity_decorator_print(tmp_path):
    _write(tmp_path / "proj", "ops/k.py", """
        import jax
        @jax.jit
        def f(x):
            print("tracing", x)
            return x + 1
    """)
    res = _lint(tmp_path / "proj")
    assert [f.rule for f in res.findings] == ["jit-purity"]
    assert "print" in res.findings[0].message


def test_jit_purity_call_form_closure(tmp_path):
    _write(tmp_path / "proj", "models/m.py", """
        import jax, time
        def make_step():
            def step(x):
                t0 = time.time()
                return x * t0
            return jax.jit(step, donate_argnums=(0,))
    """)
    res = _lint(tmp_path / "proj")
    assert [f.rule for f in res.findings] == ["jit-purity"]
    assert "time.time" in res.findings[0].message


def test_jit_purity_outside_scope_dirs_ignored(tmp_path):
    _write(tmp_path / "proj", "scripts/s.py", """
        import jax
        @jax.jit
        def f(x):
            print(x)
            return x
    """)
    assert _lint(tmp_path / "proj").findings == []


def test_jit_purity_clean_kernel(tmp_path):
    _write(tmp_path / "proj", "ops/k.py", """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def f(x):
            jax.debug.print("x={x}", x=x)
            key = jax.random.PRNGKey(0)
            return x + jax.random.normal(key, x.shape)
        def unjitted(x):
            print(x)   # not traced — fine
            return x
    """)
    assert _lint(tmp_path / "proj").findings == []


def test_jit_purity_mutable_static_default(tmp_path):
    _write(tmp_path / "proj", "ops/a.py", """
        import jax
        @jax.jit
        def f(x, cfg=[1, 2]):
            return x
    """)
    res = _lint(tmp_path / "proj")
    assert [f.rule for f in res.findings] == ["jit-purity"]
    assert "hashable" in res.findings[0].message


# -------------------------------------------------- metrics-consistency

_RAYLET_T = """
    class Raylet:
        def _collect_node_stats(self, prev):
            return {{
                "timestamp": 0,
                "workers": [],
                {entries}
            }}
"""
_GCS_T = "_FOLDED_COUNTERS = ({folded})\n"
_STATE_T = "KEYS = ({keys})\n"
_HTTP_T = "NAMES = ({names})\n"


def _metrics_tree(tmp_path, *, entries, folded, state, http):
    root = tmp_path / "proj"
    _write(root, "_private/raylet.py", _RAYLET_T.format(entries=entries))
    _write(root, "_private/gcs.py", _GCS_T.format(folded=folded))
    _write(root, "util/state.py", _STATE_T.format(keys=state))
    _write(root, "dashboard/http_server.py", _HTTP_T.format(names=http))
    return root


def test_metrics_chain_complete_is_clean(tmp_path):
    root = _metrics_tree(
        tmp_path,
        entries='"spilled": self._spilled,',
        folded='"spilled",', state='"spilled",', http='"spilled",')
    assert _lint(root).findings == []


def test_metrics_missing_stage_flagged(tmp_path):
    root = _metrics_tree(
        tmp_path,
        entries='"spilled": self._spilled,',
        folded='"spilled",', state='"spilled",', http='"other",')
    res = _lint(root)
    assert [f.rule for f in res.findings] == ["metrics-consistency"]
    assert "/api/metrics" in res.findings[0].message


def test_metrics_stale_fold_entry_flagged(tmp_path):
    root = _metrics_tree(
        tmp_path,
        entries='"spilled": self._spilled,',
        folded='"spilled", "ghost",', state='"spilled",',
        http='"spilled",')
    res = _lint(root)
    assert len(res.findings) == 1
    assert "ghost" in res.findings[0].message


def test_metrics_skips_partial_lint_runs(tmp_path):
    # only the raylet present: the chain can't be checked, no findings
    _write(tmp_path / "proj", "_private/raylet.py",
           _RAYLET_T.format(entries='"spilled": self._spilled,'))
    assert _lint(tmp_path / "proj").findings == []


# -------------------------------------------------------- durable-write

def test_durable_write_rename_without_fsync(tmp_path):
    _write(tmp_path / "proj", "workflow/api.py", """
        import os
        def save(path, data):
            with open(path + ".tmp", "w") as f:
                f.write(data)
            os.replace(path + ".tmp", path)
    """)
    res = _lint(tmp_path / "proj")
    assert [f.rule for f in res.findings] == ["durable-write"]
    assert "fsync" in res.findings[0].message


def test_durable_write_fsync_between_is_clean(tmp_path):
    _write(tmp_path / "proj", "workflow/api.py", """
        import os
        def save(path, data):
            with open(path + ".tmp", "w") as f:
                f.write(data)
                f.flush()
                os.fsync(f.fileno())
            os.replace(path + ".tmp", path)
    """)
    assert _lint(tmp_path / "proj").findings == []


def test_durable_write_manifest_must_be_last(tmp_path):
    _write(tmp_path / "proj", "workflow/api.py", """
        import json
        def commit(d, payload):
            with open(d + "/manifest.json", "w") as f:
                json.dump({"files": 1}, f)
            with open(d + "/data.bin", "w") as f:
                f.write(payload)
    """)
    res = _lint(tmp_path / "proj")
    assert [f.rule for f in res.findings] == ["durable-write"]
    assert "commit record" in res.findings[0].message


def test_durable_write_cross_module_fsync_helper(tmp_path):
    # an imported helper that provably fsyncs counts as the fsync event
    # at the call site — factored-out durability lints clean.
    _write(tmp_path / "proj", "workflow/fsutil.py", """
        import os
        def fsync_path(path):
            fd = os.open(path, os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
    """)
    _write(tmp_path / "proj", "workflow/api.py", """
        import os
        from workflow.fsutil import fsync_path
        def save(path, data):
            with open(path + ".tmp", "w") as f:
                f.write(data)
            fsync_path(path + ".tmp")
            os.replace(path + ".tmp", path)
    """)
    assert _lint(tmp_path / "proj").findings == []


def test_durable_write_only_in_configured_paths(tmp_path):
    _write(tmp_path / "proj", "misc/files.py", """
        import os
        def save(path, data):
            with open(path + ".tmp", "w") as f:
                f.write(data)
            os.replace(path + ".tmp", path)
    """)
    assert _lint(tmp_path / "proj").findings == []


# -------------------------------------------------- cancellation-safety

def test_cancellation_swallowed_cancel_flagged(tmp_path):
    _write(tmp_path / "proj", "serve/router.py", """
        import asyncio
        async def h(fut):
            try:
                return await fut
            except asyncio.CancelledError:
                return None
    """)
    res = _lint(tmp_path / "proj")
    assert [f.rule for f in res.findings] == ["cancellation-safety"]
    assert "swallows CancelledError" in res.findings[0].message


def test_cancellation_base_exception_and_bare(tmp_path):
    _write(tmp_path / "proj", "serve/router.py", """
        async def h(fut, log):
            try:
                return await fut
            except BaseException:
                log("boom")
        async def h2(fut, log):
            try:
                return await fut
            except:
                log("boom")
    """)
    res = _lint(tmp_path / "proj")
    assert len(res.findings) == 2
    assert all(f.rule == "cancellation-safety" for f in res.findings)


def test_cancellation_reraise_and_terminal_clean(tmp_path):
    _write(tmp_path / "proj", "serve/router.py", """
        import os
        async def h(fut, cleanup):
            try:
                return await fut
            except BaseException:
                cleanup()
                raise
        def watchdog(fn):
            try:
                fn()
            except BaseException:
                os._exit(1)
    """)
    assert _lint(tmp_path / "proj").findings == []


def test_cancellation_reaper_pattern_clean(tmp_path):
    _write(tmp_path / "proj", "serve/router.py", """
        import asyncio
        async def reap(task):
            task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                pass
    """)
    assert _lint(tmp_path / "proj").findings == []


def test_cancellation_mixed_tuple_flagged(tmp_path):
    # mixed tuples are never exempt: the cancel silently takes the
    # error-recovery path.
    _write(tmp_path / "proj", "serve/router.py", """
        import asyncio
        async def h(fut):
            try:
                return await fut
            except (ValueError, asyncio.CancelledError):
                return "fallback"
    """)
    res = _lint(tmp_path / "proj")
    assert [f.rule for f in res.findings] == ["cancellation-safety"]
    assert "operational errors" in res.findings[0].message


def test_cancellation_only_in_configured_paths(tmp_path):
    _write(tmp_path / "proj", "misc.py", """
        import asyncio
        async def h(fut):
            try:
                return await fut
            except asyncio.CancelledError:
                return None
    """)
    assert _lint(tmp_path / "proj").findings == []


# ---------------------------------------------------------- resource-leak

def _leak_cfg():
    return LintConfig(resource_pairs=(
        {"name": "pages", "paths": ("engine/",),
         "alloc": r"\.alloc$", "release": r"\.free$",
         "what": "KV pages"},))


def test_resource_leak_never_released(tmp_path):
    _write(tmp_path / "proj", "engine/e.py", """
        class E:
            def admit(self, n):
                pages = self.pool.alloc(n)
                self.run(pages)
    """)
    res = _lint(tmp_path / "proj", config=_leak_cfg())
    assert [f.rule for f in res.findings] == ["resource-leak"]
    assert "never released" in res.findings[0].message


def test_resource_leak_straight_line_release_flagged(tmp_path):
    _write(tmp_path / "proj", "engine/e.py", """
        class E:
            def admit(self, n):
                pages = self.pool.alloc(n)
                self.run(pages)
                self.pool.free(pages)
    """)
    res = _lint(tmp_path / "proj", config=_leak_cfg())
    assert [f.rule for f in res.findings] == ["resource-leak"]
    assert "straight-line" in res.findings[0].message


def test_resource_leak_finally_release_clean(tmp_path):
    _write(tmp_path / "proj", "engine/e.py", """
        class E:
            def admit(self, n):
                pages = self.pool.alloc(n)
                try:
                    self.run(pages)
                finally:
                    self.pool.free(pages)
    """)
    assert _lint(tmp_path / "proj", config=_leak_cfg()).findings == []


def test_resource_leak_cross_module_release(tmp_path):
    # escaping allocation: release may live anywhere in the project.
    _write(tmp_path / "proj", "engine/e.py", """
        class E:
            def admit(self, n):
                self.pages = self.pool.alloc(n)
    """)
    res = _lint(tmp_path / "proj", config=_leak_cfg())
    assert [f.rule for f in res.findings] == ["resource-leak"]
    assert "nothing can ever free it" in res.findings[0].message
    _write(tmp_path / "proj", "ingress/r.py", """
        class R:
            def retire(self, e):
                e.pool.free(e.pages)
    """)
    assert _lint(tmp_path / "proj", config=_leak_cfg()).findings == []


def test_resource_leak_default_plasma_pair(tmp_path):
    _write(tmp_path / "proj", "_private/plasma.py", """
        class Store:
            def put(self, oid, data):
                buf = self.create(oid, len(data))
                buf[:len(data)] = data
                self.seal(oid)
    """)
    res = _lint(tmp_path / "proj")
    assert [f.rule for f in res.findings] == ["resource-leak"]
    # the runtime's fix shape: release + re-raise on the error path
    _write(tmp_path / "proj", "_private/plasma.py", """
        class Store:
            def put(self, oid, data):
                buf = self.create(oid, len(data))
                try:
                    buf[:len(data)] = data
                    self.seal(oid)
                except BaseException:
                    self.delete(oid)
                    raise
    """)
    assert _lint(tmp_path / "proj").findings == []


# ------------------------------------------------------------ knob-drift

def _knob_cfg():
    return LintConfig(knob_docs=("docs/KNOBS.md",))


def test_knob_drift_undocumented_read(tmp_path):
    _write(tmp_path, "docs/KNOBS.md", "| `RT_DOCD` | 1 | documented |\n")
    _write(tmp_path / "proj", "a.py", """
        import os
        A = os.environ.get("RT_DOCD", "1")
        B = os.environ.get("RT_MYSTERY", "0")
    """)
    res = _lint(tmp_path / "proj", config=_knob_cfg())
    assert [f.rule for f in res.findings] == ["knob-drift"]
    assert "RT_MYSTERY" in res.findings[0].message


def test_knob_drift_stale_doc_token(tmp_path):
    _write(tmp_path, "docs/KNOBS.md", "Set RT_GHOST to tune nothing.\n")
    _write(tmp_path / "proj", "a.py", "X = 1\n")
    res = _lint(tmp_path / "proj", config=_knob_cfg())
    assert [f.rule for f in res.findings] == ["knob-drift"]
    assert "RT_GHOST" in res.findings[0].message
    assert res.findings[0].path == "docs/KNOBS.md"


def test_knob_drift_wildcard_and_internal_clean(tmp_path):
    _write(tmp_path, "docs/KNOBS.md", "The RT_FAM_* family of knobs.\n")
    _write(tmp_path / "proj", "a.py", """
        import os
        A = os.environ.get("RT_FAM_ALPHA")
        B = os.environ["RT_ADDRESS"]
    """)
    assert _lint(tmp_path / "proj", config=_knob_cfg()).findings == []


def test_knob_drift_fault_hook_rename(tmp_path):
    _write(tmp_path / "proj", "util/fault_injection.py", """
        class FaultSpec:
            kill_after: float = 0.0
        def kill_replica(name):
            return name
    """)
    _write(tmp_path / "proj", "chaos.py", """
        from util import fault_injection
        from util.fault_injection import kill_replica, ghost_hook
        def scenario():
            fault_injection.kill_replica("r1")
            fault_injection.stall_decode("r1")
            return fault_injection.FaultSpec(kill_after=1.0, killafter=2.0)
    """)
    res = _lint(tmp_path / "proj")
    assert all(f.rule == "knob-drift" for f in res.findings)
    msgs = " ".join(f.message for f in res.findings)
    assert "ghost_hook" in msgs       # import of a non-existent hook
    assert "stall_decode" in msgs     # attr call on a non-existent hook
    assert "killafter" in msgs        # FaultSpec kwarg with no field
    assert "kill_replica" not in msgs


def test_knob_drift_counter_chain(tmp_path):
    _write(tmp_path / "proj", "serve/metrics.py", """
        COUNTER_NAMES = ("hits", "misses")
        def bump(name, n=1):
            pass
    """)
    _write(tmp_path / "proj", "serve/router.py", """
        from serve import metrics
        def record():
            metrics.bump("hits")
            metrics.bump("typo_counter")
    """)
    _write(tmp_path / "proj", "_private/gcs.py",
           '_FOLDED_COUNTERS = ("hits",)\n')
    res = _lint(tmp_path / "proj")
    assert len(res.findings) == 2
    assert all(f.rule == "knob-drift" for f in res.findings)
    msgs = " ".join(f.message for f in res.findings)
    assert "typo_counter" in msgs     # bump of an unregistered counter
    assert "misses" in msgs           # registered but dropped by the fold


# ----------------------------------------- suppressions, baseline, CLI

def test_inline_suppression(tmp_path):
    _write(tmp_path / "proj", "a.py", """
        import time
        async def h():
            time.sleep(1)  # rtlint: disable=blocking-in-loop
        async def h2():
            time.sleep(1)  # rtlint: disable
        async def h3():
            time.sleep(1)  # still flagged
    """)
    res = _lint(tmp_path / "proj")
    assert len(res.findings) == 1
    assert res.findings[0].scope == "h3"


def test_suppression_justification_text(tmp_path):
    # everything after the rule list is free-form justification
    _write(tmp_path / "proj", "a.py", """
        import time
        async def h():
            time.sleep(1)  # rtlint: disable=blocking-in-loop - vendor API is sync
    """)
    assert _lint(tmp_path / "proj").findings == []


def test_suppression_comment_above_statement(tmp_path):
    # a standalone directive comment attaches to the next code line,
    # and only to that line
    _write(tmp_path / "proj", "a.py", """
        import time
        async def h():
            # rtlint: disable=blocking-in-loop - startup path, loop idle
            time.sleep(1)
        async def h2():
            time.sleep(1)
    """)
    res = _lint(tmp_path / "proj")
    assert [f.scope for f in res.findings] == ["h2"]


def test_suppression_comment_above_skips_blank_lines(tmp_path):
    _write(tmp_path / "proj", "a.py", """
        import time
        async def h():
            # rtlint: disable=blocking-in-loop - slow path

            # more commentary between directive and statement
            time.sleep(1)
    """)
    assert _lint(tmp_path / "proj").findings == []


def test_suppression_above_except_handler(tmp_path):
    # cancellation findings anchor on the handler line; a directive
    # comment directly above the except suppresses them
    _write(tmp_path / "proj", "serve/r.py", """
        import asyncio
        async def h(fut):
            try:
                return await fut
            # rtlint: disable=cancellation-safety - reap is documented
            except asyncio.CancelledError:
                return None
    """)
    assert _lint(tmp_path / "proj").findings == []


def test_suppression_spans_multiline_statement(tmp_path):
    _write(tmp_path / "proj", "a.py", """
        import asyncio
        async def work():
            pass
        async def h():
            asyncio.get_running_loop().create_task(
                work())  # rtlint: disable=orphan-task
    """)
    assert _lint(tmp_path / "proj").findings == []


def test_file_level_suppression(tmp_path):
    _write(tmp_path / "proj", "a.py", """
        # rtlint: disable-file=blocking-in-loop
        import time
        async def h():
            time.sleep(1)
    """)
    assert _lint(tmp_path / "proj").findings == []


def test_baseline_roundtrip(tmp_path):
    _write(tmp_path / "proj", "a.py", """
        import time
        async def h():
            time.sleep(1)
    """)
    res = _lint(tmp_path / "proj")
    assert len(res.findings) == 1
    bl = tmp_path / "baseline.json"
    write_baseline(str(bl), res.findings)
    res2 = _lint(tmp_path / "proj", baseline=load_baseline(str(bl)))
    assert res2.findings == []
    assert len(res2.baselined) == 1
    # a NEW finding is still actionable under the old baseline
    _write(tmp_path / "proj", "b.py", """
        import time
        async def g():
            time.sleep(2)
    """)
    res3 = _lint(tmp_path / "proj", baseline=load_baseline(str(bl)))
    assert len(res3.findings) == 1
    assert res3.findings[0].path == "proj/b.py"


def test_fingerprint_survives_line_drift(tmp_path):
    src = """
        import time
        async def h():
            time.sleep(1)
    """
    _write(tmp_path / "proj", "a.py", src)
    fp1 = _lint(tmp_path / "proj").findings[0].fingerprint
    _write(tmp_path / "proj", "a.py", "# a new leading comment\n"
           + textwrap.dedent(src))
    fp2 = _lint(tmp_path / "proj").findings[0].fingerprint
    assert fp1 == fp2


def test_cli_json_and_exit_codes(tmp_path, capsys):
    from ray_tpu.tools.rtlint.__main__ import main
    _write(tmp_path / "proj", "a.py", """
        import time
        async def h():
            time.sleep(1)
    """)
    rc = main(["--format", "json", "--no-baseline",
               str(tmp_path / "proj")])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out["findings"][0]["rule"] == "blocking-in-loop"
    # write-baseline then rerun: clean exit
    rc = main(["--write-baseline", str(tmp_path / "proj")])
    assert rc == 0
    capsys.readouterr()
    rc = main([str(tmp_path / "proj")])
    assert rc == 0
    assert main(["--list-rules"]) == 0
    assert main([str(tmp_path / "missing")]) == 2
    assert main(["--rules", "bogus", str(tmp_path / "proj")]) == 2


def test_rule_filter(tmp_path):
    from ray_tpu.tools.rtlint.__main__ import main
    _write(tmp_path / "proj", "a.py", """
        import time
        async def h():
            time.sleep(1)
    """)
    assert main(["--rules", "orphan-task", "--no-baseline",
                 str(tmp_path / "proj")]) == 0


def test_cli_changed_mode(tmp_path, capsys, monkeypatch):
    from ray_tpu.tools.rtlint.__main__ import main
    import subprocess
    monkeypatch.chdir(tmp_path)
    _write(tmp_path, "proj/a.py", """
        import time
        async def a():
            time.sleep(1)
    """)
    subprocess.run(["git", "init", "-q"], check=True)
    subprocess.run(["git", "add", "."], check=True)
    subprocess.run(["git", "-c", "user.name=t", "-c", "user.email=t@t",
                    "commit", "-qm", "seed"], check=True)
    # modify one tracked file, add one untracked — both report; the
    # committed-and-unchanged a.py does not, though it is still indexed
    _write(tmp_path, "proj/b.py", """
        import time
        async def b():
            time.sleep(2)
    """)
    rc = main(["--changed", "HEAD", "--format", "json", "--no-baseline",
               "proj"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert [f["path"] for f in out["findings"]] == ["proj/b.py"]
    assert out["files_checked"] == 2   # whole tree still parsed
    # unchanged worktree vs HEAD: nothing to report
    subprocess.run(["git", "add", "."], check=True)
    subprocess.run(["git", "-c", "user.name=t", "-c", "user.email=t@t",
                    "commit", "-qm", "b"], check=True)
    rc = main(["--changed", "HEAD", "--no-baseline", "proj"])
    assert rc == 0
    capsys.readouterr()


def test_cli_changed_bad_ref_reports_everything(tmp_path, capsys,
                                                monkeypatch):
    from ray_tpu.tools.rtlint.__main__ import main
    monkeypatch.chdir(tmp_path)
    _write(tmp_path, "proj/a.py", """
        import time
        async def a():
            time.sleep(1)
    """)
    rc = main(["--changed", "no-such-ref", "--format", "json",
               "--no-baseline", "proj"])
    cap = capsys.readouterr()
    out = json.loads(cap.out)
    assert rc == 1
    assert "reporting everything" in cap.err
    assert [f["path"] for f in out["findings"]] == ["proj/a.py"]


# ------------------------------------------------------- repo-clean gate

def test_new_rules_registered():
    from ray_tpu.tools.rtlint.engine import default_rules
    names = {r.name for r in default_rules()}
    assert {"durable-write", "cancellation-safety",
            "resource-leak", "knob-drift"} <= names


def test_repo_is_rtlint_clean():
    """The gate the CI preflight relies on: rtlint over the real ray_tpu/
    tree reports zero findings with all ten rules active and an EMPTY
    baseline — v2 burned the grandfathered findings down to nothing."""
    from ray_tpu.tools.rtlint.engine import default_rules
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pkg = os.path.join(repo, "ray_tpu")
    baseline = load_baseline(os.path.join(repo, ".rtlint-baseline.json"))
    assert len(default_rules()) >= 10
    assert baseline == set(), "the baseline must stay empty"
    res = lint_paths([pkg], baseline=baseline)
    assert res.errors == []
    msgs = [f.render() for f in res.findings]
    assert msgs == [], "rtlint found new issues:\n" + "\n".join(msgs)
