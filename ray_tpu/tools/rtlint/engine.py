"""rtlint engine: file loading, suppressions, baseline, rule dispatch.

rtlint is a project-native static analyzer that encodes the runtime's
load-bearing invariants as AST checks — the review-time counterpart to
the runtime guards (LoopWatchdog's ``loop_lag_ms``, ``wire.stats``
fallback counters, chaos profiles).  It never imports or executes the
code it lints: everything is ``ast.parse`` over source text, so it is
safe to run against broken or heavyweight modules.

Key concepts
------------
FileUnit      one parsed source file (source, lines, tree, suppressions)
ProjectContext all FileUnits of a run — project rules (metrics
              consistency) cross-reference files through it
Finding       one diagnostic, with a *stable fingerprint* keyed on
              (rule, path, enclosing scope, normalized source line) so
              baselines survive unrelated line drift
Baseline      checked-in JSON of grandfathered fingerprints; findings
              matching it are reported separately and don't fail the run

Suppressions
------------
``# rtlint: disable=rule-a,rule-b``  on the offending line
``# rtlint: disable``                all rules on that line
``# rtlint: disable-file=rule-a``    whole file (any line)
``# rtlint: thread=exec``            annotation consumed by the
                                     cross-thread-state rule (marks a
                                     ``def`` as exec-thread-side)

A directive on a comment-only line attaches to the next code line (so a
justification block can precede the offending statement), and anything
after the rule list — ``disable=rule - because ...`` — is justification
text, ignored by the parser but required by convention: a suppression
with no stated reason is a review comment waiting to happen.
"""

from __future__ import annotations

import ast
import hashlib
import json
import os
import re
import tokenize
from dataclasses import dataclass, field
from io import StringIO
from typing import Dict, Iterable, List, Optional, Set, Tuple

_DIRECTIVE_RE = re.compile(
    r"#\s*rtlint:\s*(disable-file|disable|thread)\s*(?:=\s*([\w\-, ]+))?")


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str            # posix-ish path as reported (root-basename relative)
    line: int
    col: int
    message: str
    scope: str = ""      # enclosing function/class qualname, "" at module level
    source: str = ""     # stripped source line (fingerprint ingredient)
    end_line: int = 0    # statement end (suppression comments anywhere in
                         # the span count); 0 → same as line

    @property
    def fingerprint(self) -> str:
        h = hashlib.sha1()
        for part in (self.rule, self.path, self.scope, self.source):
            h.update(part.encode("utf-8", "replace"))
            h.update(b"\0")
        return h.hexdigest()[:16]

    def to_dict(self) -> Dict[str, object]:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "col": self.col, "message": self.message,
                "scope": self.scope, "fingerprint": self.fingerprint}

    def render(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: "
                f"[{self.rule}] {self.message}")


@dataclass
class FileUnit:
    path: str                   # reported (relative) path
    abspath: str
    source: str
    tree: ast.AST
    lines: List[str]
    # line -> set of suppressed rule names; "*" means all rules
    line_suppress: Dict[int, Set[str]] = field(default_factory=dict)
    file_suppress: Set[str] = field(default_factory=set)
    # line -> thread annotation value ("exec" / "loop")
    thread_marks: Dict[int, str] = field(default_factory=dict)
    parents: Dict[ast.AST, ast.AST] = field(default_factory=dict)

    def scope_of(self, node: ast.AST) -> str:
        """Dotted qualname of the enclosing class/function chain."""
        names: List[str] = []
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                names.append(cur.name)
            cur = self.parents.get(cur)
        return ".".join(reversed(names))

    def source_line(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def suppressed(self, rule: str, lineno: int,
                   end_lineno: int = 0) -> bool:
        if rule in self.file_suppress or "*" in self.file_suppress:
            return True
        # a disable comment anywhere in the statement span counts (multi-
        # line calls put the comment wherever the formatter allows)
        end = min(max(lineno, end_lineno), lineno + 10)
        for ln in range(lineno, end + 1):
            rules = self.line_suppress.get(ln)
            if rules and (rule in rules or "*" in rules):
                return True
        return False


@dataclass
class LintConfig:
    """Everything path- or project-specific, overridable so tests can
    point rules at fixture trees instead of the real runtime files."""

    # rule 1: modules whose async defs run on latency-critical loops get
    # the stricter serialization checks (cloudpickle on the loop thread).
    loop_critical_suffixes: Tuple[str, ...] = (
        "_private/gcs.py", "_private/raylet.py", "_private/core_worker.py",
        "_private/worker_main.py", "_private/protocol.py",
        "_private/daemon_main.py",
    )
    # rule 2: path suffix -> regex matched against the (sync or async)
    # function name; functions matching are "fast lane": no pickle.
    fast_lane: Dict[str, str] = field(default_factory=lambda: {
        "_private/protocol.py":
            r"(_v2|^reply_soon$|^_write_frame_nowait$|^_dispatch_batch$)",
        "_private/worker_main.py": r"^(fast_actor_call|_fast_reply)$",
        "_private/core_worker.py":
            r"^(resolve_args_fast|_resolve_inline|pack_return_sync"
            r"|_fast_dispatch)$",
        # object-plane hot paths (ROADMAP item 3: the zero-pickle
        # invariant follows the wire down into chunk push/pull + spill)
        "_private/object_transfer.py":
            r"^(push_object_chunks|fetch_object_into|read_spill_chunk"
            r"|write_spill_file|read_spill_file)$",
        "_private/raylet.py":
            r"^(_h_fetch_object|_h_pull_object|_h_push_object"
            r"|_h_receive_object_chunk)$",
        # Dataset shuffle framing: shards move as raw blocks, never
        # ad-hoc pickled by the shuffle plan itself
        "data/push_shuffle.py":
            r"^(push_based_shuffle|add|finalize|_split_block_even)$",
        "data/dataset.py":
            r"^(_shuffle_partition|_shuffle_merge|_merge_blocks_local)$",
    })
    # rule 3: call names treated as safe task-spawn helpers (they attach
    # the exception-logging done callback themselves).
    spawn_helpers: Tuple[str, ...] = ("spawn", "spawn_logged")
    # rule 5: directories (path fragments) where jit purity is enforced.
    jit_dirs: Tuple[str, ...] = ("ops/", "models/", "train/", "parallel/")
    # rule 6: role -> path suffix for the metrics pipeline files.
    metrics_roles: Dict[str, str] = field(default_factory=lambda: {
        "node_stats": "_private/raylet.py",
        "fold": "_private/gcs.py",
        "state": "util/state.py",
        "http": "dashboard/http_server.py",
    })
    # node-stat dict keys that are structural, not counters.
    metrics_ignore: Tuple[str, ...] = (
        "timestamp", "load_avg", "mem_total", "mem_available",
        "object_store", "workers", "num_workers", "loop_lag_ms",
    )
    # rule 7 (durable-write): files holding commit-protocol writers —
    # every tmp-write + rename in them must follow tmp → fsync → rename,
    # with the manifest/commit record written last.
    durable_paths: Tuple[str, ...] = (
        "train/_internal/checkpoint_store.py",
        "train/jax/orbax_checkpoint.py",
        "_private/object_transfer.py",
        "_private/gcs.py",
        "_private/daemon_main.py",
        "workflow/api.py",
    )
    # rule 8 (cancellation-safety): path fragments where swallowing
    # CancelledError/Preempted/BaseException is flagged.
    cancel_paths: Tuple[str, ...] = (
        "_private/", "serve/", "train/", "util/", "dashboard/",
    )
    # rule 9 (resource-leak): paired acquire/release call specs.  ``alloc``
    # and ``release`` are regexes matched against the full dotted call
    # name; ``paths`` scopes which files are scanned for allocations
    # (releases are matched project-wide so cross-module pairing works).
    resource_pairs: Tuple[Dict[str, object], ...] = field(
        default_factory=lambda: default_resource_pairs())
    # rule 10 (knob-drift): doc files (relative to the lint root's parent,
    # i.e. the repo root) that must agree with the RT_* knobs the code
    # reads; internal plumbing vars the runtime sets for its own children
    # are exempt.
    knob_docs: Tuple[str, ...] = (
        "docs/KNOBS.md", "docs/SERVE.md", "docs/TRAIN.md",
        "docs/LINT.md", "ARCHITECTURE.md",
    )
    knob_internal: Tuple[str, ...] = (
        "RT_ADDRESS", "RT_GCS_ADDRESS", "RT_RAYLET_ADDRESS",
        "RT_NODE_ID", "RT_WORKER_ID", "RT_STORE_NAME", "RT_LOG_DIR",
        "RT_SESSION_DIR", "RT_RUNTIME_ENV", "RT_SYSTEM_CONFIG",
        "RT_JOB_SUBMISSION_ID", "RT_CLIENT_SESSION_ID",
        "RT_CLIENT_SESSION_GCS",
    )
    # suffix of the file whose defs/FaultSpec fields are the ground truth
    # for fault-injection hook names.
    fault_injection_path: str = "util/fault_injection.py"
    # suffixes of the per-package counter-registry modules checked by the
    # knob-drift bump audit (bump("x") must hit a registered counter).
    counter_registries: Tuple[str, ...] = (
        "serve/metrics.py", "train/metrics.py",
    )


def default_resource_pairs() -> Tuple[Dict[str, object], ...]:
    """The runtime's paired-resource contracts (kept out of LintConfig's
    dataclass default so tests can build small configs without them)."""
    return (
        {"name": "kv-pages",
         "paths": ("serve/engine/",),
         "alloc": r"\.alloc$",
         "release": r"\.free$",
         "what": "KV-cache pages"},
        {"name": "plasma-buffer",
         "paths": ("_private/plasma.py", "_private/raylet.py",
                   "_private/core_worker.py"),
         "alloc": r"(^|\.)(plasma\.create|_create_with_spill)$"
                  r"|^self\.create$",
         "release": r"\.(seal|delete|abort)$",
         "what": "an unsealed plasma allocation"},
        {"name": "stream-state",
         "paths": ("_private/core_worker.py",),
         "alloc": r"(^|\.)register_stream$",
         "release": r"_streams\.pop$|(^|\.)cancel_stream$",
         "what": "owner-side stream consumer state"},
    )


class Rule:
    """Base: subclasses set ``name`` and override check / check_project.
    ``index`` is the run's ProjectIndex (cross-module symbol/import table
    + one-hop call resolution); it is always provided by lint_paths but
    defaults to None so rules stay callable standalone in tests."""

    name = ""

    def check(self, unit: FileUnit, config: LintConfig,
              index=None) -> Iterable[Finding]:
        return ()

    def check_project(self, units: List[FileUnit], config: LintConfig,
                      index=None) -> Iterable[Finding]:
        return ()


def _directive_rules(arg: str) -> Set[str]:
    """Rule names from a directive argument.  Each comma-separated chunk
    keeps only its first whitespace-delimited token, so justification
    text after the rule list (``disable=rule - reason why``) is ignored."""
    rules = set()
    for chunk in arg.split(","):
        parts = chunk.split()
        if parts:
            rules.add(parts[0])
    return rules


def _parse_directives(source: str, unit: FileUnit) -> None:
    """Scan comments via tokenize so strings containing 'rtlint:' don't
    trigger; fills unit.line_suppress / file_suppress / thread_marks.

    A ``disable`` on a comment-only line attaches to the next code line
    (skipping the rest of the comment block), so a multi-line
    justification can sit above the statement it excuses."""
    try:
        tokens = tokenize.generate_tokens(StringIO(source).readline)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = _DIRECTIVE_RE.search(tok.string)
            if not m:
                continue
            kind, arg = m.group(1), (m.group(2) or "").strip()
            rules = _directive_rules(arg) if arg else {"*"}
            if kind == "disable":
                line = tok.start[0]
                stripped = unit.lines[line - 1].strip() \
                    if line <= len(unit.lines) else ""
                if stripped.startswith("#"):
                    # Standalone comment: attach to the statement below.
                    ln = line + 1
                    while ln <= len(unit.lines) and (
                            not unit.lines[ln - 1].strip()
                            or unit.lines[ln - 1].lstrip().startswith("#")):
                        ln += 1
                    line = ln
                unit.line_suppress.setdefault(line, set()).update(rules)
            elif kind == "disable-file":
                unit.file_suppress.update(rules)
            elif kind == "thread":
                unit.thread_marks[tok.start[0]] = arg or "exec"
    except (tokenize.TokenError, IndentationError, SyntaxError):
        pass


def load_unit(abspath: str, rel: str) -> Optional[FileUnit]:
    try:
        with open(abspath, "r", encoding="utf-8", errors="replace") as f:
            source = f.read()
        tree = ast.parse(source)
    except (OSError, SyntaxError, ValueError):
        return None
    unit = FileUnit(path=rel, abspath=abspath, source=source, tree=tree,
                    lines=source.splitlines())
    for parent in ast.walk(tree):
        for child in ast.iter_child_nodes(parent):
            unit.parents[child] = parent
    _parse_directives(source, unit)
    return unit


def collect_files(paths: Iterable[str]) -> List[Tuple[str, str]]:
    """Expand path args to (abspath, reported-rel) pairs.

    Reported paths are rooted at the argument's basename so fingerprints
    don't depend on the caller's cwd: ``rtlint ray_tpu/`` reports
    ``ray_tpu/_private/gcs.py`` regardless of where it runs from."""
    out: List[Tuple[str, str]] = []
    for p in paths:
        p = p.rstrip("/")
        if os.path.isfile(p):
            out.append((os.path.abspath(p), os.path.basename(p)))
            continue
        base = os.path.basename(os.path.abspath(p))
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = sorted(d for d in dirnames
                                 if d != "__pycache__"
                                 and not d.startswith("."))
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                ap = os.path.abspath(os.path.join(dirpath, fn))
                rel = os.path.join(
                    base, os.path.relpath(ap, os.path.abspath(p)))
                out.append((ap, rel.replace(os.sep, "/")))
    return out


def default_rules() -> List[Rule]:
    from ray_tpu.tools.rtlint.rules import (blocking_in_loop,
                                            cancellation_safety,
                                            cross_thread_state,
                                            durable_write, jit_purity,
                                            knob_drift,
                                            metrics_consistency,
                                            orphan_task, pickle_fast_lane,
                                            resource_leak)
    return [blocking_in_loop.BlockingInLoop(),
            pickle_fast_lane.PickleFastLane(),
            orphan_task.OrphanTask(),
            cross_thread_state.CrossThreadState(),
            jit_purity.JitPurity(),
            metrics_consistency.MetricsConsistency(),
            durable_write.DurableWrite(),
            cancellation_safety.CancellationSafety(),
            resource_leak.ResourceLeak(),
            knob_drift.KnobDrift()]


@dataclass
class LintResult:
    findings: List[Finding]          # actionable (not baselined)
    baselined: List[Finding]
    files_checked: int
    errors: List[str] = field(default_factory=list)


def load_baseline(path: str) -> Set[str]:
    try:
        with open(path, "r", encoding="utf-8") as f:
            data = json.load(f)
        return {str(e["fingerprint"]) for e in data.get("findings", [])}
    except (OSError, ValueError, KeyError, TypeError):
        return set()


def write_baseline(path: str, findings: List[Finding]) -> None:
    entries = sorted(
        ({"fingerprint": f.fingerprint, "rule": f.rule, "path": f.path,
          "line": f.line, "message": f.message}
         for f in findings),
        key=lambda e: (e["path"], e["rule"], e["line"]))
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"version": 1, "findings": entries}, f, indent=1)
        f.write("\n")


def lint_paths(paths: Iterable[str], *,
               config: Optional[LintConfig] = None,
               rules: Optional[List[Rule]] = None,
               baseline: Optional[Set[str]] = None) -> LintResult:
    config = config or LintConfig()
    rules = default_rules() if rules is None else rules
    baseline = baseline or set()
    units: List[FileUnit] = []
    errors: List[str] = []
    for abspath, rel in collect_files(paths):
        unit = load_unit(abspath, rel)
        if unit is None:
            errors.append(f"{rel}: could not parse")
            continue
        units.append(unit)

    from ray_tpu.tools.rtlint.index import ProjectIndex
    index = ProjectIndex(units)

    raw: List[Finding] = []
    for rule in rules:
        for unit in units:
            for f in rule.check(unit, config, index):
                if not unit.suppressed(f.rule, f.line, f.end_line):
                    raw.append(f)
        for f in rule.check_project(units, config, index):
            unit = next((u for u in units if u.path == f.path), None)
            if unit is None or not unit.suppressed(f.rule, f.line,
                                                   f.end_line):
                raw.append(f)

    # de-dup identical fingerprints at different lines deterministically:
    # keep all, but stable-sort for output.
    raw.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    actionable = [f for f in raw if f.fingerprint not in baseline]
    grandfathered = [f for f in raw if f.fingerprint in baseline]
    return LintResult(findings=actionable, baselined=grandfathered,
                      files_checked=len(units), errors=errors)


# ---------------------------------------------------------------- helpers
# shared AST utilities used by several rules

def dotted_name(node: ast.AST) -> str:
    """'time.sleep' for Attribute/Name chains; '' when not a plain chain."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def iter_body_calls(node: ast.AST, *, into_nested: bool = False
                    ) -> Iterable[ast.Call]:
    """Yield Call nodes in a function body; by default does NOT descend
    into nested def/lambda (their bodies typically run elsewhere — an
    executor, a thread, a traced context)."""
    stack: List[ast.AST] = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)) and not into_nested:
            continue
        if isinstance(n, ast.Call):
            yield n
        stack.extend(ast.iter_child_nodes(n))
