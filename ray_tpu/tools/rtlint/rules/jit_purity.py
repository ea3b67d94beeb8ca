"""Rule 5 — jit-purity.

Functions handed to ``jax.jit`` / ``jax.pmap`` / ``shard_map`` /
``pl.pallas_call`` are traced once and replayed as compiled XLA/Mosaic
programs: Python side effects inside them run at *trace* time only (or
not at all on cache hits), so ``print``, ``time.time``, host RNG, and
global mutation are at best misleading and at worst nondeterminism
between a traced run and a compile-cache hit.

Scope: files under ``config.jit_dirs`` (ops/, models/, train/, parallel/).
Jitted functions are found two ways:
- decorator form: ``@jax.jit``, ``@jit``, ``@partial(jax.jit, ...)``,
  ``@functools.partial(shard_map, ...)``, ``@pl.pallas_call(...)``;
- call form: any ``Name`` argument of a ``jax.jit(...)`` /
  ``pallas_call(...)`` / ``shard_map(...)`` / ``pmap(...)`` call that
  resolves to a ``def`` in the same file (including nested defs —
  closures like ``models/gpt.py``'s train ``step`` are the common case).

Inside a jitted body (including its nested defs, which trace too) the
rule flags: ``print``, ``time.time/perf_counter/monotonic/...``, host
RNG (``random.*``, ``np.random.*``), ``global``/``nonlocal``-free
global mutation via ``global`` statements, file IO (``open``), and
mutable-literal defaults for static args (lists/dicts are unhashable →
every call re-traces or raises).  ``jax.debug.print`` and
``jax.random.*`` are of course fine."""

from __future__ import annotations

import ast
from typing import Iterable, List, Optional, Set

from ray_tpu.tools.rtlint.engine import (Finding, FileUnit, LintConfig,
                                         Rule, dotted_name)

_JIT_ENTRY_LEAVES = {"jit", "pallas_call", "shard_map", "pmap", "xmap"}
_IMPURE_TIME = {"time.time", "time.perf_counter", "time.monotonic",
                "time.time_ns", "time.process_time", "time.perf_counter_ns"}
_IMPURE_RNG_PREFIX = ("random.", "np.random.", "numpy.random.")


def _is_jit_entry(name: str) -> bool:
    if not name:
        return False
    leaf = name.rsplit(".", 1)[-1]
    if leaf not in _JIT_ENTRY_LEAVES:
        return False
    # plain `jit`, `jax.jit`, `pl.pallas_call`, `shard_map`, ... — but not
    # arbitrary `foo.submit`-style homonyms: require a known module prefix
    # or a bare name.
    root = name.split(".", 1)[0]
    return root in ("jax", "pl", "pallas", "pltpu", "shard_map", leaf,
                    "functools", "partial") or "." not in name


def _collect_jitted(unit: FileUnit) -> Set[ast.AST]:
    """All def nodes (sync, any nesting) traced by a jit entry point."""
    defs_by_name: dict = {}
    for node in ast.walk(unit.tree):
        if isinstance(node, ast.FunctionDef):
            defs_by_name.setdefault(node.name, node)

    jitted: Set[ast.AST] = set()

    def mark_names_in(expr: ast.AST) -> None:
        for n in ast.walk(expr):
            if isinstance(n, ast.Name) and n.id in defs_by_name:
                jitted.add(defs_by_name[n.id])

    for node in ast.walk(unit.tree):
        if isinstance(node, ast.FunctionDef):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call):
                    dname = dotted_name(dec.func)
                    if _is_jit_entry(dname):
                        jitted.add(node)
                    elif dname.rsplit(".", 1)[-1] == "partial" and \
                            dec.args and \
                            _is_jit_entry(dotted_name(dec.args[0])):
                        jitted.add(node)
                elif _is_jit_entry(dotted_name(dec)):
                    jitted.add(node)
        elif isinstance(node, ast.Call) and _is_jit_entry(
                dotted_name(node.func)):
            for arg in node.args[:1]:
                mark_names_in(arg)
    return jitted


class JitPurity(Rule):
    name = "jit-purity"

    def check(self, unit: FileUnit, config: LintConfig,
              index=None) -> Iterable[Finding]:
        if not any(frag in unit.path for frag in config.jit_dirs):
            return
        for fn in sorted(_collect_jitted(unit), key=lambda n: n.lineno):
            yield from self._check_body(unit, fn)

    def _check_body(self, unit: FileUnit, fn: ast.AST
                    ) -> Iterable[Finding]:
        # static args with mutable (unhashable) defaults
        args = getattr(fn, "args", None)
        if args is not None:
            for default in list(args.defaults) + \
                    [d for d in args.kw_defaults if d is not None]:
                if isinstance(default, (ast.List, ast.Dict, ast.Set)):
                    yield self._finding(
                        unit, default,
                        "mutable default on a jitted function — static "
                        "args must be hashable (use a tuple / None)")
        for node in ast.walk(fn):
            if isinstance(node, ast.Global):
                yield self._finding(
                    unit, node,
                    "global mutation inside a jitted function — runs at "
                    "trace time only")
                continue
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if not name:
                continue
            if name == "print":
                yield self._finding(
                    unit, node, "print() inside a jitted function — runs "
                    "at trace time only; use jax.debug.print")
            elif name in _IMPURE_TIME:
                yield self._finding(
                    unit, node, f"{name}() inside a jitted function — "
                    "the value freezes at trace time")
            elif name.startswith(_IMPURE_RNG_PREFIX):
                yield self._finding(
                    unit, node, f"host RNG {name}() inside a jitted "
                    "function — nondeterministic across traces; use "
                    "jax.random with an explicit key")
            elif name == "open":
                yield self._finding(
                    unit, node, "file IO inside a jitted function — runs "
                    "at trace time only")

    def _finding(self, unit: FileUnit, node: ast.AST, msg: str) -> Finding:
        return Finding(rule=self.name, path=unit.path,
                       line=getattr(node, "lineno", 1),
                       col=getattr(node, "col_offset", 0), message=msg,
                       scope=unit.scope_of(node),
                       source=unit.source_line(getattr(node, "lineno", 1)))
