"""Own device time of the PREFILL programs' operations under any
``jax.named_scope``, by the scope's name: ``decode_scopes``'s twin.

``moe_scopes`` and ``decode_scopes`` count the operations that start inside a
``jit__decode`` program alone, on purpose (a prefill's experts are not a
decode step's).  This counts those that start inside a ``jit__prefill``
program of the lowest-numbered device, by ONE walk of the trace a run:
``prefill_ops`` lists, once for a file, the own seconds and the ``op_name`` of
every such operation, and ``prefill_scope_ms`` picks from that list the
operations whose ``op_name`` passes through one of the scopes it is given
(``conv_in``, ``conv_mix``, ``conv_out``, ``moe_experts``, ...), a
millisecond figure per ``jit__prefill`` call.

``prefill_regions`` pairs what the engine said of the traced window's
prefills: the ``rt:engine.prefill`` regions (``prompt_len``, ``padded_len``)
and the ``rt:engine.prefill.moe`` regions (``assignments``, ``experts_hit``,
``load_max``, ``weight_itemsize``).

Gives None where there is nothing to read: no trace, a window without a
prefill, or a program without those scopes or regions (the parent of the PR
that added them).
"""

from __future__ import annotations

import bisect
import functools
import os
import re
from typing import Optional, Sequence, Tuple

from benchmark import decode_scopes, host_regions, spec, trace_reduce

PREFILL = "jit__prefill"


@functools.lru_cache(maxsize=2)
def prefill_ops(path: str) -> Tuple[Tuple[float, str], ...]:
    """(own seconds, ``op_name``) of the operations inside
    ``jit__prefill``."""
    from jax.profiler import ProfileData
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        device = re.fullmatch(r"/device:\w+:(\d+)", plane.name)
        for line in plane.lines if device else ():
            if line.name in (trace_reduce.MODULES, trace_reduce.OPS):
                lines.setdefault(int(device.group(1)), {})[line.name] = [
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                     e.name) for e in line.events]
    first = lines[min(lines)] if lines else {}
    programs = sorted((s, e) for s, e, n in first.get(trace_reduce.MODULES, [])
                      if trace_reduce.program_name(n) == PREFILL)
    starts = [s for s, _ in programs]
    inside = []
    for start, end, text in first.get(trace_reduce.OPS, []):
        at = bisect.bisect_right(starts, start) - 1
        if at >= 0 and start < programs[at][1]:
            inside.append((start, end, text))
    names = host_regions.op_names(path)
    return tuple((seconds, names.get(text, ""))
                 for seconds, text in trace_reduce.self_times(inside))


def _program(run: dict) -> Optional[dict]:
    return run["trace"].get("programs", {}).get(PREFILL) \
        if run["trace"] else None


def prefill_scope_ms(run: dict, scopes: Sequence[str]) -> Optional[float]:
    """Own device time of the prefill programs' operations under ``scopes``,
    in milliseconds per ``jit__prefill`` call."""
    program = _program(run)
    if not program:
        return None
    from benchmark import replica
    ops = prefill_ops(replica.find_xplane(os.path.join(
        spec.ROOT, ".bench_trace", run["cell"]["name"])))
    seconds = sum(s for s, name in ops if decode_scopes.under(name, scopes))
    return 1e3 * seconds / program["calls"] if seconds else None


def prefill_device_ms(run: dict) -> Optional[float]:
    """Device time of a ``jit__prefill`` call, in milliseconds."""
    program = _program(run)
    return 1e3 * program["device_s"] / program["calls"] if program else None


def prefill_regions(run: dict) -> Optional[dict]:
    """What the engine said of the traced window's prefills: ``prefills``
    (the ``rt:engine.prefill`` regions' attributes) and ``routing`` (the
    ``rt:engine.prefill.moe`` regions', one a prefill; empty for a dense
    model)."""
    prefills = host_regions.rows(run, "engine.prefill")
    if not prefills:
        return None
    return {"prefills": prefills,
            "routing": host_regions.rows(run, "engine.prefill.moe") or []}
