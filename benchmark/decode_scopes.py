"""Own device time of the decode program's operations under any
``jax.named_scope``, by the scope's name.

``moe_scopes.SCOPES`` and ``metrics/loop_norm_device_ms.py``'s pattern are
fixed sets; the scopes that later PRs add (``latent_append``,
``latent_read``, ``mla_absorb``, ``hc_coeff``, ``hc_mix``, ``moe_shared``
from PR 34) are summed here, by ONE walk of the trace a run: ``decode_ops``
lists, once for a file, the own seconds and the ``op_name`` of every
operation that starts inside a ``jit__decode`` program of the
lowest-numbered device, and ``decode_scope_ms`` picks from that list the
operations whose ``op_name`` passes through one of the scopes it is given.

Gives None where there is nothing to read: no trace, or a program without
those scopes (the parent of the PR that added them).
"""

from __future__ import annotations

import bisect
import functools
import os
import re
from typing import Optional, Sequence, Tuple

from benchmark import host_regions, spec, trace_reduce


@functools.lru_cache(maxsize=2)
def decode_ops(path: str) -> Tuple[Tuple[float, str], ...]:
    """(own seconds, ``op_name``) of the operations inside ``jit__decode``."""
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
    decodes = sorted((s, e) for s, e, n in first.get(trace_reduce.MODULES, [])
                     if trace_reduce.program_name(n) == host_regions.DECODE)
    starts = [s for s, _ in decodes]
    inside = []
    for start, end, text in first.get(trace_reduce.OPS, []):
        at = bisect.bisect_right(starts, start) - 1
        if at >= 0 and start < decodes[at][1]:
            inside.append((start, end, text))
    names = host_regions.op_names(path)
    return tuple((seconds, names.get(text, ""))
                 for seconds, text in trace_reduce.self_times(inside))


def under(op_name: str, scopes: Sequence[str]) -> bool:
    """Whether the operation lies under one of the scopes, transformations
    included (``jit(_decode)/while/body/closed_call/hc_coeff/exp``)."""
    return any(re.search(rf"[/(]{re.escape(scope)}[/)]", op_name)
               for scope in scopes)


def decode_scope_ms(run: dict, scopes: Sequence[str]) -> Optional[float]:
    """Own device time of the decode programs' operations under ``scopes``,
    in milliseconds per ``jit__decode`` call."""
    decode = run["trace"].get("programs", {}).get(host_regions.DECODE) \
        if run["trace"] else None
    if not decode:
        return None
    from benchmark import replica
    ops = decode_ops(replica.find_xplane(os.path.join(
        spec.ROOT, ".bench_trace", run["cell"]["name"])))
    seconds = sum(s for s, name in ops if under(name, scopes))
    return 1e3 * seconds / decode["calls"] if seconds else None
