"""Own device time of the operations under the ``loop_norm`` scope (a
looped model's two norms of sublayer outputs and its final norm after every
pass) inside the decode programs, per ``jit__decode`` call.  Only
operations that start inside a ``jit__decode`` of the lowest-numbered device
are counted.  None where there is no trace or no such scope (a program
whose layers run once)."""

import bisect
import os
import re

from benchmark import host_regions, spec, trace_reduce

SCOPE = re.compile(r"[/(]loop_norm[/)]")


def scope_seconds(path: str) -> float:
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
    return sum(seconds for seconds, text in trace_reduce.self_times(inside)
               if SCOPE.search(names.get(text, "")))


def read(run):
    decode = run["trace"].get("programs", {}).get(host_regions.DECODE) \
        if run["trace"] else None
    if not decode:
        return None
    from benchmark import replica
    seconds = scope_seconds(replica.find_xplane(os.path.join(
        spec.ROOT, ".bench_trace", run["cell"]["name"])))
    return 1e3 * seconds / decode["calls"] if seconds else None
