"""Own device time of the mixture-of-experts parts of the decode program in
a traced run's profile, and what the engine said of the steps' routing.

``ray_tpu/ops/moe.py`` scopes its dropless path with ``jax.named_scope``:
``moe_router``, ``moe_dispatch``, ``moe_experts``, ``moe_combine``.  The
TPU compiler turns ``jax.lax.ragged_dot`` into grouped-matmul kernels of
its own, which it names ``ragged-dot-*`` and which lose the scope they were
written under; nothing else in the program makes such a kernel, so they are
counted under ``moe_experts``, where they were written.

Only operations that start inside a ``jit__decode`` program of the
lowest-numbered device are counted (the prefill's experts are not a decode
step's).  The engine's ``rt:engine.decode.moe`` regions carry each step's
``assignments``, ``experts_hit`` (distinct experts touched, summed over
layers), ``load_max`` (largest single-expert load, summed over layers) and
``weight_itemsize`` (bytes a parameter of the experts as stored).

Every reader gives None where there is nothing to read: no trace, or a
program without the scopes and regions (the parent of the PR that added
them).
"""

from __future__ import annotations

import bisect
import functools
import os
import re
from typing import Dict, Optional, Tuple

from benchmark import host_regions, spec, trace_reduce

SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
GROUPED_MATMUL = re.compile(r"%?ragged-dot")


def scope_of(instruction: str, op_name: str) -> Optional[str]:
    for scope in SCOPES:
        if re.search(rf"[/(]{scope}[/)]", op_name):
            return scope
    return "moe_experts" if GROUPED_MATMUL.match(instruction) else None


@functools.lru_cache(maxsize=2)
def read_decode_scopes(path: str) -> Dict[str, float]:
    """Own seconds by scope of the operations inside ``jit__decode``."""
    from jax.profiler import ProfileData
    lines: Dict[int, Dict[str, list]] = {}
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
    names = host_regions.op_names(path)
    out = dict.fromkeys(SCOPES, 0.0)
    inside = []
    for start, end, text in first.get(trace_reduce.OPS, []):
        at = bisect.bisect_right(starts, start) - 1
        if at >= 0 and start < decodes[at][1]:
            inside.append((start, end, text))
    for seconds, text in trace_reduce.self_times(inside):
        scope = scope_of(text, names.get(text, ""))
        if scope:
            out[scope] += seconds
    return out


def decode_scope_ms(run: dict, scopes: Tuple[str, ...]) -> Optional[float]:
    """Own device time of the operations of the decode programs under the
    scopes, in milliseconds per ``jit__decode`` call."""
    decode = run["trace"].get("programs", {}).get(host_regions.DECODE) \
        if run["trace"] else None
    if not decode:
        return None
    from benchmark import replica
    found = read_decode_scopes(replica.find_xplane(os.path.join(
        spec.ROOT, ".bench_trace", run["cell"]["name"])))
    seconds = sum(found[s] for s in scopes)
    return 1e3 * seconds / decode["calls"] if seconds else None


def decode_routing(run: dict) -> Optional[dict]:
    """Sums over the traced window's decode steps of what the engine's
    ``rt:engine.decode.moe`` regions say, the number of steps, and the
    bytes a stored parameter of the experts takes."""
    steps = host_regions.rows(run, "engine.decode.moe")
    if not steps:
        return None
    return {"steps": len(steps),
            "weight_itemsize": steps[0]["weight_itemsize"],
            **{key: sum(s[key] for s in steps)
               for key in ("assignments", "experts_hit", "load_max")}}
