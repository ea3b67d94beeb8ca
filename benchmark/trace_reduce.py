"""From a profiler trace to the numbers the per-layer metrics read.

Two steps, so that the second can be checked on a small recorded trace:
``read_xplane`` lists the device events of an ``.xplane.pb`` file, and
``reduce_events`` turns such a list into busy time, time per jitted
program and per operation, collective time and attributed idle gaps.

A device plane (``/device:TPU:<n>``) has a line of whole programs
("XLA Modules", one event per execution of a jitted function) and a line
of their operations ("XLA Ops"), whose events carry the instruction's
text and nest: a ``while`` spans the operations of its body.  An
operation's time is its own: its span less the operations inside it.
Times are seconds.
"""

from __future__ import annotations

import functools
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

MODULES, OPS = "XLA Modules", "XLA Ops"
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
Event = Tuple[int, str, str, float, float]   # device, line, name, start, dur


def read_xplane(path: str) -> List[Event]:
    """Every event of the module and operation lines of every device."""
    from jax.profiler import ProfileData
    events = []
    for plane in ProfileData.from_file(path).planes:
        match = re.fullmatch(r"/device:\w+:(\d+)", plane.name)
        if not match:
            continue
        for line in plane.lines:
            if line.name in (MODULES, OPS):
                short = program_name if line.name == MODULES else short_op
                events.extend(
                    (int(match.group(1)), line.name, short(e.name),
                     e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events)
    return events


def program_name(name: str) -> str:
    """``jit__decode(1234)`` -> ``jit__decode``."""
    return re.sub(r"\(\d+\)$", "", name)


@functools.lru_cache(maxsize=None)
def short_op(text: str) -> str:
    """``%fusion.1 = bf16[2,16]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.1 bf16[2,16] fusion``: instruction, result, kind.  The kind
    of a Pallas kernel is its call target, ``tpu_custom_call``.  Kept by
    text: a trace repeats its programs' few thousand instructions in a
    million events."""
    name, _, rest = text.partition(" = ")
    kind = re.search(r"\s([a-z][a-z0-9_\-]*)\(", " " + rest)
    target = re.search(r'custom_call_target="(\w+)"', rest)
    shape = re.sub(r"\{[^}]*\}", "", rest.split(" ")[0]).strip("(,")
    return " ".join((name.lstrip("%"), shape[:40],
                     target.group(1) if target else
                     kind.group(1) if kind else "op"))


def op_kind(short: str) -> str:
    return short.rsplit(" ", 1)[-1]


def self_times(ops: list) -> list:
    """(self seconds, name) of ``(start, end, name)`` events that nest."""
    out, stack = [], []
    for start, end, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and start >= stack[-1][0]:
            stack.pop()
        own = [end, end - start, name]
        if stack:
            stack[-1][1] -= end - start
        stack.append(own)
        out.append(own)
    return [(own, name) for _, own, name in out]


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Sorted disjoint cover of ``(start, end)`` intervals."""
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def length(cover: List[List[float]]) -> float:
    return sum(end - start for start, end in cover)


def subtract(cover: List[List[float]], other: List[List[float]]) -> float:
    """Length of ``cover`` outside ``other`` (both sorted and disjoint)."""
    outside, j = 0.0, 0
    for start, end in cover:
        at = start
        while j < len(other) and other[j][1] <= at:
            j += 1
        k = j
        while k < len(other) and other[k][0] < end:
            outside += max(0.0, other[k][0] - at)
            at = max(at, other[k][1])
            k += 1
        outside += max(0.0, end - at)
    return outside


def reduce_events(events: Iterable[Event]) -> Dict:
    """See the module docstring.  ``busy_s``, ``collective_s`` and
    ``collective_exposed_s`` are means over the devices; programs,
    operations and gaps are those of the lowest-numbered device."""
    by_device: Dict[int, Dict[str, list]] = defaultdict(
        lambda: {MODULES: [], OPS: []})
    for device, line, name, start, dur in events:
        by_device[device][line].append((start, start + dur, name))
    if not by_device:
        return {}
    starts = [s for d in by_device.values() for l in d.values()
              for s, _, _ in l]
    ends = [e for d in by_device.values() for l in d.values()
            for _, e, _ in l]
    out = {"devices": len(by_device), "window_s": max(ends) - min(starts)}

    busy, coll, exposed, own = [], [], [], {}
    for device, lines in by_device.items():
        work = lines[OPS] or lines[MODULES]
        busy.append(length(union((s, e) for s, e, _ in work)))
        coll.append(length(union((s, e) for s, e, n in work
                                 if _COLLECTIVE.search(n))))
        # a collective's own time: nothing runs inside its span then, and
        # what spans it (a ``while``) is no computation
        own[device] = self_times(lines[OPS])
        exposed.append(sum(t for t, n in own[device]
                           if _COLLECTIVE.search(n)))
    n = len(by_device)
    out.update(busy_s=sum(busy) / n, collective_s=sum(coll) / n,
               collective_exposed_s=sum(exposed) / n)

    first = by_device[min(by_device)]
    programs: Dict[str, list] = defaultdict(lambda: [0, 0.0])
    for start, end, name in first[MODULES]:
        entry = programs[program_name(name)]
        entry[0] += 1
        entry[1] += end - start
    ops: Dict[str, list] = defaultdict(lambda: [0, 0.0])
    for seconds, name in own[min(by_device)]:
        entry = ops[name]
        entry[0] += 1
        entry[1] += seconds
    out["programs"] = {k: {"calls": c, "device_s": s}
                       for k, (c, s) in programs.items()}
    out["ops"] = {k: {"calls": c, "device_s": s} for k, (c, s) in ops.items()}

    gaps: Dict[str, float] = defaultdict(float)
    ops_cover = union((s, e) for s, e, _ in first[OPS])
    modules = sorted(first[MODULES])
    for i, (start, end, name) in enumerate(modules):
        if first[OPS]:
            inside = subtract([[start, end]], ops_cover)
            if inside > 0:
                gaps[f"inside_{program_name(name)}"] += inside
        if i + 1 < len(modules) and modules[i + 1][0] > end:
            gaps[f"after_{program_name(name)}_before_"
                 f"{program_name(modules[i + 1][2])}"] += \
                modules[i + 1][0] - end
    out["gaps"] = dict(gaps)
    out["program_gap_s"] = sum(v for k, v in gaps.items()
                               if k.startswith("after_"))
    return out


def breakdown(reduced: Dict, top: int = 10) -> Dict:
    """The ten device operations that took most time and the ten longest
    kinds of idle gap, by what the host did between which programs."""
    def ranked(table):
        return [[re.sub(r"[^A-Za-z0-9_.\-]+", "_", k).strip("_")[:64], v]
                for k, v in sorted(table.items(),
                                   key=lambda kv: -kv[1])[:top]]
    return {"device_ops": ranked({k: v["device_s"] for k, v in
                                  reduced.get("ops", {}).items()}),
            "idle_gaps": ranked(reduced.get("gaps", {}))}
