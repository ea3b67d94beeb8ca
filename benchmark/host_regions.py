"""What the program says of itself inside a traced run's profile.

The program marks its host work with ``ray_tpu.util.tracing.region``:
events named ``rt:<name>`` on the ``/host:CPU`` plane of the run's
``.xplane.pb``, on the clock of the device's events, their attributes as
the events' stats.  On the device it names its Pallas kernels
(``flash_fwd``, ``flash_dq``, ``flash_dkv``) and scopes parts of its steps
(``jax.named_scope``: ``ce_head``, ``optimizer``, ``paged_append``,
``paged_read``).  This module reads the three:

* ``gap_kinds``: every device-idle interval between consecutive programs of
  the lowest-numbered device, split by what the host was doing in it;
* ``rows``: the attributes of the regions of one name;
* ``kernel`` and ``scope_ms``: own time of device operations by kernel
  name and by scope.

A scope reaches the trace as the operation's ``op_name``, which the
profiler keeps as the ``tf_op`` stat of the event's *metadata*;
``jax.profiler.ProfileData`` shows an event's own stats only, so
``op_names`` reads that one stat from the file's protobuf encoding itself.

The device's events are on the host's clock only up to an error that is
constant within a trace; ``device_lag`` measures it against the runtime's
own enqueue events and ``read_profile`` moves the programs by it before
anything is split or checked.

Every reader gives ``None`` where there is nothing to read: no trace (a
CPU rehearsal), a program without the regions, names or scopes (the parent
of the PR that added them), or host events that fail the clock check.
"""

from __future__ import annotations

import bisect
import functools
import os
import re
import sys
from typing import Dict, Iterator, List, Optional, Tuple

from benchmark import spec, trace_reduce

Region = Tuple[str, float, float, dict]       # name, start, end, attributes
Span = Tuple[float, float, str]               # start, end, name
KINDS = ("fetch", "resume", "deliver", "schedule", "submit", "dispatch",
         "unnamed")
SCOPES = ("ce_head", "optimizer", "paged_append", "paged_read")
DECODE, ARGMAX = "jit__decode", "jit__argmax"
# The runtime's host thread marks each program it hands to the device
# with this event.  The profiler converts device times to the host's clock
# with an error that is constant within a trace: on two v5e machines the
# device's programs read as starting 0.43 and 1.44 ms *before* the event
# that enqueued them (PERF.md, PR 24 found).  ``device_lag`` is the least
# shift that puts every ``jit__decode`` after its enqueue; what is left of
# the error moves time between the two kinds that touch the device,
# ``fetch`` at a gap's start and ``dispatch`` at its end, and leaves their
# sum and the kinds between them alone.
ENQUEUE = "DoEnqueueProgram"
# How far the two timelines may still differ before they count as
# different clocks (a runtime that has no such event is not shifted).
CLOCK_SLACK_S = 2e-3


# ------------------------------------------------------------ the host side

def kind_intervals(regions: List[Region]) -> List[Span]:
    """The regions of the engine's per-token path as intervals of a kind.
    The two thread crossings are rebuilt from the attribute of the region
    that follows them: ``resume`` ends where ``deliver`` starts and
    ``submit`` where ``dispatch`` (or a prefill) starts.  A prefill's
    whole region counts as ``dispatch``."""
    out = []
    for name, start, end, attrs in regions:
        kind = {"rt:engine.decode.fetch": "fetch",
                "rt:engine.deliver": "deliver",
                "rt:engine.schedule": "schedule",
                "rt:engine.decode.dispatch": "dispatch",
                "rt:engine.prefill": "dispatch"}.get(name)
        if kind is None:
            continue
        out.append((start, end, kind))
        for attr, crossing in (("resume_us", "resume"),
                               ("submit_us", "submit")):
            if attr in attrs:
                out.append((start - attrs[attr] * 1e-6, start, crossing))
    return sorted(out)


def program_gaps(programs: List[Span]) -> List[Tuple[float, float]]:
    """The device-idle intervals between consecutive programs, as
    ``trace_reduce.reduce_events`` counts them (``program_gap_s``)."""
    programs = sorted(programs)
    return [(end, programs[i + 1][0])
            for i, (_, end, _) in enumerate(programs[:-1])
            if programs[i + 1][0] > end]


def split_gaps(gaps: List[Tuple[float, float]], intervals: List[Span]
               ) -> Dict[str, float]:
    """Seconds of the gaps by kind.  Where intervals overlap the one that
    started last wins (the innermost), so an instant is counted once; what
    no interval covers is ``unnamed``.  The kinds sum to the gaps."""
    out = dict.fromkeys(KINDS, 0.0)
    for gap_start, gap_end in gaps:
        inside = [(max(s, gap_start), min(e, gap_end), k)
                  for s, e, k in intervals if s < gap_end and e > gap_start]
        cuts = sorted({gap_start, gap_end}
                      | {t for s, e, _ in inside for t in (s, e)})
        for a, b in zip(cuts, cuts[1:]):
            covering = [(s, k) for s, e, k in inside if s <= a and e >= b]
            out[max(covering)[1] if covering else "unnamed"] += b - a
    return out


def device_lag(enqueues: List[float], programs: List[Span]) -> float:
    """Seconds by which the device's timeline runs ahead of the host's:
    the most that a ``jit__decode`` reads as starting before the event
    that enqueued it; 0 where none does.  A step's programs are enqueued
    within a millisecond and steps are 40 ms apart, so a decode's event is
    the nearest of those that follow 10 ms of silence."""
    enqueues = sorted(enqueues)
    first = [e for before, e in zip([float("-inf")] + enqueues, enqueues)
             if e - before > 10e-3]
    ahead = [0.0]
    for start, _, name in programs:
        at = bisect.bisect_left(first, start)
        near = min(first[max(at - 1, 0):at + 1],
                   key=lambda e: abs(e - start), default=None)
        if name == DECODE and near is not None \
                and abs(near - start) < 10e-3:
            ahead.append(near - start)
    return max(ahead)


def clock_check(regions: List[Region], programs: List[Span]
                ) -> Tuple[int, int]:
    """(decode steps that can be checked, those on one clock).  A step on
    the host is an ``rt:engine.decode.dispatch`` and the
    ``rt:engine.decode.fetch`` after it.  On one clock exactly one
    ``jit__decode`` starts on the device between the start of the first
    and the end of the second, and the ``jit__argmax`` after it has ended
    by then too; the device's timeline may differ from the host's by
    ``CLOCK_SLACK_S``.  A step that the trace cut at either end (no fetch
    after the dispatch, no program in it at the trace's edge) is not
    counted."""
    programs = sorted(programs)
    decodes = []                    # (start of jit__decode, end of argmax)
    for i, (start, end, name) in enumerate(programs):
        if name == DECODE:
            decodes.append((start, next(
                (e for s, e, n in programs[i + 1:]
                 if n == ARGMAX and s >= end), None)))
    dispatches = [s for n, s, _, _ in regions
                  if n == "rt:engine.decode.dispatch"]
    fetches = sorted(e for n, _, e, _ in regions
                     if n == "rt:engine.decode.fetch")
    steps = in_order = 0
    for i, start in enumerate(dispatches):
        end = next((f for f in fetches if f > start), None)
        inside = [(p, a) for p, a in decodes
                  if start - CLOCK_SLACK_S <= p <= (end or start)]
        at_edge = i in (0, len(dispatches) - 1)
        if end is None or (at_edge and (
                not inside or inside[0][1] is None)):
            continue
        steps += 1
        in_order += len(inside) == 1 and inside[0][1] is not None \
            and inside[0][1] <= end + CLOCK_SLACK_S
    return steps, in_order


# ------------------------------------------------- the file, read once

def op_names(path: str) -> Dict[str, str]:
    """Instruction text -> ``op_name`` for the operations of the device
    planes: the ``tf_op`` stat of each event's metadata."""
    out: Dict[str, str] = {}
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for field, plane in _fields(space):
        if field != 1:                                  # XSpace.planes
            continue
        name, metadata, stat_names = "", [], {}
        for field, value in _fields(plane):
            if field == 2:                              # XPlane.name
                name = bytes(value).decode()
            elif field == 4:                            # .event_metadata
                metadata.append(_message(_message(value)[2][0]))
            elif field == 5:                            # .stat_metadata
                stat = _message(_message(value)[2][0])
                stat_names[stat[1][0]] = bytes(stat[2][0]).decode()
        if not re.fullmatch(r"/device:\w+:\d+", name):
            continue
        for meta in metadata:                           # XEventMetadata
            for stat in map(_message, meta.get(5, [])):         # XStat
                if stat_names.get(stat[1][0]) != "tf_op":
                    continue
                value = bytes(stat[5][0]).decode() if 5 in stat else \
                    stat_names.get(stat.get(7, [0])[0], "")
                out[bytes(meta[2][0]).decode()] = value
    return out


def _fields(buf: memoryview) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for a varint,
    a view of the bytes for anything with a length."""
    at, size = 0, len(buf)
    while at < size:
        key, at = _varint(buf, at)
        wire = key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            length, at = _varint(buf, at)
            value, at = buf[at:at + length], at + length
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            value, at = buf[at:at + width], at + width
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def _varint(buf: memoryview, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _message(buf: memoryview) -> Dict[int, list]:
    out: Dict[int, list] = {}
    for field, value in _fields(buf):
        out.setdefault(field, []).append(value)
    return out


def scope_of(op_name: str) -> Optional[str]:
    """The scope an operation lies under, transformations included:
    ``jit(step)/transpose(jvp(ce_head))/while/body/dot_general``."""
    for scope in SCOPES:
        if re.search(rf"[/(]{scope}[/)]", op_name):
            return scope
    return None


@functools.lru_cache(maxsize=2)
def read_profile(path: str) -> dict:
    """``regions`` of the host plane; of the lowest-numbered device its
    ``programs``, moved by ``lag_s`` onto the host's timeline, and the own
    seconds of its operations by ``scopes``; and ``kinds``, the idle time
    between those programs split by the regions, or None where the decode
    steps' host events fail the clock check or there are none."""
    from jax.profiler import ProfileData
    regions: List[Region] = []
    enqueues: List[float] = []
    devices: Dict[int, Dict[str, List[Span]]] = {}
    for plane in ProfileData.from_file(path).planes:
        device = re.fullmatch(r"/device:\w+:(\d+)", plane.name)
        for line in plane.lines:
            if plane.name == "/host:CPU":
                for e in line.events:
                    if e.name.startswith("rt:"):
                        regions.append((
                            e.name, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9,
                            dict(e.stats)))
                    elif e.name == ENQUEUE:
                        enqueues.append(e.start_ns * 1e-9)
            elif device and line.name in (trace_reduce.MODULES,
                                          trace_reduce.OPS):
                devices.setdefault(int(device.group(1)), {})[line.name] = [
                    (e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9, e.name)
                    for e in line.events]
    regions.sort(key=lambda r: r[1])
    first = devices[min(devices)] if devices else {}
    programs = sorted((s, e, trace_reduce.program_name(n))
                      for s, e, n in first.get(trace_reduce.MODULES, []))
    lag = device_lag(enqueues, programs)
    programs = [(s + lag, e + lag, n) for s, e, n in programs]
    steps, in_order = clock_check(regions, programs)
    kinds = None
    if steps and in_order == steps:
        kinds = split_gaps(program_gaps(programs), kind_intervals(regions))
    if steps:
        print(f"host_regions: device timeline moved {lag * 1e3:.3f} ms "
              f"later; {in_order} of {steps} decode steps have their host "
              "regions and device programs in order"
              + ("" if kinds else ": the two are not on one clock, no "
                 "host_gap_* is reported"), file=sys.stderr)
    scopes = dict.fromkeys(SCOPES, 0.0)
    if first.get(trace_reduce.OPS):
        names = op_names(path)
        for seconds, text in trace_reduce.self_times(
                first[trace_reduce.OPS]):
            scope = scope_of(names.get(text, ""))
            if scope:
                scopes[scope] += seconds
    return {"regions": regions, "programs": programs, "lag_s": lag,
            "kinds": kinds, "scopes": scopes}


# ------------------------------------------------- what the metrics call

def profile(run: dict) -> Optional[dict]:
    """The traced run's profile, or None where no trace was reduced."""
    if not run["trace"]:
        return None
    from benchmark import replica
    return read_profile(replica.find_xplane(os.path.join(
        spec.ROOT, ".bench_trace", run["cell"]["name"])))


def rows(run: dict, region: str) -> Optional[List[dict]]:
    """The attributes of every ``rt:<region>`` of the traced window."""
    prof = profile(run)
    found = prof and [attrs for name, _, _, attrs in prof["regions"]
                      if name == "rt:" + region]
    return found or None


def median_ms(run: dict, region: str, attr: str) -> Optional[float]:
    """Median of a microsecond attribute of a region, in milliseconds."""
    import statistics
    found = rows(run, region)
    return statistics.median(r[attr] for r in found) * 1e-3 \
        if found else None


def gap_kinds(run: dict) -> Optional[Dict[str, float]]:
    """Seconds of the window's device-idle time between programs, by kind;
    None unless every decode step's host events pass the clock check."""
    prof = profile(run)
    return prof and prof["kinds"]


def gap_ms(run: dict, kind: str) -> Optional[float]:
    """``host_gap_<kind>_ms``: that kind's idle time per decode call."""
    kinds = gap_kinds(run)
    decode = run["trace"].get("programs", {}).get(DECODE)
    return 1e3 * kinds[kind] / decode["calls"] if kinds and decode else None


def scope_ms(run: dict, scopes: Tuple[str, ...], per: float
             ) -> Optional[float]:
    """Own device time of the operations under the scopes, in
    milliseconds per ``per`` (steps, or calls of a program)."""
    prof = profile(run)
    seconds = sum(prof["scopes"][s] for s in scopes) if prof else 0.0
    return 1e3 * seconds / per if seconds and per else None


def kernel(run: dict, name: str) -> Optional[dict]:
    """Calls and own device seconds of the Pallas kernel of that name on
    the lowest-numbered device (``flash_dq.9 ... tpu_custom_call``)."""
    found = [v for k, v in run["trace"].get("ops", {}).items()
             if trace_reduce.op_kind(k) == "tpu_custom_call"
             and name in k.split(" ")[0]]
    return {"calls": sum(v["calls"] for v in found),
            "device_s": sum(v["device_s"] for v in found)} if found else None


def flash_kernel_roofline(run: dict, kind: str) -> Optional[float]:
    """``flash_<kind>_roofline``: the least time the chip could take for
    the calls of that kernel over their device time.  Shapes are one
    device's, as ``flash_roofline`` takes them: the batch over the data
    axes, the heads over tp."""
    from benchmark import costs
    found = kernel(run, "flash_" + kind)
    if not found:
        return None
    config, traffic = run["cell"]["config"], run["cell"]["traffic"]
    shape = spec.load_part("families", config["family"]).attention_shape(
        config)
    mesh = config["mesh"]
    least = costs.least_seconds(costs.flash_pass(
        kind, config["train"]["batch"] // (mesh.get("dp", 1)
                                          * mesh.get("fsdp", 1)),
        shape["heads"] // mesh.get("tp", 1), traffic["seq_len"],
        shape["head_dim"]), run["peaks"])
    return 100.0 * found["calls"] * least / found["device_s"]
