"""What the program says of itself inside a traced run's profile.

The program marks its host work with ``ray_tpu.util.tracing.region``:
events named ``rt:<name>`` on the ``/host:CPU`` plane of the run's
``.xplane.pb``, on the clock of the device's events, their attributes as
the events' stats.  On the device it names its Pallas kernels
(``flash_fwd``, ``flash_dq``, ``flash_dkv``) and scopes parts of its steps
(``jax.named_scope``: ``ce_head``, ``optimizer``, ``paged_append``,
``paged_read``).  This module reads them:

* ``rows``: the attributes of the regions of one name;
* ``kernel`` and ``scope_ms``: own time of device operations by kernel
  name and by scope.

A scope reaches the trace as the operation's ``op_name``, which the
profiler keeps as the ``tf_op`` stat of the event's *metadata*;
``jax.profiler.ProfileData`` shows an event's own stats only, so
``op_names`` reads that one stat from the file's protobuf encoding itself.

What the host costs a decode step is ``host_threads.py``'s to read (the
phases' clocks on the regions), with ``decode_ahead_share`` and
``breakdown.idle_gaps``.

Every reader gives ``None`` where there is nothing to read: no trace (a
CPU rehearsal), or a program without the regions, names or scopes (the
parent of the PR that added them).
"""

from __future__ import annotations

import functools
import os
import re
from typing import Dict, Iterator, List, Optional, Tuple

from benchmark import spec, trace_reduce

Region = Tuple[str, float, float, dict]       # name, start, end, attributes
Span = Tuple[float, float, str]               # start, end, name
SCOPES = ("ce_head", "optimizer", "paged_append", "paged_read")
DECODE = "jit__decode"


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
    """``regions`` of the host plane, and the own seconds of the
    lowest-numbered device's operations by ``scopes``."""
    from jax.profiler import ProfileData
    regions: List[Region] = []
    ops: Dict[int, List[Span]] = {}       # device number -> operations
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
            elif device and line.name == trace_reduce.OPS:
                ops[int(device.group(1))] = [
                    (e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9, e.name)
                    for e in line.events]
    regions.sort(key=lambda r: r[1])
    scopes = dict.fromkeys(SCOPES, 0.0)
    if ops:
        names = op_names(path)
        for seconds, text in trace_reduce.self_times(ops[min(ops)]):
            scope = scope_of(names.get(text, ""))
            if scope:
                scopes[scope] += seconds
    return {"regions": regions, "scopes": scopes}


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
