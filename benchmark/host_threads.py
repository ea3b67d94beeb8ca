"""What the engine's two threads did inside the phases of a decode step.

Since PR 36 the engine reads, at each boundary of a phase of its per-token
path, the CPU clock of the exec thread and of the actor's loop thread
beside the wall clock, and hangs each phase's three numbers on the region
that follows it (``ray_tpu/util/tracing.py`` has the convention):
``dispatch_us`` / ``dispatch_cpu_us`` / ``dispatch_loop_cpu_us``
on ``rt:engine.decode.fetch``; ``fetch_loop_cpu_us`` and
``resume_loop_cpu_us`` on ``rt:engine.deliver``; ``step_us`` /
``step_loop_cpu_us`` (since the previous decode step's submission) on
``rt:engine.decode.dispatch``.  The collector's passes are ``rt:gc``
regions.  This module sums those over the traced seconds: per
``jit__decode`` call, or as a share.

Every reader gives ``None`` where there is nothing to read: no trace, no
decode call, or regions without the attribute (the program before PR 36; a
call submitted before the session began, whose CPU clocks were not read).
What the numbers are worth: the session that ``replica.observe`` starts has
had the profiler's Python tracer off since PR 44 (on, it slowed the very
threads these read 1.3-1.6 times: PERF.md section 6, PR 36), so they read
the judged run's host to the 0.2-0.5 ms a step that the host tracer costs;
the chip machine's thread CPU clocks tick in 10 ms steps, so a sum over
five traced seconds is good to 10-18% and a difference of two sums may come
out a little under 0.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from benchmark import host_regions, trace_reduce

DISPATCH, FETCH, DELIVER = ("engine.decode.dispatch", "engine.decode.fetch",
                            "engine.deliver")


def total_us(run: dict, region: str, attr: str, less: Optional[str] = None
             ) -> Optional[int]:
    """Sum of a microsecond attribute over the window's ``rt:<region>``;
    with ``less``, what is left of it after that attribute's sum, signed: a
    CPU clock that ticks coarsely (10 ms on the chip's machine) gives a
    single region 0 or a whole tick, so only the sums are compared, and a
    sum of CPU that passes its wall says how coarse the reading is."""
    found = host_regions.rows(run, region)
    found = [r for r in found or [] if attr in r and (less or attr) in r]
    if not found:
        return None
    total = sum(r[attr] for r in found)
    return total - sum(r[less] for r in found) if less else total


def per_decode_call_ms(run: dict, microseconds: Optional[int]
                       ) -> Optional[float]:
    """Milliseconds per ``jit__decode`` call of the traced window."""
    decode = (run["trace"] or {}).get("programs", {}).get(
        host_regions.DECODE)
    if microseconds is None or not decode:
        return None
    return 1e-3 * microseconds / decode["calls"]


def share(run: dict, part: Tuple[str, str], whole: Tuple[str, str]
          ) -> Optional[float]:
    """Percent that one (region, attribute) sum is of another."""
    a, b = total_us(run, *part), total_us(run, *whole)
    return 100.0 * a / b if a is not None and b else None


def gc_spans(run: dict) -> Optional[List[Tuple[float, float]]]:
    """(start, end) of the collector's passes in the trace, in seconds."""
    prof = host_regions.profile(run)
    found = prof and [(start, end) for name, start, end, _ in prof["regions"]
                      if name == "rt:gc"]
    return found or None


def gc_pause_share(run: dict) -> Optional[float]:
    spans = gc_spans(run)
    window = (run["trace"] or {}).get("window_s")
    if not spans or not window:
        return None
    return 100.0 * trace_reduce.length(trace_reduce.union(spans)) / window


def gc_pause_max_ms(run: dict) -> Optional[float]:
    spans = gc_spans(run)
    return 1e3 * max(end - start for start, end in spans) if spans else None
