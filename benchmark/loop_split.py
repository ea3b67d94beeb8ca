"""Where the replica's loop thread spends a decode step, part by part.

``host_loop_cpu_ms`` is one number: the CPU time of the actor's loop thread
from one decode step's submission to the next (``step_loop_cpu_us`` of
``rt:engine.decode.dispatch``).  Since PR 53 the same region carries what
the always-on sums of the streams' fan-out and of the transport grew by over
that very interval (``ray_tpu/util/tracing.py`` has the convention), and
every ``rt:stream.yield`` its yield's stages and what the owner's ack said.
This module splits the one number with them:

* ``engine``: the lengths of ``rt:engine.deliver`` and ``rt:engine.schedule``,
  the engine's own synchronous work on that thread;
* ``stream``: ``stream_store_us + stream_after_us``, a yield's value stored
  and, once its ack is back, its reference and borrow made;
* ``rpc``: ``rpc_out_us + rpc_in_us``, frames packed and written, frames
  parsed and their messages handed on;
* ``unnamed``: the step's loop time less the three, signed: the event loop
  itself, the generators' frames, the collector, and the coarseness of the
  reading (the three are walls of sections that do not nest, the whole a
  thread CPU clock that ticks in 10 ms steps on the chip's machine).

Every reader gives ``None`` where there is nothing to read: no trace, no
decode call, or dispatches without the sums (the program before PR 53).
"""

from __future__ import annotations

import statistics
from typing import Dict, Optional, Tuple

from benchmark import host_regions, host_threads

ENGINE = ("rt:engine.deliver", "rt:engine.schedule")
PARTS = {"stream": ("stream_store_us", "stream_after_us"),
         "rpc": ("rpc_out_us", "rpc_in_us")}
YIELD = "stream.yield"


def summed(run: dict, *attrs: str) -> Optional[int]:
    """Sum over the window's dispatches of the attributes, together."""
    totals = [host_threads.total_us(run, host_threads.DISPATCH, attr)
              for attr in attrs]
    return None if None in totals else sum(totals)


def parts_us(run: dict) -> Optional[Dict[str, int]]:
    """Microseconds of the traced window's loop time by part; the four add
    up to the sum of ``step_loop_cpu_us``."""
    whole = summed(run, "step_loop_cpu_us")
    beside = {part: summed(run, *attrs) for part, attrs in PARTS.items()}
    prof = host_regions.profile(run)
    if whole is None or None in beside.values() or not prof:
        return None
    engine = int(1e6 * sum(end - start for name, start, end, _
                           in prof["regions"] if name in ENGINE))
    return {"engine": engine, **beside,
            "unnamed": whole - engine - sum(beside.values())}


def part_ms(run: dict, part: str) -> Optional[float]:
    """One part, in milliseconds per ``jit__decode`` call."""
    parts = parts_us(run)
    return host_threads.per_decode_call_ms(run, parts and parts[part])


def ratio(run: dict, over: Tuple[str, ...], under: Tuple[str, ...]
          ) -> Optional[float]:
    """One sum of dispatch attributes over another."""
    a, b = summed(run, *over), summed(run, *under)
    return a / b if a is not None and b else None


ACK_PARTS = {"out": lambda r: r["out_us"], "in": lambda r: r["in_us"],
             "held": lambda r: r["held_us"],
             "back": lambda r: r["ack_us"] - r["out_us"] - r["in_us"]
             - r["held_us"]}


def ack_median_ms(run: dict, part: str) -> Optional[float]:
    """Median, over the window's yields whose ack said what the owner did
    with them, of one part of ``ack_us``: waiting ``out`` in the replica's
    outbox, the way ``in``, ``held`` by the owner's handler, or the way
    ``back``."""
    found = [r for r in host_regions.rows(run, YIELD) or [] if "in_us" in r]
    return 1e-3 * statistics.median(map(ACK_PARTS[part], found)) \
        if found else None
