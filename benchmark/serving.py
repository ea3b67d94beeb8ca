"""What the serve generators share: the replica behind the HTTP ingress,
its warm-up, a client for one streamed request, and the gathering of what
the metrics read.  A generator adds only the order and timing of requests.
"""

from __future__ import annotations

import asyncio
import json
import os
import shutil
import time
from urllib.parse import urlparse

import numpy as np

from benchmark import spec, stats

NAME = "llm"
READY_DEADLINE_S = 300
TRACE_AFTER_S, TRACE_FOR_S = 3.0, 5.0
GAP_PERCENTILES = (50, 90, 95, 97, 98, 98.3, 98.5, 98.6, 98.7, 98.8, 98.9, 99,
                   99.1, 99.2, 99.3, 99.4, 99.5, 99.75, 99.9)


def sizes(plan: dict, count: int, rng: np.random.Generator) -> list:
    """``count`` lengths that are the same set for every seed: the
    distribution's quantile grid, shuffled by the seed."""
    grid = (np.arange(count) + 0.5) / count
    if plan["distribution"] == "lognormal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(float(q)) for q in grid])
        values = plan["median"] * np.exp(plan["sigma"] * z)
    elif plan["distribution"] == "uniform":
        values = plan["min"] + grid * (plan["max"] - plan["min"])
    else:
        raise ValueError(f"unknown distribution {plan['distribution']!r}")
    values = np.clip(np.rint(values), plan["min"], plan["max"]).astype(int)
    return [int(v) for v in rng.permutation(values)]


class Session:
    """One replica behind the ingress, and the requests sent to it."""

    def __init__(self, ctx: dict):
        self.ctx, self.cell = ctx, ctx["cell"]
        self.config = self.cell["config"]
        self.rng = np.random.default_rng(ctx["seed"])
        self.records = []
        self.loop_lag_ms = []
        self.not_ready = []         # what the readiness probe met
        self.start = None           # perf_counter at the window's start

    # ------------------------------------------------------------ set-up
    def deploy(self) -> None:
        import ray_tpu
        from ray_tpu import serve
        from benchmark.replica import BenchLLMServer
        trace_dir = os.path.join(spec.ROOT, ".bench_trace",
                                 self.cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        self.handle = serve.run(serve.deployment(
            BenchLLMServer, name=NAME,
            max_concurrent_queries=self.config["max_concurrent_queries"],
            ray_actor_options={"resources": {"TPU": 1}}).bind(
                self.config, self.ctx["seed"], trace_dir))
        url = urlparse(serve.start_http() + "/" + NAME)
        self.host, self.port, self.path = url.hostname, url.port, url.path
        # serve.run returns once a replica is registered, which is before
        # it answers; and in the first seconds of a fresh machine's life
        # the first replica has been seen to be killed and replaced (PR 23,
        # PERF.md Open questions).  So wait as a readiness probe does: its
        # first answer says that a replica made the weights and ran both
        # programs.
        deadline = time.monotonic() + READY_DEADLINE_S
        while True:
            try:
                self.numerics = ray_tpu.get(
                    self.handle.method("check_numerics").remote())
                break
            except Exception as e:   # noqa: BLE001 - told apart by the clock
                if time.monotonic() > deadline:
                    raise
                self.not_ready.append(repr(e)[:200])
                time.sleep(1.0)
        self.device = self.numerics["device"]

    def call(self, method: str, *args):
        import ray_tpu
        return ray_tpu.get(self.handle.method(method).remote(*args))

    # ----------------------------------------------------------- requests
    async def request(self, prompt_tokens: int, max_new: int,
                      due: float = None, warmup: bool = False) -> dict:
        """Stream one generation; ``due`` is when an open loop was to send
        it (a closed loop sends when it can: due is then the send time)."""
        rid = len(self.records)
        record = {"id": rid, "prompt_tokens": prompt_tokens,
                  "asked": max_new, "arrivals": [], "tokens": [],
                  "error": None}
        if not warmup:
            self.records.append(record)
        prompt = self.rng.integers(
            0, self.config["vocab_size"], prompt_tokens).tolist()
        body = json.dumps({"id": None if warmup else rid, "tokens": prompt,
                           "max_new_tokens": max_new,
                           "stream": True}).encode()
        head = (f"POST {self.path} HTTP/1.1\r\nHost: {self.host}\r\n"
                "Content-Type: application/json\r\n"
                "Accept: text/event-stream\r\n"
                f"Content-Length: {len(body)}\r\n\r\n").encode()
        record["sent"] = time.perf_counter()
        record["due"] = record["sent"] if due is None else due
        writer = None
        try:
            reader, writer = await asyncio.open_connection(self.host,
                                                           self.port)
            writer.write(head + body)
            await writer.drain()
            status = await reader.readline()
            if b" 200 " not in status:
                raise RuntimeError(f"ingress answered {status!r}")
            event = None
            while True:
                line = await reader.readline()
                if not line:
                    raise RuntimeError("stream closed before its end event")
                line = line.strip()
                if line.startswith(b"event: "):
                    event = line[7:].decode()
                elif line.startswith(b"data: "):
                    if event == "end":
                        break
                    if event is not None:
                        raise RuntimeError(f"stream sent {event}: {line!r}")
                    record["arrivals"].append(time.perf_counter())
                    record["tokens"].append(json.loads(line[6:]))
                elif not line:
                    event = None
        except Exception as e:   # noqa: BLE001 - counted as a failed request
            record["error"] = repr(e)
        finally:
            if writer is not None:
                writer.close()
        return record

    # ------------------------------------------------------------ the run
    def run(self, drive) -> dict:
        """Deploy, warm up, let ``drive(session)`` send the window's
        requests, and gather what the metrics read."""
        from ray_tpu import serve
        started = time.perf_counter()
        try:
            self.deploy()
            self.phases = {"deploy_s": time.perf_counter() - started,
                           "not_ready": self.not_ready}
            return asyncio.run(self._run(drive))
        finally:
            serve.shutdown()

    async def _run(self, drive) -> dict:
        loop = asyncio.get_running_loop()
        engine = self.config["engine"]
        warm = await self.request(engine["page_size"] * 3, 4, warmup=True)
        if warm["error"] or len(warm["tokens"]) != 4:
            raise RuntimeError(f"the warm-up request failed: {warm}")
        seconds, watchers = self.ctx["seconds"], []
        self.phases["warm_request_s"] = warm["arrivals"][-1] - warm["sent"]
        window_start_epoch = time.time()
        self.start = time.perf_counter()
        if self.ctx["trace"]:
            watchers = [
                loop.run_in_executor(None, self.call, "observe", seconds,
                                     min(TRACE_AFTER_S, seconds / 4),
                                     min(TRACE_FOR_S, seconds / 2)),
                loop.create_task(self._watch_loop_lag(seconds))]
        await drive(self)
        await asyncio.gather(*watchers)
        drained = time.perf_counter()
        replica = await loop.run_in_executor(None, self.call, "collect")
        self.phases.update(drain_s=drained - self.start - seconds,
                           collect_s=time.perf_counter() - drained)
        vocab = self.config["vocab_size"]
        failed = [r for r in self.records if r["error"]]
        inexact = sum(1 for r in self.records if r["error"] or not (
            len(r["tokens"]) == r["asked"] and all(
                isinstance(t, int) and 0 <= t < vocab for t in r["tokens"])))
        gaps = sorted(stats.token_gaps_ms(self.records))
        return {"device": {**self.device,
                           "memory_peak_bytes": replica["memory_peak_bytes"]},
                # where the tail's plateaus lie (stderr only)
                "gaps_ms": {"count": len(gaps), **{
                    f"p{q:g}": stats.percentile(gaps, q)
                    for q in GAP_PERCENTILES if gaps}},
                "correct": bool(self.numerics["ok"] and self.records
                                and not inexact),
                "attempted": len(self.records), "failed": len(failed),
                "errors": [r["error"] for r in failed][:3],
                "numerics": self.numerics,
                "checks": {"logits_rel_err": [
                    max(self.numerics["logits_rel_err"]),
                    self.numerics["rtol"]],
                    "requests_failed_or_inexact": [inexact, 0]},
                # what a knee is read from (tools/sweep.py)
                "load": {"offered": len(self.records),
                         "finished_in_window": stats.finished_by(
                             self.records, self.start + seconds)},
                "phases": {**self.phases, **replica["phases"]},
                "window_start_epoch": window_start_epoch,
                "window": (self.start, self.start + seconds),
                "requests": self.records, "replica": replica,
                # run.py reduces the profile into ``trace``
                "trace": {}, "profile": replica["profile"],
                "loop_lag_ms": self.loop_lag_ms}

    async def _watch_loop_lag(self, seconds: float) -> None:
        """The LoopWatchdog reading of the replica's node, once a second."""
        from ray_tpu.util import state
        loop = asyncio.get_running_loop()
        while time.perf_counter() < self.start + seconds:
            stats = await loop.run_in_executor(None, state.node_stats)
            self.loop_lag_ms += [s["loop_lag_ms"] for s in stats.values()
                                 if "loop_lag_ms" in s]
            await asyncio.sleep(1.0)
