"""``closed_loop_serve_checked`` for a model that keeps a recurrent state a
decode slot: the same callers and the same plan of requests, with the replica
whose check compares the slot's state rows and convolution tail as well as the
logits (``benchmark/replica_states.py``) behind the ingress, and that check's
other numbers (the state's and the tail's errors beside their limits, the
state pool's width in bytes beside the stated one) among the run's ``checks``.

As ``closed_loop_serve_checked`` does, the cell's family first builds the
program's configuration (a dataclass; no JAX backend is opened) in the
benchmark's own process: a program that cannot take the configuration (the
parent of the PR that taught it the layer) fails here, at once, with the
program's own error, and not after ``READY_DEADLINE_S`` seconds of a
readiness probe.
"""

import asyncio
import os
import shutil
import time

from benchmark import serving, spec
from benchmark.generators.closed_loop_serve import plan

class StatesSession(serving.Session):
    def deploy(self) -> None:
        """``Session.deploy`` with ``StatesBenchLLMServer`` bound: that
        method names its replica class in its body."""
        import ray_tpu
        from ray_tpu import serve
        from urllib.parse import urlparse
        from benchmark.replica_states import StatesBenchLLMServer
        trace_dir = os.path.join(spec.ROOT, ".bench_trace",
                                 self.cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        self.handle = serve.run(serve.deployment(
            StatesBenchLLMServer, name=serving.NAME,
            max_concurrent_queries=self.config["max_concurrent_queries"],
            ray_actor_options={"resources": {"TPU": 1}}).bind(
                self.config, self.ctx["seed"], trace_dir))
        url = urlparse(serve.start_http() + "/" + serving.NAME)
        self.host, self.port, self.path = url.hostname, url.port, url.path
        deadline = time.monotonic() + serving.READY_DEADLINE_S
        while True:                  # as a readiness probe waits
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

    async def _run(self, drive) -> dict:
        run = await super()._run(drive)
        said = self.numerics
        run["checks"]["state_rel_err"] = [max(said["state_rel_err"]),
                                          said["state_rtol"]]
        run["checks"]["tail_rel_err"] = [max(said["tail_rel_err"]),
                                         said["tail_rtol"]]
        run["checks"]["state_pool_itemsize"] = [
            said["state_itemsize"], said["state_itemsize_stated"]]
        return run


def run(ctx: dict) -> dict:
    config = ctx["cell"]["config"]
    engine = config["engine"]
    spec.load_part("families", config["family"]).program_config(
        config, engine["max_prompt_len"] + engine["max_new_tokens"])
    traffic = ctx["cell"]["traffic"]
    requests = plan(traffic, ctx["seed"])

    async def drive(session: StatesSession):
        end = session.start + ctx["seconds"]

        async def client():
            while time.perf_counter() < end:
                await session.request(*next(requests))
        await asyncio.gather(*(client() for _ in range(traffic["clients"])))

    return StatesSession(ctx).run(drive)
