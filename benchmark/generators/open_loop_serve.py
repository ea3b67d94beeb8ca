"""Independent users: requests sent on a schedule, whatever the replica does.

Every seed offers the same load: exactly ``round(rate * seconds)`` arrivals
at the order statistics of as many uniform draws (a Poisson process given
its count), with prompt and output lengths that are the quantile grid of
their distributions, shuffled by the seed.
"""

import asyncio
import time

import numpy as np

from benchmark.serving import Session, sizes


def plan(traffic: dict, seconds: float, seed: int) -> list:
    """(offset from the window's start, prompt tokens, output tokens)."""
    rng = np.random.default_rng(seed)
    count = max(1, round(traffic["rate_per_s"] * seconds))
    offsets = np.sort(rng.uniform(0.0, seconds, count))
    return list(zip((float(t) for t in offsets),
                    sizes(traffic["prompt_tokens"], count, rng),
                    sizes(traffic["output_tokens"], count, rng)))


def run(ctx: dict) -> dict:
    schedule = plan(ctx["cell"]["traffic"], ctx["seconds"], ctx["seed"])

    async def drive(session: Session):
        tasks = []
        for offset, prompt, output in schedule:
            due = session.start + offset
            delay = due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.create_task(
                session.request(prompt, output, due)))
        await asyncio.gather(*tasks)

    return Session(ctx).run(drive)
