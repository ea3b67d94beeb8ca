"""Callers that wait for a reply: ``clients`` of them, each sending its next
request when the last one ended, until the window closes.  What is in flight
then is drained, and counts as far as it got inside the window.

Requests come from one list that every client draws from.  It is built in
blocks of ``block`` requests, each block the quantile grid of the length
distributions shuffled by the seed, so that any run of consecutive requests
is the same work for every seed to within one block.
"""

import asyncio
import time

import numpy as np

from benchmark.serving import Session, sizes


def plan(traffic: dict, seed: int):
    """An endless stream of (prompt tokens, output tokens)."""
    rng = np.random.default_rng(seed)
    while True:
        yield from zip(sizes(traffic["prompt_tokens"], traffic["block"], rng),
                       sizes(traffic["output_tokens"], traffic["block"], rng))


def run(ctx: dict) -> dict:
    traffic = ctx["cell"]["traffic"]
    requests = plan(traffic, ctx["seed"])

    async def drive(session: Session):
        end = session.start + ctx["seconds"]

        async def client():
            while time.perf_counter() < end:
                await session.request(*next(requests))
        await asyncio.gather(*(client() for _ in range(traffic["clients"])))

    return Session(ctx).run(drive)
