"""The token step's paged read alone on the chip, the kernel against the
gather, at the five K/V serving cells' shapes (PERF.md section 6, PR 49) and
at Xing's latent pages (PR 50): the evidence
``ops/paged_attention.py::paged_read_kind`` stands on, and what
``ops/paged_read.py::_BLOCK_TOKENS`` was chosen from.

    chiprun -- python scripts/paged_read_sweep.py [--cells NAME ...]
                    [--blocks TOKENS ...] [--buffers N ...] [--seed N]

A cell's shape is its engine's (``benchmark/configs/*.json``: slots, pages,
the pools' layers, K/V heads, query heads a K/V head) and its lengths are
drawn as its traffic draws them: ``live`` of the slots hold a prompt and a
uniform share of an answer, both uniform in the cell's ranges (chat:
lognormal, clipped), the other slots are parked on page 0 (length 1, an
all-zero row); the table is as wide as the engine's rung for the longest
(``decode_rungs``), each sequence's pages scattered over the pool.  Xing's
40 callers start together and stay in step, so its lengths are drawn at two
moments of a window: ``@3-8s``, every slot a prompt and 150-400 tokens of its
answer (the traced seconds), and ``@end``, 2,600 steps in, where a slot whose
answer (1,024-3,072) has ended holds the caller that took its place.  Its
one pool is ``[6, 8193, 16, 640]``, a query a head 640 wide (576 and zeros),
the result the rows' first 512 columns; ``twocopies@TxN`` is the K/V kernel
handed the pool as both K and V (two copies a page), ``kernel@TxN`` the
latent kind's one copy.  Each read is timed alone: 24 calls chained in one
jitted ``lax.scan`` (a call's result is the next call's queries; the layer
goes round the pool's), bf16.
``gather`` is ``paged_attention`` as the CPU runs it (the jnp gather and two
einsums), ``kernel@TxN`` is ``ops/paged_read.py`` at ``T`` positions a block
and ``N`` blocks in fast memory.

One JSON line a cell: ms a call of each, ``live_mb`` the bytes of the live
positions' K and V (of a latent cell: their 576 values, as
``benchmark/costs_mla.py::latent_read`` counts them), and each read's share
of the roofline of those bytes (``live_mb`` / 819 GB/s over its time, %).

Needs the chip: a time from another backend is no reading of either, so
there the script exits 1 before it runs anything.  (The kernel's numerics on
the CPU are ``tests/test_paged_attention_kernel.py``'s.)
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.ops import paged_read

CALLS = 24
PAGE, H = 16, 128
HBM_BYTES_PER_S = 819e9

# name: slots, live slots, query heads, K/V heads, pool layers, pages of the
# pool, widest table, (prompt range), (answer range)
CELLS = {
    "serve-olmo-hybrid-decode-wide":
        (48, 48, 30, 30, 3, 4609, 96, (64, 512), (256, 1024)),
    "serve-ouro-cot-batch":
        (12, 12, 16, 16, 192, 241, 20, (32, 128), (96, 192)),
    "serve-olmoe-decode-heavy":
        (16, 16, 16, 16, 4, 1537, 96, (64, 512), (256, 1024)),
    "serve-chat-steady":
        (16, 9, 32, 8, 8, 2561, 160, (32, 2048), (16, 512)),
    "serve-longprompt-batch":
        (16, 16, 32, 8, 8, 2561, 160, (1024, 2048), (16, 64)),
    # latent pages: one K/V "head" of the padded row; the answer range is
    # the tokens every caller is into its answer, or the steps since the start
    "serve-xing-reasoning-batch@3-8s":
        (32, 32, 32, 1, 6, 8193, 256, (256, 1024), (150, 400)),
    "serve-xing-reasoning-batch@end":
        (32, 32, 32, 1, 6, 8193, 256, (256, 1024), (2600, 2600)),
}
RANK, ROPE, ROW = 512, 64, 640        # Xing's latent row, and as it is stored
XING_ANSWER = (1024, 3072)


def draw(name, rng):
    """(lengths [slots], table [slots, rung]) of one step of the cell."""
    from ray_tpu.serve.engine.engine import decode_rungs, rung_for
    slots, live, _, _, _, pages, maxp, prompt, answer = CELLS[name]
    if name == "serve-chat-steady":
        prompts = np.clip(rng.lognormal(np.log(256), 1.0, live), *prompt)
        answers = np.clip(rng.lognormal(np.log(128), 0.8, live), *answer)
    else:
        prompts = rng.integers(prompt[0], prompt[1] + 1, live)
        answers = rng.integers(answer[0], answer[1] + 1, live)
    share = rng.random(live)
    if latent(name):
        # in step: every slot ``answers`` tokens in, unless its answer ended
        # before that and the next caller's prompt took the slot
        share = 1.0
        ended = rng.integers(XING_ANSWER[0], XING_ANSWER[1] + 1, live)
        nxt = rng.integers(prompt[0], prompt[1] + 1, live)
        prompts = np.where(ended < answers, nxt, prompts)
        answers = np.where(ended < answers, answers - ended, answers)
    lengths = np.ones(slots, np.int32)
    lengths[:live] = np.minimum(
        prompts + share * answers, maxp * PAGE).astype(np.int32)
    rung = rung_for(decode_rungs(maxp), int(lengths.max() - 1) // PAGE + 1)
    table = np.zeros((slots, rung), np.int32)
    free = rng.permutation(np.arange(1, pages))
    for slot in range(live):
        held = -(-int(lengths[slot]) // PAGE)
        table[slot, :held], free = free[:held], free[held:]
    return lengths, table


def latent(name) -> bool:
    return CELLS[name][3] == 1


def chain(read):
    """A jitted chain of CALLS calls of ``read`` alone."""
    def run(q, k_pages, v_pages, lengths, table):
        def body(q, i):
            out = read(q, k_pages, v_pages, i % k_pages.shape[0], lengths,
                       table)
            # (a latent read gives the values' columns: the rest stay)
            out = jnp.concatenate([out, q[..., out.shape[-1]:]], axis=-1)
            return out.astype(q.dtype), None
        return lax.scan(body, q, jnp.arange(CALLS, dtype=jnp.int32))[0]
    return jax.jit(run)


def timed(fn, *args) -> float:
    """ms a call."""
    compiled = fn.lower(*args).compile()
    compiled(*args).block_until_ready()
    times = []
    for _ in range(5):
        start = time.perf_counter()
        compiled(*args).block_until_ready()
        times.append(time.perf_counter() - start)
    return 1e3 * min(times) / CALLS


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cells", nargs="+", default=list(CELLS),
                        choices=list(CELLS))
    parser.add_argument("--blocks", type=int, nargs="+",
                        default=[paged_read._BLOCK_TOKENS],
                        help="positions a block (default: the module's)")
    parser.add_argument("--buffers", type=int, nargs="+",
                        default=[paged_read._BUFFERS],
                        help="blocks in fast memory: one computed, the "
                             "others on their way (default: the module's)")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit(f"paged_read_sweep: the backend is {jax.default_backend()!r}"
                 ", not a TPU: nothing timed here would be a read's time")
    # the module: ray_tpu.ops re-exports the function under the same name
    pa = importlib.import_module("ray_tpu.ops.paged_attention")
    paged_read_attention = paged_read.paged_read_attention
    for name in args.cells:
        slots, live, N, NKV, L, pages, _, _, _ = CELLS[name]
        rng = np.random.default_rng(args.seed)
        lengths, table = draw(name, rng)
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
        if latent(name):
            # the stored row's padding is zeros, in the queries too
            used = jnp.arange(ROW) < RANK + ROPE
            q = jax.random.normal(keys[0], (slots, N, ROW),
                                  jnp.bfloat16) * used
            k_pages = jax.random.normal(keys[1], (L, pages, PAGE, ROW),
                                        jnp.bfloat16) * used
            v_pages = None
            live_bytes = int(lengths[:live].sum()) * (RANK + ROPE) * 2
            scale = (128 + ROPE) ** -0.5

            def gather(q, pool, _, *rest):
                return pa.paged_latent_attention(
                    q[..., :RANK + ROPE], pool, *rest, sm_scale=scale,
                    rank=RANK)

            def kernel(q, pool, _, *rest, twice=False, **how):
                return paged_read_attention(
                    q, pool, pool if twice else None, *rest, sm_scale=scale,
                    interpret=False, columns=RANK, **how)[..., :RANK]
        else:
            q = jax.random.normal(keys[0], (slots, N, H), jnp.bfloat16)
            k_pages, v_pages = (
                jax.random.normal(key, (L, pages, PAGE, NKV * H),
                                  jnp.bfloat16) for key in keys[1:])
            live_bytes = int(lengths[:live].sum()) * NKV * H * 2 * 2
            gather = pa.paged_attention

            def kernel(*a, **how):
                return paged_read_attention(*a, sm_scale=H ** -0.5,
                                            interpret=False, **how)
        line = {"cell": name, "slots": slots, "live": live,
                "rung_pages": table.shape[1], "kv_heads": NKV,
                "rep": N // NKV, "live_positions": int(lengths[:live].sum()),
                "longest": int(lengths.max()),
                "live_mb": round(live_bytes / 1e6, 2),
                "device": jax.devices()[0].device_kind}
        reads = {}
        pa._kernel_backend = lambda: False
        reads["gather"] = chain(gather)
        for block in args.blocks:
            for buffers in args.buffers:
                how = {"pages_per_block": block // PAGE, "buffers": buffers}
                reads[f"kernel@{block}x{buffers}"] = chain(
                    lambda *a, how=how: kernel(*a, **how))
                if latent(name):
                    reads[f"twocopies@{block}x{buffers}"] = chain(
                        lambda *a, how=how: kernel(*a, twice=True, **how))
        # one call of each on the same inputs: the compiled kernel's
        # result against the gather's
        one = [jax.jit(lambda *a, read=read: read(*a))(
            q, k_pages, v_pages, 1 % L, jnp.asarray(lengths),
            jnp.asarray(table)).astype(jnp.float32)
            for read in (gather, kernel)]
        line["kernel_max_abs_diff"] = float(jnp.max(jnp.abs(one[0] - one[1])))
        line["result_max_abs"] = float(jnp.max(jnp.abs(one[0])))
        for kind, fn in reads.items():
            ms = timed(fn, q, k_pages, v_pages, jnp.asarray(lengths),
                       jnp.asarray(table))
            line[kind + "_ms"] = round(ms, 4)
            line[kind + "_roofline"] = round(
                100 * live_bytes / HBM_BYTES_PER_S / (ms / 1e3), 1)
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
