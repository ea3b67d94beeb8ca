"""The three flash kernels alone on the chip (PERF.md section 6, PR 45): what
``ops/flash_attention.py::_SUB_TILE`` was chosen from, and how a tree's
kernels read at another sequence length.

    chiprun -- python scripts/flash_sweep.py [--seq S] [--heads BN]
                                             [--tree DIR] [edges...]

Each kernel is timed alone: 24 calls chained in one jitted ``lax.scan`` (a
call's result is the next call's q, or k for dkv), bf16, head size 64,
causal, the default grid blocks for S.  The default shape is the one-chip
training cell's (S = 1024, B*N = 192); ``--seq 4096 --heads 24`` is
``tests/test_tpu_compile.py``'s ``long-2x4096``, where the accumulators are
carried in scratch over four grid steps.  For each edge given (default: the
table's own) the module's ``_SUB_TILE`` is rebound, which is how a sweep
reaches a value the table holds as a constant; 1024 at S = 1024 is a grid
block as one sub-tile, masked whole.  ``--tree DIR`` times the kernels of
another checkout (``DIR/ray_tpu/ops/flash_attention.py``, say the parent
commit unpacked by ``git archive``) in the same process on the same inputs;
a tree from before PR 45 has no sub-tiles and gives one line.

A dq / dkv time holds the backward pass's glue as well (``delta``, the
``lse`` / ``delta`` carriers), the same in every line of one shape: compare
lines with each other, and read a kernel's own time from a traced run of
the cell.  One JSON line each: the plan (``tile_plan``), ms a call and the
seconds each kernel's program took to compile.

Needs the chip: a time from another backend is no reading of these kernels,
so there the script exits 1 before it runs anything.  (The kernels' numerics
on the CPU are ``tests/test_ops.py``'s.)
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
from jax import lax

H, CALLS = 64, 24


def load(tree: str):
    """``ray_tpu/ops/flash_attention.py`` of a checkout, as a module of its
    own (the package re-exports the function under the module's name)."""
    path = os.path.join(tree, "ray_tpu", "ops", "flash_attention.py")
    spec = importlib.util.spec_from_file_location(
        "flash_attention_of_" + str(abs(hash(path))), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def programs(fa, q, k, v, g, **kw):
    """{kernel: a jitted chain of CALLS calls of it alone}.  The backward
    pass's other kernel is dead code in each chain and is removed."""
    def fwd(q, k, v):
        return fa._flash_fwd_impl(q, k, v, **kw)

    o, lse = jax.jit(fwd)(q, k, v)

    def bwd(q, k, v):
        return fa._flash_bwd_impl(q, k, v, o, lse, g, **kw)

    def chain(fn, pick, arg):
        def run(q, k, v):
            def body(carry, _):
                args = [q, k, v]
                args[arg] = carry
                return pick(fn(*args)).astype(q.dtype), None
            return lax.scan(body, [q, k, v][arg], None, length=CALLS)[0]
        return jax.jit(run)

    return {"fwd": chain(fwd, lambda r: r[0], 0),
            "dq": chain(bwd, lambda r: r[0], 0),
            "dkv": chain(bwd, lambda r: r[1] + r[2], 1)}


def measure(fa, q, k, v, g, blocks) -> dict:
    """ms a call and compile seconds of each kernel of ``fa`` as it stands."""
    kw = dict(causal=True, block_q=blocks[0], block_k=blocks[1],
              sm_scale=None, interpret=False, layout="bnsh")
    line = {}
    for kind, fn in programs(fa, q, k, v, g, **kw).items():
        start = time.perf_counter()
        compiled = fn.lower(q, k, v).compile()
        line[kind + "_compile_s"] = round(time.perf_counter() - start, 2)
        compiled(q, k, v).block_until_ready()
        times = []
        for _ in range(5):
            start = time.perf_counter()
            compiled(q, k, v).block_until_ready()
            times.append(time.perf_counter() - start)
        line[kind + "_ms"] = round(1e3 * min(times) / CALLS, 4)
    return line


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seq", type=int, default=1024)
    parser.add_argument("--heads", type=int, default=192, help="B*N")
    parser.add_argument("--tree", default=ROOT)
    parser.add_argument("edges", nargs="*", type=int)
    args = parser.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit(f"flash_sweep: the backend is {jax.default_backend()!r}, "
                 "not a TPU: nothing timed here would be the kernels' time")
    fa = load(args.tree)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, g = (jax.random.normal(key, (1, args.heads, args.seq, H),
                                    jnp.bfloat16) for key in keys)
    blocks = fa._default_blocks(args.seq)
    swept = hasattr(fa, "_SUB_TILE")
    for edge in (args.edges or [fa._SUB_TILE]) if swept else [None]:
        line = {"tree": args.tree, "seq": args.seq, "heads": args.heads,
                "blocks": blocks, "device": jax.devices()[0].device_kind}
        if swept:
            fa._SUB_TILE = edge
            line.update(sub_tile=edge,
                        plan=fa.tile_plan(args.seq, *blocks, True))
        line.update(measure(fa, q, k, v, g, blocks))
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
