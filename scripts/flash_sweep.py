"""The three flash kernels alone on the chip (PERF.md section 6, PR 45): what
``ops/flash_attention.py::_SUB_TILE`` was chosen from, and how a tree's
kernels read at another sequence length.

    chiprun -- python scripts/flash_sweep.py [--seq S ...] [--heads BN]
                    [--kv-heads G] [--head-size H] [--fwd-only]
                    [--tree DIR] [edges...]

Each kernel is timed alone: 24 calls chained in one jitted ``lax.scan`` (a
call's result is the next call's q, or k for dkv), bf16, head size 64
unless ``--head-size`` says otherwise, causal, the default grid blocks for
S.  The default shape is the one-chip
training cell's (S = 1024, B*N = 192); ``--seq 4096 --heads 24`` is
``tests/test_tpu_compile.py``'s ``long-2x4096``, where the accumulators are
carried in scratch over four grid steps.  ``--fwd-only`` times the forward
kernel alone and, beside it as ``dense_ms``, the dense function the served
prefill runs where the kernel is not chosen
(``models/llama.py::_dense_causal_attention_gqa`` of this checkout), with
``--kv-heads`` k and v heads shared by the ``--heads`` query heads of one
sequence: ``--seq 512 1024 2048 --heads 32 --kv-heads 8 --head-size 128
--fwd-only`` is Mistral's prefill attention at three rungs, ``--heads 16
--kv-heads 16`` OLMoE's: the crossover ``ops/attention.py::_flash_profitable``
stands for.  (Grouped heads need a tree from PR 46 on, and have no backward
kernels.)  For each edge given (default: the
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

CALLS = 24


def load(tree: str):
    """``ray_tpu/ops/flash_attention.py`` of a checkout, as a module of its
    own (the package re-exports the function under the module's name)."""
    path = os.path.join(tree, "ray_tpu", "ops", "flash_attention.py")
    spec = importlib.util.spec_from_file_location(
        "flash_attention_of_" + str(abs(hash(path))), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def programs(fa, q, k, v, g, fwd_only, **kw):
    """{kernel: a jitted chain of CALLS calls of it alone}.  The backward
    pass's other kernel is dead code in each chain and is removed."""
    def fwd(q, k, v):
        return fa._flash_fwd_impl(q, k, v, **kw)

    def chain(fn, pick, arg):
        def run(q, k, v):
            def body(carry, _):
                args = [q, k, v]
                args[arg] = carry
                return pick(fn(*args)).astype(q.dtype), None
            return lax.scan(body, [q, k, v][arg], None, length=CALLS)[0]
        return jax.jit(run)

    if fwd_only:
        from ray_tpu.models.llama import _dense_causal_attention_gqa
        rep = q.shape[1] // k.shape[1]
        return {"fwd": chain(fwd, lambda r: r[0], 0),
                "dense": chain(lambda q, k, v: _dense_causal_attention_gqa(
                    q, k, v, rep), lambda r: r, 0)}

    o, lse = jax.jit(fwd)(q, k, v)

    def bwd(q, k, v):
        return fa._flash_bwd_impl(q, k, v, o, lse, g, **kw)

    return {"fwd": chain(fwd, lambda r: r[0], 0),
            "dq": chain(bwd, lambda r: r[0], 0),
            "dkv": chain(bwd, lambda r: r[1] + r[2], 1)}


def measure(fa, q, k, v, g, blocks, fwd_only) -> dict:
    """ms a call and compile seconds of each kernel of ``fa`` as it stands."""
    kw = dict(causal=True, block_q=blocks[0], block_k=blocks[1],
              sm_scale=None, interpret=False, layout="bnsh")
    line = {}
    for kind, fn in programs(fa, q, k, v, g, fwd_only, **kw).items():
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
    parser.add_argument("--seq", type=int, nargs="+", default=[1024])
    parser.add_argument("--heads", type=int, default=192, help="B*N")
    parser.add_argument("--kv-heads", type=int, default=None,
                        help="k and v heads (default: --heads)")
    parser.add_argument("--head-size", type=int, default=64)
    parser.add_argument("--fwd-only", action="store_true")
    parser.add_argument("--tree", default=ROOT)
    parser.add_argument("edges", nargs="*", type=int)
    args = parser.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit(f"flash_sweep: the backend is {jax.default_backend()!r}, "
                 "not a TPU: nothing timed here would be the kernels' time")
    if args.kv_heads not in (None, args.heads) and not args.fwd_only:
        sys.exit("flash_sweep: grouped heads have no backward kernels; "
                 "give --fwd-only with --kv-heads")
    fa = load(args.tree)
    swept = hasattr(fa, "_SUB_TILE")
    for seq in args.seq:
        kv_heads = args.kv_heads or args.heads
        q, k, v, g = (jax.random.normal(
            jax.random.PRNGKey(n), (1, heads, seq, args.head_size),
            jnp.bfloat16)
            for n, heads in enumerate((args.heads, kv_heads, kv_heads,
                                       args.heads)))
        blocks = fa._default_blocks(seq)
        for edge in (args.edges or [fa._SUB_TILE]) if swept else [None]:
            line = {"tree": args.tree, "seq": seq, "heads": args.heads,
                    "kv_heads": kv_heads, "head_size": args.head_size,
                    "blocks": blocks,
                    "device": jax.devices()[0].device_kind}
            if swept:
                fa._SUB_TILE = edge
                line.update(sub_tile=edge,
                            plan=fa.tile_plan(seq, *blocks, True))
            line.update(measure(fa, q, k, v, g, blocks, args.fwd_only))
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
