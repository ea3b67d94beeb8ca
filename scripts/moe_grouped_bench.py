"""The experts' two grouped matmuls alone on the chip, at the shapes of the
cells that run ``ops/moe.py::moe_dropless`` (PERF.md section 6, PR 41 and
PR 56).

For each shape, a ``lax.scan`` over the layers runs gate/up, the SwiGLU and
down as ``moe_dropless`` does, with the grouped matmul being

  stack   ``jax.lax.ragged_dot`` over the stack of L layers, group sizes of
          the whole stack with only the scanned layer's filled (the program
          before PR 41);
  one     ``jax.lax.ragged_dot`` over ONE layer's experts (E groups): what
          "the layer's groups alone" gives with the compiler's kernel;
  kernel  ``ops/grouped_matmul.py`` over the stack with the layer as an
          offset where it copies the weights from; ``kernel:tm256``
          with that row tile (handed to ``_tiled``, whatever ``_tiles``
          would say),
          ``kernel:mib4`` with the weights' tile held to 4 MiB.

``--live-share 0.75`` sizes the groups from three quarters of the rows: the
others lie behind the last group, as ``moe_dropless`` sorts the assignments
of padding and idle slots, and no visit computes them.

One process, no cluster:

    chiprun -- python scripts/moe_grouped_bench.py
    chiprun -- python scripts/moe_grouped_bench.py --shapes lfm2-prefill \
        --assignments 8192 16384 --live-share 1.0 0.75 \
        --variants kernel:tm64 kernel:tm128 kernel:tm256 kernel:tm512

prints one JSON line a shape and variant (milliseconds a layer, the bytes of
the touched experts over that time as a share of the HBM's peak, the live
rows' operations as a share of the bf16 peak) and writes them to
``chiprun_out/pr56/grouped_bench.jsonl``.
"""

from __future__ import annotations

import argparse
import functools
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import grouped_matmul as gm

HBM_BYTES_PER_S = 819e9            # one v5e chip (benchmark/peaks.json)
BF16_FLOPS_PER_S = 197e12
OUT = "chiprun_out/pr56"

SHAPES = {
    # the block cell: 1,024 assignments a step, eight experts a layer take
    # nine times the mean (the seeded router's code for a masked row)
    "sdar": dict(L=6, E=128, D=2048, M=768, dtype="bfloat16", A=1024, hot=8),
    "xing": dict(L=5, E=64, D=3584, M=1024, dtype="bfloat16", A=128, hot=0),
    "olmoe": dict(L=4, E=64, D=2048, M=1024, dtype="float32", A=128, hot=0),
    # LFM2's prefill: a rung of 2,048 or 4,096 positions, four experts a
    # token (--assignments 8192 16384), hundreds of rows an expert
    "lfm2-prefill": dict(L=8, E=64, D=2048, M=1536, dtype="bfloat16",
                         A=16384, hot=0),
    # for a rehearsal on the CPU: --shapes tiny --calls 1
    "tiny": dict(L=2, E=16, D=128, M=128, dtype="float32", A=64, hot=1),
}


def group_sizes(E: int, A: int, hot: int, seed: int) -> np.ndarray:
    rng = np.random.RandomState(seed)
    sizes = np.zeros(E, np.int64)
    if hot:
        sizes[rng.choice(E, hot, replace=False)] = 9 * A // E
    weights = rng.dirichlet(np.full(E, 2.0)) * (sizes == 0)
    sizes += rng.multinomial(A - sizes.sum(), weights / weights.sum())
    return sizes.astype(np.int32)


def stack_of(key, shape, dtype):
    """Normal weights made a layer at a time into the stack where it lies
    (neither the float32 draw of a whole stack nor a second copy of it
    would fit beside it)."""
    L, G = shape[:2]
    fill = jax.jit(
        lambda stack, k, i: jax.lax.dynamic_update_slice_in_dim(
            stack, (0.05 * jax.random.normal(k, shape[1:], jnp.float32)
                    ).astype(dtype), i * G, 0), donate_argnums=0)
    stack = jnp.zeros((L * G,) + shape[2:], dtype)
    for i, k in enumerate(jax.random.split(key, L)):
        stack = fill(stack, k, i)
    return stack


def experts_step(matmul, L: int):
    """scan over the layers of what ``moe_dropless`` does under
    ``moe_experts``; ``matmul(lhs, weights, sizes, layer)``."""
    def run(w, x2, sizes, gate_at, up_at):
        sizes2 = jnp.repeat(sizes, 2)

        def layer(carry, i):
            # every layer's rows hang on the layer before, as in a model:
            # nothing here is the same at each step of the scan, so the
            # compiler can move none of it out of the loop
            rows = x2.at[0].add((carry * 0.0).astype(x2.dtype))
            gu = matmul(rows, w["wgu"], sizes2, i)
            hidden = jax.nn.silu(gu[gate_at]) * gu[up_at]
            ys = matmul(hidden, w["wd"], sizes, i)
            return carry + ys[0].astype(jnp.float32), ys
        return jax.lax.scan(layer, jnp.zeros((x2.shape[1],), jnp.float32),
                            jnp.arange(L))
    return run


def with_row_tile(tm: int, lhs, rhs, sizes, layer):
    """``grouped_matmul`` with the row tile ``tm`` whatever the rows."""
    K, N = rhs.shape[1:]
    _, tn, interpret = gm._resolve(lhs.shape[0], K, N, lhs.dtype.itemsize,
                                   None)
    return gm._tiled(lhs, rhs, sizes.astype(jnp.int32),
                     jnp.asarray(layer, jnp.int32).reshape(1),
                     tm=tm, tn=tn, interpret=interpret)


def timed(fn, *args, calls: int):
    out = jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(calls):
        last = fn(*args)
    jax.block_until_ready(last)
    return (time.perf_counter() - start) / calls, out


def bench(name: str, calls: int, variants, assignments=None,
          live_share: float = 1.0) -> list:
    s = dict(SHAPES[name])
    if assignments:        # another load on the same experts, no hot ones
        s.update(A=assignments, hot=0)
    L, E, D, M, A = s["L"], s["E"], s["D"], s["M"], s["A"]
    kept = int(round(live_share * A))  # the rows of the A that are in a group
    dtype = jnp.dtype(s["dtype"])
    key = jax.random.PRNGKey(41)
    stacks = {"wgu": stack_of(key, (L, E * 2, D, M), dtype),
              "wd": stack_of(jax.random.fold_in(key, 1), (L, E, M, D), dtype)}
    groups = {"wgu": 2 * E, "wd": E}
    last = {k: v[(L - 1) * groups[k]:] + 0 for k, v in stacks.items()} \
        if "one" in variants else None
    sizes = group_sizes(E, kept, s["hot"], seed=41)
    # the rows as moe_dropless lays them out: expert e's rows for its gate
    # group, the same rows again for its up group
    start = np.cumsum(sizes) - sizes
    expert = np.repeat(np.arange(E), sizes)
    gate_at = np.arange(kept) + start[expert]
    up_at = gate_at + sizes[expert]
    x = (jax.random.normal(jax.random.fold_in(key, 2), (kept, D),
                           jnp.float32)).astype(dtype)
    x2 = jnp.zeros((2 * A, D), dtype).at[gate_at].set(x).at[up_at].set(x)
    # nobody's rows: behind the last group in both products
    gate_at, up_at = (np.concatenate([at, np.full(A - kept, 2 * A - 1)])
                      for at in (gate_at, up_at))

    def in_stack(n, layer):
        return jax.lax.dynamic_update_slice(
            jnp.zeros((L * n.shape[0],), jnp.int32), n,
            (layer * n.shape[0],))

    matmuls = {
        "stack": lambda lhs, w, n, i: jax.lax.ragged_dot(
            lhs, w, in_stack(n, i)),
        "one": lambda lhs, w, n, i: jax.lax.ragged_dot(lhs, w, n),
        "kernel": gm.grouped_matmul,
    }
    touched = int((sizes > 0).sum()) * 3 * D * M * dtype.itemsize
    args = (x2, jnp.asarray(sizes), jnp.asarray(gate_at), jnp.asarray(up_at))
    rows, reference = [], None
    limit = gm._WEIGHT_TILE_BYTES
    for variant in variants:
        kind, _, option = variant.partition(":")
        gm._WEIGHT_TILE_BYTES = limit
        matmuls["kernel"] = gm.grouped_matmul
        if option.startswith("tm"):          # kernel:tm256
            matmuls["kernel"] = functools.partial(with_row_tile,
                                                  int(option[2:]))
        elif option.startswith("mib"):       # kernel:mib4
            gm._WEIGHT_TILE_BYTES = int(option[3:]) * 2 ** 20
        try:
            seconds, (_, ys) = timed(
                jax.jit(experts_step(matmuls[kind], L)),
                last if kind == "one" else stacks, *args, calls=calls)
        except Exception as e:   # noqa: BLE001 - a variant the chip refuses
            rows.append({"shape": name, "variant": variant,
                         "error": repr(e)[:300]})
            continue
        # "one" reads the last layer's experts at every step of the scan
        ys = np.asarray(ys[-1, :kept].astype(jnp.float32))
        if reference is None:
            reference = ys
        rows.append({
            "shape": name, "variant": variant, "dtype": s["dtype"],
            "assignments": A, "live_share": live_share,
            "groups_a_layer": 3 * E,
            "experts_touched": int((sizes > 0).sum()),
            "ms_a_layer": 1e3 * seconds / L,
            "hbm_share": touched / HBM_BYTES_PER_S / (seconds / L),
            "bf16_peak_share": 2 * kept * 3 * D * M / BF16_FLOPS_PER_S
            / (seconds / L),
            "against_first": float(np.linalg.norm(ys - reference)
                                   / np.linalg.norm(reference))})
    gm._WEIGHT_TILE_BYTES = limit
    return rows


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--shapes", nargs="+",
                        default=["sdar", "xing", "olmoe"])
    parser.add_argument("--variants", nargs="+",
                        default=["stack", "one", "kernel"])
    parser.add_argument("--calls", type=int, default=30)
    parser.add_argument("--assignments", type=int, nargs="+", default=[None],
                        help="loads in place of each shape's own")
    parser.add_argument("--live-share", type=float, nargs="+", default=[1.0],
                        help="the share of the assignments that is in a "
                        "group; the rest lies behind the last one")
    args = parser.parse_args()
    device = jax.devices()[0]
    print(json.dumps({"platform": device.platform,
                      "device_kind": device.device_kind}), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "grouped_bench.jsonl"), "a") as f:
        for name, load, share in itertools.product(
                args.shapes, args.assignments, args.live_share):
            for row in bench(name, args.calls, args.variants, load, share):
                row["device_kind"] = device.device_kind
                print(json.dumps(row), flush=True)
                f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
