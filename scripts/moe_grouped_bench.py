"""The experts' two grouped matmuls alone on the chip, at the shapes of the
three cells that run ``ops/moe.py::moe_dropless`` (PERF.md section 6, PR 41).

For each shape, a ``lax.scan`` over the layers runs gate/up, the SwiGLU and
down as ``moe_dropless`` does, with the grouped matmul being

  stack   ``jax.lax.ragged_dot`` over the stack of L layers, group sizes of
          the whole stack with only the scanned layer's filled (the program
          before PR 41);
  one     ``jax.lax.ragged_dot`` over ONE layer's experts (E groups): what
          "the layer's groups alone" gives with the compiler's kernel;
  kernel  ``ops/grouped_matmul.py`` over the stack with the layer as an
          offset in its index map (and variants of its tiles).

One process, no cluster:

    chiprun -- python scripts/moe_grouped_bench.py

prints one JSON line a shape and variant (milliseconds a layer, the bytes of
the touched experts over that time as a share of the HBM's peak) and writes
them to ``chiprun_out/pr41/grouped_bench.jsonl``.
"""

from __future__ import annotations

import argparse
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

SHAPES = {
    # the block cell: 1,024 assignments a step, eight experts a layer take
    # nine times the mean (the seeded router's code for a masked row)
    "sdar": dict(L=6, E=128, D=2048, M=768, dtype="bfloat16", A=1024, hot=8),
    "xing": dict(L=5, E=64, D=3584, M=1024, dtype="bfloat16", A=128, hot=0),
    "olmoe": dict(L=4, E=64, D=2048, M=1024, dtype="float32", A=128, hot=0),
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
    """Normal weights made a layer at a time (the float32 draw of a whole
    stack would not fit beside it)."""
    return jnp.concatenate([
        (0.05 * jax.random.normal(k, shape[1:], jnp.float32)).astype(dtype)
        for k in jax.random.split(key, shape[0])]).reshape(
            (shape[0] * shape[1],) + shape[2:])


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


def timed(fn, *args, calls: int):
    out = jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(calls):
        last = fn(*args)
    jax.block_until_ready(last)
    return (time.perf_counter() - start) / calls, out


def bench(name: str, calls: int, variants, assignments=None) -> list:
    s = dict(SHAPES[name])
    if assignments:        # another load on the same experts, no hot ones
        s.update(A=assignments, hot=0)
    L, E, D, M, A = s["L"], s["E"], s["D"], s["M"], s["A"]
    dtype = jnp.dtype(s["dtype"])
    key = jax.random.PRNGKey(41)
    stacks = {"wgu": stack_of(key, (L, E * 2, D, M), dtype),
              "wd": stack_of(jax.random.fold_in(key, 1), (L, E, M, D), dtype)}
    groups = {"wgu": 2 * E, "wd": E}
    last = {k: v[(L - 1) * groups[k]:] + 0 for k, v in stacks.items()}
    sizes = group_sizes(E, A, s["hot"], seed=41)
    # the rows as moe_dropless lays them out: expert e's rows for its gate
    # group, the same rows again for its up group
    start = np.cumsum(sizes) - sizes
    expert = np.repeat(np.arange(E), sizes)
    gate_at = np.arange(A) + start[expert]
    up_at = gate_at + sizes[expert]
    x = (jax.random.normal(jax.random.fold_in(key, 2), (A, D), jnp.float32)
         ).astype(dtype)
    x2 = jnp.zeros((2 * A, D), dtype).at[gate_at].set(x).at[up_at].set(x)

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
    tiles, limit = gm._tiles, gm._WEIGHT_TILE_BYTES
    for variant in variants:
        kind, _, option = variant.partition(":")
        gm._tiles, gm._WEIGHT_TILE_BYTES = tiles, limit
        if option.startswith("tm"):          # kernel:tm16
            gm._tiles = lambda *a, tm=int(option[2:]): (
                min(tm, tiles(*a)[0]), tiles(*a)[1])
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
        ys = np.asarray(ys[-1].astype(jnp.float32))
        if reference is None:
            reference = ys
        rows.append({
            "shape": name, "variant": variant, "dtype": s["dtype"],
            "assignments": A, "groups_a_layer": 3 * E,
            "experts_touched": int((sizes > 0).sum()),
            "ms_a_layer": 1e3 * seconds / L,
            "hbm_share": touched / HBM_BYTES_PER_S / (seconds / L),
            "against_first": float(np.linalg.norm(ys - reference)
                                   / np.linalg.norm(reference))})
    gm._tiles, gm._WEIGHT_TILE_BYTES = tiles, limit
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
    args = parser.parse_args()
    device = jax.devices()[0]
    print(json.dumps({"platform": device.platform,
                      "device_kind": device.device_kind}), flush=True)
    os.makedirs("chiprun_out/pr41", exist_ok=True)
    with open("chiprun_out/pr41/grouped_bench.jsonl", "a") as f:
        for name, load in itertools.product(args.shapes, args.assignments):
            for row in bench(name, args.calls, args.variants, load):
                row["device_kind"] = device.device_kind
                print(json.dumps(row), flush=True)
                f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
