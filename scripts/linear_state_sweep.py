"""The token step of a linear layer alone on the chip, the kernel
(``ops/linear_state.py``) against the jnp rule (``gated_delta_step`` /
``kda_step`` / ``ssm_step`` on the layer's slab, selected and written back,
as the CPU backend's program has it), at the three published shapes (PERF.md
section 6, PR 52 and PR 61): the evidence
``ops/linear_attention.py::state_step_kind`` stands on, and what
``ops/linear_state.py::_SLOTS`` was chosen from.

    chiprun -- python scripts/linear_state_sweep.py [--cells NAME ...]
                    [--slots N ...] [--unroll N ...] [--parked N] [--seed N]

A cell's shape is its engine's (``benchmark/configs/*.json``: slots, linear
layers, heads, key and value widths) and the decay its rule's: a head's
scalar (Gated DeltaNet), a key channel's (Kimi Delta Attention), or
"shared": the rule without a correction, one key and one query a slot for all
heads (Mamba-2).  Each form
is timed alone: 36 calls chained in one jitted ``lax.scan`` through the
donated pool (the layer goes round the pool's; a call's read-out is the next
call's values), every slot live but ``--parked`` of them.  ``rule`` is
``step_pool`` as the CPU runs it, ``kernel@NxU`` the kernel at ``N`` slots a
grid step and ``U`` groups of heads a turn of its loop (0: as many as the
module picks, ``linear_state._turn``).

One JSON line a cell: ms a call of each, ``state_mb`` the bytes of the live
slots' states of one layer (``benchmark/costs_linear.py::state_step`` counts
them read once and written once), each form's share of the roofline of that
(2 x ``state_mb`` / 819 GB/s over its time, %), and the kernel's greatest
difference from the rule after one call on the same inputs (the read-out and
the states).

Needs the chip: a time from another backend is no reading of either, so
there the script exits 1 before it runs anything.  (The kernel's numerics on
the CPU are ``tests/test_linear_attention.py``'s.)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import linear_attention as la
from ray_tpu.ops import linear_state

CALLS = 36
HBM_BYTES_PER_S = 819e9

# name: slots, linear layers, heads, key width, value width, whether the
# decay is a key channel's ("shared": the rule without a correction)
CELLS = {
    "serve-olmo-hybrid-decode-wide": (48, 9, 30, 96, 192, False),
    "serve-kimi-linear-reasoning-wide": (64, 6, 32, 128, 128, True),
    "serve-granite-h-decode-wide": (64, 9, 128, 128, 64, "shared"),
}


def chain(kernel: bool, slots=None, unroll=None):
    """A jitted chain of CALLS steps alone, the pool donated."""
    def run(q, k, v, g, beta, pool, live):
        def body(carry, i):
            v, pool = carry
            with _backend(kernel, slots, unroll):
                o, pool = la.step_pool(q, k, v, g, beta, pool,
                                       i % pool.shape[0], live)
            return (o, pool), None
        return lax.scan(body, (v, pool),
                        jnp.arange(CALLS, dtype=jnp.int32))[0]
    return jax.jit(run, donate_argnums=(5,))


class _backend:
    """``step_pool`` traced as on the chip (the kernel at ``slots`` slots a
    grid step) or as on the CPU (the rule)."""

    def __init__(self, kernel, slots=None, unroll=None):
        self.kernel, self.slots, self.unroll = kernel, slots, unroll

    def __enter__(self):
        self.was = (la._kernel_backend, linear_state._SLOTS,
                    linear_state._turn)
        la._kernel_backend = lambda: self.kernel
        linear_state._SLOTS = self.slots or linear_state._SLOTS
        if self.unroll:
            linear_state._turn = lambda *_: self.unroll

    def __exit__(self, *exc):
        (la._kernel_backend, linear_state._SLOTS,
         linear_state._turn) = self.was


def timed(fn, args, pool) -> float:
    """ms a call; the pool goes round (donated, and handed back)."""
    compiled = fn.lower(*args[:5], pool, args[5]).compile()
    times = []
    for _ in range(6):
        start = time.perf_counter()
        _, pool = compiled(*args[:5], pool, args[5])
        pool.block_until_ready()
        times.append(time.perf_counter() - start)
    return 1e3 * min(times[1:]) / CALLS, pool


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cells", nargs="+", default=list(CELLS),
                        choices=list(CELLS))
    parser.add_argument("--slots", type=int, nargs="+",
                        default=[linear_state._SLOTS],
                        help="slots a grid step (default: the module's)")
    parser.add_argument("--unroll", type=int, nargs="+", default=[0],
                        help="groups of heads a turn of the kernel's loop "
                             "(default, 0: the module's choice)")
    parser.add_argument("--parked", type=int, default=0,
                        help="slots that are not live (the last ones)")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit(f"linear_state_sweep: the backend is "
                 f"{jax.default_backend()!r}, not a TPU: nothing timed here "
                 "would be a step's time")
    for name in args.cells:
        B, L, N, dk, dv, channel = CELLS[name]
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 6)
        q = la.l2_normalise(jax.random.normal(keys[0], (B, N, dk))) \
            * dk ** -0.5
        k = la.l2_normalise(jax.random.normal(keys[1], (B, N, dk)))
        v = jax.random.normal(keys[2], (B, N, dv))
        g = -0.5 * jax.random.uniform(
            keys[3], (B, N, dk) if channel is True else (B, N))
        beta = jax.nn.sigmoid(jax.random.normal(keys[4], (B, N)))
        if channel == "shared":      # one key and query a slot, no beta
            q, k, beta = q[:, 0], k[:, 0], None
        live = jnp.arange(B) < B - args.parked
        shape = (L, B, *la.state_shape(N, dk, dv))
        pool = jax.random.normal(keys[5], shape)
        state_bytes = (B - args.parked) * N * dk * dv * 4
        line = {"cell": name, "slots": B, "live": B - args.parked,
                "layers": L, "pool": list(shape),
                "state_mb": round(state_bytes / 1e6, 2),
                "device": jax.devices()[0].device_kind}
        operands = (q, k, v, g, beta, live)
        # one call of each on the same inputs: the compiled kernel's
        # result against the rule's
        one = []
        for kernel in (False, True):
            def step(*a, kernel=kernel):
                with _backend(kernel):
                    return la.step_pool(*a[:5], a[6], 1 % L, a[5])
            one.append(jax.jit(step)(*operands, pool))
        # (a parked slot's read-out is nobody's: the rule's is of the held
        # state, the kernel's zeros)
        line["kernel_max_abs_diff_o"] = float(jnp.max(jnp.abs(
            jnp.where(live[:, None, None], one[0][0] - one[1][0], 0.0))))
        line["kernel_max_abs_diff_state"] = float(jnp.max(jnp.abs(
            one[0][1] - one[1][1])))
        line["o_max_abs"] = float(jnp.max(jnp.abs(one[0][0])))
        del one
        forms = {"rule": chain(False)}
        for slots in args.slots:
            for unroll in args.unroll:
                groups = N // (la._panel_plan(N, dv)[2] or 1)
                if B % slots == 0 and not (unroll and groups % unroll):
                    forms[f"kernel@{slots}x{unroll}"] = chain(
                        True, slots, unroll)
        for kind, fn in forms.items():
            ms, pool = timed(fn, operands, pool)
            line[kind + "_ms"] = round(ms, 4)
            line[kind + "_roofline"] = round(
                100 * 2 * state_bytes / HBM_BYTES_PER_S / (ms / 1e3), 1)
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
