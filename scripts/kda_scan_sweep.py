"""The chunked scan of the rule with a decay a key channel
(``ops/linear_attention.py::kda_chunked``) alone on the chip, by sequence
length and by how many chunks' products are made together (``group``):

    chiprun -- python scripts/kda_scan_sweep.py [--seq 128 256 512 1024]
        [--groups 2 4 8 16] [--heads 32] [--width 128] [--layers 6]

One process, ~1 min.  Each case is ``--layers`` scans in a row, EVERY input of
one fed the one before's read-out (else the compiler makes what hangs on q, k
and the decay alone once and hands it out six times: PR 51's first sweep fed on
only the values and read a third of the scan's cost), compiled, run twice to warm and timed over ``--reps`` calls; a line
of JSON a case: ms a call and the share of ``costs_kda.chunked_scan``'s
roofline, and the state's distance from the recurrent form's on the first
case.  Exits 1 on any backend but the TPU.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seq", type=int, nargs="*",
                        default=[128, 256, 512, 1024])
    parser.add_argument("--groups", type=int, nargs="*",
                        default=[2, 4, 8, 16])
    parser.add_argument("--heads", type=int, default=32)
    parser.add_argument("--width", type=int, default=128)
    parser.add_argument("--layers", type=int, default=6)
    parser.add_argument("--reps", type=int, default=10)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    from benchmark import costs, costs_kda, spec
    from ray_tpu.ops import linear_attention as la
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"needs a TPU, found {device.platform}", file=sys.stderr)
        return 1
    peaks = spec.peaks_for(device.device_kind)
    N, d = args.heads, args.width

    def inputs(S):
        ks = jax.random.split(jax.random.PRNGKey(S), 5)
        q = la.l2_normalise(jax.random.normal(ks[0], (S, N, d))) * d ** -0.5
        k = la.l2_normalise(jax.random.normal(ks[1], (S, N, d)))
        v = jax.random.normal(ks[2], (S, N, d))
        g = jnp.log(jax.random.uniform(ks[3], (S, N, d), minval=0.8,
                                       maxval=1.0))
        beta = jax.random.uniform(ks[4], (S, N))
        return q, k, v, g, beta

    checked = False
    for S in args.seq:
        row = inputs(S)
        for group in args.groups:
            if group > max(S // la.CHUNK, 1) and group != args.groups[0]:
                continue             # the same program as the widest group

            @jax.jit
            def layers(q, k, v, g, beta, group=group):
                state = None
                for _ in range(args.layers):
                    o, state = la.kda_chunked(q, k, v, g, beta, group=group)
                    # every input of the next hangs on this one's read-out
                    # (key and value widths are equal here)
                    q, v = q + 1e-3 * o, v + 1e-3 * o
                    k = la.l2_normalise(k + 1e-3 * o)
                    g = g * (1.0 + 1e-3 * jnp.tanh(o))
                return o, state
            for _ in range(2):
                jax.block_until_ready(layers(*row))
            started = time.perf_counter()
            for _ in range(args.reps):
                out = layers(*row)
            jax.block_until_ready(out)
            ms = 1e3 * (time.perf_counter() - started) / args.reps
            least = costs.least_seconds(costs_kda.chunked_scan(
                S, args.layers, N, d, d), peaks)
            line = {"seq": S, "group": group, "layers": args.layers,
                    "ms": ms, "scan_roofline": 100.0 * least / (ms * 1e-3),
                    "device": device.device_kind}
            if not checked:
                _, want = la.gated_delta_recurrent(*row)
                _, got = jax.jit(la.kda_chunked)(*row)
                line["state_abs_err"] = float(jnp.abs(got - want).max())
                checked = True
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
