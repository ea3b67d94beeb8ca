#!/usr/bin/env python
"""Offline kernel-autotune sweep: populate the persistent autotune cache
for a fleet's attention shapes, once, on a real TPU VM.

    python scripts/autotune_sweep.py                 # default shape set
    python scripts/autotune_sweep.py --shapes 32x1024x12x64 2x8192x12x64
    python scripts/autotune_sweep.py --allow-cpu     # interpret mode (CI)

Each shape is BxSxNxH (batch x seq x heads x head_dim).  For every shape
the sweep tunes each applicable variant's own config (flash block_q/
block_k grid, splash block set when the shape admits it) and persists
the per-variant records plus the crossover winner (``attention_variant``)
to $RT_AUTOTUNE_CACHE (default <checkout>/.autotune.jsonl).  Ship
that file to the fleet (or point RT_AUTOTUNE_CACHE at shared storage)
and every worker dispatches from measured timings with zero warm-up.

Exits 2 when no TPU is attached (pass --allow-cpu to sweep in interpret
mode instead — useful for CI and for validating the plumbing).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# The bench/train workhorse shapes: B=32 S=1024 (train bench) plus the
# long-context curve points (bench.py _longctx_curve).
DEFAULT_SHAPES = ("32x1024x12x64", "2x4096x12x64", "1x8192x12x64",
                  "1x16384x12x64", "1x32768x12x64")


def parse_shape(s: str):
    parts = [int(x) for x in s.lower().split("x")]
    if len(parts) != 4:
        raise argparse.ArgumentTypeError(
            f"shape {s!r} is not BxSxNxH (e.g. 2x8192x12x64)")
    return tuple(parts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shapes", nargs="*", type=parse_shape,
                    default=[parse_shape(s) for s in DEFAULT_SHAPES],
                    help="BxSxNxH shapes to tune (default: bench set)")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--no-causal", action="store_true")
    ap.add_argument("--budget-s", type=float, default=120.0,
                    help="per-shape tuning budget, seconds")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="sweep in interpret mode when no TPU is attached")
    ap.add_argument("--force", action="store_true",
                    help="re-tune shapes that already have cache records")
    ap.add_argument("--compact", action="store_true",
                    help="rewrite the cache file to one line per key")
    args = ap.parse_args(argv)

    import jax
    backend = jax.default_backend()
    if backend != "tpu" and not args.allow_cpu:
        print("autotune_sweep: no TPU attached (backend=%s); pass "
              "--allow-cpu for an interpret-mode sweep" % backend,
              file=sys.stderr)
        return 2
    interpret = backend != "tpu"

    from ray_tpu.autotune import cache_path, get_cache
    from ray_tpu.autotune.dispatch import tune_attention

    causal = not args.no_causal
    print(f"autotune_sweep: backend={backend} interpret={interpret} "
          f"cache={cache_path()}")
    failed = 0
    for (B, S, N, H) in args.shapes:
        rec = tune_attention(B, S, N, H, args.dtype, causal,
                             interpret=interpret, budget_s=args.budget_s,
                             force=args.force)
        if rec is None:
            failed += 1
            print(f"  {B}x{S}x{N}x{H}: no variant ran", file=sys.stderr)
            continue
        print(f"  {B}x{S}x{N}x{H}: {json.dumps(rec['config'])} "
              f"{rec.get('ms')}ms  "
              f"timings={json.dumps((rec.get('meta') or {}).get('timings'))}")
    cache = get_cache()
    if args.compact:
        n = cache.rewrite()
        print(f"autotune_sweep: compacted to {n} records")
    print(f"autotune_sweep: cache holds {len(cache)} records "
          f"({cache.path})")
    return 1 if failed == len(args.shapes) else 0


if __name__ == "__main__":
    sys.exit(main())
