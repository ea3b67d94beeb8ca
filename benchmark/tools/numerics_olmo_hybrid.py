#!/usr/bin/env python3
"""How far the served Olmo-Hybrid is from its float32 reference, and how far
a faulty or lower-precision one would be: the readings ``numerics.logits_rtol``
of ``benchmark/configs/olmo-hybrid-7b-12l.json`` is set from.

    python3 benchmark/tools/numerics_olmo_hybrid.py [--seeds 4] [--steps 8]

One process on whatever device JAX finds (the chip, through ``chiprun``); no
cluster.  It builds the configuration's engine at the published size and
compares, as ``BenchLLMServer.check_numerics`` does, prefill (the chunked
scan, into slot 0's state rows and the full layers' pages) and then decode
(the one-step rule on those rows) by the engine's own two programs with the
reference's full forward in the recurrent form, on two seeded sequences:

* the configuration as it is, over ``--seeds`` seeds: the largest is what the
  tolerance has to admit;
* each of ``FAULTS`` planted in the program on the last seed's weights, which
  the tolerance has to refuse where it can be seen;
* one precision below what the configuration states: the recurrent STATE
  rounded to bfloat16 whenever it is written, and every matrix rounded to
  float8's three bits of mantissa in the program, the reference's left alone.

``--steps`` more than the replica's 8 shows what a longer decode would see
(the state in bfloat16 rounds once a position).  Lines of JSON on stdout, and
appended to ``chiprun_out/numerics_olmo_hybrid.jsonl``.
"""

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

STEPS = 8
MATRICES = {"wq", "wkv", "wo", "wgu", "wd", "lm_head", "wqkv", "wz", "wba"}
_REAL = {}


# ---- functions that stand in for the program's own while it is traced

def _no_decay(a, b, A_log, dt_bias, neg_eigval):
    g, beta = _REAL["decay_and_beta"](a, b, A_log, dt_bias, neg_eigval)
    return 0.0 * g, beta


def _not_normalised(x, eps=1e-6):
    import jax.numpy as jnp
    return x.astype(jnp.float32)


def _conv_left_out(x, w):
    import jax
    import jax.numpy as jnp
    return jax.nn.silu(x.astype(jnp.float32)).astype(x.dtype)


def _conv_step_left_out(x, w, tail):
    return _conv_left_out(x, w), tail


def _gate_left_out(cfg, scale, o, z):
    from ray_tpu.models import llama
    return llama._rms_norm(o, scale, cfg.rms_eps).astype(cfg.dtype)


def _tail_updates_the_state(q, k, v, g, beta, length=None, chunk=64):
    return _REAL["gated_delta_chunked"](q, k, v, g, beta, None, chunk)


def _bf16(a):
    import jax.numpy as jnp
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def _step_state_in_bf16(q, k, v, g, beta, folded):
    o, folded = _REAL["gated_delta_step"](q, k, v, g, beta, folded)
    return o, _bf16(folded)


def _fold_state_in_bf16(S):
    return _bf16(_REAL["fold_state"](S))


# what is planted: a change of the program's configuration, functions of
# ray_tpu.models.llama or ray_tpu.ops.linear_attention replaced while the
# programs are traced, or the program's weights (the reference keeps its own)
FAULTS = {
    "beta without its 2": {"config": {"linear_neg_eigval": False}},
    "the decay left out": {"patch": {"la.decay_and_beta": _no_decay}},
    "q and k not normalised": {"patch": {
        "la.l2_normalise": _not_normalised}},
    "the convolution left out": {"patch": {
        "la.causal_conv": _conv_left_out,
        "la.causal_conv_step": _conv_step_left_out}},
    "the gate silu(z) left out": {"patch": {
        "llama._gated_norm": _gate_left_out}},
    "the padded tail updating the state": {"patch": {
        "la.gated_delta_chunked": _tail_updates_the_state}},
    "rotation on the full layers": {"config": {"rope_theta": 500000.0}},
    "state in bfloat16": {"patch": {
        "la.gated_delta_step": _step_state_in_bf16,
        "la.fold_state": _fold_state_in_bf16}},
    "float8 weights": {"weights": True},
}


@contextlib.contextmanager
def planted(fault: dict):
    """The fault's functions in place of the program's own, for as long as
    the programs that should have it are traced."""
    from ray_tpu.models import llama
    from ray_tpu.ops import linear_attention
    modules = {"llama": llama, "la": linear_attention}
    kept = {}
    for where, fn in fault.get("patch", {}).items():
        module, name = where.split(".")
        kept[where] = _REAL[name] = getattr(modules[module], name)
        setattr(modules[module], name, fn)
    try:
        yield
    finally:
        for where, fn in kept.items():
            module, name = where.split(".")
            setattr(modules[module], name, fn)


def to_float8(params):
    """Every matrix rounded to float8's three bits of mantissa; the norm
    scales, the gates' ``A_log`` and ``dt_bias``, the convolution's taps and
    the embedding (a lookup) as they are."""
    import jax
    from benchmark.tools.numerics_xing import round_to_float8
    return jax.tree_util.tree_map_with_path(
        lambda path, a: round_to_float8(a) if path[-1].key in MATRICES
        else a, params)


def served_with(family, config, engine_args, model, params, fault, key,
                steps):
    """(the sequences, their served logits) from an engine with ``fault``
    planted (``{}``: none)."""
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    from benchmark.tools.numerics_olmoe import sequences
    from benchmark.tools.numerics_ouro import served_logits
    model = dataclasses.replace(model, **fault.get("config", {}))
    with planted(fault):
        engine = InferenceEngine(EngineConfig(
            model=family.ENGINE_MODEL, model_config=model, **engine_args),
            params=params)
        try:
            seqs = sequences(config, engine.config, key, steps)
            return seqs, served_logits(engine, seqs)
        finally:
            engine.close()


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default="olmo-hybrid-7b-12l")
    parser.add_argument("--seeds", type=int, default=4)
    parser.add_argument("--seed", type=int, default=2 ** 31 + 4800)
    parser.add_argument("--steps", type=int, default=STEPS,
                        help="decode positions a sequence (the replica's "
                        "own check takes 8)")
    parser.add_argument("--faults", nargs="*", default=list(FAULTS))
    args = parser.parse_args()

    import jax
    from benchmark import spec
    from benchmark.replica import device_report, seeded_key
    from benchmark.tools.numerics_ouro import errors, reference_logits
    config = spec.load_json("configs", args.config + ".json")
    family = spec.load_part("families", config["family"])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = os.path.join(ROOT, "chiprun_out", "numerics_olmo_hybrid.jsonl")

    def report(**line):
        line = {"device": device_report(), "steps": args.steps, **line}
        print(json.dumps(line), flush=True)
        with open(log, "a") as f:
            f.write(json.dumps(line) + "\n")

    engine_args = config["engine"]
    model = family.program_config(
        config, engine_args["max_prompt_len"] + engine_args["max_new_tokens"])
    init = jax.jit(lambda key: family.init(key, model))

    def run(what, params, reference_params, seed):
        seqs, served = served_with(
            family, config, engine_args, model, params, FAULTS.get(what, {}),
            seeded_key(seed + 1), args.steps)
        gc.collect()                  # the engine, in cycles
        report(what=what, seed=seed, logits_rel_err=errors(
            served, reference_logits(family, config, reference_params,
                                     seqs)))

    for n in range(args.seeds):
        seed = args.seed + 7919 * n
        params = init(seeded_key(seed))
        run("as configured", params, params, seed)
        if n == args.seeds - 1:              # faults: the last seed's weights
            for what in args.faults:
                if not FAULTS[what].get("weights"):
                    run(what, params, params, seed)
        del params
    # the matrices a precision below: the program on rounded weights; two
    # trees and the pools do not fit, so the reference's are made again once
    # those are gone
    for what in args.faults:
        if FAULTS[what].get("weights"):
            gc.collect()
            rounded = jax.jit(lambda key: to_float8(
                family.init(key, model)))(seeded_key(seed))
            seqs, served = served_with(
                family, config, engine_args, model, rounded, {},
                seeded_key(seed + 1), args.steps)
            del rounded
            gc.collect()              # the engine, in cycles
            jax.clear_caches()        # the rounded tree's programs with it
            init = jax.jit(lambda key: family.init(key, model))
            params = init(seeded_key(seed))
            report(what=what, seed=seed, logits_rel_err=errors(
                served, reference_logits(family, config, params, seqs)))
            del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
