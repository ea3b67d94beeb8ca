#!/usr/bin/env python3
"""How far the served Ouro is from its float32 reference, and how far a
faulty or lower-precision one would be: the readings ``numerics.logits_rtol``
of ``benchmark/configs/ouro-2.6b.json`` is set from.

    python3 benchmark/tools/numerics_ouro.py [--seeds 4] [--steps 8]

One process on whatever device JAX finds (the chip, through ``chiprun``);
no cluster.  It builds the configuration's engine at the published size and
compares, as ``BenchLLMServer.check_numerics`` does, prefill and then decode
through the paged cache by the engine's own two programs (the consuming
views, on the engine's own pools: one pool is all that fits) with the
reference's full forward, on two seeded sequences:

* the configuration as it is, over ``--seeds`` seeds: the largest is what
  the tolerance has to admit;
* each of ``FAULTS`` planted in the program on the last seed's weights,
  which it has to refuse: a pass too few, no norm between passes, no norms
  on the sublayers' outputs, every pass on the last pass's cache (what the
  authors describe as an approximation for decoding), and the nearest
  precision below bfloat16, the program's matrices rounded to float8's
  three bits of mantissa with the reference's left alone.

Lines of JSON on stdout, and appended to ``chiprun_out/numerics_ouro.jsonl``.
"""

import argparse
import contextlib
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

STEPS = 8
MATRICES = {"wq", "wkv", "wo", "wgu", "wd", "lm_head"}


def _passes_without_norm_between(cfg, params, layers_pass, carry):
    """``llama._passes`` with the final norm after the last pass only."""
    import jax.numpy as jnp
    from ray_tpu.models import llama
    for t in range(cfg.ut_steps):
        carry, ys = layers_pass(carry, jnp.int32(t))
    x, *rest = carry
    return (llama._rms_norm(x, params["ln_f"]["scale"], cfg.rms_eps),
            *rest), ys


def _last_passes_pool_layers(cfg, t):
    """``llama._pool_layers`` with one cache for all passes, the last's:
    every pass writes there (the last one last) and reads from there."""
    import jax.numpy as jnp
    return (cfg.ut_steps - 1) * cfg.num_layers + jnp.arange(cfg.num_layers)


# what is planted: a change of the program's configuration, functions of
# ray_tpu.models.llama replaced while the programs are traced, or weights
FAULTS = {
    "three passes for four": {"config": lambda m: {"ut_steps":
                                                   m.ut_steps - 1}},
    "no norm between passes": {"patch": {
        "_passes": _passes_without_norm_between}},
    "no post-norms": {"config": lambda m: {"post_norm": False}},
    "every pass on the last pass's cache": {"patch": {
        "_pool_layers": _last_passes_pool_layers}},
    "float8 weights": {"weights": True},
}


@contextlib.contextmanager
def planted(fault: dict):
    """The fault's functions in place of ``ray_tpu.models.llama``'s own,
    for as long as the programs that should have it are traced."""
    from ray_tpu.models import llama
    kept = {name: getattr(llama, name) for name in fault.get("patch", {})}
    for name, fn in fault.get("patch", {}).items():
        setattr(llama, name, fn)
    try:
        yield
    finally:
        for name, fn in kept.items():
            setattr(llama, name, fn)


def to_float8(params):
    """The matrices of the layers and the head rounded to float8's three
    bits of mantissa (e4m3's precision; its range is not imposed, which
    flatters the lower precision); norm scales and the embedding (a lookup)
    as they are.  Done on the bits: a compiler for a chip without the type
    may widen a cast to it and round nothing."""
    import jax
    import jax.numpy as jnp

    def rounded(path, a):
        if path[-1].key not in MATRICES:
            return a
        bits = jax.lax.bitcast_convert_type(a.astype(jnp.float32),
                                            jnp.uint32)
        bits = (bits + jnp.uint32(1 << 19)) & jnp.uint32(0xFFF00000)
        return jax.lax.bitcast_convert_type(bits, jnp.float32).astype(
            a.dtype)
    return jax.tree_util.tree_map_with_path(rounded, params)


def served_logits(engine, seqs):
    """For each sequence the logits of prefill and then of each decode
    position through the paged cache (first slot live), by the engine's
    own two programs on the engine's own pools."""
    import numpy as np
    cfg, out = engine.config, []
    for tokens, prompt_len in seqs:
        table = np.zeros((cfg.max_batch, engine._maxp), np.int32)
        table[0] = np.arange(1, engine._maxp + 1)
        padded = np.zeros((1, cfg.max_prompt_len), np.int32)
        padded[0, :prompt_len] = tokens[:prompt_len]
        logits, kp, vp = engine._prefill(
            engine._params, padded, np.int32(prompt_len), engine._k_pages,
            engine._v_pages, table[:1])
        got = [np.asarray(logits[0])]
        tok = np.zeros((cfg.max_batch,), np.int32)
        pos = np.zeros((cfg.max_batch,), np.int32)
        for at in range(prompt_len, len(tokens)):
            tok[0], pos[0] = tokens[at], at
            logits, kp, vp = engine._decode(engine._params, tok, pos, kp,
                                            vp, table)
            got.append(np.asarray(logits[0]))
        out.append(np.stack(got))
        del kp, vp
    return out


def reference_logits(family, config, params, seqs):
    """The same positions' logits by the reference's full forward."""
    import jax
    import numpy as np
    reference = jax.jit(lambda p, t: family.reference_forward(p, t, config))
    return [np.asarray(reference(params, tokens[None])[0])[prompt_len - 1:]
            for tokens, prompt_len in seqs]


def errors(served, reference):
    """Relative Frobenius error of each sequence's logits."""
    import numpy as np
    return [float(np.linalg.norm(got - want) / np.linalg.norm(want))
            for got, want in zip(served, reference)]


def served_with(family, config, engine_args, model, params, fault, key,
                steps):
    """(the sequences, their served logits) from an engine with ``fault``
    planted (``{}``: none)."""
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    from benchmark.tools.numerics_olmoe import sequences
    if "config" in fault:
        model = dataclasses.replace(model, **fault["config"](model))
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
    parser.add_argument("--config", default="ouro-2.6b")
    parser.add_argument("--seeds", type=int, default=4)
    parser.add_argument("--seed", type=int, default=2 ** 31 + 3000)
    parser.add_argument("--steps", type=int, default=STEPS,
                        help="decode positions a sequence (the replica's "
                        "own check takes 8)")
    args = parser.parse_args()

    import jax
    from benchmark import spec
    from benchmark.replica import device_report, seeded_key
    config = spec.load_json("configs", args.config + ".json")
    family = spec.load_part("families", config["family"])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = os.path.join(ROOT, "chiprun_out", "numerics_ouro.jsonl")

    def report(**line):
        line = {"device": device_report(), **line}
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
        report(what=what, seed=seed, logits_rel_err=errors(
            served, reference_logits(family, config, reference_params,
                                     seqs)))

    for n in range(args.seeds):
        seed = args.seed + 7919 * n
        params = init(seeded_key(seed))
        run("as configured", params, params, seed)
        if n == args.seeds - 1:              # faults: the last seed's weights
            for what, fault in FAULTS.items():
                if not fault.get("weights"):
                    run(what, params, params, seed)
        del params
    # the precision below: the program on rounded weights; two trees and the
    # pool do not fit, so the reference's are made again once those are gone
    for what, fault in FAULTS.items():
        if fault.get("weights"):
            seqs, served = served_with(
                family, config, engine_args, model,
                jax.jit(lambda key: to_float8(family.init(key, model)))(
                    seeded_key(seed)), fault, seeded_key(seed + 1),
                args.steps)
            report(what=what, seed=seed, logits_rel_err=errors(
                served, reference_logits(family, config,
                                         init(seeded_key(seed)), seqs)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
