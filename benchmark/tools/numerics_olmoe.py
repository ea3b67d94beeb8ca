#!/usr/bin/env python3
"""How far the served OLMoE is from its float32 reference, and how far a
faulty or lower-precision one would be: the readings ``numerics.logits_rtol``
of ``benchmark/configs/olmoe-1b-7b-0125-4l.json`` is set from.

    python3 benchmark/tools/numerics_olmoe.py [--layers 2 4] [--seeds 3]

One process on whatever device JAX finds (the chip, through ``chiprun``);
no cluster.  For each depth it builds the configuration's engine at the
published widths and compares, as ``BenchLLMServer.check_numerics`` does,
prefill and then decode through the paged cache by the engine's own two
programs with the reference's full forward, on two seeded sequences:

* the configuration as it is, over ``--seeds`` seeds: the largest is what
  the tolerance has to admit;
* three planted faults (gates renormalised, no q/k norm, top-7), which it
  has to refuse;
* the nearest precision below bfloat16: the program's weights rounded to
  float8's three bits of mantissa, the reference's left alone.

Beside each error, the share of (decode position, layer) pairs whose expert
set equals the reference's.  Lines of JSON on stdout, and appended to
``chiprun_out/numerics_olmoe.jsonl``.
"""

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

STEPS = 16


def sequences(config, engine_config, key, steps):
    """Two seeded sequences, as ``BenchLLMServer.check_numerics`` takes
    them: (tokens, prompt length), ``steps`` positions to decode."""
    import jax
    import numpy as np
    lengths = (engine_config.max_prompt_len // 8 + 5,
               engine_config.max_prompt_len // 16 + 3)
    return [(np.asarray(jax.random.randint(
        jax.random.fold_in(key, n), (length + steps,), 0,
        config["vocab_size"]), np.int32), length)
        for n, length in enumerate(lengths)]


def served_logits(engine, seqs):
    """For each sequence the logits of prefill and then of each decode
    position through the paged cache (first slot live), by the engine's own
    two programs, and each decode position's expert set [steps, L, E]."""
    import numpy as np
    cfg, out = engine.config, []
    for tokens, prompt_len in seqs:
        table = np.zeros((cfg.max_batch, engine._maxp), np.int32)
        table[0] = np.arange(1, engine._maxp + 1)
        padded = np.zeros((1, cfg.max_prompt_len), np.int32)
        padded[0, :prompt_len] = tokens[:prompt_len]
        logits, kp, vp, load = engine._prefill_program(
            engine._params, padded, np.int32(prompt_len), engine._k_pages,
            engine._v_pages, table[:1])
        got, sets = [np.asarray(logits[0])], []
        tok = np.zeros((cfg.max_batch,), np.int32)
        pos = np.zeros((cfg.max_batch,), np.int32)
        for at in range(prompt_len, len(tokens)):
            tok[0], pos[0] = tokens[at], at
            logits, kp, vp, load = engine._decode_program(
                engine._params, tok, pos, kp, vp, table)
            got.append(np.asarray(logits[0]))
            # one live token: its load is its expert set, layer by layer
            sets.append(np.asarray(load) > 0)
        out.append((np.stack(got), np.stack(sets)))
        del kp, vp
    return out


def reference_logits(family, config, params, seqs):
    """The same positions' logits and expert sets by the reference's full
    forward."""
    import jax
    import numpy as np
    reference = jax.jit(lambda p, t: family.reference_forward(
        p, t, config, with_gates=True))
    out = []
    for tokens, prompt_len in seqs:
        logits, gates = reference(params, tokens[None])
        chosen = np.asarray(gates[:, 0]) > 0                  # [L, S, E]
        out.append((np.asarray(logits[0])[prompt_len - 1:],
                    np.moveaxis(chosen[:, prompt_len:], 0, 1)))
    return out


def compare(served, reference):
    """(relative Frobenius error of each sequence's logits, the (decode
    position, layer) pairs with equal expert sets, all such pairs)."""
    import numpy as np
    errs, same, pairs = [], 0, 0
    for (got, sets), (want, chosen) in zip(served, reference):
        errs.append(float(np.linalg.norm(got - want)
                          / np.linalg.norm(want)))
        equal = (sets == chosen).all(axis=-1)
        same, pairs = same + int(equal.sum()), pairs + equal.size
    return errs, same, pairs


def served_and_reference(engine, family, config, reference_params, key,
                         steps=STEPS):
    seqs = sequences(config, engine.config, key, steps)
    return compare(served_logits(engine, seqs),
                   reference_logits(family, config, reference_params, seqs))


MATRICES = {"wq", "wkv", "wo", "router", "wgu", "wd", "lm_head"}


def to_float8(params):
    """The matrices of the layers and the head rounded to float8's three
    bits of mantissa (e4m3's precision; its range is not imposed, which
    flatters the lower precision); norm scales and the embedding (a
    lookup) as they are.  Done on the bits: a compiler for a chip without
    the type may widen a cast to it and round nothing."""
    import jax
    import jax.numpy as jnp

    def rounded(path, a):
        if path[-1].key not in MATRICES:
            return a
        bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
        bits = (bits + jnp.uint32(1 << 19)) & jnp.uint32(0xFFF00000)
        return jax.lax.bitcast_convert_type(bits, jnp.float32)
    return jax.tree_util.tree_map_with_path(rounded, params)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default="olmoe-1b-7b-0125-4l")
    parser.add_argument("--layers", type=int, nargs="+", default=[2, 4])
    parser.add_argument("--seeds", type=int, default=3)
    parser.add_argument("--seed", type=int, default=2 ** 31 + 2600)
    parser.add_argument("--steps", type=int, default=STEPS,
                        help="decode positions a sequence (the replica's "
                        "own check takes 8)")
    args = parser.parse_args()

    import jax
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    from benchmark import spec
    from benchmark.replica import device_report, seeded_key
    config = spec.load_json("configs", args.config + ".json")
    family = spec.load_part("families", config["family"])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = os.path.join(ROOT, "chiprun_out", "numerics_olmoe.jsonl")

    def report(**line):
        line = {"device": device_report(), **line}
        print(json.dumps(line), flush=True)
        with open(log, "a") as f:
            f.write(json.dumps(line) + "\n")

    engine_args = config["engine"]
    positions = engine_args["max_prompt_len"] + engine_args["max_new_tokens"]
    faults = {"gates renormalised": {"norm_topk_prob": True},
              "no q/k norm": {"qk_norm": False},
              "top-7": {"experts_per_token":
                        config["num_experts_per_tok"] - 1}}
    for depth in args.layers:
        cut = {**config, "num_hidden_layers": depth}
        model = family.program_config(cut, positions)
        init = jax.jit(lambda key: family.init(key, model))

        def engine_of(model, params):
            return InferenceEngine(EngineConfig(
                model=family.ENGINE_MODEL, model_config=model,
                **engine_args), params=params)

        for n in range(args.seeds):
            seed = args.seed + 7919 * n
            params = init(seeded_key(seed))
            versions = {"as configured": model}
            if n == args.seeds - 1:          # faults: the last seed's weights
                versions.update({what: dataclasses.replace(model, **change)
                                 for what, change in faults.items()})
            for what, version in versions.items():
                engine = engine_of(version, params)
                errs, same, pairs = served_and_reference(
                    engine, family, cut, params, seeded_key(seed + 1),
                    args.steps)
                engine.close()
                del engine
                report(layers=depth, what=what, seed=seed,
                       logits_rel_err=errs, expert_sets_equal=same,
                       of=pairs)
            del params
        # the precision below: the program on rounded weights first, then
        # the reference on the weights as they are (both do not fit)
        engine = engine_of(model, jax.jit(
            lambda key: to_float8(family.init(key, model)))(
                seeded_key(seed)))
        seqs = sequences(cut, engine.config, seeded_key(seed + 1),
                         args.steps)
        served = served_logits(engine, seqs)
        engine.close()
        del engine
        errs, same, pairs = compare(served, reference_logits(
            family, cut, init(seeded_key(seed)), seqs))
        report(layers=depth, what="float8 weights", seed=seed,
               logits_rel_err=errs, expert_sets_equal=same, of=pairs)
    return 0


if __name__ == "__main__":
    sys.exit(main())
