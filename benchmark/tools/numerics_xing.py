#!/usr/bin/env python3
"""How far the served Xing4.0 is from its float32 reference, and how far a
faulty or lower-precision one would be: the readings ``numerics.logits_rtol``
of ``benchmark/configs/xing4.0-29b-a4b-6l.json`` is set from.

    python3 benchmark/tools/numerics_xing.py [--seeds 4] [--steps 8]

One process on whatever device JAX finds (the chip, through ``chiprun``);
no cluster.  It builds the configuration's engine at the published size and
compares, as ``BenchLLMServer.check_numerics`` does, prefill and then decode
through the latent pages by the engine's own two programs (the consuming
views, on the engine's own pool) with the reference's full forward, on two
seeded sequences:

* the configuration as it is, over ``--seeds`` seeds: the largest is what
  the tolerance has to admit.  Beside each error, the share of (decode
  position, expert layer) pairs whose expert set equals the reference's;
  on the first seed also what the reference says the seeded routing bias
  and hyper-connection parameters do (``drawn``);
* each of ``FAULTS`` planted in the program on the last seed's weights,
  which the tolerance has to refuse;
* the nearest precision below bfloat16: the latent path's matrices (on
  every seed's weights; on the last seed's also the heads' queries and the
  cached rows with them, and every matrix) rounded to float8's three bits
  of mantissa in the program, the reference's left alone.

Lines of JSON on stdout, and appended to ``chiprun_out/numerics_xing.jsonl``.
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
EVERY_SEED = ("float8 latent path",)    # read on every seed's weights
LATENT = {"wq_a", "wq_b", "wkv_a", "wkv_b", "wo"}
MATRICES = LATENT | {"wgu", "wd", "lm_head"}


# ---- functions that stand in for the program's own while it is traced

def _route_with_softmax(logits, bias, top_k, scoring, norm, scale):
    return _REAL["_route"](logits, None, top_k, "softmax", norm, scale)


def _route_bias_in_gates(logits, bias, top_k, scoring, norm, scale):
    import jax
    import jax.numpy as jnp
    gates, experts = jax.lax.top_k(jax.nn.sigmoid(logits) + bias, top_k)
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return gates * scale, experts


def _project_rotating_everything(cfg, p, h, cos, sin):
    """The rotation applied to a head's unrotated query values too (the
    first ``qk_rope_dim`` of them, at the same angles)."""
    import jax.numpy as jnp
    from ray_tpu.models import llama
    q_nope, q_rope, latent = _REAL["_mla_project"](cfg, p, h, cos, sin)
    dr = cfg.qk_rope_dim
    turned = llama.apply_rope_pairs(q_nope[..., :dr], cos[..., None, :],
                                    sin[..., None, :])
    return jnp.concatenate([turned, q_nope[..., dr:]], -1), q_rope, latent


def _scale_without_mscale(cfg):
    return float((cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5)


def _coeff_post_without_its_two(cfg, hp, x):
    pre, post, res = _REAL["_hc_coeff"](cfg, hp, x)
    return pre, post / 2.0, res


def _reduce_by_mean(x):
    return (_REAL["_hc_reduce"](x) / x.shape[-2]).astype(x.dtype)


def _project_in_float8(cfg, p, h, cos, sin):
    """The heads' queries and the position's cache row rounded too: what a
    latent path computed and cached in float8 hands on."""
    return tuple(round_to_float8(a)
                 for a in _REAL["_mla_project"](cfg, p, h, cos, sin))


_REAL = {}

# what is planted: a change of the program's configuration, functions of
# ray_tpu.models.llama or ray_tpu.ops.moe replaced while the programs are
# traced, or the program's weights changed (the reference keeps its own)
FAULTS = {
    "softmax scores for sigmoid": {"patch": {
        "moe._route": _route_with_softmax}},
    "bias left out of the selection": {"weights": "no_bias"},
    "bias left in the gates": {"patch": {"moe._route": _route_bias_in_gates}},
    "no routed_scaling_factor": {"config": lambda m: {"routed_scaling": 1.0}},
    "no shared expert": {"weights": "no_shared"},
    "rotation on the unrotated query values": {"patch": {
        "llama._mla_project": _project_rotating_everything}},
    "no m^2 in the softmax scale": {"patch": {
        "llama.mla_softmax_scale": _scale_without_mscale}},
    "one Sinkhorn round": {"config": lambda m: {"hc_sinkhorn_iters": 1}},
    "H_post without its 2": {"patch": {
        "llama._hc_coeff": _coeff_post_without_its_two}},
    "rows averaged at the end": {"patch": {
        "llama._hc_reduce": _reduce_by_mean}},
    "float8 latent path": {"weights": "float8_latent"},
    "float8 latent path and cache": {"weights": "float8_latent", "patch": {
        "llama._mla_project": _project_in_float8}},
    "float8 weights": {"weights": "float8"},
}


@contextlib.contextmanager
def planted(fault: dict):
    """The fault's functions in place of the program's own, for as long as
    the programs that should have it are traced."""
    from ray_tpu.models import llama
    from ray_tpu.ops import moe
    modules = {"llama": llama, "moe": moe}
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


def round_to_float8(a):
    """``a`` rounded to float8's three bits of mantissa (e4m3's precision;
    its range is not imposed, which flatters the lower precision), in a's
    own type.  Done on the bits: a compiler for a chip without the type may
    widen a cast to it and round nothing."""
    import jax
    import jax.numpy as jnp
    bits = jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
    bits = (bits + jnp.uint32(1 << 19)) & jnp.uint32(0xFFF00000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32).astype(a.dtype)


def to_float8(params, names=MATRICES):
    """The leaves called ``names`` rounded to float8's precision;
    everything else as it is."""
    import jax
    return jax.tree_util.tree_map_with_path(
        lambda path, a: round_to_float8(a) if path[-1].key in names else a,
        params)


def with_weights_fault(params, kind: str):
    """The program's tree with the fault ``kind`` in its weights."""
    import jax.numpy as jnp
    layers = params["layers"]
    if kind == "no_bias":
        mlp = {**layers["mlp"], "router_bias": jnp.zeros_like(
            layers["mlp"]["router_bias"])}
        return {**params, "layers": {**layers, "mlp": mlp}}
    if kind == "no_shared":
        shared = {**layers["shared"],
                  "wd": jnp.zeros_like(layers["shared"]["wd"])}
        return {**params, "layers": {**layers, "shared": shared}}
    return to_float8(params, LATENT if kind == "float8_latent" else MATRICES)


def served_logits(engine, seqs):
    """For each sequence the logits of prefill and then of each decode
    position through the latent pages (first slot live), by the engine's own
    two programs on the engine's own pool, and each decode position's expert
    set [steps, expert layers, E]."""
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
        got, sets = [np.asarray(logits[0])], []
        tok = np.zeros((cfg.max_batch,), np.int32)
        pos = np.zeros((cfg.max_batch,), np.int32)
        for at in range(prompt_len, len(tokens)):
            tok[0], pos[0] = tokens[at], at
            logits, kp, vp, load = engine._decode_donating(
                engine._params, tok, pos, kp, vp, table)
            engine._k_pages, engine._v_pages = kp, vp
            got.append(np.asarray(logits[0]))
            sets.append(np.asarray(load) > 0)   # one live token: its experts
        out.append((np.stack(got), np.stack(sets)))
        del kp, vp
    return out


def reference_logits(family, config, params, seqs):
    """The same positions' logits and expert sets by the reference's full
    forward (``numerics_olmoe``'s: the families share ``with_gates``)."""
    from benchmark.tools import numerics_olmoe
    return numerics_olmoe.reference_logits(family, config, params, seqs)


def compare(served, reference):
    """(relative Frobenius error of each sequence's logits, the share of
    (decode position, expert layer) pairs with equal expert sets)."""
    from benchmark.tools import numerics_olmoe
    errs, same, pairs = numerics_olmoe.compare(served, reference)
    return errs, same / pairs


def drawn(family, config, params, tokens):
    """What the seeded routing bias and hyper-connection parameters do, by
    the reference on ``tokens`` [S]: the share of (position, expert layer)
    pairs whose expert set the bias changes, and of ``H_res`` in the first
    layer (on the embedding) its mean diagonal, its least and largest entry
    and how far its rows and columns are from summing to 1."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.reference import xing
    unbiased = with_weights_fault(params, "no_bias")
    gates = [np.asarray(jax.jit(lambda p, t: family.reference_forward(
        p, t, config, with_gates=True)[1])(p, tokens[None])) > 0
        for p in (params, unbiased)]
    moved = 1.0 - float((gates[0] == gates[1]).all(axis=-1).mean())
    group = params.get("dense_layers", params["layers"])
    hp = jax.tree.map(lambda a: a[0].astype(jnp.float32), group["hc_attn"])
    x = params["wte"][tokens[None]].astype(jnp.float32)
    x = jnp.repeat(x[:, :, None, :], config["hc_mult"], axis=2)
    with jax.default_matmul_precision("highest"):
        _, _, res = xing.hyper_coefficients(x, hp, config)
    res = np.asarray(res)
    return {"expert_sets_the_bias_changes": moved,
            "h_res_mean_diagonal": float(np.trace(
                res, axis1=-2, axis2=-1).mean() / res.shape[-1]),
            "h_res_min": float(res.min()), "h_res_max": float(res.max()),
            "h_res_sums_off_one": float(max(
                np.abs(res.sum(-1) - 1).max(),
                np.abs(res.sum(-2) - 1).max()))}


def served_with(family, config, engine_args, model, params, fault, key,
                steps):
    """(the sequences, their served logits and expert sets) from an engine
    with ``fault`` planted (``{}``: none)."""
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    from benchmark.tools.numerics_olmoe import sequences
    if "config" in fault:
        model = dataclasses.replace(model, **fault["config"](model))
    if "weights" in fault:
        params = with_weights_fault(params, fault["weights"])
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
    parser.add_argument("--config", default="xing4.0-29b-a4b-6l")
    parser.add_argument("--seeds", type=int, default=4)
    parser.add_argument("--seed", type=int, default=2 ** 31 + 3400)
    parser.add_argument("--steps", type=int, default=STEPS,
                        help="decode positions a sequence (the replica's "
                        "own check takes 8)")
    parser.add_argument("--faults", nargs="*", default=list(FAULTS))
    args = parser.parse_args()

    import jax
    from benchmark import spec
    from benchmark.replica import device_report, seeded_key
    config = spec.load_json("configs", args.config + ".json")
    family = spec.load_part("families", config["family"])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = os.path.join(ROOT, "chiprun_out", "numerics_xing.jsonl")

    def report(**line):
        line = {"device": device_report(), **line}
        print(json.dumps(line), flush=True)
        with open(log, "a") as f:
            f.write(json.dumps(line) + "\n")

    engine_args = config["engine"]
    model = family.program_config(
        config, engine_args["max_prompt_len"] + engine_args["max_new_tokens"])
    init = jax.jit(lambda key: family.init(key, model))

    def float8(kind):
        return str(FAULTS.get(kind, {}).get("weights", "")).startswith(
            "float8")

    for n in range(args.seeds):
        seed = args.seed + 7919 * n
        params = init(seeded_key(seed))
        faults = [""] + ([f for f in args.faults if not float8(f)]
                         if n == args.seeds - 1 else [])
        for what in faults:         # faults: on the last seed's weights
            seqs, served = served_with(
                family, config, engine_args, model, params,
                FAULTS.get(what, {}), seeded_key(seed + 1), args.steps)
            errs, same = compare(served, reference_logits(
                family, config, params, seqs))
            report(what=what or "as configured", seed=seed,
                   logits_rel_err=errs, expert_sets_equal=same)
        if n == 0:
            report(what="drawn", seed=seed,
                   **drawn(family, config, params, seqs[0][0]))
        del params
    # the precision below: the program on rounded weights; two trees do not
    # fit, so the rounded one is made from the seed inside one program and
    # the reference's made again once it and its engine are gone
    seeds = [args.seed + 7919 * n for n in range(args.seeds)]
    for what, seed in [(what, seed) for what in filter(float8, args.faults)
                       for seed in (seeds if what in EVERY_SEED
                                    else seeds[-1:])]:
        kind = FAULTS[what]["weights"]
        gc.collect()                  # the last engine, in cycles
        rounded = jax.jit(lambda key: with_weights_fault(
            family.init(key, model), kind))(seeded_key(seed))
        seqs, served = served_with(
            family, config, engine_args, model, rounded,
            {"patch": FAULTS[what].get("patch", {})}, seeded_key(seed + 1),
            args.steps)
        del rounded
        gc.collect()
        jax.clear_caches()            # the rounded tree's programs with it
        init = jax.jit(lambda key: family.init(key, model))
        params = init(seeded_key(seed))
        errs, same = compare(served, reference_logits(
            family, config, params, seqs))
        del params
        report(what=what, seed=seed, logits_rel_err=errs,
               expert_sets_equal=same)
    return 0


if __name__ == "__main__":
    sys.exit(main())
