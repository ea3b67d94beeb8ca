#!/usr/bin/env python3
"""How far the served LFM2 (mixture of experts) is from its float32
reference, and how far a faulty or lower-precision one would be: the readings
``numerics.logits_rtol`` of ``benchmark/configs/lfm2-24b-a2b-9l.json`` is set
from.

    python3 benchmark/tools/numerics_lfm2_moe.py [--seeds 4] [--steps 8]

One process on whatever device JAX finds (the chip, through ``chiprun``); no
cluster.  It builds the configuration's engine at the published size and
compares, as ``BenchLLMServer.check_numerics`` does, prefill (the convolution
over the padded rung and the hand-over of its tail to slot 0, the flash
prefill into the attention layers' pages, the experts at prefill rows) and
then decode (the one-position convolution on that tail, the paged read of
those pages through the 64-wide walker) by the engine's own two programs with
the reference's full forward, on two seeded sequences that do not fill their
rung:

* the configuration as it is, over ``--seeds`` seeds: the largest is what the
  tolerance has to admit;
* each of ``FAULTS`` planted in the program on the last seed's weights, which
  the tolerance has to refuse;
* one precision below what the configuration states: every matrix rounded to
  float8's three bits of mantissa in the program, the reference's left alone.

Lines of JSON on stdout, and appended to
``chiprun_out/numerics_lfm2_moe.jsonl``.
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
MATRICES = {"win", "wout", "wq", "wkv", "wo", "wgu", "wd", "wte"}
_REAL = {}


# ---- functions that stand in for the program's own while it is traced

def _conv_with_silu(x, w, silu=True):
    return _REAL["causal_conv"](x, w, True)


def _conv_step_with_silu(x, w, tail, silu=True):
    return _REAL["causal_conv_step"](x, w, tail, True)


def _conv_taps_reversed(x, w, silu=True):
    return _REAL["causal_conv"](x, w[::-1], silu)


def _conv_step_taps_reversed(x, w, tail, silu=True):
    return _REAL["causal_conv_step"](x, w[::-1], tail, silu)


def _tail_one_position_stale(x, length, K):
    return _REAL["conv_tail"](x, length - 1, K)


def _tail_at_the_rungs_end(x, length, K):
    return _REAL["conv_tail"](x, x.shape[0], K)


def _operator_without(gate: str):
    """``llama._conv_operator`` with ``B`` or ``C`` left out (ones)."""
    def operator(cfg, p, h, state, layer, pools):
        import jax.numpy as jnp
        a, dt, D = p["conv"], cfg.dtype, cfg.embed_dim
        bcz = jnp.einsum("...d,dc->...c", h, a["win"].astype(dt))
        b, c, z = bcz[..., :D], bcz[..., D:2 * D], bcz[..., 2 * D:]
        mixed, pools = state.conv(p, layer, pools,
                                  z if gate == "B" else b * z)
        y = mixed if gate == "C" else c * mixed
        return jnp.einsum("...c,cd->...d", y, a["wout"].astype(dt)), pools
    return operator


def _qk_normed_over_all_heads(cfg, p, q, k, cos, sin):
    """q and k RMS-normed over all heads together (OLMoE's way) with the
    head's scale, then rotated."""
    from ray_tpu.models import llama
    q = llama._rms_norm(q, p["attn"]["q_norm"], cfg.rms_eps, axis=(1, -1))
    k = llama._rms_norm(k, p["attn"]["k_norm"], cfg.rms_eps, axis=(1, -1))
    if cos is None:
        return q, k
    return llama.apply_rope(q, cos, sin), llama.apply_rope(k, cos, sin)


def _nothing_rotated(cfg, S):
    return None, None


def _route_with_softmax(logits, bias, top_k, scoring, norm, scale, **kw):
    return _REAL["_route"](logits, None, top_k, "softmax", norm, scale)


def _route_bias_in_gates(logits, bias, top_k, scoring, norm, scale,
                         norm_eps=1e-20):
    import jax
    import jax.numpy as jnp
    gates, experts = jax.lax.top_k(jax.nn.sigmoid(logits) + bias, top_k)
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + norm_eps)
    return gates * scale, experts


def _route_not_renormalised(logits, bias, top_k, scoring, norm, scale, **kw):
    return _REAL["_route"](logits, bias, top_k, scoring, False, scale, **kw)


# what is planted: functions of ray_tpu.models.llama, ray_tpu.ops.moe or
# ray_tpu.ops.linear_attention replaced while the programs are traced, or the
# program's weights (the reference keeps its own)
FAULTS = {
    "the convolution's SiLU left in": {"patch": {
        "la.causal_conv": _conv_with_silu,
        "la.causal_conv_step": _conv_step_with_silu}},
    "B left out": {"patch": {"llama._conv_operator": _operator_without("B")}},
    "C left out": {"patch": {"llama._conv_operator": _operator_without("C")}},
    "the taps in reverse order": {"patch": {
        "la.causal_conv": _conv_taps_reversed,
        "la.causal_conv_step": _conv_step_taps_reversed}},
    "a tail one position stale": {"patch": {
        "la.conv_tail": _tail_one_position_stale}},
    "the tail taken at the rung's end": {"patch": {
        "la.conv_tail": _tail_at_the_rungs_end}},
    "q/k normed over all heads": {"patch": {
        "llama._qk": _qk_normed_over_all_heads}},
    "the rotation left out": {"patch": {
        "llama._rope_tables": _nothing_rotated}},
    "softmax scoring": {"patch": {"moe._route": _route_with_softmax}},
    "the bias in the gates": {"patch": {"moe._route": _route_bias_in_gates}},
    "gates not renormalised": {"patch": {
        "moe._route": _route_not_renormalised}},
    "float8 weights": {"weights": True},
}


@contextlib.contextmanager
def planted(fault: dict):
    """The fault's functions in place of the program's own, for as long as
    the programs that should have it are traced."""
    import importlib
    modules = {"llama": importlib.import_module("ray_tpu.models.llama"),
               "la": importlib.import_module("ray_tpu.ops.linear_attention"),
               "moe": importlib.import_module("ray_tpu.ops.moe")}
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
    """Every matrix rounded to float8's three bits of mantissa, the table
    (which is the head) among them; the norm scales, the convolution's taps
    and the router (the routing is a code: a rounded router chooses the same
    experts) as they are."""
    from benchmark.tools import numerics_xing
    return numerics_xing.to_float8(params, MATRICES)


def served(engine, seqs):
    """For each sequence the logits of prefill and then of each decode
    position through the cache, slot 0 live, by the engine's own two programs
    on the engine's own pools."""
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


def reference(family, config, params, seqs):
    """The same positions' logits by the reference's full forward."""
    import jax
    import numpy as np
    whole = jax.jit(lambda p, t: family.reference_forward(p, t, config))
    return [np.asarray(whole(params, tokens[None])[0])[prompt_len - 1:]
            for tokens, prompt_len in seqs]


def errors(got, want):
    """Relative Frobenius error of each sequence's logits."""
    import numpy as np
    return {"logits_rel_err": [
        float(np.linalg.norm(g - w) / np.linalg.norm(w))
        for g, w in zip(got, want)]}


def served_with(family, config, engine_args, model, params, fault, key,
                steps):
    """(the sequences, what ``served`` reads) from an engine with ``fault``
    planted (``{}``: none)."""
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    from benchmark.tools.numerics_olmoe import sequences
    model = dataclasses.replace(model, **fault.get("config", {}))
    with planted(fault):
        engine = InferenceEngine(EngineConfig(
            model=family.ENGINE_MODEL, model_config=model, **engine_args),
            params=params)
        try:
            seqs = sequences(config, engine.config, key, steps)
            return seqs, served(engine, seqs)
        finally:
            # a rung still compiling keeps its thread, the thread the
            # engine, the engine its tree: wait, then drop
            for future in (*engine._rung_programs.values(),
                           *engine._decode_programs.values()):
                future.result()
            engine.close()


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default="lfm2-24b-a2b-9l")
    parser.add_argument("--seeds", type=int, default=4)
    parser.add_argument("--seed", type=int, default=2 ** 31 + 5500)
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
    log = os.path.join(ROOT, "chiprun_out", "numerics_lfm2_moe.jsonl")

    def report(**line):
        line = {"device": device_report(), "steps": args.steps, **line}
        print(json.dumps(line), flush=True)
        with open(log, "a") as f:
            f.write(json.dumps(line) + "\n")

    engine_args = config["engine"]
    model = family.program_config(
        config, engine_args["max_prompt_len"] + engine_args["max_new_tokens"])
    init = jax.jit(lambda key: family.init(key, model))

    def run(what, params, seed):
        seqs, got = served_with(
            family, config, engine_args, model, params, FAULTS.get(what, {}),
            seeded_key(seed + 1), args.steps)
        gc.collect()                  # the engine, in cycles
        report(what=what, seed=seed, **errors(
            got, reference(family, config, params, seqs)))

    seed = args.seed
    for n in range(args.seeds):
        seed = args.seed + 7919 * n
        params = init(seeded_key(seed))
        run("as configured", params, seed)
        if n == args.seeds - 1:              # faults: the last seed's weights
            for what in args.faults:
                if not FAULTS[what].get("weights"):
                    run(what, params, seed)
        del params
    # the matrices a precision below: the program on rounded weights; two
    # trees and the pools do not fit, so the reference's are made again once
    # those are gone
    for what in args.faults:
        if FAULTS[what].get("weights"):
            gc.collect()
            rounded = jax.jit(lambda key: to_float8(
                family.init(key, model)))(seeded_key(seed))
            seqs, got = served_with(
                family, config, engine_args, model, rounded, {},
                seeded_key(seed + 1), args.steps)
            del rounded
            gc.collect()              # the engine, in cycles
            jax.clear_caches()        # the rounded tree's programs with it
            params = jax.jit(lambda key: family.init(key, model))(
                seeded_key(seed))
            report(what=what, seed=seed, **errors(
                got, reference(family, config, params, seqs)))
            del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
