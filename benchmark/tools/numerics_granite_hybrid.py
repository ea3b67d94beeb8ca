#!/usr/bin/env python3
"""How far the served Granite 4.0-H is from its float32 reference, in its
logits AND in what a decode slot keeps, and how far a faulty or
lower-precision one would be: the readings that ``numerics.logits_rtol``,
``state_rtol`` and ``tail_rtol`` of
``benchmark/configs/granite-4.0-h-small-10l.json`` are set from.

    python3 benchmark/tools/numerics_granite_hybrid.py [--seeds 4] [--steps 8]

One process on whatever device JAX finds (the chip, through ``chiprun``); no
cluster.  It builds the configuration's engine at the published size and
compares, as ``StatesBenchLLMServer.check_numerics`` does, prefill (the
chunked form into slot 0's state rows and tail, the attention layer's K/V
into its pages) and then decode (the one-position rule on those rows, the
paged read) by the engine's own two programs with the reference's full
forward in the recurrent form, on two seeded sequences: the logits of the
prefill and ``--steps`` token steps, and the slot's state rows and tail after
the prefill and after the steps against what the reference hands out
(``states_after``), the state's error LAYER BY LAYER:

* the configuration as it is, over ``--seeds`` seeds: the largest is what the
  limits have to admit;
* each of ``FAULTS`` planted in the program on the last seed's weights, which
  the limits have to refuse where it can be seen;
* one precision below what the configuration states: the recurrent STATE
  rounded to bfloat16 whenever it is written (on the bits), and every matrix
  rounded to float8's three bits of mantissa in the program, the reference's
  left alone.

``--steps 64`` (or more) shows what the state's own rounding does over many
writes.  ``--prefill-trace DIR`` profiles one prefill at each rung and prints
the program's device time, the chunked form's (everything under
``linear_state``) and its share of ``costs_ssm.chunked_scan``'s roofline: the
cell's traced seconds hold no prefill (its first answers end after them).
Lines of JSON on stdout, and appended to
``chiprun_out/numerics_granite_hybrid.jsonl``.
"""

import argparse
import dataclasses
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tools.numerics_kimi_linear import (   # noqa: E402
    _REAL, _bf16, _device_ops, planted)

STEPS = 8
MATRICES = {"wq", "wkv", "wo", "wgu", "wd", "win", "wout"}


# ---- functions that stand in for the program's own while it is traced

def _step_state_in_bf16(q, k, v, g, folded):
    o, folded = _REAL["ssm_step"](q, k, v, g, folded)
    return o, _bf16(folded)


def _fold_state_in_bf16(S):
    return _bf16(_REAL["fold_state"](S))


def _tail_updates_the_state(q, k, v, g, length=None, **kw):
    return _REAL["ssm_chunked"](q, k, v, g, None, **kw)


def _mixer(gate_after_norm=False, norm_a_head=False, raw_delta=False):
    """``llama._ssm_mixer`` with one thing done otherwise: the gate AFTER the
    norm (the delta-rule layers' order), the norm over each head's values
    (``_gated_norm``'s), or ``delta`` without its softplus (its magnitude,
    so that the decay stays under 1 and the fault reads as a number)."""
    def mixer(cfg, p, h, state, layer, pools):
        import jax
        import jax.numpy as jnp
        from ray_tpu.models import llama
        a, dt = p["ssm"], cfg.dtype
        N, dv = cfg.linear_heads, cfg.linear_value_dim
        inner, C = N * dv, llama._mixer_channels(cfg, "ssm")
        zxbcdt = jnp.einsum("...d,dc->...c", h, a["win"].astype(dt))
        z, xbc = zxbcdt[..., :inner], zxbcdt[..., inner:inner + C]
        raw = zxbcdt[..., inner + C:].astype(jnp.float32) + a["dt_bias"]
        delta = jnp.abs(raw) if raw_delta else jax.nn.softplus(raw)
        o, pools = state.recurrent(p, layer, pools, xbc,
                                   -jnp.exp(a["A_log"]) * delta, delta)
        gate = jax.nn.silu(z.astype(jnp.float32))
        if norm_a_head:
            y = llama._rms_norm(
                o * gate.reshape(o.shape), a["norm"].reshape(N, dv),
                cfg.rms_eps).reshape(*o.shape[:-2], inner)
        elif gate_after_norm:
            y = llama._rms_norm(o.reshape(*o.shape[:-2], inner), a["norm"],
                                cfg.rms_eps) * gate
        else:
            y = llama._rms_norm(o.reshape(*o.shape[:-2], inner) * gate,
                                a["norm"], cfg.rms_eps)
        return jnp.einsum("...c,cd->...d", y.astype(dt),
                          a["wout"].astype(dt)), pools
    return mixer


def zeroed(params, leaf: str):
    """The program's tree with every ssm layer's ``leaf`` zero."""
    import jax.numpy as jnp
    return {**params, "layers": tuple(
        {**g, "ssm": {**g["ssm"], leaf: jnp.zeros_like(g["ssm"][leaf])}}
        if "ssm" in g else g for g in params["layers"])}


# what is planted: functions of ray_tpu.models.llama or
# ray_tpu.ops.linear_attention replaced while the programs are traced, fields
# of the program's configuration, or the program's weights (the reference
# keeps its own)
FAULTS = {
    "state in bfloat16": {"patch": {"la.ssm_step": _step_state_in_bf16,
                                    "la.fold_state": _fold_state_in_bf16}},
    "delta without its softplus": {"patch": {
        "llama._ssm_mixer": _mixer(raw_delta=True)}},
    "the gate after the norm": {"patch": {
        "llama._ssm_mixer": _mixer(gate_after_norm=True)}},
    "the norm a head": {"patch": {
        "llama._ssm_mixer": _mixer(norm_a_head=True)}},
    "the padded tail updating the state": {"patch": {
        "la.ssm_chunked": _tail_updates_the_state}},
    "D left out": {"zero": "D"},
    "the convolution's bias left out": {"zero": "conv_bias"},
    "the scale 128 ** -0.5": {"config": {"attention_multiplier": 0.0}},
    "embedding_multiplier 1": {"config": {"embedding_multiplier": 1.0}},
    "residual_multiplier 1": {"config": {"residual_multiplier": 1.0}},
    "logits_scaling 1": {"config": {"logits_scaling": 1.0}},
    "the experts of the other share": {"config": {"expert_share": (1, 2)}},
    "float8 weights": {"weights": True},
}


def to_float8(params):
    """Every matrix rounded to float8's three bits of mantissa; the norm
    scales, ``A_log``, ``D``, ``dt_bias``, the convolution, the router (the
    routing is a code) and the table (a lookup, and the head) as they
    are."""
    from benchmark.tools import numerics_xing
    return numerics_xing.to_float8(params, MATRICES)


def served(engine, seqs):
    """For each sequence what ``replica_states.drive`` reads: the logits of
    prefill and then of each decode position through the cache, slot 0's
    rows after the prefill and after the steps, the pool's dtype."""
    from benchmark.replica_states import drive
    out = []
    for tokens, prompt_len in seqs:
        logits, rows, dtype = drive(engine, tokens, prompt_len,
                                    len(tokens) - prompt_len)
        out.append((logits, rows, str(dtype)))
    return out


def reference(family, config, params, seqs):
    """The same positions' logits by the reference's full forward, and what
    it keeps after the prompt and after the last position."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    whole = jax.jit(lambda p, t, after: family.reference_forward(
        p, t, config, states_after=after))
    out = []
    for tokens, prompt_len in seqs:
        logits, kept = whole(params, tokens[None], jnp.asarray(
            [prompt_len, len(tokens)], jnp.int32))
        out.append((np.asarray(logits[0])[prompt_len - 1:],
                    np.asarray(kept["state"])[:, :, 0],
                    np.asarray(kept["tail"])[:, :, 0]))
    return out


def errors(got, want):
    """Relative Frobenius errors of each sequence's logits, and of its state
    rows (the worst layer's, and every layer's) and tail at the two moments
    [after the prefill, after the steps]."""
    import numpy as np
    from benchmark.replica_states import rel_errs
    out = {"logits_rel_err": [], "state_rel_err": [], "tail_rel_err": [],
           "state_rel_err_by_layer": [], "state_dtype": got[0][2]}
    for (logits, rows, _), (want_logits, states, tails) in zip(got, want):
        out["logits_rel_err"].append(float(
            np.linalg.norm(logits - want_logits)
            / np.linalg.norm(want_logits)))
        for moment, (state, tail) in enumerate(rows):
            worst, each = rel_errs(state, states[:, moment])
            out["state_rel_err"].append(worst)
            out["state_rel_err_by_layer"].append(
                [float(f"{e:.5g}") for e in each])
            out["tail_rel_err"].append(
                rel_errs(tail, tails[:, moment])[0])
    return out


def served_with(family, config, engine_args, model, params, fault, key,
                steps):
    """(the sequences, what ``served`` reads) from an engine with ``fault``
    planted (``{}``: none)."""
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    from benchmark.tools.numerics_olmoe import sequences
    model = dataclasses.replace(model, **fault.get("config", {}))
    if fault.get("zero"):
        params = zeroed(params, fault["zero"])
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


def prefill_scan_roofline(family, config, engine_args, model, params,
                          trace_dir):
    """One prefill at every rung under the profiler: the program's device
    time, the chunked form's (everything under ``linear_state`` in
    ``jit__prefill``) and its share of ``costs_ssm.chunked_scan``'s roofline
    at the rung's full length, by rung."""
    import jax
    import numpy as np
    from benchmark import (costs, costs_ssm, decode_scopes, host_regions,
                           replica, spec, trace_reduce)
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    engine = InferenceEngine(EngineConfig(
        model=family.ENGINE_MODEL, model_config=model, **engine_args),
        params=params)
    peaks = spec.peaks_for(jax.devices()[0].device_kind)
    shape = family.linear_shape(config)
    out = {}
    for rung in engine._rungs:
        table = np.arange(1, engine._maxp + 1, dtype=np.int32)[None]
        padded = np.zeros((1, rung), np.int32)
        args = (engine._params, padded, np.int32(rung))
        engine._prefill(*args, engine._k_pages, engine._v_pages, table)
        folder = os.path.join(trace_dir, str(rung))
        jax.profiler.start_trace(folder)
        jax.block_until_ready(engine._prefill(
            *args, engine._k_pages, engine._v_pages, table))
        jax.profiler.stop_trace()
        path = replica.find_xplane(folder)
        names = host_regions.op_names(path)
        own = trace_reduce.self_times(_device_ops(path))

        def under(*scopes):
            return sum(sec for sec, text in own if decode_scopes.under(
                names.get(text, ""), scopes))
        scan_s = under("linear_state")
        least = costs.least_seconds(costs_ssm.chunked_scan(
            rung, shape["layers"], shape["heads"], shape["key_dim"],
            shape["value_dim"]), peaks)
        out[rung] = {"prefill_ms": 1e3 * sum(sec for sec, _ in own),
                     "scan_ms": 1e3 * scan_s,
                     "proj_ms": 1e3 * under("ssm_proj"),
                     "experts_ms": 1e3 * under("moe_experts"),
                     "scan_roofline": 100.0 * least / scan_s
                     if scan_s else None}
    for future in (*engine._rung_programs.values(),
                   *engine._decode_programs.values()):
        future.result()
    engine.close()
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default="granite-4.0-h-small-10l")
    parser.add_argument("--seeds", type=int, default=4)
    parser.add_argument("--seed", type=int, default=2 ** 31 + 6100)
    parser.add_argument("--steps", type=int, default=STEPS,
                        help="decode positions a sequence (the replica's "
                        "own check takes 8)")
    parser.add_argument("--faults", nargs="*", default=list(FAULTS))
    parser.add_argument("--prefill-trace", default=None)
    args = parser.parse_args()

    import jax
    from benchmark import spec
    from benchmark.replica import device_report, seeded_key
    config = spec.load_json("configs", args.config + ".json")
    family = spec.load_part("families", config["family"])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = os.path.join(ROOT, "chiprun_out", "numerics_granite_hybrid.jsonl")

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
        seqs, got = served_with(
            family, config, engine_args, model, params, FAULTS.get(what, {}),
            seeded_key(seed + 1), args.steps)
        gc.collect()                  # the engine, in cycles
        report(what=what, seed=seed, **errors(
            got, reference(family, config, reference_params, seqs)))

    seed = args.seed
    for n in range(args.seeds):
        seed = args.seed + 7919 * n
        params = init(seeded_key(seed))
        run("as configured", params, params, seed)
        if n == args.seeds - 1:              # faults: the last seed's weights
            for what in args.faults:
                if not FAULTS[what].get("weights"):
                    run(what, params, params, seed)
            if args.prefill_trace:
                report(what="prefill scan", seed=seed,
                       rungs=prefill_scan_roofline(
                           family, config, engine_args, model, params,
                           args.prefill_trace))
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
            init = jax.jit(lambda key: family.init(key, model))
            params = init(seeded_key(seed))
            report(what=what, seed=seed, **errors(
                got, reference(family, config, params, seqs)))
            del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
