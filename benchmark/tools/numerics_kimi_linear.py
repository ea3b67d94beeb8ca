#!/usr/bin/env python3
"""How far the served Kimi Linear is from its float32 reference, and how far
a faulty or lower-precision one would be: the readings ``numerics.logits_rtol``
of ``benchmark/configs/kimi-linear-48b-a3b-8l.json`` is set from.

    python3 benchmark/tools/numerics_kimi_linear.py [--seeds 4] [--steps 8]

One process on whatever device JAX finds (the chip, through ``chiprun``); no
cluster.  It builds the configuration's engine at the published size and
compares, as ``BenchLLMServer.check_numerics`` does, prefill (the chunked scan
with sub-chunks into slot 0's state rows, the latent prefill into the latent
layers' pages) and then decode (the one-step rule on those rows, the latent
read of those pages) by the engine's own two programs with the reference's
full forward in the recurrent form, on two seeded sequences:

* the configuration as it is, over ``--seeds`` seeds: the largest is what the
  tolerance has to admit;
* each of ``FAULTS`` planted in the program on the last seed's weights, which
  the tolerance has to refuse where it can be seen;
* one precision below what the configuration states: the recurrent STATE
  rounded to bfloat16 whenever it is written, and every matrix rounded to
  float8's three bits of mantissa in the program, the reference's left alone.

Every line also carries ``state_rel_err``: the KDA layers' STATES as the
prefill left them in slot 0's rows against the reference's after the prompt's
last position (relative Frobenius error over all six layers), because a
state in bfloat16 passes a check on logits (PERF.md section 6, PR 48) and
this is the comparison that can see it.  ``--prefill-trace DIR`` profiles one
prefill at each rung and prints the program's device time, the chunked scan's
(everything under ``linear_state``) and the scan's share of its roofline
(``costs_kda.chunked_scan`` over that time).
Lines of JSON on stdout, and appended to
``chiprun_out/numerics_kimi_linear.jsonl``.
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
MATRICES = {"wq", "wkv_a", "wkv_b", "wo", "wgu", "wd", "lm_head", "wqkv",
            "wf_a", "wf_b", "wg_a", "wg_b", "wb"}
_REAL = {}


# ---- functions that stand in for the program's own while it is traced

def _decay_a_head(f, A_log, dt_bias):
    import jax.numpy as jnp
    g = _REAL["kda_gate"](f, A_log, dt_bias)
    return jnp.broadcast_to(g.mean(axis=-1, keepdims=True), g.shape)


def _chunked_beta_doubled(q, k, v, g, beta, *rest, **kw):
    return _REAL["kda_chunked"](q, k, v, g, 2.0 * beta, *rest, **kw)


def _step_beta_doubled(q, k, v, g, beta, folded):
    return _REAL["kda_step"](q, k, v, g, 2.0 * beta, folded)


def _rotated(cfg, S):
    from ray_tpu.models import llama
    return llama.rope_tables(S, cfg.qk_rope_dim, 10000.0)


def _gate_by_silu(cfg, scale, o, z, gate=None):
    return _REAL["_gated_norm"](cfg, scale, o, z)


def _no_shared_expert(x, p, **kw):
    return _REAL["moe_dropless"](x, p, **{**kw, "shared": None})


def _bias_in_the_gates(logits, bias, top_k, scoring, norm_topk_prob,
                       routed_scaling):
    import jax
    import jax.numpy as jnp
    scores = jax.nn.sigmoid(logits) + bias
    gates, experts = jax.lax.top_k(scores, top_k)
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return gates * routed_scaling, experts


def _bf16(a):
    """float32 ``a`` rounded to bfloat16's 8 bits of mantissa (to nearest,
    ties to even), done on the bits: the TPU's compiler takes a cast there
    and back out of the program (it may keep excess precision), and the
    fault then plants nothing (PR 51's first readings were the honest
    program's to the last digit)."""
    import jax
    import jax.numpy as jnp
    bits = jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))) \
        & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _step_state_in_bf16(q, k, v, g, beta, folded):
    o, folded = _REAL["kda_step"](q, k, v, g, beta, folded)
    return o, _bf16(folded)


def _fold_state_in_bf16(S):
    return _bf16(_REAL["fold_state"](S))


def _tail_updates_the_state(q, k, v, g, beta, length=None, **kw):
    return _REAL["kda_chunked"](q, k, v, g, beta, None, **kw)


# what is planted: functions of ray_tpu.models.llama, ray_tpu.ops.moe or
# ray_tpu.ops.linear_attention replaced while the programs are traced, or the
# program's weights (the reference keeps its own)
FAULTS = {
    "a decay a head, not a channel": {"patch": {
        "la.kda_gate": _decay_a_head}},
    "beta doubled": {"patch": {"la.kda_chunked": _chunked_beta_doubled,
                               "la.kda_step": _step_beta_doubled}},
    "the latent layers' shared key rotated": {"patch": {
        "llama._rope_tables": _rotated}},
    "the output gate by silu": {"patch": {
        "llama._gated_norm": _gate_by_silu}},
    "the shared expert left out": {"patch": {
        "moe.moe_dropless": _no_shared_expert}},
    "the bias in the gates": {"patch": {"moe._route": _bias_in_the_gates}},
    "the padded tail updating the state": {"patch": {
        "la.kda_chunked": _tail_updates_the_state}},
    "state in bfloat16": {"patch": {"la.kda_step": _step_state_in_bf16,
                                    "la.fold_state": _fold_state_in_bf16}},
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
    """Every matrix rounded to float8's three bits of mantissa; the norm
    scales, ``A_log``, ``dt_bias``, the convolution's taps, the router (the
    routing is a code: a rounded router chooses the same experts) and the
    embedding (a lookup) as they are."""
    from benchmark.tools import numerics_xing
    return numerics_xing.to_float8(params, MATRICES)


def served(engine, seqs):
    """For each sequence (the logits of prefill and then of each decode
    position through the cache, slot 0 live, by the engine's own two programs
    on the engine's own pools; the KDA layers' states [layers, N, dk, dv] as
    the prefill left them in slot 0's rows)."""
    import numpy as np
    from ray_tpu.ops.linear_attention import unfold_state
    cfg, model, out = engine.config, engine.model_config, []
    for tokens, prompt_len in seqs:
        table = np.zeros((cfg.max_batch, engine._maxp), np.int32)
        table[0] = np.arange(1, engine._maxp + 1)
        padded = np.zeros((1, cfg.max_prompt_len), np.int32)
        padded[0, :prompt_len] = tokens[:prompt_len]
        logits, kp, vp = engine._prefill(
            engine._params, padded, np.int32(prompt_len), engine._k_pages,
            engine._v_pages, table[:1])
        states = np.stack([np.asarray(unfold_state(
            rows, model.linear_heads, model.linear_value_dim))
            for rows in vp.state[:, 0]])
        got = [np.asarray(logits[0])]
        tok = np.zeros((cfg.max_batch,), np.int32)
        pos = np.zeros((cfg.max_batch,), np.int32)
        for at in range(prompt_len, len(tokens)):
            tok[0], pos[0] = tokens[at], at
            logits, kp, vp = engine._decode(engine._params, tok, pos, kp,
                                            vp, table)
            got.append(np.asarray(logits[0]))
        out.append((np.stack(got), states))
        del kp, vp
    return out


def reference(family, config, params, seqs):
    """The same positions' logits by the reference's full forward, and its
    states after the prompt's last position."""
    import jax
    import numpy as np
    whole = jax.jit(lambda p, t: family.reference_forward(p, t, config))
    prompt = jax.jit(lambda p, t: family.reference_forward(
        p, t, config, True)[1])
    return [(np.asarray(whole(params, tokens[None])[0])[prompt_len - 1:],
             np.asarray(prompt(params, tokens[None, :prompt_len]))[:, 0])
            for tokens, prompt_len in seqs]


def errors(got, want):
    """Relative Frobenius errors of each sequence's logits and states."""
    import numpy as np

    def rel(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))
    return {"logits_rel_err": [rel(g[0], w[0]) for g, w in zip(got, want)],
            "state_rel_err": [rel(g[1], w[1]) for g, w in zip(got, want)]}


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


def prefill_scan_roofline(family, config, engine_args, model, params,
                          trace_dir):
    """One prefill at every rung under the profiler: the chunked scan's
    device time (everything under ``linear_state`` in ``jit__prefill``) and
    its share of ``costs_kda.chunked_scan``'s roofline, by rung."""
    import jax
    import numpy as np
    from benchmark import costs, costs_kda, decode_scopes, host_regions, \
        replica, spec, trace_reduce
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
        scan_s = sum(sec for sec, text in own if decode_scopes.under(
            names.get(text, ""), ("linear_state",)))
        least = costs.least_seconds(costs_kda.chunked_scan(
            rung, shape["layers"], shape["heads"], shape["key_dim"],
            shape["value_dim"]), peaks)
        out[rung] = {"prefill_ms": 1e3 * sum(sec for sec, _ in own),
                     "scan_ms": 1e3 * scan_s,
                     "scan_roofline": 100.0 * least / scan_s
                     if scan_s else None}
    for future in (*engine._rung_programs.values(),
                   *engine._decode_programs.values()):
        future.result()
    engine.close()
    return out


def _device_ops(path):
    """(start, end, text) of the first device's operations in a profile."""
    import re
    from jax.profiler import ProfileData
    from benchmark import trace_reduce
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        device = re.fullmatch(r"/device:\w+:(\d+)", plane.name)
        for line in plane.lines if device else ():
            if line.name == trace_reduce.OPS:
                lines[int(device.group(1))] = [
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                     e.name) for e in line.events]
    return lines[min(lines)] if lines else []


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default="kimi-linear-48b-a3b-8l")
    parser.add_argument("--seeds", type=int, default=4)
    parser.add_argument("--seed", type=int, default=2 ** 31 + 5100)
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
    log = os.path.join(ROOT, "chiprun_out", "numerics_kimi_linear.jsonl")

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
