#!/usr/bin/env python3
"""How far the served SDAR is from its float32 reference, and how far a
faulty or lower-precision one would be: the readings ``numerics.logits_rtol``
of ``benchmark/configs/sdar-30b-a3b-chat-6l.json`` is set from.

    python3 benchmark/tools/numerics_sdar.py [--seeds 4]

One process on whatever device JAX finds (the chip, through ``chiprun``);
no cluster.  It builds the configuration's engine at the published size and
compares, as ``BlockBenchLLMServer.check_numerics`` does (the same
``replica_blocks.drive``), the prefill and then three blocks of two seeded
sequences, each block through fixed masked states by the engine's own two
programs, with the reference's full forward:

* the configuration as it is, over ``--seeds`` seeds: the largest is what
  the tolerance has to admit;
* each of ``FAULTS`` planted in the program on the last seed's weights,
  which the tolerance has to refuse;
* the nearest precision below bfloat16: every matrix rounded to float8's
  three bits of mantissa in the program, the reference's left alone;
* with ``--random-router`` all of it with the router left random
  (``families/sdar.py::init(routing_code=False)``), program and reference
  alike: the readings that say why the routing is drawn as a code.

Lines of JSON on stdout, and appended to ``chiprun_out/numerics_sdar.jsonl``.
"""

import argparse
import concurrent.futures
import contextlib
import dataclasses
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

MATRICES = {"wq", "wkv", "wo", "wgu", "wd", "lm_head", "wte"}


# ---- functions that stand in for the program's own while it is traced

def _qk_pooled_over_heads(cfg, p, q, k, cos, sin):
    """The q/k norm over the WHOLE projection (all heads together, as
    OLMoE's), with the per-head scale: the second kind mistaken for the
    first."""
    from ray_tpu.models import llama

    def norm(a, scale):              # heads on axis 1, a head's values last
        return llama._rms_norm(a, scale, cfg.rms_eps, axis=(1, -1))
    return (llama.apply_rope(norm(q, p["attn"]["q_norm"]), cos, sin),
            llama.apply_rope(norm(k, p["attn"]["k_norm"]), cos, sin))


def _prefill_causal(q, k, v, rep, block=0):
    return _REAL["_dense_causal_attention_gqa"](q, k, v, rep)


def _block_attention_causal(q, k_pages, v_pages, layer, lengths, page_table):
    """Row ``i`` of a block sees positions up to its own, not the block's
    end: one ``paged_attention`` a row."""
    import jax.numpy as jnp
    from ray_tpu.ops.paged_attention import paged_attention
    B = q.shape[2]
    return jnp.stack([paged_attention(
        q[:, :, i], k_pages, v_pages, layer, lengths - (B - 1 - i),
        page_table) for i in range(B)], axis=2)


def _step_keeping_stale_rows(params, cfg, state, end, kp, vp, table):
    """The commit pass writes nothing: the pages keep what the last denoise
    pass left, K/V computed from masks."""
    import jax
    import jax.numpy as jnp
    logits, new_k, new_v, *load = _REAL["llama_block_step"](
        params, cfg, state, end, kp, vp, table)
    commit = ~jnp.any(state[1][0])           # the check drives slot 0
    keep = lambda old, new: jax.lax.select(   # noqa: E731
        jnp.broadcast_to(commit, old.shape), old, new)
    return (logits, keep(kp, new_k), keep(vp, new_v), *load)


def _step_shifted(params, cfg, state, end, kp, vp, table):
    """Row ``i``'s logits taken as the prediction for position ``i + 1``
    (the next-token convention): every row one place late."""
    import jax.numpy as jnp
    logits, *rest = _REAL["llama_block_step"](params, cfg, state, end, kp,
                                              vp, table)
    return (jnp.roll(logits, 1, axis=1), *rest)


_REAL = {}

# what is planted: a change of the program's configuration, functions of
# ray_tpu.models.llama or ray_tpu.ops.paged_attention replaced while the
# programs are traced, or the program's weights changed (the reference
# keeps its own)
FAULTS = {
    "float8 weights": {"weights": "float8"},
    "top-7": {"config": lambda m: {"experts_per_token":
                                   m.experts_per_token - 1}},
    "no q/k norm": {"config": lambda m: {"qk_norm_per_head": False}},
    "q/k norm pooled over all heads": {"patch": {
        "llama._qk": _qk_pooled_over_heads}},
    "causal mask inside the block": {"patch": {
        "llama._dense_causal_attention_gqa": _prefill_causal,
        "paged.paged_block_attention": _block_attention_causal}},
    "last denoise pass's K/V kept at a commit": {"patch": {
        "llama.llama_block_step": _step_keeping_stale_rows}},
    "logits shifted by one": {"patch": {
        "llama.llama_block_step": _step_shifted}},
}


@contextlib.contextmanager
def planted(fault: dict):
    """The fault's functions in place of the program's own, for as long as
    the programs that should have it are traced."""
    import importlib
    from ray_tpu.models import llama
    # (``ray_tpu.ops.paged_attention`` the attribute is the function)
    modules = {"llama": llama, "paged": importlib.import_module(
        "ray_tpu.ops.paged_attention")}
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


def to_float8(params):
    """The matrices rounded to float8's precision; norms and router as
    they are."""
    import jax
    return jax.tree_util.tree_map_with_path(
        lambda path, a: round_to_float8(a)
        if path[-1].key in MATRICES else a, params)


def served(family, engine_args, model, params, fault, seqs):
    """For each sequence ``replica_blocks.drive``'s passes from an engine
    with ``fault`` planted (``{}``: none): [(stood, logits, pos0)]."""
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    from benchmark.replica_blocks import drive
    if "config" in fault:
        model = dataclasses.replace(model, **fault["config"](model))
    with planted(fault):
        engine = InferenceEngine(EngineConfig(
            model=family.ENGINE_MODEL, model_config=model, **engine_args),
            params=params)
        try:
            return [list(drive(engine, engine._params, tokens, length,
                               model.mask_token))
                    for tokens, length in seqs]
        finally:
            # a rung still compiling keeps its thread, the thread the
            # engine, and the engine its tree: two trees do not fit
            concurrent.futures.wait([*engine._rung_programs.values(),
                                     *engine._decode_programs.values()])
            engine.close()


def errors(family, config, params, passes):
    """Relative Frobenius error of each sequence's served logits against
    the reference's full forward over the sequence as it stood, and the
    same for each of the three kinds of pass (all masked, half, commit)."""
    import jax
    import numpy as np
    B = family.generation(config)["block_length"]
    reference = jax.jit(lambda p, t: family.reference_forward(p, t, config))
    longest = max(len(stood) for seq in passes for stood, _, _ in seq)
    errs, kinds = [], [[], [], []]
    for seq in passes:
        got, want = [], []
        for stood, logits, pos0 in seq:
            padded = np.zeros((longest,), np.int32)
            padded[:len(stood)] = stood
            want.append(np.asarray(reference(params, padded)[pos0:pos0 + B]))
            got.append(logits)
        got, want = np.stack(got), np.stack(want)
        errs.append(float(np.linalg.norm(got - want) / np.linalg.norm(want)))
        for kind in range(3):
            kinds[kind].append(float(
                np.linalg.norm(got[kind::3] - want[kind::3])
                / np.linalg.norm(want[kind::3])))
    return errs, {"all_masked": kinds[0], "half_unmasked": kinds[1],
                  "commit": kinds[2]}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default="sdar-30b-a3b-chat-6l")
    parser.add_argument("--seeds", type=int, default=4)
    parser.add_argument("--seed", type=int, default=2 ** 31 + 4000)
    parser.add_argument("--faults", nargs="*", default=list(FAULTS))
    parser.add_argument("--random-router", action="store_true")
    args = parser.parse_args()

    import jax
    from benchmark import spec
    from benchmark.replica import device_report, seeded_key
    config = spec.load_json("configs", args.config + ".json")
    family = spec.load_part("families", config["family"])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = os.path.join(ROOT, "chiprun_out", "numerics_sdar.jsonl")

    def report(**line):
        line = {"device": device_report(), **line}
        print(json.dumps(line), flush=True)
        with open(log, "a") as f:
            f.write(json.dumps(line) + "\n")

    engine_args = config["engine"]
    model = family.program_config(
        config, engine_args["max_prompt_len"] + engine_args["max_new_tokens"])
    from benchmark.replica_blocks import sequences

    def read(what, seed, fault, make):
        """One reading: the program on ``make(key)``'s tree with ``fault``
        planted, the reference on the honest tree.  Two trees do not fit,
        so a changed one is dropped before the honest one is made again."""
        seqs = sequences(seeded_key(seed + 1), engine_args["max_prompt_len"],
                         model.block_length, model.vocab_size)
        honest = jax.jit(lambda key: family.init(
            key, model, routing_code=not args.random_router))
        params = jax.jit(make)(seeded_key(seed)) if make else \
            honest(seeded_key(seed))
        passes = served(family, engine_args, model, params, fault, seqs)
        if make:
            del params
            gc.collect()
            jax.clear_caches()
            params = honest(seeded_key(seed))
        errs, kinds = errors(family, config, params, passes)
        del params
        gc.collect()
        report(what=what, seed=seed, logits_rel_err=errs,
               router="random" if args.random_router else "code", **kinds)

    seeds = [args.seed + 7919 * n for n in range(args.seeds)]
    for seed in seeds:
        read("as configured", seed, {}, None)
    for what in args.faults:         # faults: on the last seed's weights
        fault = FAULTS[what]
        read(what, seeds[-1], {k: v for k, v in fault.items()
                               if k != "weights"},
             (lambda key: to_float8(family.init(
                 key, model, routing_code=not args.random_router)))
             if "weights" in fault else None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
