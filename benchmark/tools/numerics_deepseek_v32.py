#!/usr/bin/env python3
"""How far the served DeepSeek-V3.2-Exp is from its float32 reference, how
many of a query's kept positions the two choose alike, and how far a faulty
or lower-precision program would be: the readings ``numerics.logits_rtol``
and ``numerics.selection_common_min`` of
``benchmark/configs/deepseek-v3.2-exp-5l.json`` are set from.

    python3 benchmark/tools/numerics_deepseek_v32.py [--seeds 4]

One process on whatever device JAX finds (the chip, through ``chiprun``); no
cluster.  It builds the configuration's engine at the published size and
compares, as ``LongctxBenchLLMServer.check_numerics`` does (its own
``drive``), a prompt of two chunks whose queries past position 2,048 select
and one of 1,027 positions, each followed by eight token steps, by the
engine's own two programs with the reference's full forward:

* the configuration as it is, over ``--seeds`` seeds: ``logits_rel_err`` of
  both sequences and, on the long one, the share of the positions the
  reference's queries kept that the program's kept too, in the token steps
  (``steps_common_share``, what the replica reports) and, on the first seed,
  in the prompt's chunks (``chunks_common_share``: a second prefill program
  traced with ``select_mask`` handing its rows to the host);
* each of ``FAULTS`` planted in the program on the last seed's weights, which
  the tolerance has to refuse;
* one precision below what the configuration states: every matrix rounded to
  float8's three bits of mantissa in the program, the reference's left alone.

Lines of JSON on stdout, and appended to
``chiprun_out/numerics_deepseek_v32.jsonl``.
"""

import argparse
import contextlib
import gc
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

MATRICES = {"wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "index_wq", "index_wk",
            "index_w", "wgu", "wd", "wte", "lm_head"}
_REAL = {}


# ---- functions that stand in for the program's own while it is traced

def _pages_in_float8(x, pool):
    from benchmark.tools.numerics_xing import round_to_float8
    return _REAL["_padded"](round_to_float8(x), pool)


def _index_key_rotated_whole(cfg, p, h, cq, cos, sin):
    """The indexer's key with its SECOND half rotated too (the queries as
    they should be)."""
    import jax.numpy as jnp
    from ray_tpu.models import llama
    q, w, k = _REAL["_index_project"](cfg, p, h, cq, cos, sin)
    dr = cfg.qk_rope_dim
    second = llama.apply_rope_pairs(k[..., dr:2 * dr], cos, sin)
    return q, w, jnp.concatenate([k[..., :dr], second, k[..., 2 * dr:]],
                                 axis=-1)


def _mask_of_everything(scores, valid, k):
    return valid


def _positions_all(scores, lengths, k):
    return _REAL["select_positions"](scores, lengths, scores.shape[1])


def _mask_one_short(scores, valid, k):
    return _REAL["select_mask"](scores, valid, k - 1)


def _positions_one_short(scores, lengths, k):
    return _REAL["select_positions"](scores, lengths, k - 1)


def _route_bias_in_gates(logits, bias, top_k, scoring, norm, scale,
                         norm_eps=1e-20, groups=(1, 1)):
    import jax
    import jax.numpy as jnp
    _, experts = _REAL["_route"](logits, bias, top_k, scoring, norm, scale,
                                 groups=groups)
    gates = jnp.take_along_axis(jax.nn.sigmoid(logits) + bias, experts,
                                axis=-1)
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + norm_eps)
    return gates * scale, experts


def _route_without_groups(logits, bias, top_k, scoring, norm, scale, **kw):
    return _REAL["_route"](logits, bias, top_k, scoring, norm, scale)


# what is planted: functions of ray_tpu.models.llama, ray_tpu.ops.moe or
# ray_tpu.ops.paged_attention replaced while the programs are traced, or the
# program's weights (the reference keeps its own)
FAULTS = {
    "float8 pages": {"patch": {"pa._padded": _pages_in_float8}},
    "the indexer's key rotated where it should not be": {"patch": {
        "llama._index_project": _index_key_rotated_whole}},
    "the selection left out": {"patch": {
        "pa.select_mask": _mask_of_everything,
        "pa.select_positions": _positions_all}},
    "the selection one short": {"patch": {
        "pa.select_mask": _mask_one_short,
        "pa.select_positions": _positions_one_short}},
    "the bias in the gates": {"patch": {"moe._route": _route_bias_in_gates}},
    "the group limit left out": {"patch": {
        "moe._route": _route_without_groups}},
    "float8 weights": {"weights": True},
}


@contextlib.contextmanager
def planted(fault: dict):
    """The fault's functions in place of the program's own, for as long as
    the programs that should have it are traced."""
    modules = {"llama": importlib.import_module("ray_tpu.models.llama"),
               "pa": importlib.import_module("ray_tpu.ops.paged_attention"),
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
    """Every matrix rounded to float8's three bits of mantissa, embedding and
    head among them; the norms, the indexer's LayerNorm and the router (the
    routing is a code: a rounded router chooses the same experts) as they
    are."""
    from benchmark.tools import numerics_xing
    return numerics_xing.to_float8(params, MATRICES)


@contextlib.contextmanager
def told_masks(store: list, columns: int):
    """While a prefill program is traced inside this, ``select_mask`` also
    hands the first ``columns`` columns of what it returns to ``store``,
    eight positions a byte, a [rows, columns / 8] array a layer a chunk."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    ops = importlib.import_module("ray_tpu.ops.paged_attention")
    real = ops.select_mask

    def telling(scores, valid, k):
        keep = real(scores, valid, k)
        jax.debug.callback(
            lambda rows: store.append(np.asarray(rows)),
            jnp.packbits(keep[:, :columns], axis=1), ordered=True)
        return keep
    ops.select_mask = telling
    try:
        yield
    finally:
        ops.select_mask = real


def chunks_common_share(engine, family, model, tokens, prompt_len, keep):
    """The prompt driven again through a prefill program that tells its
    masks: of the positions the reference's queries past ``index_topk`` kept
    (``keep`` [layers, S, S]), the share the program's kept too."""
    import jax
    import numpy as np
    from ray_tpu.models.serving import serving_model
    from benchmark.replica_longctx import drive
    columns = -(-keep.shape[2] // 8) * 8
    told, real = [], engine._prefill
    with told_masks(told, columns):
        prefill = serving_model(family.ENGINE_MODEL, model).prefill
        telling = jax.jit(
            lambda p, t, n, kp, vp, pt, slot, start: prefill(
                p, model, t, n, kp, vp, pt, slot, start),
            donate_argnums=(3, 4))
        engine._prefill = lambda *a: engine._consuming(telling, *a)
        try:
            drive(engine, engine._params, tokens, prompt_len, steps=0)
        finally:
            engine._prefill = real
    jax.effects_barrier()
    layers, chunk = model.num_layers, engine.config.prefill_chunk
    kept = common = 0
    for n, rows in enumerate(told):
        at, layer = divmod(n, layers)
        mine = np.unpackbits(rows, axis=1)[:, :keep.shape[2]].astype(bool)
        first = max(at * chunk, model.index_topk)    # the queries that select
        last = min((at + 1) * chunk, prompt_len)
        want = keep[layer, first:last]
        kept += int(want.sum())
        common += int((want & mine[first - at * chunk:last - at * chunk])
                      .sum())
    return common / kept


def compared(engine, family, config, model, key, chunks: bool):
    """The replica's check on ``engine``: both sequences' errors, the token
    steps' share on the long one and, with ``chunks``, its chunks'."""
    import jax
    import numpy as np
    from ray_tpu.models.serving import serving_model
    from benchmark.replica_longctx import (STEPS, common_share, drive,
                                           prompt_lengths, told_selections)
    lengths = prompt_lengths(config["engine"],
                             config.get("check_topk", model.index_topk))
    longest = max(lengths) + STEPS
    reference = jax.jit(lambda p, t, first: family.reference_forward(
        p, t, config, rows=(first, STEPS + 1), with_selection=True))
    out = {"logits_rel_err": [], "prompt_lengths": list(lengths)}
    for n, prompt_len in enumerate(lengths):
        tokens = np.asarray(jax.random.randint(
            jax.random.fold_in(key, n), (longest,), 0, model.vocab_size),
            np.int32)
        want, keep = reference(engine._params, tokens,
                               np.int32(prompt_len - 1))
        want, keep = np.asarray(want), np.asarray(keep)
        got = drive(engine, engine._params, tokens, prompt_len)
        out["logits_rel_err"].append(float(
            np.linalg.norm(got - want) / np.linalg.norm(want)))
        if n:
            continue
        told = []
        with told_selections(told):
            step = serving_model(family.ENGINE_MODEL, model).step
            telling = jax.jit(
                lambda p, tok, pos, kp, vp, pt: step(p, model, tok, pos, kp,
                                                     vp, pt),
                donate_argnums=(3, 4))
            drive(engine, engine._params, tokens, prompt_len,
                  decode=lambda *a: engine._consuming(telling, *a))
        jax.effects_barrier()
        out["steps_common_share"], out["selected_positions"] = common_share(
            told, keep, prompt_len, model.num_layers)
        out["selected_of_live"] = float(
            keep[:, prompt_len:].sum() / np.tril(np.ones(
                keep.shape[1:], bool))[prompt_len:].sum() / keep.shape[0])
        if chunks:
            out["chunks_common_share"] = chunks_common_share(
                engine, family, model, tokens, prompt_len, keep)
    return out


def compared_with(family, config, model, params, fault, key, chunks=False):
    """``compared`` on an engine with ``fault`` planted (``{}``: none)."""
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    with planted(fault):
        engine = InferenceEngine(EngineConfig(
            model=family.ENGINE_MODEL, model_config=model,
            **config["engine"]), params=params)
        try:
            return compared(engine, family, config, model, key, chunks)
        finally:
            # a rung still compiling keeps its thread, the thread the
            # engine, the engine its tree: wait, then drop
            for future in (*engine._rung_programs.values(),
                           *engine._decode_programs.values()):
                future.result()
            engine.close()


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default="deepseek-v3.2-exp-5l")
    parser.add_argument("--seeds", type=int, default=4)
    parser.add_argument("--seed", type=int, default=2 ** 31 + 5700)
    parser.add_argument("--faults", nargs="*", default=list(FAULTS))
    parser.add_argument("--index-topk", type=int, default=0,
                        help="the positions a query keeps, in program and "
                        "reference alike (the sequence's length or more: "
                        "nothing is deselected, and what is left of the "
                        "error is not the selection's)")
    args = parser.parse_args()

    import jax
    from benchmark import spec
    from benchmark.replica import device_report, seeded_key
    config = spec.load_json("configs", args.config + ".json")
    if args.index_topk:          # the check's two prompts stay what they are
        config["check_topk"] = config["index_topk"]
        config["index_topk"] = args.index_topk
    family = spec.load_part("families", config["family"])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = os.path.join(ROOT, "chiprun_out", "numerics_deepseek_v32.jsonl")

    def report(**line):
        line = {"device": device_report(), "index_topk": config["index_topk"],
                **line}
        print(json.dumps(line), flush=True)
        with open(log, "a") as f:
            f.write(json.dumps(line) + "\n")

    engine = config["engine"]
    model = family.program_config(
        config, engine["max_prompt_len"] + engine["max_new_tokens"])
    init = jax.jit(lambda key: family.init(key, model))

    def run(what, params, seed, **more):
        found = compared_with(family, config, model, params,
                              FAULTS.get(what, {}), seeded_key(seed + 1),
                              **more)
        gc.collect()                  # the engine, in cycles
        report(what=what, seed=seed, **found)

    seed = args.seed
    for n in range(args.seeds):
        seed = args.seed + 7919 * n
        params = init(seeded_key(seed))
        run("as configured", params, seed,
            chunks=n == 0 and not args.index_topk)
        if n == args.seeds - 1:              # faults: the last seed's weights
            for what in args.faults:
                if not FAULTS[what].get("weights"):
                    run(what, params, seed)
        del params
    # the matrices a precision below: the program on rounded weights, the
    # reference on its own.  Two trees and the pools do not fit, so the
    # engine reads the rounded tree and the reference is handed the honest
    # one through the engine's place for it once the rounded is compared
    for what in args.faults:
        if FAULTS[what].get("weights"):
            gc.collect()
            report(what=what, seed=seed, **float8_compared(
                family, config, model, seeded_key(seed)))
    return 0


def float8_compared(family, config, model, key):
    """The program on the weights of ``key`` rounded to float8's precision
    against the reference on the honest ones: the program's logits first,
    then, with its engine and its tree gone (nobody else holds them), the
    reference's."""
    import jax
    import numpy as np
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    from benchmark.replica_longctx import STEPS, drive, prompt_lengths
    lengths = prompt_lengths(config["engine"],
                             config.get("check_topk", model.index_topk))
    longest = max(lengths) + STEPS
    seqs = [np.asarray(jax.random.randint(
        jax.random.fold_in(jax.random.fold_in(key, 1), n), (longest,), 0,
        model.vocab_size), np.int32) for n in range(len(lengths))]
    engine = InferenceEngine(EngineConfig(
        model=family.ENGINE_MODEL, model_config=model, **config["engine"]),
        params=jax.jit(lambda k: to_float8(family.init(k, model)))(key))
    try:
        got = [drive(engine, engine._params, tokens, n)
               for tokens, n in zip(seqs, lengths)]
    finally:
        for future in (*engine._rung_programs.values(),
                       *engine._decode_programs.values()):
            future.result()
        engine.close()
    del engine
    gc.collect()
    jax.clear_caches()                # the rounded tree's programs with it
    params = jax.jit(lambda k: family.init(k, model))(key)
    reference = jax.jit(lambda p, t, first: family.reference_forward(
        p, t, config, rows=(first, STEPS + 1)))
    errs = []
    for tokens, n, mine in zip(seqs, lengths, got):
        want = np.asarray(reference(params, tokens, np.int32(n - 1)))
        errs.append(float(np.linalg.norm(mine - want)
                          / np.linalg.norm(want)))
    return {"logits_rel_err": errs, "prompt_lengths": list(lengths)}


if __name__ == "__main__":
    sys.exit(main())
