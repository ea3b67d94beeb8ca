"""Whether two trees lower to the same programs: one ``sha256  configuration
program@rung`` line for every program the engine compiles for the benchmark's
serving configurations, for the program that makes each one's weights
(``init``), for the train step of each training configuration on its own
mesh (``gpt2-medium`` on one chip, ``gpt2-large`` over fsdp=2 x tp=2), and
for a tiny llama train step.

    JAX_PLATFORMS=cpu python scripts/lowered_texts.py [--tree DIR]
        [--only mistral xing ...] [--texts OUT_DIR]

A change that is said to leave the served programs alone is shown to by
running this at the parent (``--tree`` a ``git archive`` of it) and at the
change and comparing the lines (``diff``): an equal hash is an equal
``jax.jit(...).lower(...).as_text()``, operation for operation; where a line
differs, ``--texts`` keeps both texts to ``diff``.  Needs no chip and runs
nothing: the model comes from ``benchmark/configs/*.json`` through the
family's ``program_config``, the stored tree's and the pools' shapes from
``jax.eval_shape``, every program is lowered with the pools donated for a
DESCRIBED ``v5e:2x2`` device, and ``jax.default_backend()`` answers "tpu" to
this repo's code for the length of the run, so that what asks it
(``ops/kernel_source.py::kernels_compiled``, and through it
``ops/attention.py::_flash_profitable``, the kernels' ``_resolve`` and
``ops/latent_prefill.py::_compiled``) decides
as on the chip: Mistral's prefill rungs of 1024 and 2048 hold the flash
forward kernel, the expert models' programs the grouped matmul, both as
Mosaic modules (which name no source file: ``ops/kernel_source.py``).  Reads,
never writes, under ``benchmark/``.  ~2 min a tree.

The programs are the engine's own two (``serve/engine/engine.py``'s
``_prefill`` at every rung of ``prefill_rungs`` and ``_decode_next``, the
model's step and what feeds the next one, at every width of
``decode_rungs``), rebuilt here from ``ray_tpu.models.serving_model``'s
record, as the engine builds them (the prefill told its slot, the pools made
for the engine's slots; an argument a program does not use is no part of its
lowered text); a tree from before PR 47 has no record and is read through the
functions the record names, one from before PR 48 takes neither.
"""

from __future__ import annotations

import argparse
import glob
import hashlib
import inspect
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs under /tmp

POOLS = (3, 4)


def _programs(model: str, cfg):
    """(prefill, decode) as the engine's loop has them, by those names (the
    lowered module's name is the function's)."""
    import jax.numpy as jnp
    try:
        from ray_tpu.models import serving_model
    except ImportError:              # a tree from before PR 47
        from ray_tpu.models import llama
        prefill_fn = llama.llama_prefill
        step_fn = llama.llama_block_step if cfg.block_length \
            else llama.llama_decode_step

        def feed(cfg, logits, token, pos):
            if cfg.block_length:
                return None, llama.block_unmask(cfg, logits, token, pos)
            return logits, jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        served = serving_model(model, cfg)
        prefill_fn, step_fn, feed = served.prefill, served.step, served.feed

    # (a tree from before PR 48 tells no prefill its slot)
    takes_slot = "slot" in inspect.signature(prefill_fn).parameters

    def _prefill(params, tokens, length, kp, vp, pt, slot=0):
        return prefill_fn(params, cfg, tokens, length, kp, vp, pt,
                          *((slot,) if takes_slot else ()))

    def _decode(params, token, pos, kp, vp, pt):
        logits, *rest = step_fn(params, cfg, token, pos, kp, vp, pt)
        logits, nxt = feed(cfg, logits, token, pos)
        return (logits, *rest, nxt)
    return _prefill, _decode


def _serving(name: str, device):
    """(label, lowered text) of every engine program of one configuration."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from benchmark import spec
    from ray_tpu.models.llama import (llama_init_paged_cache,
                                      llama_serving_params)
    from ray_tpu.serve.engine.engine import decode_rungs, prefill_rungs

    config = spec.load_json("configs", name)
    family = spec.load_part("families", config["family"])
    eng = config["engine"]
    page, batch = eng["page_size"], eng["max_batch"]
    positions = eng["max_prompt_len"] + eng["max_new_tokens"]
    cfg = family.program_config(config, positions)
    maxp = -(-positions // page)
    one = SingleDeviceSharding(device)

    def on(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = on(jax.eval_shape(lambda: llama_serving_params(
        family.init(jax.random.PRNGKey(0), cfg), cfg)))
    # a model with a row a decode slot sizes its pools by the slots too
    slots = (batch,) if "slots" in inspect.signature(
        llama_init_paged_cache).parameters else ()
    kp, vp = on(jax.eval_shape(lambda: llama_init_paged_cache(
        cfg, eng["num_pages"], page, eng.get("dtype"), *slots)))
    # the replica's first program: the family's weights from the seed (a
    # text that moves costs every cell of the family one cold compile)
    yield "init", jax.jit(lambda key: family.init(key, cfg)).lower(
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)).as_text()
    prefill, decode = _programs(family.ENGINE_MODEL, cfg)
    for rung in prefill_rungs(eng["max_prompt_len"], page):
        yield f"prefill@{rung}", jax.jit(prefill, donate_argnums=POOLS).lower(
            params, arg((1, rung)), arg(()), kp, vp, arg((1, maxp)),
            arg(())).as_text()
    if cfg.block_length:             # a block's state and the slots' ends
        rows = (batch, cfg.block_length)
        token = (arg(rows), arg(rows, jnp.bool_), arg((batch,)),
                 arg((batch,)))
    else:
        token = arg((batch,))
    for width in decode_rungs(maxp):
        yield f"decode@{width}", jax.jit(decode, donate_argnums=POOLS).lower(
            params, token, arg((batch,)), kp, vp,
            arg((batch, width))).as_text()


def _trained(name: str, devices):
    """(label, lowered text) of a training configuration's step as
    ``benchmark/generators/train_steps.py`` builds it: the family's
    ``train_config`` at the published length, the configuration's mesh over
    the described chips, parameters and moments placed by the rule table."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec
    from benchmark import spec
    from ray_tpu.parallel import LogicalAxisRules, MeshSpec
    from ray_tpu.parallel.sharding import logical_sharding

    config = spec.load_json("configs", name)
    family = spec.load_part("families", config["family"])
    seq = config["n_positions"]
    cfg = family.train_config(config, seq)
    mesh_spec = MeshSpec(**config["mesh"])
    mesh = mesh_spec.build(devices=devices[:mesh_spec.num_devices])
    rules = LogicalAxisRules.for_transformer(mesh_spec)

    def placed(tree, shardings):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), tree, shardings)

    shardings = jax.tree.map(
        lambda axes: logical_sharding(mesh, rules, axes),
        family.param_axes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    params = placed(jax.eval_shape(
        lambda: family.init(jax.random.PRNGKey(0), cfg)), shardings)
    tx = optax.adamw(3e-4, b2=0.95)
    opt = optax.tree_map_params(
        tx, lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        jax.eval_shape(tx.init, params), shardings,
        transform_non_params=lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, PartitionSpec())))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (config["train"]["batch"], seq + 1), jnp.int32,
        sharding=logical_sharding(mesh, rules, ("batch", None)))}
    with jax.sharding.set_mesh(mesh):
        step = family.make_train_step(cfg, tx, rules)
        label = "x".join(f"{axis}{n}" for axis, n in
                         mesh_spec.axis_sizes.items() if n > 1) or "1chip"
        yield f"train_step@{label}", step.lower(params, opt, batch).as_text()


def _train(device):
    """The tiny llama's train step, its attention dense and by the flash
    kernels (the two branches of the training trunk's attention)."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.models.llama import (LlamaConfig, llama_init,
                                      make_train_step)

    one = SingleDeviceSharding(device)
    tx = optax.adamw(3e-4)
    for attention in ("dense", "flash"):
        cfg = dataclasses.replace(LlamaConfig.tiny(), attention=attention)
        params = jax.eval_shape(
            lambda: llama_init(jax.random.PRNGKey(0), cfg))
        args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            (params, jax.eval_shape(tx.init, params),
             {"tokens": jax.ShapeDtypeStruct((2, 129), jnp.int32)}))
        step = make_train_step(cfg, tx)
        yield f"train_step@{attention}", step.lower(*args).as_text()


def main(argv=None) -> int:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tree", default=here,
                        help="the checkout whose ray_tpu/ and benchmark/ "
                             "are read (default: this one)")
    parser.add_argument("--only", nargs="*", default=None,
                        help="configurations by a part of their file's "
                             "name, and 'train'")
    parser.add_argument("--texts", default=None,
                        help="a directory to keep every lowered text in")
    args = parser.parse_args(argv)
    tree = os.path.abspath(args.tree)
    sys.path.insert(0, tree)

    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    jax.default_backend = lambda: "tpu"   # what this repo's code is told
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    device = devices[0]

    def wanted(name):
        return args.only is None or any(part in name for part in args.only)

    def lines():
        for path in sorted(glob.glob(
                os.path.join(tree, "benchmark", "configs", "*.json"))):
            name = os.path.basename(path)
            with open(path) as f:
                config = json.load(f)
            if not wanted(name):
                continue
            for label, text in (_serving(name, device) if "engine" in config
                                else _trained(name, devices)):
                yield name[:-len(".json")], label, text
        if wanted("train"):
            for label, text in _train(device):
                yield "llama-tiny", label, text

    if args.texts:
        os.makedirs(args.texts, exist_ok=True)
    for name, label, text in lines():
        print(hashlib.sha256(text.encode()).hexdigest(), name, label,
              flush=True)
        if args.texts:
            with open(os.path.join(args.texts, f"{name}.{label}.txt"),
                      "w") as f:
                f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
