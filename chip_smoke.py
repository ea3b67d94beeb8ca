#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at
GPT-2-small's published width (12 layers, 768 wide, 12 heads, vocabulary
50257, bf16 compute) with random weights from a fixed seed, in a cluster
started by ``ray_tpu.init()``:

  numerics  in the worker that holds the chip, before it trains: the flash
            kernels against the dense reference, and the paged prefill and
            decode steps against ``gpt_forward`` on the same tokens;
  train     ``JaxTrainer`` with one TPU worker: mesh, ``shard_params``,
            ``make_train_step`` and five steps on one fixed batch;
  serve     ``serve.run(LLMServer...)`` behind ``serve.start_http()``: one
            request alone, then eight at once, over SSE.

With ``--chips 4`` it runs, and runs only, what exists only across chips:
the same train step over ``fsdp=2 x tp=2`` against a one-device mesh.

This process is the driver.  It never initialises a JAX backend: a chip
belongs to one process, and that is the worker the raylet leased it to.
Platform, device kind and device count are what those workers report; any
answer but ``tpu`` fails the run.  Every phase has a deadline.  The last
line of stdout, printed only when every phase passed, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import signal
import sys
import threading
import time
import urllib.request

SEED = 0
BF16_EPS = 2.0 ** -8
# Relative Frobenius error allowed between two bf16 computations of the
# same quantity (five roundings' worth); the CPU gives ~1 eps at small size.
BF16_RTOL = 5 * BF16_EPS

# The sizes of a real run.  A rehearsal on the CPU passes smaller ones to
# the same functions (tests/test_chip_smoke.py); the command line cannot.
FULL = dict(
    platform="tpu",
    model=dict(vocab_size=50257, num_layers=12, num_heads=12, embed_dim=768),
    batch=32, seq=1024, ce_block=256, train_steps=5, mesh_steps=3,
    flash_shape=(32, 1024, 12, 64),
    page_size=16, max_prompt_len=512, max_new_tokens=128, max_batch=16,
    decode_steps=8,
    train_deadline_s=700, serve_deadline_s=400,
)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


@contextlib.contextmanager
def deadline(seconds: int, phase: str):
    """A phase that waits for a TPU no node advertises, or on a process
    that never lets the chip go, would wait for ever."""
    def on_alarm(signum, frame):
        raise SmokeFailure(f"{phase}: not done within {seconds}s")
    previous = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, previous)


def say(phase: str, device: dict, **numbers) -> None:
    print(json.dumps({"phase": phase, **device, **numbers}), flush=True)


# ------------------------------------------------- in the chip-holding worker
#
# These run in the JaxTrainer worker, shipped there by value: nothing here
# may be imported from this file by name.

def _device_report(sizes):
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != sizes["platform"]:
        raise RuntimeError(
            f"the worker that leased the TPU found {device}, "
            f"not {sizes['platform']!r}")
    return device


def _gpt_config(sizes, **overrides):
    from ray_tpu.models.gpt import GPTConfig
    return GPTConfig(**{**sizes["model"], "max_seq_len": sizes["seq"],
                        "attention": "flash", "remat": True,
                        "remat_policy": "dots",
                        "ce_block": sizes["ce_block"], **overrides})


def _memory_stat(devices, name: str) -> list:
    # the CPU backend of a rehearsal reports none
    return [(d.memory_stats() or {}).get(name, 0) for d in devices]


def _rel_err(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _flash_numerics(sizes) -> dict:
    """Flash forward and gradients against the dense reference, and the
    kernel itself in the lowered program."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.flash_attention import _dense_reference, flash_attention

    keys = jax.random.split(jax.random.PRNGKey(SEED), 4)
    q, k, v, g = (jax.random.normal(key, sizes["flash_shape"], jnp.bfloat16)
                  for key in keys)
    flash = jax.jit(lambda q, k, v: flash_attention(q, k, v, True))
    text = flash.lower(q, k, v).as_text()
    on_tpu = sizes["platform"] == "tpu"
    if on_tpu and "tpu_custom_call" not in text:
        raise RuntimeError("no tpu_custom_call in the lowered flash call")
    t0 = time.perf_counter()
    out, vjp = jax.vjp(flash, q, k, v)
    grads = jax.block_until_ready(vjp(g))
    first_s = time.perf_counter() - t0
    ref, ref_vjp = jax.vjp(
        lambda q, k, v: _dense_reference(q, k, v, True, None), q, k, v)
    errs = {"o": _rel_err(out, ref)}
    for name, a, b in zip(("dq", "dk", "dv"), grads, ref_vjp(g)):
        errs[name] = _rel_err(a, b)
    return {"flash_rel_err": errs, "flash_first_call_s": round(first_s, 2),
            "tpu_custom_call": "tpu_custom_call" in text}


def _paged_numerics(sizes) -> dict:
    """Paged prefill plus decode steps, teacher-forced, against the logits
    gpt_forward gives for the same tokens."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models.gpt import (gpt_decode_step, gpt_forward, gpt_init,
                                    gpt_prefill, init_paged_cache)

    page, max_prompt = sizes["page_size"], sizes["max_prompt_len"]
    steps, max_batch = sizes["decode_steps"], sizes["max_batch"]
    cfg = dataclasses.replace(
        _gpt_config(sizes), attention="dense",
        max_seq_len=max_prompt + sizes["max_new_tokens"])
    maxp = cfg.max_seq_len // page
    params = gpt_init(jax.random.PRNGKey(SEED), cfg)
    prompt_len = max_prompt - 3 * page - 5      # ragged last page, padding
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(SEED + 1), (prompt_len + steps,), 0,
        cfg.vocab_size), np.int32)
    want = jax.jit(lambda p, t: gpt_forward(p, t, cfg))(
        params, tokens[None])[0]

    kp, vp = init_paged_cache(cfg, max_batch * maxp + 1, page)
    table = np.zeros((max_batch, maxp), np.int32)
    table[0] = np.arange(1, maxp + 1)
    padded = np.zeros((1, max_prompt), np.int32)
    padded[0, :prompt_len] = tokens[:prompt_len]
    prefill = jax.jit(lambda p, *a: gpt_prefill(p, cfg, *a))
    decode = jax.jit(lambda p, *a: gpt_decode_step(p, cfg, *a))
    logits, kp, vp = prefill(params, padded, np.int32(prompt_len), kp, vp,
                             table[:1])
    errs = [_rel_err(logits[0], want[prompt_len - 1])]
    agree = [int(jnp.argmax(logits[0])) == int(
        jnp.argmax(want[prompt_len - 1]))]
    tok = np.zeros((max_batch,), np.int32)
    pos = np.zeros((max_batch,), np.int32)
    for i in range(steps):
        tok[0], pos[0] = tokens[prompt_len + i], prompt_len + i
        logits, kp, vp = decode(params, tok, pos, kp, vp, table)
        errs.append(_rel_err(logits[0], want[prompt_len + i]))
        agree.append(int(jnp.argmax(logits[0])) == int(
            jnp.argmax(want[prompt_len + i])))
    return {"paged_rel_err_max": max(errs), "paged_positions": len(errs),
            "paged_argmax_agree": sum(agree)}


def _train(sizes, spec, devices, steps, on_step=lambda *a: None) -> dict:
    """Build the mesh and the step as a user's loop does, and take
    ``steps`` steps on one fixed batch, calling ``on_step(i, loss, seconds,
    compile_seconds)`` after each."""
    import jax
    import jax.numpy as jnp
    import optax
    from ray_tpu.models.gpt import gpt_init, gpt_param_axes, make_train_step
    from ray_tpu.parallel import LogicalAxisRules
    from ray_tpu.parallel.sharding import logical_sharding, shard_params

    cfg = _gpt_config(sizes)
    mesh = spec.build(devices=devices)
    rules = LogicalAxisRules.for_transformer(spec)
    with jax.sharding.set_mesh(mesh):
        params = gpt_init(jax.random.PRNGKey(SEED), cfg)
        params = shard_params(params, mesh, rules, gpt_param_axes(cfg))
        tx = optax.adamw(3e-4, b2=0.95)
        opt_state = tx.init(params)
        batch = {"tokens": jax.device_put(
            jax.random.randint(jax.random.PRNGKey(SEED + 1),
                               (sizes["batch"], sizes["seq"] + 1), 0,
                               cfg.vocab_size, jnp.int32),
            logical_sharding(mesh, rules, ("batch", None)))}
        peak_after_init = _memory_stat(mesh.devices.flat,
                                       "peak_bytes_in_use")
        lowered = make_train_step(cfg, tx, rules).lower(
            params, opt_state, batch)
        t0 = time.perf_counter()
        step = lowered.compile()
        compile_s = time.perf_counter() - t0
        losses, step_s = [], []
        for i in range(steps):
            t0 = time.perf_counter()
            params, opt_state, metrics = step(params, opt_state, batch)
            losses.append(float(jax.block_until_ready(metrics["loss"])))
            step_s.append(time.perf_counter() - t0)
            on_step(i, losses[-1], step_s[-1], compile_s)
        # parameters and optimizer state are both alive here
        in_use = _memory_stat(mesh.devices.flat, "bytes_in_use")
    return {"losses": losses, "step_s": step_s, "compile_s": compile_s,
            "params": params, "peak_bytes_after_init": peak_after_init,
            "bytes_in_use": in_use}


def one_chip_loop(sizes):
    """train_loop_per_worker of the one-chip run."""
    from ray_tpu.air import session
    from ray_tpu.parallel import MeshSpec

    device = _device_report(sizes)
    report = {"device": device, "pid": os.getpid()}
    session.report({"phase": "numerics", **report,
                    **_flash_numerics(sizes), **_paged_numerics(sizes)})
    _train(sizes, MeshSpec(), None, sizes["train_steps"],
           lambda step, loss, seconds, compile_s: session.report({
               "phase": "train", **report, "step": step, "loss": loss,
               "step_s": seconds, "compile_s": compile_s}))


def four_chip_loop(sizes):
    """train_loop_per_worker of ``--chips 4``: the step over fsdp=2 x tp=2,
    how the parameters and the memory are spread, then the same steps on
    one device of the four."""
    import jax
    from ray_tpu.air import session
    from ray_tpu.models.gpt import gpt_param_axes
    from ray_tpu.parallel import LogicalAxisRules, MeshSpec

    device = _device_report(sizes)
    report = {"device": device, "pid": os.getpid()}
    spec = MeshSpec(fsdp=2, tp=2)
    if device["count"] != spec.num_devices:
        raise RuntimeError(f"needs {spec.num_devices} chips, found {device}")

    mesh = _train(sizes, spec, None, sizes["mesh_steps"])
    params = mesh.pop("params")
    rules = LogicalAxisRules.for_transformer(spec)
    sizes_of = spec.axis_sizes
    sharded = unspread = 0
    leaves = zip(jax.tree.leaves(params), jax.tree.leaves(
        gpt_param_axes(_gpt_config(sizes)),
        is_leaf=lambda x: isinstance(x, tuple)))
    for leaf, axes in leaves:
        used = [a for entry in rules.spec_for(axes) if entry
                for a in ((entry,) if isinstance(entry, str) else entry)]
        if any(sizes_of[a] > 1 for a in used):
            sharded += 1
            shards = leaf.addressable_shards
            if len({s.device for s in shards}) != spec.num_devices or \
                    any(s.data.size >= leaf.size for s in shards):
                unspread += 1
    session.report({"phase": "mesh", **report, "mesh": "fsdp=2,tp=2", **mesh,
                    "sharded_leaves": sharded, "unspread_leaves": unspread})
    del params

    one = _train(sizes, MeshSpec(), jax.devices()[:1], sizes["mesh_steps"])
    del one["params"]
    session.report({"phase": "one_device", **report, **one})


# ------------------------------------------------------------- in the driver

def fit(loop, sizes, chips: int) -> list:
    """Run ``loop`` in a JaxTrainer gang of one worker that leases ``chips``
    chips; returns everything it reported."""
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train.jax import JaxTrainer

    trainer = JaxTrainer(
        loop, train_loop_config=sizes,
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     chips_per_worker=chips))
    return trainer.fit().metrics_history


def train_phase(sizes) -> dict:
    with deadline(sizes["train_deadline_s"], "train"):
        history = fit(one_chip_loop, sizes, 1)
    numerics = history[0]
    device = numerics["device"]
    errs = numerics["flash_rel_err"]
    say("numerics", device,
        flash_rel_err={k: round(v, 5) for k, v in errs.items()},
        flash_first_call_s=numerics["flash_first_call_s"],
        tpu_custom_call=numerics["tpu_custom_call"],
        paged_rel_err_max=round(numerics["paged_rel_err_max"], 5),
        paged_argmax_agree=f"{numerics['paged_argmax_agree']}/"
                           f"{numerics['paged_positions']}",
        rtol=BF16_RTOL)
    check(max(errs.values()) <= BF16_RTOL,
          f"flash differs from the dense reference: {errs}")
    check(numerics["paged_rel_err_max"] <= BF16_RTOL,
          f"paged logits differ from gpt_forward: {numerics}")
    check(numerics["paged_positions"] == sizes["decode_steps"] + 1,
          "a paged position was not compared")

    steps = [m for m in history if m["phase"] == "train"]
    losses = [m["loss"] for m in steps]
    say("train", device, compile_s=round(steps[0]["compile_s"], 2),
        run_s=round(sum(m["step_s"] for m in steps), 3),
        step_s=[round(m["step_s"], 4) for m in steps],
        losses=[round(x, 4) for x in losses],
        batch=sizes["batch"], seq=sizes["seq"])
    check(len(losses) == sizes["train_steps"], f"{len(losses)} steps ran")
    check(all(x == x and abs(x) != float("inf") for x in losses),
          f"a loss is not finite: {losses}")
    check(losses[-1] < losses[0], f"the loss did not fall: {losses}")
    return {"device": device, "pid": numerics["pid"]}


def mesh_phase(sizes) -> dict:
    with deadline(sizes["train_deadline_s"], "mesh"):
        mesh, one = fit(four_chip_loop, sizes, 4)
    device = mesh["device"]
    diffs = [abs(a - b) / abs(b)
             for a, b in zip(mesh["losses"], one["losses"])]
    in_use = mesh["bytes_in_use"]
    mean = sum(in_use) / len(in_use) or 1
    for m in (mesh, one):
        say(m["phase"], device, mesh=m.get("mesh", "one device"),
            compile_s=round(m["compile_s"], 2),
            step_s=[round(x, 4) for x in m["step_s"]],
            losses=[round(x, 4) for x in m["losses"]])
    say("mesh_check", device,
        loss_rel_diff=[round(d, 6) for d in diffs], rtol=BF16_EPS,
        sharded_leaves=mesh["sharded_leaves"],
        unspread_leaves=mesh["unspread_leaves"],
        bytes_in_use=in_use,
        max_over_mean=round(max(in_use) / mean, 3),
        peak_bytes_after_init=mesh["peak_bytes_after_init"])
    check(len(diffs) == sizes["mesh_steps"], "a step is missing")
    # one bf16 rounding of the loss itself
    check(max(diffs) <= BF16_EPS,
          f"four chips and one disagree: {mesh['losses']} {one['losses']}")
    check(mesh["sharded_leaves"] > 0 and mesh["unspread_leaves"] == 0,
          f"{mesh['unspread_leaves']} of {mesh['sharded_leaves']} sharded "
          "parameters are not spread over four devices")
    check(max(in_use) <= 1.5 * mean,
          f"device memory is not spread: {in_use}")
    return {"device": device, "pid": mesh["pid"]}


def sse_tokens(url: str, prompt: list, max_new: int) -> list:
    """One streamed generation over the HTTP ingress."""
    body = json.dumps({"tokens": prompt, "max_new_tokens": max_new,
                       "stream": True}).encode()
    request = urllib.request.Request(
        url, data=body, headers={"Accept": "text/event-stream",
                                 "Content-Type": "application/json"})
    tokens, event = [], None
    with urllib.request.urlopen(request, timeout=300) as response:
        check("text/event-stream" in response.headers.get(
            "Content-Type", ""), "the answer is not an event stream")
        for raw in response:
            line = raw.decode().rstrip("\r\n")
            if line.startswith("event: "):
                event = line[len("event: "):]
            elif line.startswith("data: "):
                if event == "end":
                    return tokens
                check(event is None, f"stream sent {event}: {line}")
                tokens.append(json.loads(line[len("data: "):]))
            elif not line:
                event = None
    raise SmokeFailure("stream closed before its end event")


def chip_holders() -> set:
    """Pids on this host with a chip's device node open."""
    holders = set()
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            for fd in os.listdir(f"/proc/{pid}/fd"):
                target = os.readlink(f"/proc/{pid}/fd/{fd}")
                if re.fullmatch(r"/dev/(accel\d+|vfio/\d+)", target):
                    holders.add(int(pid))
        except OSError:
            continue    # gone, or not ours to read
    return holders


def replica_pid(name: str) -> int:
    from ray_tpu.util import state
    actor_ids = {a["actor_id"] for a in state.list_actors()
                 if (a["name"] or "").startswith(f"_serve:{name}:")
                 and a["state"] == "ALIVE"}
    check(len(actor_ids) == 1, f"{len(actor_ids)} live replicas of {name}")
    while True:     # the raylet's worker table arrives every few seconds
        pids = [w["pid"] for w in state.list_workers()
                if w["actor_id"] in actor_ids]
        if pids:
            return pids[0]
        time.sleep(0.5)


def serve_phase(sizes, previous_holder: int) -> dict:
    import random
    from ray_tpu import serve
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.serve.engine import EngineConfig, LLMServer

    max_prompt, max_new = sizes["max_prompt_len"], sizes["max_new_tokens"]
    page, max_batch = sizes["page_size"], sizes["max_batch"]
    model = GPTConfig(**sizes["model"], max_seq_len=max_prompt + max_new,
                      attention="dense")
    engine = EngineConfig(
        model="gpt", model_config=model, page_size=page,
        num_pages=max_batch * ((max_prompt + max_new) // page) + 1,
        max_batch=max_batch, max_prompt_len=max_prompt,
        max_new_tokens=max_new)
    rng = random.Random(SEED)
    vocab = model.vocab_size
    # eight prompts from one page to the longest allowed, each asking for
    # its own number of tokens, so sequences retire mid-batch
    lengths = [page, max_prompt // 8 + 3, max_prompt // 4, max_prompt // 3,
               max_prompt // 2 + 1, max_prompt - page - 1, max_prompt - 1,
               max_prompt]
    asks = [max_new, max_new // 2, max_new // 8, max_new, max_new // 4,
            max_new - 1, max_new // 2 + 1, max_new]
    prompts = [[rng.randrange(vocab) for _ in range(n)] for n in lengths]
    lone = 3

    import ray_tpu
    with deadline(sizes["serve_deadline_s"], "serve"):
        t0 = time.perf_counter()
        handle = serve.run(serve.deployment(
            LLMServer, name="llm", max_concurrent_queries=32,
            ray_actor_options={"resources": {"TPU": 1}}).bind(engine))
        url = serve.start_http() + "/llm"
        # serve.run returns once the replica is registered; its first
        # answer says that its process has started and built the engine.
        ray_tpu.get(handle.method("stats").remote())
        start_s = time.perf_counter() - t0
        check(not os.path.exists(f"/proc/{previous_holder}"),
              f"the train worker (pid {previous_holder}) outlived the "
              "start of the replica")

        t0 = time.perf_counter()
        alone = sse_tokens(url, prompts[lone], asks[lone])
        alone_s = time.perf_counter() - t0
        together = [None] * len(prompts)

        def ask(i):
            try:
                together[i] = sse_tokens(url, prompts[i], asks[i])
            except Exception as e:   # noqa: BLE001 - raised below
                together[i] = e
        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(prompts))]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        together_s = time.perf_counter() - t0

        stats = ray_tpu.get(handle.method("stats").remote())
        device = stats["device"]
        replica, holders = replica_pid("llm"), chip_holders()
        serve.shutdown()

    say("serve", device, replica_start_s=round(start_s, 2),
        first_call_s={k: round(v, 2)
                      for k, v in stats["first_call_s"].items()},
        alone_s=round(alone_s, 3), together_s=round(together_s, 3),
        tokens=[len(t) if isinstance(t, list) else repr(t)
                for t in together],
        engine_steps=stats["steps"], replica_pid=replica,
        chip_holders=sorted(holders))
    check(device["platform"] == sizes["platform"],
          f"the replica found {device}")
    for i, got in enumerate([*together, alone]):
        if isinstance(got, Exception):
            raise SmokeFailure(f"request {i} failed: {got!r}")
        want = asks[i] if i < len(asks) else asks[lone]
        check(len(got) == want, f"request {i}: {len(got)} of {want} tokens")
        check(all(isinstance(t, int) and 0 <= t < vocab for t in got),
              f"request {i}: ids outside the vocabulary")
    check(alone == together[lone],
          "the same prompt alone and among eight gave different ids")
    if sizes["platform"] == "tpu":
        check(holders == {replica},
              f"processes with the chip open: {sorted(holders)}; "
              f"the replica is {replica}")
    return {"device": device}


def run(sizes, chips: int) -> dict:
    """All phases for ``chips`` chips in a fresh cluster; returns the
    device the chip-holding workers agree on."""
    import ray_tpu
    from ray_tpu._private import jaxutil

    ray_tpu.init()
    try:
        advertised = ray_tpu.cluster_resources().get("TPU", 0)
        check(advertised >= chips,
              f"needs {chips} TPU chip(s), the cluster advertises "
              f"{advertised:g}")
        if chips == 4:
            reports = [mesh_phase(sizes)]
        else:
            reports = [train_phase(sizes)]
            reports.append(serve_phase(sizes, reports[0]["pid"]))
    finally:
        ray_tpu.shutdown()
    devices = [r["device"] for r in reports]
    check(all(d == devices[0] for d in devices),
          f"the phases saw different devices: {devices}")
    check(devices[0]["platform"] == sizes["platform"]
          and devices[0]["count"] == chips, f"ran on {devices[0]}")
    check(not jaxutil.initialized_backends(),
          "the driver initialised a JAX backend")
    return devices[0]


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args()
    try:
        device = run(FULL, args.chips)
    except Exception:   # noqa: BLE001 - any failure is the run's failure
        import traceback
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
