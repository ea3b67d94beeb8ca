"""Training as a user's loop runs it: ``JaxTrainer`` with one worker that
leases the cell's chips, a mesh from the configuration, ``shard_params``,
``make_train_step``, and a batch from a seeded host generator handed to the
step each iteration, the loss fetched every few steps.
"""

import math
import os
import shutil
import time

# The program's loss on two sequences (bf16 compute, flash kernels, blocked
# head) against the float32 reference's.  It is a mean over 2 x 1024
# positions, so bf16 roundings average out: the chip gave 2e-5 and 5e-6
# (PR 23), and ten times that is allowed.  With random weights the loss sits
# near ln(vocabulary) whatever the model does, so this check is weaker than
# the serving one: PERF.md lists a comparison of gradients as an open question.
LOSS_RTOL = 2e-4


def loop(job: dict) -> None:
    """train_loop_per_worker; runs in the worker that leased the chips."""
    import jax
    import numpy as np
    import optax
    from ray_tpu.air import session
    from ray_tpu.parallel import LogicalAxisRules, MeshSpec
    from ray_tpu.parallel.sharding import logical_sharding, shard_params

    from benchmark import replica, spec, trace_reduce

    started = time.perf_counter()
    phases = {}

    def mark(name):
        phases[name] = time.perf_counter() - started

    config, traffic = job["config"], job["traffic"]
    family = spec.load_part("families", config["family"])
    seq, batch_size = traffic["seq_len"], config["train"]["batch"]
    cfg = family.train_config(config, seq)
    mesh_spec = MeshSpec(**config["mesh"])
    device = replica.device_report()
    if device["count"] < mesh_spec.num_devices:
        raise RuntimeError(f"the mesh needs {mesh_spec.num_devices} "
                           f"devices, the worker found {device}")
    mesh = mesh_spec.build(devices=jax.devices()[:mesh_spec.num_devices])
    rules = LogicalAxisRules.for_transformer(mesh_spec)
    batch_sharding = logical_sharding(mesh, rules, ("batch", None))
    rng = np.random.default_rng(job["seed"])
    mark("devices_s")

    def next_batch(rows=batch_size):
        return {"tokens": jax.device_put(
            rng.integers(0, cfg.vocab_size, (rows, seq + 1), np.int32),
            batch_sharding)}

    with jax.sharding.set_mesh(mesh):
        params = jax.jit(lambda key: family.init(key, cfg))(
            replica.seeded_key(job["seed"]))
        params = shard_params(params, mesh, rules, family.param_axes(cfg))
        tx = optax.adamw(3e-4, b2=0.95)
        opt_state = tx.init(params)
        step = family.make_train_step(cfg, tx, rules)
        jax.block_until_ready(opt_state)
        mark("weights_s")

        # two sequences, or one to each shard of the batch axis
        check = next_batch(max(2, mesh_spec.batch_shard_size))
        got = float(jax.jit(lambda p, b: family.loss(p, b, cfg, rules))(
            params, check))
        want = float(jax.jit(lambda p, t: family.reference_loss(
            p, t, config))(params, check["tokens"]))
        mark("loss_check_s")
        # the one shape the window uses
        params, opt_state, metrics = step(params, opt_state, next_batch())
        losses = [float(metrics["loss"])]
        mark("first_step_s")

        window_start_epoch = time.time()
        start = time.perf_counter()
        steps = 0
        while time.perf_counter() - start < job["seconds"]:
            params, opt_state, metrics = step(params, opt_state,
                                              next_batch())
            steps += 1
            if steps % traffic["fetch_loss_every"] == 0:
                losses.append(float(metrics["loss"]))
        losses.append(float(jax.block_until_ready(metrics["loss"])))
        elapsed = time.perf_counter() - start

        trace = {}
        if job["trace"]:
            # a few more steps under the profiler, after the window, so
            # that writing and reading the trace costs the window nothing
            jax.profiler.start_trace(job["trace_dir"])
            for _ in range(traffic["traced_steps"]):
                params, opt_state, metrics = step(params, opt_state,
                                                  next_batch())
            losses.append(float(jax.block_until_ready(metrics["loss"])))
            jax.profiler.stop_trace()
            trace = trace_reduce.reduce_events(trace_reduce.read_xplane(
                replica.find_xplane(job["trace_dir"])))
            if trace:    # the CPU of a rehearsal has no device plane
                trace["steps"] = traffic["traced_steps"]

    session.report({
        "device": {**device, "memory_peak_bytes": replica.memory_peak_bytes()},
        "window_start_epoch": window_start_epoch, "elapsed_s": elapsed,
        "steps": steps, "tokens_per_step": batch_size * seq,
        "chips": mesh_spec.num_devices, "losses": losses,
        "loss_check": {"program": got, "reference": want,
                       "rel_err": abs(got - want) / abs(want),
                       "rtol": LOSS_RTOL},
        "trace": trace,
        "phases": phases})


def run(ctx: dict) -> dict:
    from ray_tpu.air.config import FailureConfig, RunConfig, ScalingConfig
    from ray_tpu.train.jax import JaxTrainer

    from benchmark import spec

    cell = ctx["cell"]
    trace_dir = os.path.join(spec.ROOT, ".bench_trace", cell["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    job = {"config": cell["config"], "traffic": cell["traffic"],
           "seed": ctx["seed"], "seconds": ctx["seconds"],
           "trace": ctx["trace"], "trace_dir": trace_dir}
    # One restart is allowed, for the worker that a fresh machine's first
    # seconds have been seen to kill (PR 23, PERF.md Open questions); a
    # restarted run shows in its set-up time.
    trainer = JaxTrainer(loop, train_loop_config=job,
                         scaling_config=ScalingConfig(
                             num_workers=1, use_tpu=True,
                             chips_per_worker=cell["chips"]),
                         run_config=RunConfig(
                             failure_config=FailureConfig(max_failures=1)))
    report = trainer.fit().metrics_history[-1]
    check = report["loss_check"]
    finite = [x for x in report["losses"] if math.isfinite(x)]
    return {**report,
            "checks": {"loss_rel_err": [check["rel_err"], check["rtol"]]},
            "correct": bool(check["rel_err"] <= check["rtol"]
                            and len(finite) == len(report["losses"])),
            "attempted": report["steps"],
            "failed": len(report["losses"]) - len(finite)}
