"""The flash-attention kernels' share of their roofline: the least time
the chip could take for the calls the trace shows, over their time.

The train step's only Pallas kernels (``tpu_custom_call``) are the three
flash kernels.  A layer
calls dq and dkv once a step and the forward kernel once, or twice where
the backward pass recomputes it; the number of calls tells which.  Shapes
are one device's: the batch over the data axes, the heads over tp.
"""

from benchmark import costs, spec
from benchmark.trace_reduce import op_kind


def read(run):
    trace = run["trace"]
    calls = [v for k, v in trace.get("ops", {}).items()
             if op_kind(k) == "tpu_custom_call"]
    if not calls:
        return None
    config, traffic = run["cell"]["config"], run["cell"]["traffic"]
    family = spec.load_part("families", config["family"])
    shape = family.attention_shape(config)
    mesh = config["mesh"]
    batch = config["train"]["batch"] // (mesh.get("dp", 1)
                                         * mesh.get("fsdp", 1))
    heads = shape["heads"] // mesh.get("tp", 1)
    per_layer_step = sum(v["calls"] for v in calls) / (
        trace["steps"] * shape["layers"])
    forwards = per_layer_step - 2
    if forwards < 1:
        return None

    def least(kind):
        return costs.least_seconds(costs.flash_pass(
            kind, batch, heads, traffic["seq_len"], shape["head_dim"]),
            run["peaks"])
    floor = trace["steps"] * shape["layers"] * (
        forwards * least("fwd") + least("dq") + least("dkv"))
    return 100.0 * floor / sum(v["device_s"] for v in calls)
