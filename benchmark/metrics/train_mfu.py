"""Model FLOP/s utilisation: the operations forward and backward require
per token (recomputation not counted) times tokens per second per chip,
over the chip's published bf16 peak."""

from benchmark import costs, spec


def read(run):
    config, traffic = run["cell"]["config"], run["cell"]["traffic"]
    family = spec.load_part("families", config["family"])
    shape = family.attention_shape(config)
    per_token = costs.train_flops_per_token(
        family.matmul_params(config), shape["layers"], traffic["seq_len"],
        shape["embed"])
    rate = run["steps"] * run["tokens_per_step"] / run["elapsed_s"] \
        / run["chips"]
    return 100.0 * per_token * rate / run["peaks"]["bf16_flops_per_s"]
