"""Tokens of the steps finished in the window, over the window's seconds
(closed by block_until_ready on the last step), over the chips."""


def read(run):
    return run["steps"] * run["tokens_per_step"] / run["elapsed_s"] \
        / run["chips"]
