"""``closed_loop_serve``, after the cell's family has built the program's
configuration (a dataclass; no JAX backend is opened) in the benchmark's own
process.  A program that cannot take the configuration (the parent of the PR
that taught it to) then fails here, at once, with the program's own error.
Left to the replica's constructor the same error is retried by the readiness
probe of ``benchmark/serving.py`` for ``READY_DEADLINE_S`` seconds, which a
driver that gives a run two minutes reads as a hang.
"""

from benchmark import spec
from benchmark.generators import closed_loop_serve


def run(ctx: dict) -> dict:
    config = ctx["cell"]["config"]
    engine = config["engine"]
    spec.load_part("families", config["family"]).program_config(
        config, engine["max_prompt_len"] + engine["max_new_tokens"])
    return closed_loop_serve.run(ctx)
