"""Which causal attention a model's trunk runs at a sequence length, and the
two forms it chooses between as a block takes them (head-major, [B,N,S,H]):
XLA's dense attention and the Pallas flash kernels of
``ops/flash_attention.py``, per shard under ``shard_map`` where a mesh has
several devices.  ``models/gpt.py``, ``models/llama.py`` and the GPT's
pipeline stages (``models/gpt_pipeline.py``) all ask here; the kernels' block
sizes are ``ops/flash_attention.py::_default_blocks``'s.

A plain file beside the kernels', not inside ``flash_attention.py``: that
module takes its own frames out of every traceback
(``kernel_source.exclude(__file__)``, so that a Mosaic kernel's module names
no source line), and the dense form's and the ``shard_map``'s operations
would lose their place in the lowered module's locations with them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.kernel_source import kernels_compiled


def _flash_profitable(S: int) -> bool:
    """Whether the Pallas flash kernels are expected to beat XLA's dense
    attention at sequence length S, from what the code can observe: S and
    the backend.  The kernels need S in whole 128-lane tiles and enough of
    them to amortize the grid and the K/V stream; the interpreter on the
    CPU never wins.  Measured on the chip for the forward at head size 128
    (``scripts/flash_sweep.py --fwd-only``, PERF.md section 6, PR 46: the
    kernel against the dense function alone, ms a call at S = 512 / 1024 /
    2048): 0.058 / 0.112 / 0.440 against 0.062 / 0.426 / 1.497 with 32
    query heads on 8, 0.044 / 0.074 / 0.244 against 0.045 / 0.095 / 0.858
    with 16 on 16: a tie at 512, the kernel from 1024 on.  Both training
    cells of the benchmark pin ``flash`` at S = 1024; the served prefill
    (``models/llama.py::llama_prefill``) asks here rung by rung.  The
    backward kernels' crossover is not measured (ROADMAP Speed 10, Reach
    6).  A measured crossover edits this function."""
    if S < 1024 or S % 128:
        return False
    return kernels_compiled()


def resolve_attention(attention: str, S: int) -> str:
    """The attention variant a model runs at sequence length S: a pinned
    ``attention`` ("dense", "flash", "ring") as it is, "auto" as flash or
    dense by `_flash_profitable`.  ``ring`` is never picked: it is a
    commitment to a mesh with an ``sp`` axis."""
    if attention != "auto":
        return attention
    return "flash" if _flash_profitable(S) else "dense"


def _flash_attention_bnsh(rules, mesh=None):
    """Head-major [B,N,S,H] flash attention for a model's block.  The
    compiler cannot partition a Mosaic kernel, so across several devices
    it runs per shard under ``shard_map``: batch over the data axes, heads
    over ``tp``, every shard holding whole sequences (sequence parallelism
    is ring attention's).  ``rules`` is the model's logical-axis rule table
    (``parallel/sharding.py::LogicalAxisRules``, asked only for its
    ``spec_for``) or None; ``mesh`` defaults to the one ``set_mesh`` made
    current."""
    from ray_tpu.ops.flash_attention import flash_attention

    def attn_fn(q, k, v):
        return flash_attention(q, k, v, True, None, None, None, None, "bnsh")

    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
    if rules is not None and mesh.size > 1:
        spec = rules.spec_for(("batch", "heads", None, None))
        attn_fn = jax.shard_map(attn_fn, mesh=mesh, in_specs=(spec,) * 3,
                                out_specs=spec, check_vma=False)
    attn_fn._layout = "bnsh"
    return attn_fn


def _dense_causal_attention_bnsh(q, k, v):
    """[B,N,S,H] (head-major) dense causal attention; softmax in f32."""
    S = q.shape[2]
    scores = jnp.einsum("bnqh,bnkh->bnqk", q, k) / np.sqrt(q.shape[-1])
    mask = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(mask[None, None], scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bnqk,bnkh->bnqh", probs, v)


_dense_causal_attention_bnsh._layout = "bnsh"
