"""Logical-axis sharding rules (GSPMD partitioning tables).

Replaces the reference's model wrapping (DDP/FSDP at `train/torch/
train_loop_utils.py:70-74`): instead of wrapping modules at runtime, arrays
carry *logical* axis names ("batch", "embed", "mlp", "heads", ...) and a rule
table maps each logical axis to zero or more mesh axes.  This is the t5x/
MaxText-style recipe and is what lets one model definition run under any
combination of dp/fsdp/tp/pp/sp/ep without code changes.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]


class LogicalAxisRules:
    """Ordered mapping logical-axis-name -> mesh axis (or axes, or None).

    The first rule whose mesh axes are still unused by the current spec wins,
    so rules act like t5x's `logical_axis_rules` priority list.
    """

    def __init__(self, rules: Sequence[Tuple[str, MeshAxes]]):
        self.rules = list(rules)

    def spec_for(self, logical_axes: Sequence[Optional[str]]) -> P:
        """PartitionSpec for an array whose dims have these logical names."""
        out = []
        used: set = set()
        for name in logical_axes:
            assignment: MeshAxes = None
            if name is not None:
                for lname, maxes in self.rules:
                    if lname != name or maxes is None:
                        continue
                    cand = (maxes,) if isinstance(maxes, str) else tuple(maxes)
                    if any(m in used for m in cand):
                        continue
                    assignment = cand if len(cand) > 1 else cand[0]
                    used.update(cand)
                    break
            out.append(assignment)
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    @staticmethod
    def for_transformer(spec=None) -> "LogicalAxisRules":
        """Standard Megatron-style layout over the MeshSpec axes.

        batch    -> (dp, fsdp)   activations' leading dim
        seq      -> sp           sequence/context parallelism
        res_seq  -> (sp, tp)     the residual stream's rows BETWEEN a
                                 block's projection pairs where the mesh has
                                 tp > 1: each pair gathers them over tp in
                                 its first product and scatters the partial
                                 sums in its last (collectives.py)
        embed    -> fsdp         ZeRO-3 weight sharding on the data axis
        heads    -> tp           attention heads (Megatron col-parallel)
        kv       -> None         head_dim stays replicated
        mlp      -> tp           FFN hidden (col-parallel in, row-parallel out)
        vocab    -> tp           embedding/LM-head vocab sharding
        expert   -> ep           MoE expert dim
        layers   -> pp           stacked-layer dim (pipeline stages)

        ``spec`` is still read for nothing: the table is one for every
        mesh (an axis of size 1 shards nothing).  What differs by mesh, a
        block's form under tp, is chosen where the block is traced, from
        the mesh that is current there (``models/gpt.py::_tp_mesh``), so a
        caller that hands no spec gets the same program as one that does.
        """
        return LogicalAxisRules([
            ("batch", ("dp", "fsdp")),
            ("seq", "sp"),
            ("res_seq", ("sp", "tp")),
            ("embed", "fsdp"),
            ("heads", "tp"),
            ("kv", None),
            ("mlp", "tp"),
            ("vocab", "tp"),
            ("expert", "ep"),
            ("layers", "pp"),
            ("norm", None),
        ])


def logical_sharding(mesh: Mesh, rules: LogicalAxisRules,
                     logical_axes: Sequence[Optional[str]]) -> NamedSharding:
    return NamedSharding(mesh, rules.spec_for(logical_axes))


def with_logical_constraint(x, rules: LogicalAxisRules,
                            logical_axes: Sequence[Optional[str]]):
    """`lax.with_sharding_constraint` by logical names (inside jit)."""
    return jax.lax.with_sharding_constraint(
        x, rules.spec_for(logical_axes))


def init_sharded(init_fn, mesh: Mesh, rules: LogicalAxisRules, annotations,
                 *args):
    """Multi-controller-safe sharded init.

    ``device_put`` cannot span another process's devices, so on a
    multi-host mesh params must be BORN sharded: run ``init_fn`` inside
    ``jit`` with ``out_shardings`` derived from the logical annotations —
    every process traces the same program and receives its addressable
    shards of one global array per leaf.
    """
    shardings = jax.tree_util.tree_map(
        lambda ann: logical_sharding(mesh, rules, ann), annotations,
        is_leaf=lambda x: x is None or isinstance(x, tuple))
    return jax.jit(init_fn, out_shardings=shardings)(*args)


def shard_params(params, mesh: Mesh, rules: LogicalAxisRules, annotations):
    """Device-put a param pytree according to per-leaf logical annotations.

    `annotations` mirrors `params` with tuples of logical axis names
    (None entries for replicated dims).
    """
    def _place(p, ann):
        return jax.device_put(p, logical_sharding(mesh, rules, ann))

    return jax.tree_util.tree_map(
        _place, params, annotations,
        is_leaf=lambda x: not isinstance(x, dict))
