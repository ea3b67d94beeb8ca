"""What the serving engine asks a model for: one record a model.

``serve/engine/engine.py`` drives a paged cache and two programs and knows
no model's entry points by name: ``serving_model(EngineConfig.model,
model_config, seq)`` hands it a ``ServedModel``, which each model module
builds from the functions it has (``gpt.served``, ``llama.served``).  The
functions are looked up when the record is asked for, not when ``models/``
is imported: one replaced on its module before an engine is constructed is
the one that engine traces.
"""

from __future__ import annotations

import importlib
from typing import Any, Callable, NamedTuple

import jax.numpy as jnp

_MODULES = {"gpt": "ray_tpu.models.gpt", "llama": "ray_tpu.models.llama"}


class ServedModel(NamedTuple):
    config: Any                  # the model's (the module's tiny one if None)
    init: Callable               # (rng, config) -> parameters
    stored: Callable             # (parameters, config) -> the tree the two
    #                              programs read, each leaf in its dtype
    new_pools: Callable          # (num_pages, page_size, dtype, slots) ->
    #                              (k_pages, v_pages), zeroed; v_pages None:
    #                              one pool; either may be a tree of arrays
    prefill: Callable            # (params, config, tokens [1, S], length,
    #                              k_pages, v_pages, page_table, slot[,
    #                              start]) -> (logits, k_pages, v_pages[,
    #                              the experts' load]); ``slot``: the decode
    #                              slot the sequence will be stepped in;
    #                              ``start`` (``chunked`` models): the
    #                              position of the chunk's first token
    step: Callable               # the decode step, (params, config, token,
    #                              pos, ...) likewise; a block model's takes
    #                              the blocks' state and ends for token, pos
    prefill_attention: Callable  # (config, rung, whether the call is given
    #                              a start) -> "flash" | "dense" |
    #                              "latent_chunk"
    paged_read: Callable         # (config, k_pages) -> "kernel" |
    #                              "gather": what the step's programs read
    #                              the pages with (``ops/paged_attention.py``)
    block: int                   # positions a step yields a sequence; 0:
    #                              one, by one token
    feed: Callable               # (config, logits, token, pos) -> (the
    #                              logits the loop's step returns, or None;
    #                              what the next step takes for ``token``)
    slot_rows: Any = None        # (k_pages, v_pages) -> the arrays among
    #                              them that hold a row a decode SLOT and not
    #                              pages (a recurrent state: written whole by
    #                              a prefill, stepped where it lies, its
    #                              leading dims [layers, slots]); None: none
    conv_tails: Any = None       # (k_pages, v_pages) -> the one of
    #                              ``slot_rows``' arrays that holds the last
    #                              inputs of short convolutions (a model may
    #                              keep those alone: no state matrix)
    linear_state: Any = None     # (config, v_pages) -> "kernel" | "rule":
    #                              what the step's programs step the linear
    #                              layers' states with (``ops/
    #                              linear_attention.py``); None: no such layer
    page_kind: str = "kv"        # what the pages hold: "kv" (two pools) or
    #                              "latent" (one, no V pool)
    expert_stack: Any = None     # (params) -> a stack of routed experts as
    #                              stored ({"wd", ...}); None: a dense model
    chunked: bool = False        # ``prefill`` takes a ``start`` and reads
    #                              what lies before it from the pages: a
    #                              prompt may run as a row of calls
    index_pool: Any = None       # (k_pages, v_pages) -> the pool that holds
    #                              a sparse-attention indexer's key a
    #                              position, beside the pages; None: none
    select_topk: int = 0         # > 0: a decode step's attention reads at
    #                              most this many positions a sequence


def greedy(config, logits, token, pos):
    """A one-token step's ``feed``: every slot's next token is the argmax
    of its logits, chosen where they are."""
    return logits, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def serving_model(model: str, config: Any = None,
                  seq: int = 0) -> ServedModel:
    """The record of ``model`` ("gpt" | "llama") for ``config``, or for the
    module's tiny configuration of ``seq`` positions."""
    if model not in _MODULES:
        raise ValueError(f"unknown engine model '{model}'")
    return importlib.import_module(_MODULES[model]).served(config, seq)
