"""The hybrid configuration's two programs at the published widths, compiled
for a described v5e from shapes alone (ISSUE 48), beside
``test_tpu_compile.py``: the decode step and the 512 rung fit the chip with
the cell's pools, every pool comes back in its argument's buffer, and no
program copies a stack of weights (a layer sliced at an index that is not
the scan's own cost a re-laid-out copy of the whole stack every call)."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from test_tpu_compile import (_fits, _linear_state_kernels,  # noqa: F401
                              _paged_read_kernels, compiled_linear_state,
                              compiled_paged_read, la, made_of_shape,
                              no_persistent_cache, pa, topo)

GIB = 1024 ** 3


@pytest.fixture(scope="module")
def cell():
    from benchmark import spec
    config = spec.load_json("configs", "olmo-hybrid-7b-12l.json")
    family = spec.load_part("families", config["family"])
    engine = config["engine"]
    model = family.program_config(
        config, engine["max_prompt_len"] + engine["max_new_tokens"])
    return family, model, engine


def compiled(topo, cell, program):
    from ray_tpu.models import llama
    family, model, engine = cell
    one = SingleDeviceSharding(topo.devices[0])

    def on(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    params = on(jax.eval_shape(
        lambda: family.init(jax.random.PRNGKey(0), model)))
    pools = on(jax.eval_shape(lambda: llama.llama_init_paged_cache(
        model, engine["num_pages"], engine["page_size"], None,
        engine["max_batch"])))
    maxp = (engine["max_prompt_len"] + engine["max_new_tokens"]) \
        // engine["page_size"]
    slots = engine["max_batch"]
    if program == "decode":
        lowered = jax.jit(
            lambda p, *a: llama.llama_decode_step(p, model, *a),
            donate_argnums=(3, 4)).lower(
                params, arg(slots), arg(slots), *pools, arg(slots, maxp))
    else:
        lowered = jax.jit(
            lambda p, *a: llama.llama_prefill(p, model, *a),
            donate_argnums=(3, 4)).lower(
                params, arg(1, program), arg(), *pools, arg(1, maxp), arg())
    return params, pools, lowered.compile()


@pytest.mark.parametrize("program", ["decode", 512])
def test_the_hybrid_program_fits_and_copies_no_stack(topo, cell, program):
    params, pools, exe = compiled(topo, cell, program)
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pools))
    assert round(weights / 1e9, 2) == 6.54
    assert round(held / 1e9, 2) == 4.38       # pages 3.40, rows 0.99
    memory = exe.memory_analysis()
    # every pool is the argument's buffer: K, V, the states, the tails
    assert memory.alias_size_in_bytes >= held
    # 10.9 GB held; the decode step's gather of one full layer's pages
    # (48 x 96 pages) is its temporaries, the prefill has next to none
    assert _fits(exe) < 12.5 * GIB
    assert memory.temp_size_in_bytes < (1.6 if program == "decode"
                                        else 0.4) * GIB
    text = exe.as_text()
    # no instruction MAKES an array as large as a group's stack of
    # feed-forward weights [3 periods, 2, 3840, 11008]
    made = [line for line in text.splitlines() if re.search(
        r"= bf16\[3,(2,3840,11008|11008,3840|3840,11520)\]\S* "
        r"(?!parameter|get-tuple-element|bitcast)", line)]
    assert made == []
    # the states are float32 and folded: 45 panels of 128 lanes, 96 deep
    assert "f32[9,48,45,96,128]" in text


def test_the_hybrid_decode_reads_the_pages_through_the_kernel(
        topo, cell, compiled_paged_read, monkeypatch):
    """On the chip the three full layers read K/V through the kernel that
    walks the page table, at the decode ladder's top rung here: one kernel
    in the full layers' scan, both pools its operands as they are stored
    and held once (aliased to the results, no operation of a pool's size
    but the appends in place), and the gather's two costliest operations
    gone with it: the rows gathered for every slot of the rung and their
    relayout by heads, 30 K/V heads being no whole tile of 8 sublanes."""
    _, pools, exe = compiled(topo, cell, "decode")
    text = exe.as_text()
    assert len(_paged_read_kernels(text, "bf16[3,4609,16,3840]")) == 1
    gathered = r"bf16\[(48,1536,30,128|4608,16,3840)\]"
    assert not re.search(gathered, text)
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pools))
    memory = exe.memory_analysis()
    assert memory.alias_size_in_bytes >= held
    # nothing makes an array of a pool's size but the appends in place
    made = [line.strip()[:120] for line in text.splitlines() if re.search(
        r"= bf16\[3,4609,16,3840\]\S* (?!parameter|get-tuple-element|"
        r"bitcast|scatter|fusion)", line)]
    assert made == []
    monkeypatch.setattr(pa, "_kernel_backend", lambda: False)
    _, _, gather = compiled(topo, cell, "decode")
    assert re.search(gathered, gather.as_text())
    assert memory.temp_size_in_bytes \
        < gather.memory_analysis().temp_size_in_bytes / 4


def test_the_hybrid_decode_steps_the_states_through_the_kernel(
        topo, cell, compiled_paged_read, compiled_linear_state, monkeypatch):
    """On the chip the nine linear layers step their states through
    ``ops/linear_state.py`` (ISSUE 52): one kernel a linear layer of the
    scanned period, the whole pool its operand and its result in one buffer,
    and NOTHING else in the program makes an array of the pool's shape (the
    rule's program has three update-slice fusions of it a period) or of one
    layer's slab; the temporaries are no larger than the rule's."""
    pool, slab = "f32[9,48,45,96,128]", "f32[48,45,96,128]"
    _, pools, exe = compiled(topo, cell, "decode")
    text = exe.as_text()
    assert len(_linear_state_kernels(text, pool)) == 3
    assert made_of_shape(text, pool, but="custom-call") == []
    assert made_of_shape(text, slab) == []
    # nor spread keys of a slab's size, in either layout
    assert not re.search(r"f32\[48,(45|30),96,(128|192)\]", text)
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pools))
    memory = exe.memory_analysis()
    assert memory.alias_size_in_bytes >= held
    monkeypatch.setattr(la, "_kernel_backend", lambda: False)
    _, _, rule = compiled(topo, cell, "decode")
    assert _linear_state_kernels(rule.as_text(), pool) == []
    assert len(made_of_shape(rule.as_text(), pool)) >= 3
    assert memory.temp_size_in_bytes \
        <= rule.memory_analysis().temp_size_in_bytes
