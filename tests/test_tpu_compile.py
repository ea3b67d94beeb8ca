"""The main path's programs, compiled for a v5e chip that is described and
not attached (on-chip-measurement guide, section 2.3).

Nothing runs, so these say nothing about results or times: they say that
the chip's compiler accepts each program at its real size — kernel tiling,
fast-memory use, device memory, partitioning — which interpret mode on the
CPU cannot.  Each case lowers with explicit shapes on ``topo.devices`` and
asserts ``tpu_custom_call`` wherever a Pallas kernel must be in the text.
Code that picks interpret mode from ``jax.default_backend()`` would pick it
here (the backend is the CPU), so the cases pass ``interpret=False``
themselves, or steer ``_resolve`` where a model makes the call.
"""

import dataclasses
import importlib
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs under /tmp

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from ray_tpu.models.gpt import (GPTConfig, gpt_decode_step, gpt_init,
                                gpt_param_axes, gpt_prefill,
                                init_paged_cache, make_train_step)
from ray_tpu.ops.ring_attention import make_ring_attention_fn
from ray_tpu.parallel import LogicalAxisRules, MeshSpec
from ray_tpu.parallel.sharding import logical_sharding

# the module: ray_tpu.ops re-exports the function under the same name
fa = importlib.import_module("ray_tpu.ops.flash_attention")
gm = importlib.import_module("ray_tpu.ops.grouped_matmul")
pa = importlib.import_module("ray_tpu.ops.paged_attention")
pr = importlib.import_module("ray_tpu.ops.paged_read")
la = importlib.import_module("ray_tpu.ops.linear_attention")
ls = importlib.import_module("ray_tpu.ops.linear_state")

HBM_BYTES = 16 * 1024 ** 3      # one v5e chip

# GPT-2-small as published (vocab 50257, not the padded default), at the
# sizes chip_smoke.py trains and serves it.
GPT2 = GPTConfig(vocab_size=50257, attention="flash", remat=True,
                 remat_policy="dots", ce_block=256)
B, S = 32, 1024
PAGE, MAX_PROMPT, MAX_NEW, MAX_BATCH = 16, 512, 128, 16
MAXP = (MAX_PROMPT + MAX_NEW) // PAGE
NUM_PAGES = MAX_BATCH * MAXP + 1
SERVE = dataclasses.replace(GPT2, max_seq_len=MAX_PROMPT + MAX_NEW)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip: the next one would warn."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Models call flash_attention with interpret=None; make that mean the
    compiled kernel, as it does on the chip."""
    real = fa._resolve
    monkeypatch.setattr(
        fa, "_resolve",
        lambda q, bq, bk, interpret, layout:
            real(q, bq, bk, False, layout))


@pytest.fixture
def compiled_experts(monkeypatch):
    """``moe_dropless`` calls the grouped matmul with interpret=None; make
    that mean the compiled kernel, as it does on the chip."""
    real = gm._resolve
    monkeypatch.setattr(gm, "_resolve",
                        lambda *a: real(*a[:-1], False))


@pytest.fixture
def compiled_paged_read(monkeypatch):
    """``paged_attention`` asks the backend whether to read the pages
    through ``ops/paged_read.py``'s kernel, and the kernel whether to run in
    the interpreter; make both answer as they do on the chip."""
    monkeypatch.setattr(pa, "_kernel_backend", lambda: True)
    real = pr._resolve
    monkeypatch.setattr(pr, "_resolve", lambda *a: real(*a[:-1], False))


@pytest.fixture
def compiled_linear_state(monkeypatch):
    """``step_pool`` asks the backend whether to step the states through
    ``ops/linear_state.py``'s kernel, and the kernel whether to run in the
    interpreter; make both answer as they do on the chip."""
    monkeypatch.setattr(la, "_kernel_backend", lambda: True)
    monkeypatch.setattr(ls, "_interpreted", lambda: False)


def _linear_state_kernels(text: str, pool: str) -> list:
    """The program's instructions that are the Pallas kernel ``linear_state``
    under the scope ``linear_state`` (what the benchmark's readers find the
    states' device time by), each held to take the whole pool ``pool`` as its
    one operand of that shape, and to hand it back as a result in the same
    buffer (``output_to_operand_aliasing``)."""
    calls = _paged_read_kernels(text, pool, "linear_state", pools=1,
                                kernel="linear_state")
    for call in calls:
        assert "output_to_operand_aliasing={{1}: (4, {})}" in call, call
    return calls


def made_of_shape(text: str, shape: str, but: str = "") -> list:
    """The program's instructions that MAKE an array of ``shape`` (a fusion,
    a copy, an update-slice: anything but a parameter, an element of a tuple,
    a bitcast, a loop, or ``but``), each cut to its head."""
    shape = re.escape(shape)
    skip = "parameter|get-tuple-element|bitcast|while|tuple|conditional" + (
        "|" + but if but else "")
    return [line.strip()[:160] for line in text.splitlines() if re.search(
        rf"= {shape}\S* (?!{skip})", line)]


def _paged_read_kernels(text: str, pool: str, scope: str = "paged_read",
                        pools: int = 2, kernel: str = "paged_read") -> list:
    """The program's instructions that are the Pallas kernel ``kernel``
    under the scope ``scope`` (what the benchmark's readers find the read's
    device time by), each held to take ``pools`` pools of the whole shape
    ``pool`` as operands: every layer's pages as they are stored."""
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and re.search(rf'op_name="[^"]*[/(]{scope}[/)]', line)]
    for call in calls:
        assert re.match(rf"\s*%{kernel}[\w.]* = ", call), call[:80]
        operands = call[call.index("operand_layout_constraints="):
                        call.index("backend_config=")]
        assert operands.count(pool + "{") == pools, operands
    return calls


def _compile(fn, *args, donate=()):
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    return compiled, compiled.as_text()


def _kernel_calls(text: str) -> list:
    """The flash kernel named by each instruction that is a Pallas kernel
    (``%flash_dq.9``; ``%transpose_jvp_flash_dq__`` where ``jax.grad`` is
    applied to the kernel's own ``custom_vjp``): what a profile shows.  A
    Pallas kernel of another name matches nothing and raises."""
    return sorted(
        re.match(r"\s*(?:ROOT )?%\w*?(flash_(?:fwd|dq|dkv))[\w.]* = ",
                 line).group(1) for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line)


def _kernels(text: str) -> set:
    return set(_kernel_calls(text))


def _expert_kernels(text: str, *stacks: str) -> list:
    """The program's instructions that are a Pallas kernel written under
    the scope ``moe_experts`` (what the benchmark's readers find the
    experts' device time by), each held to read one of ``stacks``: the
    experts of every layer in their stored type and whole shape, as the
    kernel's operand."""
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and re.search(r'op_name="[^"]*[/(]moe_experts[/)]', line)]
    for call in calls:
        operands = call[call.index("operand_layout_constraints="):
                        call.index("backend_config=")]
        assert any(stack + "{" in operands for stack in stacks), operands
    return calls


def _scoped(text: str, scope: str) -> bool:
    """Whether some operation's ``op_name`` passes through the scope,
    transformations (``transpose(jvp(scope))``) included."""
    return any(re.search(rf"[/(]{scope}[/)]", name)
               for name in re.findall(r'op_name="([^"]*)"', text))


def _permutes_by_body(text: str, shape: str) -> dict:
    """The computations of a compiled (scheduled) program that hold a
    ``collective-permute-start`` of ``shape``, each with, for every such
    start, how many matrix products (a convolution, or a fusion that holds
    one) the schedule put between it and its ``-done``."""
    bodies = {}
    for block in text.split("\n\n"):
        head, _, body = block.strip("\n").partition("\n")
        name = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(", head)
        if name:
            bodies[name.group(1)] = body.split("\n")

    def is_product(line):
        called = re.search(r" fusion\(.*calls=%?([\w.\-]+)", line)
        return " convolution(" in line or bool(called) and any(
            " convolution(" in inner for inner in bodies[called.group(1)])

    found = {}
    for name, lines in bodies.items():
        started = {}
        for at, line in enumerate(lines):
            start = re.match(r"\s*%?([\w.\-]+) = \(" + re.escape(shape)
                             + r"\S* .* collective-permute-start\(", line)
            done = re.search(r" collective-permute-done\(%?([\w.\-]+)\)",
                             line)
            if start:
                started[start.group(1)] = at
            elif done and done.group(1) in started:
                found.setdefault(name, []).append(sum(
                    map(is_product, lines[started[done.group(1)] + 1:at])))
    return found


def _names_no_source(lowered, name: bytes) -> None:
    """The one Pallas kernel of a lowered program: its serialized module
    holds the kernel's ``name`` and no file's."""
    import base64
    body, = re.findall(r'body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                       lowered.as_text())
    module = base64.b64decode(body)
    assert name in module
    assert b".py" not in module and os.getcwd().encode() not in module


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB does not fit one chip"
    return used


def _on(sharding, tree):
    """Shapes of ``tree`` (from eval_shape) placed by ``sharding`` (one
    sharding, or a matching tree of them)."""
    if not isinstance(sharding, dict):
        sharding = jax.tree.map(lambda _: sharding, tree)
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, sharding)


# ------------------------------------------------------------------ kernels

def _flash(q, k, v, layout="bsnh"):
    return fa.flash_attention(q, k, v, True, None, None, None, False, layout)


def _flash_gqa(q, k, v):
    # models/llama.py: head-major, K/V repeated up to the query heads.
    rep = q.shape[1] // k.shape[1]
    return _flash(q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1),
                  "bnsh")


def _grads(fn):
    return jax.grad(lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))


FLASH_CASES = {
    "train-32x1024": (_flash, (32, 1024, 12, 64), (32, 1024, 12, 64)),
    "long-2x4096": (_flash, (2, 4096, 12, 64), (2, 4096, 12, 64)),
    "gqa-12q4kv": (_flash_gqa, (8, 12, 1024, 64), (8, 4, 1024, 64)),
}


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_kernel_compiles(topo, case, direction):
    fn, q_shape, kv_shape = FLASH_CASES[case]
    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct(kv_shape, jnp.bfloat16, sharding=one)
    compiled, text = _compile(fn if direction == "fwd" else _grads(fn),
                              q, kv, kv)
    # forward is one kernel; backward runs it again, then dq and dk/dv
    assert text.count("tpu_custom_call") >= (1 if direction == "fwd" else 3)
    assert _kernels(text) == ({"flash_fwd"} if direction == "fwd" else
                              {"flash_fwd", "flash_dq", "flash_dkv"})
    _fits(compiled)


# --------------------------------------------------------------- train step

def _train_step_args(cfg, mesh, rules, tx):
    params = jax.eval_shape(lambda: gpt_init(jax.random.PRNGKey(0), cfg))
    shardings = jax.tree.map(
        lambda ann: logical_sharding(mesh, rules, ann), gpt_param_axes(cfg),
        is_leaf=lambda x: isinstance(x, tuple))
    params = _on(shardings, params)
    # What tx.init gives on placed parameters: each moment lies as its
    # parameter does, and the step count is replicated.
    opt = optax.tree_map_params(
        tx, lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        jax.eval_shape(tx.init, params), shardings,
        transform_non_params=lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, P())))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (B, S + 1), jnp.int32,
        sharding=logical_sharding(mesh, rules, ("batch", None)))}
    return params, opt, batch


@pytest.mark.parametrize("spec", [MeshSpec(), MeshSpec(fsdp=2, tp=2)],
                         ids=["1chip", "fsdp2xtp2"])
def test_gpt2_small_train_step_compiles(topo, compiled_kernels, spec):
    mesh = spec.build(devices=topo.devices[:spec.num_devices])
    rules = LogicalAxisRules.for_transformer(spec)
    tx = optax.adamw(3e-4, b2=0.95)
    args = _train_step_args(GPT2, mesh, rules, tx)
    with jax.sharding.set_mesh(mesh):
        compiled = make_train_step(GPT2, tx, rules).lower(*args).compile()
    text = compiled.as_text()
    # the kernels by the names a profile reads, under shard_map too; and
    # what benchmark/metrics/flash_roofline.py counts on: every Pallas
    # kernel of the step is one of the three, dq and dkv once in the
    # backward layer body, the forward in both bodies (remat_policy "dots"
    # runs it again)
    assert _kernel_calls(text) == ["flash_dkv", "flash_dq", "flash_fwd",
                                   "flash_fwd"]
    assert _scoped(text, "ce_head") and _scoped(text, "optimizer")
    # a step's results lie as its arguments do, or its second call
    # compiles again (and a compiled step refuses its own results)
    (was, _), now = compiled.input_shardings, compiled.output_shardings
    for arg, a, b in zip(jax.tree.leaves(args[:2]),
                         jax.tree.leaves(was[:2]), jax.tree.leaves(now[:2])):
        assert a.is_equivalent_to(b, arg.ndim), (arg.shape, a, b)
    if spec.tp > 1:
        # tp's activation sums travel as chunks beside the products
        # (parallel/collectives.py): no all-reduce of a whole activation
        # [B/fsdp, S, D] is left, and both layer bodies (forward; backward
        # with the rematerialised forward) move [B/fsdp, S/tp, D] by
        # collective-permute, each with a product inside some pair
        rows, width = B // spec.fsdp, GPT2.embed_dim
        assert not re.search(
            rf"= bf16\[{rows},{S},{width}\]\S* all-reduce(-start)?\(", text)
        found = _permutes_by_body(
            text, f"bf16[{rows},{S // spec.tp},{width}]")
        # a layer moves four half-sums (the entry computation moves one:
        # the head takes whole sequences)
        bodies = [pairs for pairs in found.values() if len(pairs) >= 4]
        assert len(bodies) == 2 and all(map(max, bodies)), found
    _fits(compiled)


def test_moe_train_step_compiles(topo):
    # ops/moe.py is jax.numpy: only its step needs the compiler's word.
    cfg = GPTConfig(vocab_size=50257, num_layers=2, attention="dense",
                    remat=True, remat_policy="dots", ce_block=256,
                    num_experts=8, expert_top_k=2)
    spec = MeshSpec()
    mesh = spec.build(devices=topo.devices[:1])
    rules = LogicalAxisRules.for_transformer(spec)
    tx = optax.adamw(3e-4, b2=0.95)
    params, opt, _ = _train_step_args(cfg, mesh, rules, tx)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (8, S + 1), jnp.int32, sharding=NamedSharding(mesh, P()))}
    with jax.sharding.set_mesh(mesh):
        _fits(make_train_step(cfg, tx, rules).lower(params, opt, batch)
              .compile())


# ------------------------------------------------------------------ serving

POOLS = (3, 4)      # the pools among a paged program's arguments: donated


def _pools_in_place(compiled, text, kp, pools=2):
    """The engine's program, compiled as the engine compiles it (pools
    donated), updates the pools where they lie: both (a latent model's
    one: ``pools``) are aliased to the results, and no instruction copies,
    slices or update-slices a buffer of a whole pool's or one layer's
    pool's size (by its own opcode or as the fusion the compiler names for
    it)."""
    pool_bytes = kp.size * kp.dtype.itemsize
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        pools * pool_bytes
    moved = []
    for line in text.splitlines():
        found = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]+)\]\S* "
                         r"([\w\-]+)\(", line)
        if not found:
            continue
        name, dims, opcode = found.groups()
        count = math.prod(int(d) for d in dims.split(","))
        if count in (kp.size, kp.size // kp.shape[0]) and re.search(
                r"copy|dynamic-slice|dynamic-update-slice",
                name if opcode == "fusion" else opcode) \
                and not (pools == 1
                         and _writes_a_sliver(text, line, opcode)):
            moved.append(line.strip()[:160])
    assert moved == []


def _writes_a_sliver(text, line, opcode) -> bool:
    """A ``dynamic-update-slice`` of the pool whose update is a position's
    row (a latent model's append is a loop of them): written where the pool
    lies, its result the pool's own buffer."""
    update = re.search(r"dynamic-update-slice\(%[\w.\-]+, %([\w.\-]+),",
                       line)
    made = update and opcode == "dynamic-update-slice" and re.search(
        rf"%{re.escape(update.group(1))} = \w+\[([\d,]+)\]", text)
    return bool(made) and math.prod(
        int(d) for d in made.group(1).split(",")) <= 4096


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_paged_engine_program_compiles(topo, program):
    one = SingleDeviceSharding(topo.devices[0])
    params = _on(one, jax.eval_shape(
        lambda: gpt_init(jax.random.PRNGKey(0), SERVE)))
    kp, vp = _on(one, jax.eval_shape(
        lambda: init_paged_cache(SERVE, NUM_PAGES, PAGE)))

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    if program == "prefill":
        compiled, text = _compile(
            lambda p, *a: gpt_prefill(p, SERVE, *a),
            params, arg((1, MAX_PROMPT)), arg(()), kp, vp, arg((1, MAXP)),
            donate=POOLS)
    else:
        compiled, text = _compile(
            lambda p, *a: gpt_decode_step(p, SERVE, *a),
            params, arg((MAX_BATCH,)), arg((MAX_BATCH,)), kp, vp,
            arg((MAX_BATCH, MAXP)), donate=POOLS)
        assert _scoped(text, "paged_read")
    assert _scoped(text, "paged_append")
    # 12 heads of 64: the head size the heads-apart layout is not clean for
    _pools_in_place(compiled, text, kp)
    _fits(compiled)


_COMPILED = {}      # what a test of this file compiled, for its neighbours


def _narrowest_is_no_larger(narrow, top):
    """A decode step at the narrowest rung of ``decode_rungs`` against the
    top rung's: it gathers fewer pages, so it may need no more room."""
    narrow, top = narrow.memory_analysis(), top.memory_analysis()
    assert narrow.temp_size_in_bytes <= top.temp_size_in_bytes
    assert narrow.alias_size_in_bytes == top.alias_size_in_bytes


def _llama_engine_program(topo, cfg, program, prompt, new, num_pages,
                          max_batch=MAX_BATCH, gather_is_the_temporaries=True,
                          rung=None):
    """The engine's prefill or decode for ``cfg``, compiled as the engine
    compiles it (pools donated) from the shapes of the tree the engine
    stores (``llama_serving_params``) and of its pool, and held to
    ``_pools_in_place``: (those shapes, the executable, its text).  The
    prefill is that of the top rung, ``prompt``, or of ``rung``; the decode
    that of the top rung of the decode ladder (a page table of ``maxp``
    columns) or, as ``decode@narrow``, of its narrowest, which is also held
    to need no more room than the top rung's."""
    key = (repr(cfg), program, prompt, new, num_pages, max_batch, rung,
           pa._kernel_backend())
    if key in _COMPILED:
        return _COMPILED[key]
    from ray_tpu.models.llama import (llama_decode_step, llama_init,
                                      llama_init_paged_cache, llama_prefill,
                                      llama_serving_params)
    one = SingleDeviceSharding(topo.devices[0])
    params = _on(one, jax.eval_shape(lambda: llama_serving_params(
        llama_init(jax.random.PRNGKey(0), cfg), cfg)))
    kp, vp = _on(one, jax.eval_shape(lambda: llama_init_paged_cache(
        cfg, num_pages, PAGE)))
    maxp = (prompt + new) // PAGE

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    if program == "prefill":
        compiled, text = _compile(
            lambda p, *a: llama_prefill(p, cfg, *a), params,
            arg((1, rung or prompt)), arg(()), kp, vp, arg((1, maxp)),
            donate=POOLS)
    else:
        from ray_tpu.serve.engine.engine import decode_rungs
        width = maxp if program == "decode" else decode_rungs(maxp)[0]
        assert program in ("decode", "decode@narrow") and 0 < width <= maxp
        compiled, text = _compile(
            lambda p, *a: llama_decode_step(p, cfg, *a), params,
            arg((max_batch,)), arg((max_batch,)), kp, vp,
            arg((max_batch, width)), donate=POOLS)
    _pools_in_place(compiled, text, kp)
    if program == "decode" and gather_is_the_temporaries:
        # from the stored tree a step's temporaries are what it gathers of
        # one layer, and that is nearly one layer's pools (``max_batch x
        # maxp`` pages of ``num_pages``); with the pools handed to the scan
        # a layer at a time they were four layers' pools and more
        layer_bytes = kp.size * kp.dtype.itemsize // kp.shape[0]
        assert compiled.memory_analysis().temp_size_in_bytes < 2 * layer_bytes
    if program == "decode@narrow":
        _narrowest_is_no_larger(compiled, _llama_engine_program(
            topo, cfg, "decode", prompt, new, num_pages, max_batch,
            gather_is_the_temporaries)[1])
    _COMPILED[key] = params, compiled, text
    return params, compiled, text


# Mistral-7B-v0.3 at its published widths, 8 of its 32 layers, with the engine
# of benchmark/configs/mistral-7b-v0.3-8l.json: from the stored tree no program
# casts a parameter (from the caller's f32 tree both cast all six stacks and
# the table, the feed-forward's as bf16[8,2,4096,14336] and bf16[8,14336,4096],
# 8.5 GB read and written a call), and without the f32 arguments and the bf16
# temporaries they need 7.3 and 6.6 GiB where the f32 tree's need 14.5 and 13.6.
MISTRAL_BUDGET = int(9.5 * 1024 ** 3)


def _mistral_engine_program(topo, program, rung=None, **overrides):
    from ray_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig(vocab_size=32768, num_layers=8, num_heads=32,
                      num_kv_heads=8, embed_dim=4096, mlp_dim=14336,
                      rope_theta=1e6, rms_eps=1e-5, max_seq_len=2048 + 512,
                      **overrides)
    return _llama_engine_program(topo, cfg, program, prompt=2048, new=512,
                                 num_pages=2561, rung=rung)


@pytest.mark.parametrize("program", ["prefill", "decode", "decode@narrow"])
def test_mistral_engine_program_reads_stored_weights(topo, program):
    params, compiled, text = _mistral_engine_program(topo, program)
    assert params["layers"]["mlp"]["wgu"].dtype == jnp.bfloat16
    assert params["layers"]["ln1"]["scale"].dtype == jnp.float32
    # a program parameter is %p__<path>__; the f32 tree's text has six
    # `bf16[...] convert(%p__...)` and the table's
    assert "convert(%p__" not in text
    made = [line for line in text.splitlines() if re.search(
        r"= bf16\[8,(2,4096,14336|14336,4096)\]\S* (?!parameter|get-tuple)",
        line)]
    assert made == []
    assert _fits(compiled) < MISTRAL_BUDGET


@pytest.mark.parametrize("rung", [128, 256, 512, 1024])
def test_mistral_prefill_compiles_at_every_lower_rung(topo, rung):
    """The engine compiles a prefill for every rung of its ladder
    (``prefill_rungs``): the ones under the top rung are new shapes for the
    compiler, the page table as wide as ever."""
    from ray_tpu.serve.engine.engine import prefill_rungs
    assert rung in prefill_rungs(2048, PAGE)
    _, compiled, text = _mistral_engine_program(topo, "prefill", rung=rung)
    assert "convert(%p__" not in text
    assert _fits(compiled) < MISTRAL_BUDGET


@pytest.mark.parametrize("rung", [1024, 2048])
def test_mistral_prefill_runs_the_flash_kernel_on_its_long_rungs(
        topo, compiled_kernels, rung):
    """On the chip ``ops/attention.py::resolve_attention`` gives the rungs
    of 1024 and 2048 the flash forward kernel (here the backend is the CPU, so the test pins
    it): one kernel in the layer scan, grouped k and v read where they lie
    (no [1, 32, S, 128] copy of them), no [S, S] scores in memory, which
    were 0.5 GiB of the dense program's temporaries at 2048; and the
    kernel's module, part of the compile cache's key in every replica's
    start, names no file (``test_grouped_matmul_module_names_no_source``)."""
    _, compiled, text = _mistral_engine_program(topo, "prefill", rung=rung,
                                                attention="flash")
    assert _kernel_calls(text) == ["flash_fwd"]
    assert f"8,4,{rung},{rung}]" not in text      # scores
    assert f"bf16[1,32,{rung},128]" in text       # q and the output
    assert not re.search(rf"bf16\[1,32,{rung},128\]\S* broadcast", text)
    _, dense, _ = _mistral_engine_program(topo, "prefill", rung=rung)
    saved = dense.memory_analysis().temp_size_in_bytes \
        - compiled.memory_analysis().temp_size_in_bytes
    assert saved > 32 * rung * rung * 2           # a bf16 copy of the scores
    assert _fits(compiled) < MISTRAL_BUDGET
    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((1, 32, rung, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, 8, rung, 128), jnp.bfloat16, sharding=one)
    _names_no_source(
        jax.jit(lambda *a: _flash(*a, "bnsh")).lower(q, kv, kv), b"flash_fwd")


def test_mistral_decode_reads_the_pages_through_the_kernel(
        topo, compiled_paged_read):
    """On the chip the token step reads K/V through the kernel that walks
    the page table: one kernel in the layer scan, the pools its operands as
    they are stored and still updated in place beside it
    (``_pools_in_place``, inside ``_mistral_engine_program``), and nothing
    gathered: the gather's [16 x 160 pages, 16, 1024] rows of K and of V
    were the step's temporaries."""
    _, compiled, text = _mistral_engine_program(topo, "decode")
    assert len(_paged_read_kernels(text, "bf16[8,2561,16,1024]")) == 1
    assert not re.search(r"bf16\[2560,16,1024\]", text)
    _fits(compiled)


def _gathering(monkeypatch, program):
    """``program()`` with the token step reading through the gather."""
    with monkeypatch.context() as patched:
        patched.setattr(pa, "_kernel_backend", lambda: False)
        return program()


def test_mistral_decode_by_the_kernel_needs_less_room(
        topo, compiled_paged_read, monkeypatch):
    _, compiled, _ = _mistral_engine_program(topo, "decode")
    _, gather, text = _gathering(
        monkeypatch, lambda: _mistral_engine_program(topo, "decode"))
    assert re.search(r"bf16\[2560,16,1024\]", text)
    assert _paged_read_kernels(text, "bf16[8,2561,16,1024]") == []
    saved = gather.memory_analysis().temp_size_in_bytes \
        - compiled.memory_analysis().temp_size_in_bytes
    assert saved >= 2560 * 16 * 1024 * 2       # the rows' buffer (K's, then V's)


# OLMoE-1B-7B-0125-Instruct at its published widths, 4 of its 16 layers, with
# the engine of benchmark/configs/olmoe-1b-7b-0125-4l.json: the dropless
# expert path's grouped matmuls have to be ``ops/grouped_matmul.py``'s kernel
# (a ``tpu_custom_call`` under the scope ``moe_experts``, at the tiles its
# ``_tiles`` picks for these shapes) reading the f32 experts where they
# lie (no bf16 copy of the stack: that alone is 3.2 GB), and the parameters
# and the KV pool, held once since the programs update it in place (7.3 GiB
# compiled; 9.0 while the pool was held twice), have to leave half the chip.
OLMOE_PROMPT, OLMOE_NEW = 512, 1024
OLMOE_BUDGET = 8 * 1024 ** 3


def _olmoe_engine_program(topo, program):
    from ray_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig(vocab_size=50304, num_layers=4, num_heads=16,
                      num_kv_heads=16, embed_dim=2048, mlp_dim=1024,
                      rope_theta=10000.0, rms_eps=1e-5, num_experts=64,
                      experts_per_token=8, qk_norm=True,
                      max_seq_len=OLMOE_PROMPT + OLMOE_NEW)
    return _llama_engine_program(
        topo, cfg, program, OLMOE_PROMPT, OLMOE_NEW,
        MAX_BATCH * (OLMOE_PROMPT + OLMOE_NEW) // PAGE + 1)


@pytest.mark.parametrize("program", ["prefill", "decode", "decode@narrow"])
def test_olmoe_engine_program_compiles(topo, compiled_experts, program):
    params, compiled, text = _olmoe_engine_program(topo, program)
    assert params["layers"]["mlp"]["wgu"].dtype == jnp.float32
    if program != "prefill":
        assert _scoped(text, "paged_read")
    for scope in ("moe_router", "moe_dispatch", "moe_experts",
                  "moe_combine", "paged_append"):
        assert _scoped(text, scope), scope
    # gate/up and down: two grouped matmuls a layer, work by assignment,
    # on the stacked f32 experts themselves
    assert len(_expert_kernels(text, "f32[512,2048,1024]",
                               "f32[256,1024,2048]")) == 2
    assert "ragged-dot" not in text
    assert "bf16[4,64," not in text
    assert _fits(compiled) < OLMOE_BUDGET


def test_olmoe_decode_by_the_kernel_copies_no_gathered_heads(
        topo, compiled_experts, compiled_paged_read, monkeypatch):
    """The gather's rows are copied head by head for the two einsums
    (``bf16[.., 8, 16, 128]``: 16 heads as two sublane tiles, twice a
    layer); the kernel reads a head as a run of lanes and copies nothing."""
    def decode():
        return _olmoe_engine_program(topo, "decode")
    heads = r"= bf16\[\d{3,},8,16,128\]\S* (copy|fusion)\("
    _, compiled, text = decode()
    assert len(_paged_read_kernels(text, "bf16[4,1537,16,2048]")) == 1
    assert not re.search(heads, text)
    _, gather, text = _gathering(monkeypatch, decode)
    assert re.search(heads, text)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < gather.memory_analysis().temp_size_in_bytes


def test_grouped_matmul_module_names_no_source(topo):
    """The kernel's serialized module is part of the persistent compilation
    cache's key, and Pallas writes each operation's source location into
    it, calling frames included (this test's own, here): a checkout at
    another path, or a line moved in a caller, would compile every expert
    program again.  The module of ``ops/grouped_matmul.py`` names its
    kernel and no file."""
    one = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)
    _names_no_source(
        jax.jit(lambda *a: gm.grouped_matmul(*a, interpret=False)).lower(
            shape((256, 2048), jnp.float32),
            shape((4 * 128, 2048, 1024), jnp.float32),
            shape((128,), jnp.int32), shape((), jnp.int32)),
        b"grouped_matmul")


def test_paged_read_module_names_no_source(topo):
    """As ``test_grouped_matmul_module_names_no_source``, for the kernel
    every decode rung of four served configurations holds: jnp functions
    traced here first (their jitted bodies are cached with the source
    locations of whoever traced them first) carry nothing into it."""
    one = SingleDeviceSharding(topo.devices[0])
    at = jnp.arange(8)
    _ = at // 3, jnp.clip(at, 1, 3), jnp.where(at > 2, at, 0)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)
    pool = shape((3, 4609, 16, 3840), jnp.bfloat16)
    _names_no_source(
        jax.jit(lambda *a: pr.paged_read_attention(
            *a, sm_scale=128 ** -0.5, interpret=False)).lower(
            shape((48, 30, 128), jnp.bfloat16), pool, pool,
            shape((), jnp.int32), shape((48,), jnp.int32),
            shape((48, 96), jnp.int32)),
        b"paged_read")


@pytest.mark.parametrize("heads,dk,dv,layers,slots", [
    (30, 96, 192, 9, 48), (32, 128, 128, 6, 64)])
def test_linear_state_compiles_and_its_module_names_no_source(
        topo, heads, dk, dv, layers, slots):
    """``ops/linear_state.py`` at the two published shapes (Olmo-Hybrid's 45
    panels, 15 of them two heads side by side; Kimi-Linear's 32): Mosaic
    takes the loop's dynamic rotation of the columns' tile, the lane slices
    and broadcasts of the columns and the rows indexed by the panel, the pool donated is
    the result's buffer with no temporary, and the module names its kernel
    and no file (``test_grouped_matmul_module_names_no_source``)."""
    one = SingleDeviceSharding(topo.devices[0])
    _, whole, side = la._panel_plan(heads, dv)
    panels = la.state_shape(heads, dk, dv)[0]

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)
    lowered = jax.jit(
        lambda *a: ls.state_step(*a, heads=heads, whole=whole, side=side,
                                 interpret=False), donate_argnums=0).lower(
        shape((layers, slots, panels, dk, 128)), shape((), jnp.int32),
        shape((slots,), jnp.bool_), shape((slots, dk, 128)),
        shape((slots, 3, panels, 128)))
    _names_no_source(lowered, b"linear_state")
    memory = lowered.compile().memory_analysis()
    assert memory.alias_size_in_bytes == layers * slots * panels * dk * 512
    assert memory.temp_size_in_bytes < 2 ** 20


# ---------------------------------------------------------------- four chips

def test_ring_attention_sp4_compiles(topo):
    mesh = MeshSpec(sp=4).build(devices=topo.devices)
    x = jax.ShapeDtypeStruct(
        (1, 8192, 12, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(("dp", "fsdp"), "sp", "tp", None)))
    compiled, text = _compile(_grads(make_ring_attention_fn(mesh)), x, x, x)
    assert "collective-permute" in text
    _fits(compiled)
