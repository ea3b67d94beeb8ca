"""Continuous-batching inference engine over the paged KV cache.

Reference analogs: vLLM's LLMEngine/Scheduler (continuous batching,
paged KV) and the reference repo's serve replicas; the model side is
``models/serving.py``'s record (``ServedModel``), asked once at construction:
what is a model's is named nowhere here.

The narrative is ARCHITECTURE.md's: "Continuous batching" (iteration-level
scheduling, the prefill and decode rungs, the loop one decode step ahead of
its own tokens, weights stored once), "The loop, kind of model by kind of
model" (a block's pass, latent pages, rows a decode slot, what a paged read
fetches, chunked prompts, an index pool, experts: what the loop does about
each and which ``stats()`` keys and region attributes say so) and
"Observability of the serving path" (the regions, a call's six boundaries
on three clocks).  ``InferenceEngine.stats()``'s docstring has the keys.
Below are the conditions the code relies on and does not show.

**One exec lane.**  Every dispatch (a prefill, a decode step and the fetch
behind it) runs on ONE single-thread executor, so the actor's event loop
keeps serving admissions and cancellations while XLA computes, and the pools
change in program order.  Between a donating dispatch and the loop taking
the result, ``_k_pages`` / ``_v_pages`` name deleted arrays: only the lane
may touch the pools.

**Pools donated and taken back.**  Both programs carry the pools through
their layer scan and the loop's two calls donate them, so a call's result
pools are its argument's buffers and no copy of a pool, whole or a layer's,
is made or held (``stats()["kv_pool_in_place"]`` says whether each program's
first loop call did come back so).  Either pool may be a tree of arrays, and
``_v_pages`` None (one pool): the loop hands them on, donates them and copies
them as the trees they are and never looks inside.  Callers outside the
loop, while it is idle, have two kinds of view of the same steps (the decode
views' program is the loop's less its last result, the chosen tokens:
``_decode_donating``, the same function under ``jax.jit``, beside the loop's
``_decode_next_donating``).  ``_prefill_program`` / ``_decode_program`` hand
them a copy of the pools they are given and never consume their arguments (a
test, a tool that wants both).  The three-result ``_prefill`` / ``_decode``
copy nothing, because a pool may be the largest thing on the chip (a looped
model's is ``ut_steps`` times a plain one's) and then no second one fits:
they CONSUME the pools they are given, and where those are the engine's own
the engine keeps the result as its pools, so the names the caller gets back
alias ``_k_pages`` / ``_v_pages`` and can go straight into the next such
call.  A readiness check made of them holds one pool.

**Page 0 is scratch.**  A prefill's padding (it lies after the prompt under
a causal mask) writes its K/V there, and a decode step's inactive slots are
parked there, so a shorter rung gives the logits and pages of a longer one
and the decode shape is always ``[max_batch]``; the allocator never hands
page 0 out.  A row of the page table keeps its pages in order, so the first
``W`` columns of a narrower table (``decode_rungs``) are the sequence's first
``W`` pages and what it leaves out is positions the step would have masked to
a weight of exactly 0: a narrow rung gives the top rung's logits (to the
rounding of a shorter sum) and pools.

**A sequence writes a position before any step reads it.**  Admission
reserves the worst case ``ceil((prompt + max_new) / page)`` pages up front
(kv_cache.py), so a sequence admitted is a sequence that finishes: the loop
never preempts and never runs out mid-decode.  What a readiness check, a
stray step or a block's denoise pass leaves in pages is inside some
sequence's own reservation or never read.  Rows a decode SLOT (the record's
``slot_rows``) are allocated and freed by nobody: a prefill is told the slot
its sequence will be stepped in and overwrites the rows whole, and the pools
thread through every call, so that prefill is ordered after the last step
that touched them.  A block model's page size is a multiple of its block, so
a block lies in one page.

**The pipe is drained before a prefill.**  At most one decode step is in
flight (``_Step``); it remembers its own ``{slot: sequence}``, and a token
goes to the sequence that was stepped, never to whoever holds the slot now.
The loop drains (the step in flight is fetched and delivered with nothing
queued behind it) when the batch about to be stepped is not the batch in
flight less those that end: an admission, whose prefill runs alone and whose
first token the host has to see; the last token of a batch; the chaos hook's
stall.  The step after a drain takes its tokens (a block model: its blocks'
state) from the host.  No step is dispatched for a sequence whose last token
is coming.  What the host cannot foresee (an ``eos_token``, a cancellation, a
deadline) retires the sequence when the loop learns of it; the step already
in flight still computes its slot, a **stray** slot step: its token is
dropped, its K/V row lands inside the sequence's own reservation, and a later
prefill into the freed pages is ordered after it on the device.  With
``prefill_chunk`` a prompt is a row of prefill calls back to back and ONE
prompt a pass of the loop; the constructor refuses the field for a model
whose record is not ``chunked``.  The host of a block model learns a step
late which masks a pass lifted, but a block without masks is committed by the
pass it is handed to, so it knows every ``pos0`` of the step it dispatches.

**No request meets a compile.**  Every rung's program (``prefill_rungs``,
``decode_rungs``: derived from ``max_prompt_len``, ``page_size`` and the
reservation's width alone, nothing configures them) is compiled while the
engine is constructed, ``_COMPILE_THREADS`` at a time on threads of their
own, and the loop admits nobody before all of them are there.

**What a failed step leaves.**  A failure surfaces at a dispatch or at the
fetch of the step in flight: every live and waiting caller gets it once, the
step in flight is dropped (its slots counted stray), and since a call that
fails after it was given the pools may have consumed them, the loop makes
fresh pools (no live sequence is left to own a page).

The parameters are stored once in the dtype the two programs read them in
(the record's ``stored``), and the engine keeps no reference to the caller's
tree (``stats()["weight_bytes"]``)."""

from __future__ import annotations

import asyncio
import bisect
import collections
import concurrent.futures
import dataclasses
import logging
import threading
import time
from typing import (Any, AsyncIterator, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

import numpy as np

from ray_tpu.serve import resilience
from ray_tpu.serve.engine.kv_cache import PageAllocator, table_row
from ray_tpu.util import tracing
from ray_tpu.util.tracing import region

logger = logging.getLogger(__name__)

_DONE = object()
# Threads that compile the rungs' programs at construction.  A program read
# from the compile cache is mostly the interpreter's time (trace, lower), so
# more threads than a few only contend: twelve programs of the largest model
# served were there after 30.5 s on twelve threads, 12.7 s on three (PERF.md,
# PR 35); cold, a few compiles side by side already fill the host's cores.
_COMPILE_THREADS = 4
# With no profiler session to carry them, one call in this many (a decode
# step, a prefill) has its exec lane's boundaries read on the thread CPU
# clocks too: seven system calls, 6 us each under the chip machine's
# sandbox, which read at every step cost chat's 10 ms step ~0.5% (PERF.md
# section 6, PR 36).  A session reads them at every call: the chip machine's
# clocks tick in 10 ms steps, and five traced seconds need every tick.
_CPU_EVERY = 16
# What else the actor's loop did between two decode steps' submissions, as
# ``rt:engine.decode.dispatch`` carries it: the growth of ``tracing``'s
# always-on sums (attribute -> key) over the interval of ``step_loop_cpu_us``.
# The streams' fan-out (``_private/worker_main.py``: a yield's ``store`` and
# ``after``) and the transport (``_private/protocol.py``: frames packed and
# written, frames parsed and handed on) are synchronous sections of that
# thread that nest neither in each other nor in the engine's regions, so with
# ``rt:engine.deliver`` and ``rt:engine.schedule`` they add up to no more
# than the step's loop time; the rest is the event loop itself.
_BESIDE = {"yields": "stream.yields", "stream_store_us": "stream.store_s",
           "stream_after_us": "stream.after_s", "rpc_out_us": "rpc.out_s",
           "rpc_in_us": "rpc.in_s", "msgs_out": "rpc.msgs_out",
           "msgs_in": "rpc.msgs_in", "frames_out": "rpc.frames_out",
           "frames_in": "rpc.frames_in"}


class _Clocks(NamedTuple):
    """One boundary of a call (seconds); the CPU clocks ``None`` where the
    call is not one of those sampled."""
    wall: float                 # time.perf_counter()
    cpu: Optional[float]        # the reading thread's CPU clock
    loop_cpu: Optional[float]   # the actor loop thread's, from any thread


def _us(seconds: float) -> int:
    """An attribute of a region: whole microseconds."""
    return int(seconds * 1e6)


def _counts(sums: Dict[str, float]) -> Dict[str, Any]:
    """``tracing.sums`` for ``stats()``: seconds (``*_s``) as they are, the
    counts as integers."""
    return {key: value if key.endswith("_s") else int(value)
            for key, value in sums.items()}


class _Step(NamedTuple):
    """A decode step the device has been handed and the host has not fetched:
    the one step the loop keeps in flight."""
    seqs: Dict[int, "_Sequence"]   # slot -> the sequence that was stepped
    nxt: Any                       # int32[max_batch] on the device: every
    #                                slot's next token, the next step's input
    #                                (a block model's: block_unmask's dict,
    #                                its "state" the next step's input)
    load: List[Any]                # an expert model's assignments, there too
    # its dispatch phase's two boundaries where no fetch followed it in its
    # call (a step dispatched on a drained pipe): the next fetch carries them
    alone: Optional[Tuple[_Clocks, _Clocks]]


@dataclasses.dataclass
class EngineConfig:
    model: str = "gpt"                 # "gpt" | "llama"
    model_config: Any = None           # GPTConfig/LlamaConfig; tiny default
    page_size: int = 8
    num_pages: int = 128               # pool size; page 0 is scratch
    max_batch: int = 8                 # decode slots per step
    max_prompt_len: int = 64           # multiple of page_size; the top
    #                                    rung of the prefill ladder, which
    #                                    is derived from it (prefill_rungs)
    max_new_tokens: int = 32           # per-request cap
    eos_token: Optional[int] = None
    dtype: Any = None                  # KV pool dtype (default: model's)
    prefill_chunk: int = 0             # > 0: a prompt runs as chunks of at
    #                                    most this many positions (whole
    #                                    pages), each against the
    #                                    sequence's own pages; 0: one call


def prefill_rungs(max_prompt_len: int, page_size: int) -> Tuple[int, ...]:
    """The padded lengths a prefill is compiled for, rising: 128 x 2^k, each
    rounded up to whole pages, as far as they stay below ``max_prompt_len``,
    and ``max_prompt_len`` itself.  A prompt runs at the least that holds it
    (``rung_for``), so a short prompt does not pay for ``max_prompt_len``
    positions; doubling keeps the programs few and the padding under half."""
    rungs, width = [], 128
    while True:
        rung = -(-width // page_size) * page_size
        if rung >= max_prompt_len:
            return (*rungs, max_prompt_len)
        if not rungs or rung > rungs[-1]:
            rungs.append(rung)
        width *= 2


def decode_rungs(maxp: int) -> Tuple[int, ...]:
    """The page-table widths, in pages, a decode step is compiled for,
    rising: multiples of ``ceil(maxp / 4)`` below ``maxp``, and ``maxp``
    itself (at most four programs).  A step runs at the least that holds
    the batch's longest live sequence (``rung_for``), so its paged read does
    not gather pages that nobody has reached.  Equal steps and not doubling:
    the longest of many live sequences sits in the upper half of ``maxp``
    most of the time, where doubling has one rung.  Four and not eight: a
    rung is a program to trace, lower and load before the first admission,
    about a second each for the largest model served (PERF.md, PR 35)."""
    step = -(-maxp // 4)
    return (*range(step, maxp, step), maxp)


def rung_for(rungs: Sequence[int], need: int) -> int:
    """The least rung that holds ``need`` (a prompt's positions on the
    prefill ladder, a batch's pages on the decode ladder)."""
    return rungs[bisect.bisect_left(rungs, need)]


class _Sequence:
    __slots__ = ("prompt", "max_new", "pages", "row", "queue", "generated",
                 "pos", "last_token", "cancelled", "slot", "prefilled",
                 "deadline", "queued", "block", "masked", "passes", "end",
                 "waited")

    def __init__(self, prompt: List[int], max_new: int,
                 deadline: Optional[float] = None, block: int = 0,
                 mask_token: int = 0):
        self.prompt = prompt
        self.max_new = max_new
        self.deadline = deadline       # absolute epoch seconds, or None
        self.pages: List[int] = []
        self.row: Optional[np.ndarray] = None
        self.queue: asyncio.Queue = asyncio.Queue()
        self.generated = 0
        self.pos = len(prompt)         # next KV write position
        if block:
            # A block model's: ``pos`` is its current block's first
            # position, and the block as the host last saw it (after the
            # last step it fetched) follows: the prompt's trailing part of
            # a block, then masks.
            tail = len(prompt) % block
            self.pos = len(prompt) - tail
            self.block = np.full((block,), mask_token, np.int32)
            self.block[:tail] = prompt[self.pos:]
            self.masked = np.arange(block) >= tail
            self.passes = 0
            # where its last block ends: the first multiple of the block
            # length at or after the last token asked for
            self.end = -(-(len(prompt) + max_new) // block) * block
        self.last_token: Optional[int] = None
        self.cancelled = False
        self.slot: Optional[int] = None
        self.prefilled = False
        self.queued = time.perf_counter()   # generate() to prefill: the wait
        self.waited = 0.0                   # ... as its first call found it


class InferenceEngine:
    """Paged continuous-batching engine; see module docstring."""

    def __init__(self, config: EngineConfig, params: Any = None,
                 rng_seed: int = 0):
        import jax
        from ray_tpu.models.serving import serving_model

        cfg = config
        if cfg.max_prompt_len % cfg.page_size:
            raise ValueError("max_prompt_len must be a multiple of "
                             f"page_size ({cfg.page_size})")
        # everything that is the model's: its programs, pools and config
        served = serving_model(cfg.model, cfg.model_config,
                               cfg.max_prompt_len + cfg.max_new_tokens)
        mc = served.config
        if mc.max_seq_len < cfg.max_prompt_len + cfg.max_new_tokens:
            raise ValueError(
                f"model max_seq_len {mc.max_seq_len} < max_prompt_len + "
                f"max_new_tokens ({cfg.max_prompt_len + cfg.max_new_tokens})")

        if cfg.prefill_chunk and (cfg.prefill_chunk % cfg.page_size
                                  or not served.chunked):
            raise ValueError(
                f"prefill_chunk={cfg.prefill_chunk} must be whole pages of "
                f"{cfg.page_size}, for a model whose prefill can start at a "
                "position other than 0 and read what lies before it from "
                "the pages (the record's ``chunked``): a recurrent state or "
                "a convolution's tail is not addressable by position, and "
                "the scans that make them take no initial row")
        self.config = cfg
        self.model_config = mc
        self._tree = jax.tree
        # positions a decode step yields a sequence: 0 is one, by one token
        self._block = served.block
        # Stored once as the two programs read them (module docstring); the
        # caller's tree is not kept, so what was cast is the caller's to free.
        self._params = served.stored(
            params if params is not None else
            served.init(jax.random.PRNGKey(rng_seed), mc), mc)
        self._weight_bytes = sum(
            leaf.nbytes for leaf in jax.tree.leaves(self._params))
        # The model's kind of pages: K and V pools, or one pool of latent
        # pages and None where the V pool would be (models/llama.py).
        self._new_pools = lambda: served.new_pools(
            cfg.num_pages, cfg.page_size, cfg.dtype, cfg.max_batch)
        self._k_pages, self._v_pages = self._new_pools()
        # of the pools' arrays, those that hold a row a slot and not pages
        self._slot_rows = served.slot_rows is not None
        self._page_kind = served.page_kind
        # what a parameter of the routed experts takes as stored
        self._expert_itemsize = served.expert_stack(
            self._params)["wd"].dtype.itemsize if served.expert_stack else 0
        self._recurrent_state_bytes = sum(
            a.nbytes for a in served.slot_rows(
                self._k_pages, self._v_pages)) if self._slot_rows else 0
        self._kv_pool_bytes = sum(p.nbytes for p in self._pools()) \
            - self._recurrent_state_bytes
        # of the rows, the short convolutions' last inputs (a model of conv
        # layers keeps those alone)
        self._conv_tail_bytes = served.conv_tails(
            self._k_pages, self._v_pages).nbytes if served.conv_tails else 0
        # of the pages, a sparse-attention indexer's keys (a second pool a
        # position, addressed by the same page table), and the positions a
        # step's attention reads at most
        self._index_pool_bytes = served.index_pool(
            self._k_pages, self._v_pages).nbytes if served.index_pool else 0
        self._select_topk = served.select_topk
        self._alloc = PageAllocator(cfg.num_pages)
        self._maxp = -(-(cfg.max_prompt_len + cfg.max_new_tokens)
                       // cfg.page_size)

        # Shapes fixed ([1, rung] prefill, one compile a rung of the ladder
        # derived from max_prompt_len; [max_batch] decode with a table of
        # [max_batch, rung], one compile a rung of the ladder derived from
        # maxp), so the steady-state loop never re-traces.  The parameters
        # are arguments, not closed over: as constants they would be part of
        # the program and of its compile-cache key, one copy per entry point.
        # Both donate the pools (module docstring).
        def _prefill(params, tokens, length, kp, vp, pt, slot=0, *start):
            return served.prefill(params, mc, tokens, length, kp, vp, pt,
                                  slot, *start)

        def _decode(params, token, pos, kp, vp, pt):
            return served.step(params, mc, token, pos, kp, vp, pt)

        # The loop's decode step: the same, and what the next step is fed
        # (every slot's next token; a block model's next state, its [max_batch,
        # B, V] logits then no result) chosen where the logits are, so that
        # the step after it can take it from the device.  Under the same
        # name: the device's timeline and its readers know the program as
        # ``jit__decode``.
        def _decode_next(params, token, pos, kp, vp, pt):
            logits, *rest = _decode(params, token, pos, kp, vp, pt)
            logits, nxt = served.feed(mc, logits, token, pos)
            return (logits, *rest, nxt)
        _decode_next.__name__ = _decode.__name__

        self._prefill_donating = jax.jit(_prefill, donate_argnums=(3, 4))
        self._decode_donating = jax.jit(_decode, donate_argnums=(3, 4))
        self._decode_next_donating = jax.jit(_decode_next,
                                             donate_argnums=(3, 4))
        self._kv_in_place: Dict[str, bool] = {}
        # What stats() says about where this engine runs: the device that
        # holds the KV pool, and how long each program took to be there
        # (a rung's trace + compile or cache load).
        dev = next(iter(self._k_pages.devices()))
        self._device = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": jax.device_count()}
        self._first_call_s: Dict[str, float] = {}
        # The loop's prefill programs: ``_prefill_donating`` compiled for
        # every rung, now, on a few threads, so whatever the caller does
        # between construction and its first request hides them.  The views
        # keep the jitted function, which takes any [1, S] and any tree.
        # The loop's decode programs likewise: ``_decode_next_donating``
        # compiled for every width of the decode ladder.
        # (under ``prefill_chunk`` no call is wider than a chunk)
        self._rungs = prefill_rungs(
            min(cfg.prefill_chunk or cfg.max_prompt_len, cfg.max_prompt_len),
            cfg.page_size)
        # what each rung's attention runs as ("flash": the kernel, "dense";
        # a chunk over latent pages by its own kernel: "latent_chunk")
        self._rung_attention = {
            rung: served.prefill_attention(mc, rung, bool(cfg.prefill_chunk))
            for rung in self._rungs}
        self._decode_rungs = decode_rungs(self._maxp)
        # what the decode programs read the pages with ("kernel": each
        # sequence's own pages copied where they lie; "gather")
        self._paged_read = served.paged_read(mc, self._k_pages)
        # what they step the linear layers' states with ("kernel", "rule";
        # None: the model has no such layer)
        self._linear_state = served.linear_state and served.linear_state(
            mc, self._v_pages)
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            (self._params, self._k_pages, self._v_pages))
        pool = concurrent.futures.ThreadPoolExecutor(
            min(_COMPILE_THREADS, len(self._rungs) + len(self._decode_rungs)),
            thread_name_prefix="rt-engine-compile")
        self._rung_programs = {
            rung: pool.submit(self._compile_rung, rung, *shapes)
            for rung in self._rungs}
        self._decode_programs = {
            width: pool.submit(self._compile_decode_rung, width, *shapes)
            for width in self._decode_rungs}
        pool.shutdown(wait=False)    # the threads end with their compiles
        self._prefill_shapes = dict.fromkeys(self._rungs, 0)
        self._prefill_attention = {"dense": 0, "flash": 0, "latent_chunk": 0}
        self._decode_shapes = dict.fromkeys(self._decode_rungs, 0)
        self._decode_paged_read = {"gather": 0, "kernel": 0}
        self._decode_linear_state = {"kernel": 0, "rule": 0}

        self._waiting: collections.deque = collections.deque()
        self._active: Dict[int, _Sequence] = {}   # slot -> sequence
        self._free_slots: List[int] = list(range(cfg.max_batch - 1, -1, -1))
        self._wake = asyncio.Event()
        self._loop_task: Optional[asyncio.Task] = None
        self._steps = 0
        self._flight: Optional[_Step] = None   # the decode step in flight
        # Always-on counters, see stats().
        self._decode_ahead_steps = 0
        self._stray_slot_steps = 0
        self._admitted = 0
        self._queue_wait_s = 0.0
        self._prefill_tokens = 0
        self._prefill_padded_tokens = 0
        self._slot_steps = 0
        self._retired = {"done": 0, "cancelled": 0, "expired": 0,
                         "error": 0}
        self._moe = {"moe_assignments": 0, "moe_assignments_made": 0,
                     "moe_rows_offered": 0, "moe_experts_hit": 0,
                     "moe_load_max": 0}
        self._moe_load: Optional[np.ndarray] = None   # [layers, held]
        self._block_stats = {
            "slot_steps_denoise": 0, "slot_steps_commit": 0,
            "blocks_committed": 0, "tokens_committed": 0,
            "tokens_dropped_tail": 0, "tokens_dropped_stray": 0,
            "denoise_passes_by_count": dict.fromkeys(
                range(1, mc.denoise_steps + 1), 0),
            "unmasked_by_threshold": 0, "unmasked_by_count": 0} \
            if self._block else None
        self._kv_live_token_steps = 0
        self._kv_gathered_token_steps = 0
        self._state_rows_written = 0
        self._prefill_chunks = 0
        self._prefill_chunk_tokens = 0
        self._selected_positions = 0
        self._host_s = dict.fromkeys(
            ("schedule", "submit", "dispatch", "fetch", "resume", "deliver"),
            0.0)
        self._host_cpu_s = dict.fromkeys(
            ("exec_dispatch", "exec_fetch", "loop", "loop_in_dispatch",
             "loop_in_fetch", "loop_in_resume"), 0.0)
        self._calls = 0          # submissions to the exec lane
        self._cpu_sampled = 0    # of them, those read on the CPU clocks too
        self._loop_cpu_clock: Optional[int] = None   # set by _run_loop
        self._sums_from = self._beside_now()         # see _beside()
        tracing.watch_gc()
        # Single lane for XLA dispatches: the device serializes anyway,
        # and one lane keeps (k_pages, v_pages) updates ordered.
        self._exec = concurrent.futures.ThreadPoolExecutor(
            1, thread_name_prefix="rt-engine")

    def _pools(self) -> List[Any]:
        """The pool arrays the engine holds: two, a latent model's one, or
        every array of a model whose pools are trees."""
        return self._tree.leaves((self._k_pages, self._v_pages))

    def _pools_deleted(self) -> bool:
        return any(p.is_deleted() for p in self._pools())

    # ------------------------------------------------------------- public

    async def generate(self, tokens: Sequence[int],
                       max_new_tokens: Optional[int] = None,
                       deadline: Optional[float] = None
                       ) -> AsyncIterator[int]:
        """Admit one sequence; yields generated token ids as they decode.
        Closing the iterator early (client disconnect) cancels the
        sequence and frees its pages at the next step boundary.  An
        absolute ``deadline`` (epoch seconds) bounds the whole request:
        expiry raises DeadlineExceeded to the consumer AND retires the
        sequence inside the batch loop — its slot and KV pages free at
        the next step boundary instead of decoding tokens nobody reads."""
        tokens = [int(t) for t in tokens]
        if not tokens:
            raise ValueError("empty prompt")
        if len(tokens) > self.config.max_prompt_len:
            raise ValueError(f"prompt length {len(tokens)} exceeds "
                             f"max_prompt_len {self.config.max_prompt_len}")
        max_new = min(max_new_tokens or self.config.max_new_tokens,
                      self.config.max_new_tokens)
        self._ensure_loop()
        seq = _Sequence(tokens, max_new, deadline, self._block,
                        self._block and self.model_config.mask_token)
        self._waiting.append(seq)
        self._wake.set()
        try:
            while True:
                if seq.deadline is None:
                    item = await seq.queue.get()
                else:
                    rem = seq.deadline - time.time()
                    if rem <= 0:
                        raise resilience.DeadlineExceeded(
                            "deadline expired while decoding")
                    try:
                        item = await asyncio.wait_for(seq.queue.get(), rem)
                    except asyncio.TimeoutError:
                        raise resilience.DeadlineExceeded(
                            "deadline expired while decoding") from None
                if item is _DONE:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            seq.cancelled = True
            self._wake.set()

    def stats(self) -> Dict[str, Any]:
        """Gauges (``active``, ``waiting``, ``free_pages``; ``prefilling``: of
        the active, the admitted sequences whose prompt's prefill has not
        ended, which under ``prefill_chunk`` wait a prompt a pass) and counters
        since the engine started: ``steps`` decode steps dispatched, of them
        ``decode_ahead_steps`` while the step before was in flight (the
        rest on a drained pipe: module docstring), and the
        ``slot_steps`` live slots they carried (occupancy is
        ``slot_steps / (steps * max_batch)``), of them ``stray_slot_steps``
        whose token nobody got (the sequence was retired with the step in
        flight, or the step was dropped by a failure: every other slot step
        is a token delivered), ``admitted`` sequences and
        the ``queue_wait_s`` they spent between ``generate()`` and their
        prefill's dispatch, ``prefill_tokens`` of prompt against the
        ``prefill_padded_tokens`` the padded programs ran (a prefill adds
        its rung), ``prefill_shapes``, the prefills by rung, and
        ``prefill["attention"]``, the prefills by what their rung's
        attention ran as ("flash": the kernel, "dense", "latent_chunk": a
        chunk's kernel over latent pages),
        ``decode_shapes``, the decode steps by the width of their page table
        in pages (a rung of ``decode_rungs``; they sum to ``steps``),
        ``decode["paged_read"]``, the decode steps by what their program
        reads the pages with ("kernel", "gather"),
        ``decode["linear_state"]``, the decode steps by what their program
        steps the linear layers' states with ("kernel": each slot's rows read
        once and written where they lie; "rule": the jnp step on the layer's
        slab; both 0 for a model with no linear layer),
        ``retired`` sequences by reason, and of a model with experts the
        ``moe_assignments`` of real tokens (token x layer x k), the
        ``moe_experts_hit`` (distinct experts a step touched, summed over
        layers and steps: over ``layers x num_experts`` a step, the share
        of expert weights it had to read) and ``moe_load_max`` (the largest
        single-expert load, summed likewise: over ``moe_assignments /
        num_experts``, how uneven the routing was); the experts are those
        the program HOLDS (``LlamaConfig.expert_share``), so beside them
        ``moe_assignments_made`` counts every real token's assignments,
        held or not (their ratio is the share that fell on this program's
        experts; 1 where it holds them all), ``moe_rows_offered`` the same
        count for every row the programs' shapes offered (a prefill's rung,
        a step's slots: ``moe_assignments_made`` over it is the share of
        rows the experts' products saw, the rest padding and idle slots
        routed nowhere), and ``moe_load`` [expert
        layers, held experts] is the held experts' load since the start.
        ``weight_bytes`` is
        the size of the parameters as the engine stores them,
        ``kv_pool_bytes`` that of the K and V pools or, where
        ``kv_page_kind`` is "latent" and not "kv", of the one pool of latent
        pages, ``kv_pool_layers``
        their leading dimension (a layer for every pass of a looped model)
        and ``kv_bytes_per_token`` what one cached position takes in all of
        them, and ``kv_pool_in_place`` says of each program ("prefill",
        "decode"), once the loop has called it, whether that first call's
        result pools lay in its arguments' buffers (the donation was used).
        ``index_pool_bytes`` is, of ``kv_pool_bytes``, the pool of a
        sparse-attention indexer's keys (0: the model has none);
        ``prefill_chunks`` counts the prefill calls that ran a CHUNK of a
        prompt under ``prefill_chunk`` (every call of every prompt then, a
        short prompt's one) and ``prefill_chunk_tokens`` their real
        positions; ``dsa_selected_positions`` sums over decode steps the
        positions the live sequences' attention READ, ``min(index_topk, pos
        + 1)`` each, against the ``dsa_live_positions`` they held (both 0 for
        a model that selects nothing).
        A model with rows a decode slot (module docstring) adds
        ``recurrent_state_bytes``, what those rows take for all slots
        (``conv_tail_bytes`` of it the short convolutions' last inputs and
        ``recurrent_matrix_bytes`` the state matrices: 0 for a model of conv
        layers, whose slots keep tails alone),
        ``state_rows_written``, the prefills that wrote a slot's, and
        ``recurrent_step_bytes_share``: of the bytes the decode steps moved
        so far (the weights once a step, the live positions' pages, the live
        slots' rows read and written), the rows' share.
        ``kv_live_token_steps`` sums over decode steps the positions the
        live sequences held (``pos + 1`` each), ``kv_gathered_token_steps``
        the positions the step's paged read fetched per pool layer
        (``max_batch x W x page_size``, ``W`` the step's rung, under the
        gather; every slot's positions in whole pages under the kernel:
        ``decode["paged_read"]`` counts the steps by which): their ratio
        is the share of the read that was of use.  ``first_call_s`` says
        how long each program took to be there: ``prefill@<rung>`` and
        ``decode@<pages>`` that rung's trace and compile (or load from the
        compile cache) at construction, beside the other rungs'.
        ``host_s`` is the wall seconds the per-token path has taken since the
        engine started, by phase, the phases tiling the loop's busy time:
        ``schedule`` (a delivery's end, or the loop's waking, to the next
        ``run_in_executor``), ``submit`` (to the exec lane running),
        ``dispatch`` and ``fetch`` (the two regions of a decode call:
        ``dispatch`` sends step N+1, ``fetch`` is then the wait for step N's
        tokens with N+1 queued behind it, so over a settled batch it is the
        device's step less the host's other phases, and the device idles
        only where the phases together outlast its step; on a drained pipe
        a call has the one or the other; a prefill's
        whole call counts under ``fetch``, its lane sends and waits in one
        region), ``resume`` (the lane's return to ``_deliver``) and
        ``deliver``.  ``host_cpu_s`` is CPU seconds beside them:
        ``loop`` the loop thread's from each decode step's submission (or
        the loop's waking) to the next: everything a step costs the loop, so
        ``loop`` over the sum of ``host_s`` is how full that thread is.  The
        other five are sums over ``host_cpu_calls`` of the lane's calls
        (decode steps, drains and prefills: every one while a profiler
        session records, one in ``_CPU_EVERY`` otherwise; ``steps`` +
        ``admitted`` + a drain for every step not dispatched ahead in
        all), so compare them per call: ``exec_dispatch`` and ``exec_fetch``
        the exec lane's own (``host_s["dispatch"]`` a call less
        ``exec_dispatch`` a sampled call is what the lane spent inside
        ``dispatch`` not running: the GIL, a lock of the runtime;
        ``host_s["fetch"]`` less ``exec_fetch`` its wait on the device),
        ``loop_in_dispatch`` / ``loop_in_fetch`` / ``loop_in_resume`` the
        actor loop thread's in those phases.  Plain additions on the loop
        thread from the clock reads that feed the regions' attributes; no
        lock.  ``gc`` is ``tracing.gc_stats()``: this process's collector
        passes.  ``stream`` and ``rpc`` are this PROCESS's always-on sums
        (``tracing.sums``) of what the actor's loop does beside the engine:
        ``stream`` the streamed ``yields`` and the wall seconds of the
        loop's own work on them (``store_s``, and ``after_s`` from the ack's
        arrival to the next step of the body; ``store_aside_s`` the stores
        that went to the object store behind awaits), ``rpc`` the
        transport's (``msgs_out`` / ``msgs_in``, ``frames_*``, ``bytes_*``;
        ``out_s`` packing and writing frames, ``in_s`` parsing them and
        handing their messages on).  Read twice, the growth of
        ``store_s + after_s`` and of ``out_s + in_s`` over the growth of
        ``steps`` is the streams' and the transport's share of
        ``host_cpu_s["loop"]`` a step.  What costs a loop a message or
        spans an await grows only while a profiler session records
        (``LLMServer.profile``): ``stream``'s ``wait_s`` (for the body's
        next value) and ``ack_s`` (for the owner's ack), and the messages
        by kind and type under ``rpc``'s ``out`` and ``in``
        (``request.stream_yield``, ``reply``)."""
        return {"active": len(self._active), "waiting": len(self._waiting),
                "prefilling": sum(not seq.prefilled
                                  for seq in self._active.values()),
                "free_pages": self._alloc.free_pages, "steps": self._steps,
                "decode_ahead_steps": self._decode_ahead_steps,
                "slot_steps": self._slot_steps,
                "stray_slot_steps": self._stray_slot_steps,
                "admitted": self._admitted,
                "queue_wait_s": self._queue_wait_s,
                "prefill_tokens": self._prefill_tokens,
                "prefill_padded_tokens": self._prefill_padded_tokens,
                "prefill_shapes": dict(self._prefill_shapes),
                "prefill": {"attention": dict(self._prefill_attention)},
                "decode": {"paged_read": dict(self._decode_paged_read),
                           "linear_state": dict(self._decode_linear_state)},
                "decode_shapes": dict(self._decode_shapes),
                "retired": dict(self._retired), **self._moe,
                **({} if self._moe_load is None else
                   {"moe_load": self._moe_load.tolist()}),
                **({"block": {**self._block_stats, "denoise_passes_by_count":
                              dict(self._block_stats[
                                  "denoise_passes_by_count"])}}
                   if self._block else {}),
                "weight_bytes": self._weight_bytes,
                "kv_pool_bytes": self._kv_pool_bytes,
                "kv_page_kind": self._page_kind,
                "kv_pool_layers": self._k_pages.shape[0],
                "kv_bytes_per_token": self._kv_pool_bytes // (
                    self.config.num_pages * self.config.page_size),
                "kv_live_token_steps": self._kv_live_token_steps,
                "kv_gathered_token_steps": self._kv_gathered_token_steps,
                "kv_pool_in_place": dict(self._kv_in_place),
                "index_pool_bytes": self._index_pool_bytes,
                "prefill_chunks": self._prefill_chunks,
                "prefill_chunk_tokens": self._prefill_chunk_tokens,
                "dsa_selected_positions": self._selected_positions,
                "dsa_live_positions": self._kv_live_token_steps
                if self._select_topk else 0,
                **self._recurrent_stats(),
                "device": self._device,
                "first_call_s": dict(self._first_call_s),
                "host_s": dict(self._host_s),
                "host_cpu_s": dict(self._host_cpu_s),
                "host_cpu_calls": self._cpu_sampled,
                "gc": tracing.gc_stats(),
                "stream": _counts(tracing.sums("stream.")),
                "rpc": {**_counts(tracing.sums("rpc.")),
                        "out": _counts(tracing.sums("msgs.out.")),
                        "in": _counts(tracing.sums("msgs.in."))}}

    def _recurrent_stats(self) -> Dict[str, Any]:
        """``stats()`` of a model with rows a slot (none of another)."""
        if not self._slot_rows:
            return {}
        # a live slot's rows are read and written once a step
        rows = 2 * self._recurrent_state_bytes // self.config.max_batch \
            * self._slot_steps
        pages = self._kv_pool_bytes // (
            self.config.num_pages * self.config.page_size) \
            * self._kv_live_token_steps
        step_bytes = self._weight_bytes * self._steps + pages + rows
        return {"recurrent_state_bytes": self._recurrent_state_bytes,
                "conv_tail_bytes": self._conv_tail_bytes,
                "recurrent_matrix_bytes": self._recurrent_state_bytes
                - self._conv_tail_bytes,
                "state_rows_written": self._state_rows_written,
                "recurrent_step_bytes_share":
                    rows / step_bytes if step_bytes else 0.0}

    def close(self):
        if self._loop_task is not None:
            self._loop_task.cancel()
            self._loop_task = None
        self._exec.shutdown(wait=False)

    # ----------------------------------------------------------- internals

    # The two programs for callers that run while the loop is idle (a
    # test, a tool): the loop's own executables, handed a copy of the
    # pools they are given, so the caller's arrays (the engine's pools
    # among them) are left alive and as they were.

    @staticmethod
    def _on_copies(step, params, a, b, kp, vp, pt, *slot):
        import jax
        import jax.numpy as jnp
        return step(params, a, b, *jax.tree.map(jnp.copy, (kp, vp)), pt,
                    *slot)

    def _prefill_program(self, *args):
        return self._on_copies(self._prefill_donating, *args)

    def _decode_program(self, *args):
        return self._on_copies(self._decode_donating, *args)

    # (logits, k_pages, v_pages) of either, with no copy (module
    # docstring): the pools that go in are consumed, and the engine's own
    # are replaced by what comes out.  An expert model's fourth result
    # stays behind.

    def _consuming(self, step, params, a, b, kp, vp, pt, *slot):
        own = kp is self._k_pages and vp is self._v_pages
        try:
            logits, kp, vp = step(params, a, b, kp, vp, pt, *slot)[:3]
        except Exception:
            if own and self._pools_deleted():
                self._k_pages, self._v_pages = self._new_pools()
            raise
        if own:
            self._k_pages, self._v_pages = kp, vp
        return logits, kp, vp

    def _prefill(self, *args):
        return self._consuming(self._prefill_donating, *args)

    def _decode(self, *args):
        return self._consuming(self._decode_donating, *args)

    def _compiled(self, name: str, step, *shapes):
        """``step`` (a donating program) lowered and compiled for ``shapes``,
        on a compile thread; ``first_call_s[name]`` says how long it took."""
        t0 = time.perf_counter()
        program = step.lower(*shapes).compile()
        self._first_call_s[name] = time.perf_counter() - t0
        return program

    def _compile_rung(self, rung: int, params, kp, vp):
        """``_prefill_donating`` compiled for [1, ``rung``] tokens from the
        shapes of the engine's tree and pools."""
        import jax
        import jax.numpy as jnp
        return self._compiled(
            f"prefill@{rung}", self._prefill_donating, params,
            jax.ShapeDtypeStruct((1, rung), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32), kp, vp,
            jax.ShapeDtypeStruct((1, self._maxp), jnp.int32),
            # the slot and, under ``prefill_chunk``, the chunk's start
            *[jax.ShapeDtypeStruct((), jnp.int32)] * (
                1 + bool(self.config.prefill_chunk)))

    def _compile_decode_rung(self, width: int, params, kp, vp):
        """``_decode_next_donating`` compiled for a page table of
        [max_batch, ``width``] from the same shapes."""
        import jax
        import jax.numpy as jnp
        slots = jax.ShapeDtypeStruct((self.config.max_batch,), jnp.int32)
        token = slots
        if self._block:              # a block's state in the tokens' place
            rows = (self.config.max_batch, self._block)
            token = (jax.ShapeDtypeStruct(rows, jnp.int32),
                     jax.ShapeDtypeStruct(rows, jnp.bool_), slots, slots)
        return self._compiled(
            f"decode@{width}", self._decode_next_donating, params, token,
            slots, kp, vp,
            jax.ShapeDtypeStruct((self.config.max_batch, width), jnp.int32))

    def _donate_pools(self, program: str, step, a, b, pt, *slot):
        """One call of ``step`` (a donating program) on the engine's pools,
        on the exec lane: its results, the pools among them for the loop to
        take.  The first call of each program notes whether they came back
        in the buffers that went in."""
        kp, vp = self._k_pages, self._v_pages
        if program in self._kv_in_place:
            return step(self._params, a, b, kp, vp, pt, *slot)
        before = [p.unsafe_buffer_pointer() for p in self._pools()]
        out = step(self._params, a, b, kp, vp, pt, *slot)
        self._kv_in_place[program] = before == [
            p.unsafe_buffer_pointer() for p in self._tree.leaves(out[1:3])]
        return out

    def _ensure_loop(self):
        if self._loop_task is None or self._loop_task.done():
            self._loop_task = asyncio.get_running_loop().create_task(
                self._run_loop())

    def _pages_needed(self, seq: _Sequence) -> int:
        return -(-(len(seq.prompt) + seq.max_new) // self.config.page_size)

    @staticmethod
    def _deadline_expired(seq: _Sequence) -> bool:
        return seq.deadline is not None and time.time() > seq.deadline

    def _admit(self):
        while self._waiting and self._free_slots:
            seq = self._waiting[0]
            if seq.cancelled:
                self._waiting.popleft()
                continue
            if self._deadline_expired(seq):
                # Expired while queued: reject instead of spending pages
                # and decode steps on a request nobody is waiting for.
                self._waiting.popleft()
                seq.queue.put_nowait(resilience.DeadlineExceeded(
                    "deadline expired while waiting for admission"))
                continue
            need = self._pages_needed(seq)
            if not self._alloc.can_alloc(need):
                if not self._active:
                    # Nothing will ever free up: the request exceeds the
                    # whole pool.  Fail it instead of parking forever.
                    self._waiting.popleft()
                    seq.queue.put_nowait(MemoryError(
                        f"request needs {need} KV pages, pool has "
                        f"{self._alloc.free_pages} free and 0 active"))
                    continue
                break   # head-of-line waits for a retire
            self._waiting.popleft()
            seq.pages = self._alloc.alloc(need)
            seq.row = table_row(seq.pages, self._maxp)
            seq.slot = self._free_slots.pop()
            self._active[seq.slot] = seq
            self._admitted += 1

    def _retire(self, seq: _Sequence, reason: str):
        """Free the sequence's slot and pages; ``reason`` is its key in
        ``stats()["retired"]``, and "done" also ends the caller's stream."""
        self._active.pop(seq.slot, None)
        self._free_slots.append(seq.slot)
        seq.slot = None
        if seq.pages:
            self._alloc.free(seq.pages)
            seq.pages = []
        self._retired[reason] += 1
        if reason == "done":
            seq.queue.put_nowait(_DONE)

    def _sweep(self):
        for seq in [s for s in self._active.values() if s.cancelled]:
            self._retire(seq, "cancelled")
        # Deadline sweep: an expired sequence stops decoding NOW — its
        # slot and KV pages free for live requests and the rest of the
        # batch keeps stepping unharmed.
        for seq in [s for s in self._active.values()
                    if self._deadline_expired(s)]:
            self._retire(seq, "expired")
            if not seq.cancelled:
                seq.queue.put_nowait(resilience.DeadlineExceeded(
                    "deadline expired while decoding"))

    def _steppable(self) -> Dict[int, _Sequence]:
        """slot -> sequence of the next decode step: every live sequence but
        those whose last token is already on its way (``generated`` and the
        token in flight make ``max_new``).  The host counts; it needs no
        token for that."""
        flying = self._flight.seqs if self._flight is not None else {}
        if self._block:
            # a block model's last tokens are coming when the step in
            # flight was handed its last block whole: that step commits it
            return {slot: seq for slot, seq in self._active.items()
                    if not (flying.get(slot) is seq and not seq.masked.any()
                            and seq.pos + self._block >= seq.end)}
        return {slot: seq for slot, seq in self._active.items()
                if seq.prefilled
                and seq.generated + (flying.get(slot) is seq) < seq.max_new}

    def _block_inputs(self, stepped: Dict[int, _Sequence]):
        """``_decode_inputs`` of a block model: the blocks' state where the
        one-token step has ``token`` (the host's mirror of it on a drained
        pipe, the step in flight's result on the device otherwise), the
        sequences' ends where it has ``pos`` (0 parks a slot), and the
        tables, wide enough for the block each sequence is AT: the host
        fetched the state the step in flight was handed, a block without
        masks is committed by the pass it is handed to, so the host knows
        every ``pos0`` of the step it dispatches though not yet its masks."""
        cfg, B = self.config, self._block
        flying = self._flight.seqs if self._flight is not None else {}
        at = {slot: seq.pos + B * (flying.get(slot) is seq
                                   and not seq.masked.any())
              for slot, seq in stepped.items()}
        width = rung_for(self._decode_rungs,
                         (max(at.values()) + B - 1) // cfg.page_size + 1)
        end = np.zeros((cfg.max_batch,), np.int32)
        tables = np.zeros((cfg.max_batch, width), np.int32)
        for slot, seq in stepped.items():
            end[slot] = seq.end
            tables[slot] = seq.row[:width]
        # positions held: what is committed and the block
        live_tokens = sum(at.values()) + B * len(at)
        if self._flight is not None:
            return self._flight.nxt["state"], end, tables, live_tokens
        tokens = np.zeros((cfg.max_batch, B), np.int32)
        masked = np.zeros((cfg.max_batch, B), np.bool_)
        pos0 = np.zeros((cfg.max_batch,), np.int32)
        passes = np.zeros((cfg.max_batch,), np.int32)
        for slot, seq in stepped.items():
            tokens[slot], masked[slot] = seq.block, seq.masked
            pos0[slot], passes[slot] = seq.pos, seq.passes
        return (tokens, masked, pos0, passes), end, tables, live_tokens

    def _follows_flight(self, stepped: Dict[int, _Sequence]) -> bool:
        """Whether ``stepped`` can be dispatched behind the step in flight,
        its tokens taken from that step's result on the device: somebody is
        stepped, and each of them was stepped by it, in the same slot."""
        flying = self._flight.seqs
        return bool(stepped) and all(
            flying.get(slot) is seq for slot, seq in stepped.items())

    def _decode_inputs(self, stepped: Dict[int, _Sequence]):
        """One batched decode step's host arrays over the slots of
        ``stepped``.  Every other slot runs at pos 0 against an all-zero
        table row — its write lands in scratch page 0.  The table is as
        wide as the decode ladder's least rung that holds the longest
        stepped sequence: the step writes at ``pos`` and reads ``pos + 1``
        positions, so the page that holds ``pos`` is the last it needs,
        and a row's first pages are the sequence's first pages.  ``token``
        is the sequences' last tokens as the host knows them or, with a
        step in flight, that step's result where it lies on the device.
        Last, the positions the stepped sequences hold (``live_tokens``)."""
        cfg = self.config
        if self._block:
            return self._block_inputs(stepped)
        longest = max(seq.pos for seq in stepped.values())
        width = rung_for(self._decode_rungs, longest // cfg.page_size + 1)
        pos = np.zeros((cfg.max_batch,), np.int32)
        tables = np.zeros((cfg.max_batch, width), np.int32)
        for slot, seq in stepped.items():
            pos[slot] = seq.pos
            tables[slot] = seq.row[:width]
        # what the step's paged read is for
        live_tokens = int(pos.sum()) + len(stepped)
        if self._flight is not None:
            return self._flight.nxt, pos, tables, live_tokens
        token = np.zeros((cfg.max_batch,), np.int32)
        for slot, seq in stepped.items():
            token[slot] = seq.last_token
        return token, pos, tables, live_tokens

    def _clocks(self, cpu: bool, on_loop: bool = False) -> _Clocks:
        """Now: the wall first (what the regions' neighbours are held to),
        then with ``cpu`` the two thread clocks.  ``on_loop`` says the
        caller is the loop thread, whose own CPU clock is the third (one
        system call, not two)."""
        wall = time.perf_counter()
        if not cpu:
            return _Clocks(wall, None, None)
        own = time.thread_time()
        return _Clocks(wall, own, own if on_loop
                       else time.clock_gettime(self._loop_cpu_clock))

    def _submit(self, cpu: bool = False) -> Tuple[_Clocks, bool]:
        """The loop thread hands a call to the exec lane: the boundary, with
        the loop's CPU clock where ``cpu`` asks for it, and whether the
        call's later boundaries read the CPU clocks (``_CPU_EVERY``)."""
        sampled = tracing.recording() or self._calls % _CPU_EVERY == 0
        self._calls += 1
        submitted = self._clocks(cpu, on_loop=True)
        self._host_s["schedule"] += submitted.wall - self._loop_free_at
        return submitted, sampled

    def _loop_woke(self):
        """The loop thread starts a stretch of work: ``schedule`` and the
        next decode step's ``step_us`` count from here."""
        self._step_from = self._clocks(True, on_loop=True)
        self._loop_free_at = self._step_from.wall
        self._sums_from = self._beside_now()

    @staticmethod
    def _beside_now() -> List[float]:
        """The sums of ``_BESIDE`` as they stand, in its order."""
        sums = tracing.accumulator()
        return [sums.get(key, 0.0) for key in _BESIDE.values()]

    def _beside(self) -> Dict[str, int]:
        """``_BESIDE`` since the decode step's submission before (or the
        loop's waking): whole microseconds and counts."""
        before, self._sums_from = self._sums_from, self._beside_now()
        return {attr: (_us if attr.endswith("_us") else int)(now - was)
                for attr, now, was in zip(_BESIDE, self._sums_from, before)}

    def _deliver(self, tokens: List[Tuple[_Sequence, int]],
                 submitted: _Clocks, lane: Tuple[_Clocks, _Clocks, _Clocks],
                 **told):
        """Push each sequence's token to its caller and retire what
        finished; every call of the exec lane ends here, one that fetched
        nothing (a step dispatched on a drained pipe) with no tokens.
        ``lane`` is the exec thread's three boundaries of the call
        (dispatch start, dispatch end, returned; the first two are one
        where it dispatched nothing, the last two where it fetched
        nothing), ``submitted`` the loop's before it: the call's phases are
        added to ``stats()``'s sums here, and those that no region has
        carried yet ride on this one.  A sequence may have several tokens
        in the list (a block model's); what follows the one that finished
        it is not pushed.  ``told`` is what a block model's fetched step
        said of itself."""
        start, sent, returned = lane
        sampled = returned.cpu is not None
        entry = self._clocks(sampled, on_loop=True)
        wall, cpu = self._host_s, self._host_cpu_s
        wall["submit"] += start.wall - submitted.wall
        wall["dispatch"] += sent.wall - start.wall
        wall["fetch"] += returned.wall - sent.wall
        wall["resume"] += entry.wall - returned.wall
        attrs = {"resume_us": _us(entry.wall - returned.wall)}
        if sampled:
            self._cpu_sampled += 1
            cpu["exec_dispatch"] += sent.cpu - start.cpu
            cpu["exec_fetch"] += returned.cpu - sent.cpu
            cpu["loop_in_dispatch"] += sent.loop_cpu - start.loop_cpu
            cpu["loop_in_fetch"] += returned.loop_cpu - sent.loop_cpu
            cpu["loop_in_resume"] += entry.loop_cpu - returned.loop_cpu
            attrs["fetch_loop_cpu_us"] = _us(
                returned.loop_cpu - sent.loop_cpu)
            attrs["resume_loop_cpu_us"] = _us(
                entry.loop_cpu - returned.loop_cpu)
        with region("engine.deliver", tokens=len(tokens), **attrs, **told):
            for seq, token in tokens:
                if seq.slot is None:     # retired by a token before this
                    continue
                if self._push(seq, token) or seq.cancelled:
                    self._retire(seq, "cancelled" if seq.cancelled
                                 else "done")
        self._loop_free_at = time.perf_counter()
        wall["deliver"] += self._loop_free_at - entry.wall

    def _fetch(self, step: _Step, dispatched, **crossing):
        """On the exec lane: wait for ``step``'s tokens (and an expert
        model's assignments), whose copy down began at its dispatch.  The
        region carries the dispatch phases that ended on this lane since
        the fetch before it: ``dispatched`` (start, end), the step this
        call has just queued behind ``step``, and ``step``'s own where it
        was dispatched alone; zeros where neither (the pipe drains)."""
        phases = [p for p in (step.alone, dispatched) if p is not None]
        attrs = {"dispatch_us": _us(sum(
            sent.wall - start.wall for start, sent in phases))}
        if all(start.cpu is not None for start, _ in phases):
            attrs["dispatch_cpu_us"] = _us(sum(
                sent.cpu - start.cpu for start, sent in phases))
            attrs["dispatch_loop_cpu_us"] = _us(sum(
                sent.loop_cpu - start.loop_cpu for start, sent in phases))
        with region("engine.decode.fetch", **attrs, **crossing):
            # (a block model's ``nxt`` is a dict of arrays)
            return self._tree.map(np.asarray, step.nxt), \
                [np.asarray(a) for a in step.load]

    def _deliver_step(self, step: _Step, fetched, submitted: _Clocks,
                      lane: Tuple[_Clocks, _Clocks, _Clocks]):
        """``step``'s fetched tokens to the sequences it stepped.  One that
        was retired while the step was in flight (an ``eos_token``, a
        cancellation, a deadline: what the host could not foresee) gets
        nothing, whoever holds its slot now: a stray slot step."""
        nxt, load = fetched
        self._count_moe("decode", load,
                        len(step.seqs) * max(self._block, 1),
                        self.config.max_batch * max(self._block, 1))
        if self._block:
            return self._deliver_blocks(step, nxt, submitted, lane)
        tokens = []
        for slot, seq in step.seqs.items():
            if self._active.get(slot) is seq:
                tokens.append((seq, int(nxt[slot])))
            else:
                self._stray_slot_steps += 1
        self._deliver(tokens, submitted, lane)

    def _deliver_blocks(self, step: _Step, told, submitted: _Clocks,
                        lane: Tuple[_Clocks, _Clocks, _Clocks]):
        """A block model's fetched step: every stepped sequence's mirror
        of its block brought up to the state the step left, and of a block
        the step committed the tokens that are the caller's: those at the
        positions the request asked for (the first block starts with the
        prompt's tail, the last may end past ``max_new``: the dropped
        tail), as far as an ``eos_token``."""
        B, counts = self._block, self._block_stats
        tokens, dropped = [], 0
        state = told["state"]
        denoise = commit = strayed = 0
        for slot, seq in step.seqs.items():
            committed = bool(told["committed"][slot])
            # the block's first position that is not the prompt's
            first = max(len(seq.prompt) - seq.pos, 0)
            if self._active.get(slot) is not seq:
                self._stray_slot_steps += 1
                strayed += (B - first) * committed
                continue
            commit += committed
            denoise += not committed
            counts["unmasked_by_threshold"] += int(
                told["by_threshold"][slot])
            counts["unmasked_by_count"] += int(told["by_count"][slot])
            if committed:
                asked = min(B, len(seq.prompt) + seq.max_new - seq.pos)
                mine = [int(t) for t in told["emitted"][slot][first:asked]]
                eos = self.config.eos_token
                if eos is not None and eos in mine:
                    mine = mine[:mine.index(eos) + 1]
                counts["blocks_committed"] += 1
                counts["tokens_committed"] += B - first
                counts["denoise_passes_by_count"][
                    int(told["passes"][slot])] += 1
                dropped += B - first - len(mine)
                tokens += [(seq, t) for t in mine]
            seq.block, seq.masked = state[0][slot], state[1][slot]
            seq.pos, seq.passes = int(state[2][slot]), int(state[3][slot])
        counts["slot_steps_denoise"] += denoise
        counts["slot_steps_commit"] += commit
        counts["tokens_dropped_tail"] += dropped
        counts["tokens_dropped_stray"] += strayed
        counts["tokens_committed"] += strayed
        self._deliver(tokens, submitted, lane, dropped_tail=dropped,
                      dropped_stray=strayed, denoise_slots=denoise,
                      commit_slots=commit)

    async def _drain(self, loop):
        """Fetch and deliver the step in flight with nothing queued behind
        it: the device is idle from its end to the next dispatch, as it was
        after every step before the loop ran ahead."""
        step, self._flight = self._flight, None
        submitted, sampled = self._submit()

        def _call():
            start = self._clocks(sampled)
            fetched = self._fetch(
                step, None, submit_us=_us(start.wall - submitted.wall))
            return fetched, (start, start, self._clocks(sampled))
        fetched, lane = await loop.run_in_executor(self._exec, _call)
        self._deliver_step(step, fetched, submitted, lane)

    async def _decode_step(self, loop, stepped: Dict[int, _Sequence], batch):
        """Dispatch one decode step over ``stepped``, whose host arrays are
        ``batch`` (``_decode_inputs``).  With a step in
        flight this one is queued behind it, its tokens that step's result
        on the device, and the same call of the exec lane then fetches that
        step's tokens, which are delivered while the device runs this one.
        On a drained pipe the tokens are the host's and the call fetches
        nothing.  Either way this step is the one in flight afterwards."""
        cfg = self.config
        prev = self._flight
        token, pos, tables, live_tokens = batch
        width = tables.shape[1]
        program = self._decode_programs[width].result()
        active = len(stepped)
        # what the step's paged read fetches: the table whole, or under the
        # kernel every slot's own pages (a parked slot's one of page 0)
        paged_read = self._paged_read
        gathered_tokens = cfg.page_size * (
            int((pos // cfg.page_size).sum()) + cfg.max_batch
            if paged_read == "kernel" else tables.size)
        linear_state = self._linear_state
        # what only some models' steps carry
        more = {**({"block_len": self._block} if self._block else {}),
                **({"linear_state": linear_state} if linear_state else {})}
        if self._select_topk:    # what the selection leaves of the read
            selected = int(np.minimum(
                pos[pos > 0] + 1, self._select_topk).sum())
            more.update(selected=selected, live=live_tokens)
            self._selected_positions += selected
        submitted, sampled = self._submit(cpu=True)
        # everything the step before cost the loop: its delivery,
        # the streams' fan-out, schedule, the prefills between
        step_s = submitted.wall - self._step_from.wall
        step_loop_cpu_s = submitted.loop_cpu - self._step_from.loop_cpu
        self._host_cpu_s["loop"] += step_loop_cpu_s
        self._step_from = submitted
        more.update(self._beside())

        def _call():
            start = self._clocks(sampled)
            with region("engine.decode.dispatch", active=active,
                        live_tokens=live_tokens,
                        gathered_tokens=gathered_tokens,
                        width_pages=width, paged_read=paged_read,
                        ahead=int(prev is not None),
                        submit_us=_us(start.wall - submitted.wall),
                        step_us=_us(step_s),
                        step_loop_cpu_us=_us(step_loop_cpu_s), **more):
                _, kp, vp, *load, nxt = self._donate_pools(
                    "decode", program, token, pos, tables)
                # on their way once the step ends
                for a in self._tree.leaves((nxt, load)):
                    a.copy_to_host_async()
            sent = self._clocks(sampled)
            if prev is None:
                return _Step(stepped, nxt, load, (start, sent)), kp, vp, \
                    None, (start, sent, sent)
            fetched = self._fetch(prev, (start, sent))
            return _Step(stepped, nxt, load, None), kp, vp, fetched, \
                (start, sent, self._clocks(sampled))
        self._flight, self._k_pages, self._v_pages, fetched, lane = \
            await loop.run_in_executor(self._exec, _call)
        self._steps += 1
        self._decode_ahead_steps += prev is not None
        self._decode_shapes[width] += 1
        self._decode_paged_read[paged_read] += 1
        if linear_state:
            self._decode_linear_state[linear_state] += 1
        self._slot_steps += active
        self._kv_live_token_steps += live_tokens
        self._kv_gathered_token_steps += gathered_tokens
        if not self._block:   # a block's position moves when it commits
            for seq in stepped.values():
                seq.pos += 1
        if prev is None:
            self._deliver([], submitted, lane)
        else:
            self._deliver_step(prev, fetched, submitted, lane)

    async def _prefill_call(self, loop, seq: _Sequence, start: int,
                            width: int, last: bool):
        """One prefill call for ``seq`` on the exec lane, padded to its
        rung: the whole prompt (``start`` 0, ``width`` its length) or, under
        ``prefill_chunk``, the chunk of ``width`` positions from ``start``,
        which reads what the calls before it left in the sequence's pages.
        The ``last`` call's token is the sequence's first."""
        import jax.numpy as jnp
        chunked = bool(self.config.prefill_chunk)
        S = rung_for(self._rungs, width)
        program = self._rung_programs[S].result()
        toks = np.zeros((1, S), np.int32)
        toks[0, :width] = seq.prompt[start:start + width]
        submitted, sampled = self._submit()
        if not start:
            self._queue_wait_s += submitted.wall - seq.queued
        self._prefill_padded_tokens += S
        self._prefill_shapes[S] += 1
        attention = self._rung_attention[S]
        self._prefill_attention[attention] += 1
        self._prefill_chunks += chunked
        self._prefill_chunk_tokens += width * chunked
        # (the chunk's place, only where prompts run as chunks: the region
        # is then the CHUNK's, ``prompt_len`` the real positions it ran, and
        # every chunk of a prompt carries the wait its first one found)
        where = {"prompt_len": len(seq.prompt)} if not chunked else \
            {"prompt_len": width, "start": start, "width": width, "rung": S}

        def _run():
            begun = self._clocks(sampled)
            if not start:
                seq.waited = begun.wall - seq.queued
            with region("engine.prefill", padded_len=S, attention=attention,
                        waited_us=_us(seq.waited),
                        submit_us=_us(begun.wall - submitted.wall),
                        **where):
                logits, kp, vp, *load = self._donate_pools(
                    "prefill", program, toks, np.int32(width),
                    seq.row[None], np.int32(seq.slot),
                    *([np.int32(start)] if chunked else []))
                tok = int(jnp.argmax(logits[0])) \
                    if last and not self._block else None
                load = [np.asarray(a) for a in load]
            return tok, kp, vp, load, (begun, begun, self._clocks(sampled))
        tok, self._k_pages, self._v_pages, load, lane = \
            await loop.run_in_executor(self._exec, _run)
        self._count_moe("prefill", load, int(width), S)
        self._deliver([] if tok is None else [(seq, tok)], submitted, lane)

    def _count_moe(self, program: str, load: Sequence[np.ndarray],
                   tokens: int, rows: int):
        """What an expert model's program said of its ``tokens`` real
        tokens' routing (``load`` [L, E] over the experts the program HOLDS,
        inside a list that is empty for a dense model): into ``stats()`` and
        onto the profiler's timeline, there with the bytes a parameter of the
        experts takes as the program stores them.  ``assignments`` are those
        that fell on a held expert, of the ``assignments_made`` (every
        token's ``experts_per_token`` a layer): all of them where the
        program holds every expert.  ``rows_offered`` is the same count for
        the ``rows`` the program's shape offered (the prefill's rung, the
        step's slots, a block's rows for each): ``assignments_made`` over it
        is the share of rows that were somebody's, and the rest is padding
        and idle slots, which the experts' products do not multiply."""
        for per_layer in load:
            per_row = per_layer.shape[0] * self.model_config.experts_per_token
            step = {"assignments": int(per_layer.sum()),
                    "assignments_made": tokens * per_row,
                    "rows_offered": rows * per_row,
                    "experts_hit": int(np.count_nonzero(per_layer)),
                    "load_max": int(per_layer.max(axis=1).sum())}
            for key, value in step.items():
                self._moe["moe_" + key] += value
            self._moe_load = per_layer if self._moe_load is None \
                else self._moe_load + per_layer
            with region(f"engine.{program}.moe",
                        weight_itemsize=self._expert_itemsize, **step):
                pass

    def _push(self, seq: _Sequence, token: int) -> bool:
        """Deliver one token; returns True when the sequence is finished
        (EOS or max_new reached)."""
        seq.generated += 1
        seq.last_token = token
        if not seq.cancelled:
            seq.queue.put_nowait(token)
        eos = self.config.eos_token
        return seq.generated >= seq.max_new or \
            (eos is not None and token == eos)

    async def _run_loop(self):
        loop = asyncio.get_running_loop()
        cfg = self.config
        self._loop_cpu_clock = time.pthread_getcpuclockid(
            threading.get_ident())
        # No request meets a compile: every rung's program, prefill and
        # decode, is there before the first admission.  A rung that failed
        # to compile raises where a prompt or a step needs it, to the
        # sequences of that pass.
        await asyncio.gather(*map(asyncio.wrap_future,
                                  (*self._rung_programs.values(),
                                   *self._decode_programs.values())),
                             return_exceptions=True)
        self._loop_woke()
        while True:
            try:
                with region("engine.schedule", active=len(self._active),
                            waiting=len(self._waiting)):
                    self._sweep()
                    self._admit()
                    fresh = [s for s in self._active.values()
                             if not s.prefilled]
                    # with nobody to prefill the batch is settled: its
                    # arrays are part of the same stretch of host work
                    stepped = {} if fresh else self._steppable()
                    drain = self._flight is not None \
                        and not self._follows_flight(stepped)
                    batch = self._decode_inputs(stepped) \
                        if stepped and not drain else None
                if drain:
                    # An admission needs its prefill's token on the host
                    # and changes the batch; or everybody in flight has
                    # its last token coming.  What is delivered may free a
                    # slot: schedule again.
                    await self._drain(loop)
                    continue
                if not self._active:
                    if self._waiting:
                        continue   # admission makes progress every pass
                    self._wake.clear()
                    # Re-check: generate() may have appended between the
                    # test above and the clear.
                    if not self._waiting:
                        await self._wake.wait()
                        self._loop_woke()
                    continue

                # Prefill new admissions one at a time (B=1), each padded
                # to its prompt's rung; the pipe is drained.  Where prompts
                # run as chunks (seconds of device time each), ONE prompt a
                # pass: the live batch takes a step between two prompts, so
                # nobody's stream stands still for a queue of long prompts.
                for seq in fresh[:1] if cfg.prefill_chunk else fresh:
                    # a block model prefills the prompt's whole blocks;
                    # what is left of it is in its first block
                    whole = seq.pos if self._block else len(seq.prompt)
                    self._prefill_tokens += len(seq.prompt)
                    # under ``prefill_chunk`` a row of calls, each a chunk
                    # against the sequence's pages; else the one
                    chunk = cfg.prefill_chunk or max(whole, 1)
                    for start in range(0, max(whole, 1), chunk):
                        await self._prefill_call(
                            loop, seq, start, min(chunk, whole - start),
                            last=start + chunk >= whole)
                    seq.prefilled = True
                    self._state_rows_written += self._slot_rows

                if not self._active:
                    continue
                # Chaos hook: a stalled decode (wedged device, stuck
                # dispatch) is indistinguishable from a dead replica to
                # the client — the ingress's stall detector must fail the
                # stream over.  The hook injects exactly that: the step in
                # flight finishes and is delivered, and no other follows.
                from ray_tpu.util import fault_injection
                stall = fault_injection.stall_replica_decode_s()
                if stall:
                    if self._flight is not None:
                        await self._drain(loop)
                    await asyncio.sleep(stall)
                    continue   # (the hook fires once) schedule again
                if batch is None:   # the prefills changed the batch
                    with region("engine.schedule", active=len(self._active),
                                waiting=len(self._waiting)):
                        stepped = self._steppable()
                        if not stepped:      # only prompts still to prefill
                            continue
                        batch = self._decode_inputs(stepped)
                await self._decode_step(loop, stepped, batch)
            except asyncio.CancelledError:
                raise
            except Exception as e:   # noqa: BLE001
                logger.exception("inference engine step failed")
                if self._flight is not None:   # its tokens are nobody's
                    self._stray_slot_steps += len(self._flight.seqs)
                    self._flight = None
                for seq in list(self._active.values()):
                    self._retire(seq, "error")
                    seq.queue.put_nowait(e)
                while self._waiting:
                    self._waiting.popleft().queue.put_nowait(e)
                if self._pools_deleted():
                    # the call that failed had been given the pools; every
                    # sequence that owned a page of them is retired above
                    self._k_pages, self._v_pages = self._new_pools()


class LLMServer:
    """Ready-made serve deployment body around an InferenceEngine.

    ``serve.deployment(LLMServer).bind(EngineConfig(...))`` gives an HTTP
    +handle-callable token streamer: payloads are
    ``{"tokens": [...], "max_new_tokens": N}``; the response is the
    stream of generated token ids (a list for unary callers, per-token
    SSE events through the streaming ingress)."""

    def __init__(self, config: Optional[EngineConfig] = None,
                 params: Any = None, **config_kwargs):
        self._engine = InferenceEngine(config or EngineConfig(
            **config_kwargs), params=params)

    async def __call__(self, payload):
        if not isinstance(payload, dict) or "tokens" not in payload:
            raise ValueError(
                'expected {"tokens": [...], "max_new_tokens": N}')
        # The replica publishes the request's end-to-end deadline via
        # contextvar (see serve/resilience.py); handing it to the engine
        # lets an expired request free its KV pages mid-batch.
        async for tok in self._engine.generate(
                payload["tokens"], payload.get("max_new_tokens"),
                deadline=resilience.current_deadline()):
            yield tok

    def stats(self) -> Dict[str, Any]:
        return self._engine.stats()

    async def profile(self, log_dir: str, seconds: float) -> str:
        """Run the JAX profiler in this replica for ``seconds`` while it
        keeps serving, and return the trace's path (an ``.xplane.pb``
        under ``log_dir``): the device's programs and operations with the
        engine's ``rt:`` regions beside them on one clock.  Call it as
        ``handle.method("profile").remote(log_dir, seconds)``.  The
        profiler's Python tracer is off: on by default, it makes every
        Python call of every thread an event, which slowed the loop's
        per-token work two to six times and a decode step by 3-14 ms, so
        that the trace showed a host the untraced replica does not have
        (PERF.md section 6, PR 36); the regions need only the host tracer."""
        import functools
        import glob
        import os

        import jax
        loop = asyncio.get_running_loop()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        await loop.run_in_executor(None, functools.partial(
            jax.profiler.start_trace, log_dir, profiler_options=options))
        try:
            await asyncio.sleep(seconds)
        finally:
            await loop.run_in_executor(None, jax.profiler.stop_trace)
        traces = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                           recursive=True)
        return max(traces, key=os.path.getmtime)
