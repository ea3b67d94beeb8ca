"""Streaming inference: generator streaming protocol, paged KV decode,
continuous batching, SSE ingress.

Reference analogs: python/ray/tests/test_streaming_generator.py (per-yield
object refs consumable mid-task), vLLM's paged-attention equivalence tests,
python/ray/serve/tests/test_proxy + streaming response tests.
"""

import asyncio
import json
import socket
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture(scope="module")
def serve_cluster():
    ray_tpu.init(num_cpus=16, _worker_env={"JAX_PLATFORMS": "cpu"})
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def _tiny_gpt():
    from ray_tpu.models.gpt import GPTConfig
    # f32 end to end: the paged-vs-dense equivalence below is exact in
    # f32; bf16 would add rounding nondeterminism to the argmax.
    return GPTConfig(vocab_size=97, max_seq_len=96, num_layers=2,
                     num_heads=4, embed_dim=32, dtype=jnp.float32,
                     attention="dense", remat=False)


# ------------------------------------------------------ core streaming


def test_streaming_task_refs_and_completion(serve_cluster):
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i * 10

    g = gen.remote(5)
    assert isinstance(g, ray_tpu.StreamingObjectRefGenerator)
    # Hold the yielded refs: dropping them frees the per-yield objects
    # (each yield is an owned, refcounted object like any task return).
    yielded = list(g)
    vals = [ray_tpu.get(r, timeout=30) for r in yielded]
    assert vals == [0, 10, 20, 30, 40]
    # The ref0 terminal holds an ObjectRefGenerator over every yield.
    refs = list(ray_tpu.get(g.completed(), timeout=30))
    assert [r.hex() for r in refs] == [r.hex() for r in yielded]
    assert [ray_tpu.get(r, timeout=30) for r in refs] == vals


def test_streaming_yields_arrive_before_task_completes(serve_cluster):
    @ray_tpu.remote
    class Gate:
        def __init__(self):
            self._open = False
        def open(self):
            self._open = True
        def is_open(self):
            return self._open

    gate = Gate.remote()

    @ray_tpu.remote(num_returns="streaming")
    def gen(gate):
        yield "first"
        while not ray_tpu.get(gate.is_open.remote()):
            time.sleep(0.02)
        yield "second"

    g = gen.remote(gate)
    it = iter(g)
    # First yield is consumable while the task is parked on the gate —
    # i.e. strictly before the generator completes.
    assert ray_tpu.get(next(it)) == "first"
    ray_tpu.get(gate.open.remote())
    assert ray_tpu.get(next(it)) == "second"
    with pytest.raises(StopIteration):
        next(it)


def test_streaming_error_propagates_after_partial_stream(serve_cluster):
    @ray_tpu.remote(num_returns="streaming")
    def bad():
        yield 1
        yield 2
        raise ValueError("decode exploded")

    g = bad.remote()
    it = iter(g)
    assert ray_tpu.get(next(it)) == 1
    assert ray_tpu.get(next(it)) == 2
    with pytest.raises(ray_tpu.exceptions.TaskError, match="decode exploded"):
        while True:
            next(it)


def test_streaming_actor_async_generator(serve_cluster):
    @ray_tpu.remote
    class Streamer:
        async def tokens(self, n):
            for i in range(n):
                await asyncio.sleep(0.005)
                yield i * i

    a = Streamer.remote()
    g = a.tokens.options(num_returns="streaming").remote(4)
    assert [ray_tpu.get(r) for r in g] == [0, 1, 4, 9]


def test_streaming_cancel_runs_generator_finally(serve_cluster):
    @ray_tpu.remote
    class Flag:
        def __init__(self):
            self.v = False
        def set(self):
            self.v = True
        def get(self):
            return self.v

    flag = Flag.remote()

    @ray_tpu.remote(num_returns="streaming")
    def gen(flag):
        try:
            for i in range(10_000):
                yield i
                time.sleep(0.01)
        finally:
            ray_tpu.get(flag.set.remote())

    g = gen.remote(flag)
    it = iter(g)
    assert ray_tpu.get(next(it)) == 0
    g.cancel()
    # Cancellation closes the user generator executor-side: its finally
    # block must run (that is what releases engine KV pages in serve).
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if ray_tpu.get(flag.get.remote()):
            break
        time.sleep(0.05)
    assert ray_tpu.get(flag.get.remote())


def test_dropped_generator_ref_frees_per_yield_extras(serve_cluster):
    """Regression (ownership gap): a reply whose generator ref was freed
    before it arrived must free the per-yield plasma extras instead of
    leaking them (they would otherwise hold directory entries and an
    executor-node copy forever)."""
    from ray_tpu._private.ids import ObjectID, TaskID
    from ray_tpu._private.worker import get_core

    core = get_core()
    tid = TaskID.from_random()
    ref0 = ObjectID.for_task_return(tid, 0)
    extra1 = ObjectID.for_task_return(tid, 1)
    extra2 = ObjectID.for_task_return(tid, 2)
    # ref0 deliberately NOT in core.owned — the caller freed it.
    reply = {"ok": True, "returns": [
        (ref0.hex(), "inline", b"x"),
        (extra1.hex(), "plasma", None),
        (extra2.hex(), "inline", b"y"),
    ]}

    sent = []
    orig_notify = core.gcs.notify

    async def spy(msg):
        if msg.get("type") == "object_freed":
            sent.append(msg["object_id"])
            return None
        return await orig_notify(msg)

    core.gcs.notify = spy
    try:
        done = __import__("threading").Event()

        def _run():
            core._store_task_returns(reply, [ref0])
            done.set()

        core.loop.call_soon_threadsafe(_run)
        assert done.wait(10)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not sent:
            time.sleep(0.02)
    finally:
        core.gcs.notify = orig_notify
    assert extra1.hex() in sent            # plasma extra freed
    assert extra1.hex() not in core.owned  # and not adopted
    assert extra2.hex() not in core.owned


# -------------------------------------------------- paged KV equivalence


def test_page_allocator_accounting():
    from ray_tpu.serve.engine import PageAllocator, table_row

    alloc = PageAllocator(8)
    assert alloc.free_pages == 7           # page 0 reserved
    pages = alloc.alloc(3)
    assert 0 not in pages
    assert alloc.free_pages == 4
    with pytest.raises(MemoryError):
        alloc.alloc(5)
    alloc.free(pages)
    assert alloc.free_pages == 7
    with pytest.raises(ValueError):
        alloc.free([0])                    # scratch page is untouchable
    row = table_row([3, 1], 4)
    assert row.tolist() == [3, 1, 0, 0]


def _greedy_dense(forward, params, cfg, prompt, n):
    cur = list(prompt)
    out = []
    for _ in range(n):
        logits = forward(params, jnp.array([cur], jnp.int32), cfg)
        t = int(jnp.argmax(logits[0, -1]))
        out.append(t)
        cur.append(t)
    return out


def test_gpt_paged_decode_matches_dense():
    from ray_tpu.models.gpt import (gpt_decode_step, gpt_forward, gpt_init,
                                    gpt_prefill, init_paged_cache)

    cfg = _tiny_gpt()
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    page = 8
    kp, vp = init_paged_cache(cfg, 32, page)
    prompt = [5, 17, 3, 88, 41]
    toks = jnp.array([prompt + [0] * (8 - len(prompt))], jnp.int32)
    pt = jnp.array([[1, 2, 0, 0]], jnp.int32)

    logits, kp, vp = gpt_prefill(params, cfg, toks,
                                 jnp.int32(len(prompt)), kp, vp, pt)
    dense = gpt_forward(params, toks[:, : len(prompt)], cfg)
    np.testing.assert_allclose(logits[0], dense[0, -1].astype(jnp.float32),
                               rtol=1e-5, atol=1e-5)

    tok, pos, out = int(jnp.argmax(logits[0])), len(prompt), []
    out.append(tok)
    for _ in range(9):
        lg, kp, vp = gpt_decode_step(
            params, cfg, jnp.array([tok], jnp.int32),
            jnp.array([pos], jnp.int32), kp, vp, pt)
        tok = int(jnp.argmax(lg[0]))
        out.append(tok)
        pos += 1
    assert out == _greedy_dense(gpt_forward, params, cfg, prompt, 10)


def test_llama_paged_decode_matches_dense():
    from ray_tpu.models.llama import (LlamaConfig, llama_decode_step,
                                      llama_forward, llama_init,
                                      llama_init_paged_cache, llama_prefill)

    cfg = LlamaConfig(vocab_size=97, max_seq_len=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, embed_dim=32,
                      mlp_dim=64, dtype=jnp.float32, attention="dense",
                      remat=False)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    kp, vp = llama_init_paged_cache(cfg, 32, 8)
    # GQA: pools at kv_heads width
    assert kp.shape[-1] == cfg.num_kv_heads * cfg.head_dim
    prompt = [5, 17, 3, 88, 41]
    toks = jnp.array([prompt + [0] * (8 - len(prompt))], jnp.int32)
    pt = jnp.array([[1, 2, 0, 0]], jnp.int32)

    logits, kp, vp = llama_prefill(params, cfg, toks,
                                   jnp.int32(len(prompt)), kp, vp, pt)
    dense = llama_forward(params, toks[:, : len(prompt)], cfg)
    np.testing.assert_allclose(logits[0], dense[0, -1].astype(jnp.float32),
                               rtol=1e-5, atol=1e-5)

    tok, pos, out = int(jnp.argmax(logits[0])), len(prompt), []
    out.append(tok)
    for _ in range(9):
        lg, kp, vp = llama_decode_step(
            params, cfg, jnp.array([tok], jnp.int32),
            jnp.array([pos], jnp.int32), kp, vp, pt)
        tok = int(jnp.argmax(lg[0]))
        out.append(tok)
        pos += 1
    assert out == _greedy_dense(llama_forward, params, cfg, prompt, 10)


# --------------------------------------------------- continuous batching


def test_engine_concurrent_sequences_match_dense():
    """One engine decodes 10 concurrent sequences (> the 8 slots, so
    admission queues and retires mid-run) and every stream matches the
    dense greedy reference; pages and slots fully recover."""
    from ray_tpu.models.gpt import GPTConfig, gpt_forward, gpt_init
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    cfg = _tiny_gpt()
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    eng_cfg = EngineConfig(model="gpt", model_config=cfg, page_size=8,
                           num_pages=64, max_batch=8, max_prompt_len=32,
                           max_new_tokens=12)

    async def run_all():
        eng = InferenceEngine(eng_cfg, params=params)
        prompts = [[(7 * i + j) % 97 for j in range(3 + i % 5)]
                   for i in range(10)]

        async def consume(p):
            return [t async for t in eng.generate(p, 10)]

        results = await asyncio.gather(*[consume(p) for p in prompts])
        stats = eng.stats()
        eng.close()
        return prompts, results, stats

    prompts, results, stats = asyncio.run(run_all())
    for p, got in zip(prompts, results):
        assert got == _greedy_dense(gpt_forward, params, cfg, p, 10), p
    assert stats["active"] == 0 and stats["waiting"] == 0
    assert stats["free_pages"] == 63           # everything returned
    # Continuous batching: 10 sequences of 10 tokens in far fewer than
    # 10*10 dispatches (sequences decode as one batch).
    assert stats["steps"] < 40, stats


def test_engine_cancel_frees_pages():
    from ray_tpu.models.gpt import gpt_init
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    cfg = _tiny_gpt()
    eng_cfg = EngineConfig(model="gpt", model_config=cfg, page_size=8,
                           num_pages=64, max_batch=4, max_prompt_len=32,
                           max_new_tokens=32)

    async def run():
        eng = InferenceEngine(
            eng_cfg, params=gpt_init(jax.random.PRNGKey(0), cfg))
        agen = eng.generate([1, 2, 3], 32)
        first = await agen.__anext__()
        assert isinstance(first, int)
        await agen.aclose()                    # client disconnected
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            st = eng.stats()
            if st["active"] == 0 and st["free_pages"] == 63:
                break
            await asyncio.sleep(0.05)
        st = eng.stats()
        eng.close()
        return st

    st = asyncio.run(run())
    assert st["active"] == 0
    assert st["free_pages"] == 63, st


def test_engine_rejects_oversized_request():
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    cfg = _tiny_gpt()
    eng_cfg = EngineConfig(model="gpt", model_config=cfg, page_size=8,
                           num_pages=4, max_batch=2, max_prompt_len=32,
                           max_new_tokens=32)   # 3 usable pages: too few

    async def run():
        eng = InferenceEngine(eng_cfg)
        with pytest.raises(MemoryError, match="KV pages"):
            async for _ in eng.generate(list(range(30)), 32):
                pass
        eng.close()

    asyncio.run(run())


# ------------------------------------------------------ serve integration


def _read_http_response(sock):
    resp = b""
    while True:
        if b"\r\n\r\n" in resp:
            head, rest = resp.split(b"\r\n\r\n", 1)
            n = int([h for h in head.split(b"\r\n")
                     if h.lower().startswith(b"content-length")][0]
                    .split(b":")[1])
            if len(rest) >= n:
                return head, rest[:n]
        c = sock.recv(65536)
        if not c:
            return resp.split(b"\r\n\r\n", 1)[0], b""
        resp += c


def _post(sock, path, body: bytes, extra: str = ""):
    sock.sendall(f"POST {path} HTTP/1.1\r\nHost: x\r\n"
                 f"Content-Type: application/json\r\n{extra}"
                 f"Content-Length: {len(body)}\r\n\r\n".encode() + body)


def test_serve_streaming_end_to_end(serve_cluster):
    """The acceptance path: LLMServer replica, handle + HTTP SSE clients,
    first token on the wire before the stream completes, streamed tokens
    equal to the unary (drained) result."""
    from ray_tpu.serve.engine import EngineConfig, LLMServer

    ecfg = EngineConfig(model="gpt", model_config=_tiny_gpt(), page_size=8,
                        num_pages=64, max_batch=8, max_prompt_len=32,
                        max_new_tokens=16)
    dep = serve.deployment(name="llm", max_concurrent_queries=16,
                           ray_actor_options={"num_cpus": 0.1})(LLMServer)
    handle = serve.run(dep.bind(ecfg))
    payload = {"tokens": [5, 17, 3], "max_new_tokens": 8}

    # Streaming handle: per-token ObjectRefs as they decode.
    toks = [ray_tpu.get(r) for r in handle.remote_stream(payload)]
    assert len(toks) == 8
    # Unary handle call drains the same generator to a list.
    assert ray_tpu.get(handle.remote(payload), timeout=60) == toks

    url = serve.start_http()
    host, port = url.split("//")[1].split(":")
    s = socket.create_connection((host, int(port)), timeout=60)
    try:
        _post(s, "/llm", json.dumps({**payload, "stream": True}).encode())
        buf = b""
        saw_token_before_end = False
        # Read through the chunked TERMINATOR, not just the end event —
        # stopping early would leave terminator bytes in the socket to
        # pollute the next keep-alive response on this connection.
        while b"event: end" not in buf or not buf.endswith(b"0\r\n\r\n"):
            c = s.recv(4096)
            assert c, f"connection closed early: {buf!r}"
            buf += c
            if b"data: " in buf and b"event: end" not in buf:
                saw_token_before_end = True
        assert saw_token_before_end
        assert b"Transfer-Encoding: chunked" in buf
        assert b"text/event-stream" in buf
        events = [l for l in buf.replace(b"\r\n", b"\n").split(b"\n")
                  if l.startswith(b"data: ")]
        assert [json.loads(e[6:]) for e in events][:-1] == toks
        # Keep-alive: the same connection serves a unary request next.
        _post(s, "/llm", json.dumps(payload).encode())
        head, body = _read_http_response(s)
        assert b"200" in head.split(b"\r\n")[0]
        assert json.loads(body)["result"] == toks
    finally:
        s.close()


def test_replica_profile_holds_the_engines_and_the_streams_regions(
        serve_cluster, tmp_path):
    """``LLMServer.profile`` through the handle: the JAX profiler runs in
    the replica while it serves, and the trace it returns holds the
    engine's regions and the transport's, one ``rt:stream.yield`` a
    streamed token with the ack's wait as its attribute."""
    from jax.profiler import ProfileData
    from ray_tpu.serve.engine import EngineConfig, LLMServer

    ecfg = EngineConfig(model="gpt", model_config=_tiny_gpt(), page_size=8,
                        num_pages=64, max_batch=8, max_prompt_len=32,
                        max_new_tokens=16)
    dep = serve.deployment(name="llm_profiled", max_concurrent_queries=16,
                           ray_actor_options={"num_cpus": 0.1})(LLMServer)
    handle = serve.run(dep.bind(ecfg))
    payload = {"tokens": [5, 17, 3], "max_new_tokens": 8}
    warm = [ray_tpu.get(r) for r in handle.remote_stream(payload)]
    before = ray_tpu.get(handle.method("stats").remote(), timeout=60)
    trace = handle.method("profile").remote(str(tmp_path), 3.0)
    time.sleep(1.0)                      # the session has started
    toks = [ray_tpu.get(r) for r in handle.remote_stream(payload)]
    assert toks == warm
    path = ray_tpu.get(trace, timeout=120)
    assert path.startswith(str(tmp_path)) and path.endswith(".xplane.pb")
    plane, = [p for p in ProfileData.from_file(path).planes
              if p.name == "/host:CPU"]
    regions = [(e.name, dict(e.stats)) for line in plane.lines
               for e in line.events if e.name.startswith("rt:")]
    names = [name for name, _ in regions]
    assert names.count("rt:engine.prefill") == 1
    assert names.count("rt:engine.decode.dispatch") == \
        names.count("rt:engine.decode.fetch") == len(toks) - 1
    yields = [stats for name, stats in regions if name == "rt:stream.yield"]
    assert len(yields) == len(toks)
    assert all(y["ack_us"] >= 0 for y in yields)
    after = ray_tpu.get(handle.method("stats").remote(), timeout=60)
    assert after["retired"]["done"] == before["retired"]["done"] + 1
    assert after["slot_steps"] == before["slot_steps"] + len(toks) - 1
    # what an operator reads of the per-token path from a live replica
    assert all(after["host_s"][phase] > before["host_s"][phase]
               for phase in after["host_s"]), (before, after)
    assert after["host_cpu_s"]["exec_dispatch"] > \
        before["host_cpu_s"]["exec_dispatch"]
    assert after["gc"]["passes"][0] >= before["gc"]["passes"][0]


def test_http_client_disconnect_cancels_stream(serve_cluster):
    """A client that walks away mid-stream must cancel the replica-side
    generator (releasing engine slots/pages), not leave it producing into
    the void."""
    @serve.deployment(name="slowgen", ray_actor_options={"num_cpus": 0.1})
    class SlowGen:
        def __init__(self):
            self.closed = 0
        async def __call__(self, payload):
            try:
                for i in range(200):
                    await asyncio.sleep(0.02)
                    yield i
            except BaseException:
                self.closed += 1
                raise
        def stats(self):
            return self.closed

    handle = serve.run(SlowGen.bind())
    url = serve.start_http()
    host, port = url.split("//")[1].split(":")
    s = socket.create_connection((host, int(port)), timeout=30)
    _post(s, "/slowgen", json.dumps({"stream": True}).encode())
    buf = b""
    while b"data: " not in buf:
        buf += s.recv(4096)
    s.close()                                   # vanish mid-stream
    deadline = time.monotonic() + 30
    closed = 0
    while time.monotonic() < deadline:
        closed = ray_tpu.get(handle.method("stats").remote(), timeout=30)
        if closed:
            break
        time.sleep(0.1)
    assert closed == 1


def test_http_robustness_malformed_and_oversized(serve_cluster):
    url = serve.start_http()
    host, port = url.split("//")[1].split(":")

    # Malformed content-length: clean 400, no reader hang.
    s = socket.create_connection((host, int(port)), timeout=10)
    s.sendall(b"POST /x HTTP/1.1\r\nHost: x\r\nContent-Length: zork\r\n\r\n")
    head, _ = _read_http_response(s)
    assert b"400" in head.split(b"\r\n")[0]
    s.close()

    # Oversized body: 413 before reading the body.
    s = socket.create_connection((host, int(port)), timeout=10)
    s.sendall(b"POST /x HTTP/1.1\r\nHost: x\r\n"
              b"Content-Length: 99999999999\r\n\r\n")
    head, _ = _read_http_response(s)
    assert b"413" in head.split(b"\r\n")[0]
    s.close()

    # Garbage request line: 400.
    s = socket.create_connection((host, int(port)), timeout=10)
    s.sendall(b"NONSENSE\r\n\r\n")
    head, _ = _read_http_response(s)
    assert b"400" in head.split(b"\r\n")[0]
    s.close()
