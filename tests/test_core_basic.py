"""Core task/object API tests (reference analog: python/ray/tests/test_basic*.py)."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.exceptions import GetTimeoutError, TaskError


@ray_tpu.remote
def f_add(a, b):
    return a + b


@ray_tpu.remote
def f_identity(x):
    return x


@ray_tpu.remote
def f_fail():
    raise ValueError("boom")


def test_simple_task(ray_start):
    assert ray_tpu.get(f_add.remote(1, 2)) == 3


def test_kwargs_and_options(ray_start):
    @ray_tpu.remote
    def g(a, b=10):
        return a * b

    assert ray_tpu.get(g.remote(3)) == 30
    assert ray_tpu.get(g.options(num_cpus=0.5).remote(3, b=2)) == 6


def test_multiple_returns(ray_start):
    @ray_tpu.remote(num_returns=3)
    def three():
        return 1, 2, 3

    a, b, c = three.remote()
    assert ray_tpu.get([a, b, c]) == [1, 2, 3]


def test_put_get_roundtrip(ray_start):
    for value in [42, "hello", {"k": [1, 2]}, None, (1, "x")]:
        assert ray_tpu.get(ray_tpu.put(value)) == value


def test_large_object_plasma(ray_start):
    arr = np.random.rand(500_000).astype(np.float32)  # ~2MB -> plasma
    out = ray_tpu.get(ray_tpu.put(arr))
    np.testing.assert_array_equal(out, arr)


def test_task_arg_by_ref(ray_start):
    big = np.arange(300_000, dtype=np.int64)  # > inline threshold
    ref = ray_tpu.put(big)
    out = ray_tpu.get(f_identity.remote(ref))
    np.testing.assert_array_equal(out, big)


def test_task_dependency_chain(ray_start):
    r1 = f_add.remote(1, 1)
    r2 = f_add.remote(r1, 1)
    r3 = f_add.remote(r2, r1)
    assert ray_tpu.get(r3) == 5


def test_task_error_propagates(ray_start):
    with pytest.raises(TaskError) as exc_info:
        ray_tpu.get(f_fail.remote())
    assert "boom" in str(exc_info.value)
    assert isinstance(exc_info.value.cause, ValueError)


def test_get_timeout(ray_start):
    @ray_tpu.remote
    def slow():
        import time
        time.sleep(30)

    with pytest.raises(GetTimeoutError):
        ray_tpu.get(slow.remote(), timeout=0.5)


def test_wait(ray_start):
    import time

    @ray_tpu.remote
    def sleeper(t):
        time.sleep(t)
        return t

    fast = sleeper.remote(0.01)
    slow = sleeper.remote(5.0)
    ready, not_ready = ray_tpu.wait([fast, slow], num_returns=1, timeout=10)
    assert ready == [fast]
    assert not_ready == [slow]


def test_nested_tasks(ray_start):
    @ray_tpu.remote
    def outer(n):
        refs = [f_add.remote(i, i) for i in range(n)]
        return sum(ray_tpu.get(refs))

    assert ray_tpu.get(outer.options(num_cpus=0.5).remote(3)) == 6


def test_nested_ref_in_container(ray_start):
    inner = ray_tpu.put(np.arange(200_000))  # plasma object

    @ray_tpu.remote
    def consume(d):
        return int(ray_tpu.get(d["ref"]).sum())

    assert ray_tpu.get(consume.remote({"ref": inner})) == \
        int(np.arange(200_000).sum())


def test_cluster_resources(ray_start):
    total = ray_tpu.cluster_resources()
    assert total.get("CPU") == 16.0


def test_jax_array_roundtrip(ray_start):
    import jax.numpy as jnp

    x = jnp.arange(32, dtype=jnp.float32)
    out = ray_tpu.get(ray_tpu.put(x))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_dynamic_num_returns_generator_task(ray_start):
    """num_returns="dynamic" (reference: generator tasks): the task
    yields a data-dependent number of values; get(ref) returns an
    ObjectRefGenerator of per-yield refs."""
    @ray_tpu.remote(num_returns="dynamic")
    def splat(n):
        for i in range(n):
            yield i * i

    gen = ray_tpu.get(splat.remote(5), timeout=60)
    from ray_tpu import ObjectRefGenerator
    assert isinstance(gen, ObjectRefGenerator)
    assert len(gen) == 5
    assert ray_tpu.get(list(gen), timeout=60) == [0, 1, 4, 9, 16]
    # Works with zero yields too.
    assert len(ray_tpu.get(splat.remote(0), timeout=60)) == 0
    # Refs remain gettable individually (ownership registered).
    g2 = ray_tpu.get(splat.remote(3), timeout=60)
    assert ray_tpu.get(g2[2], timeout=60) == 4


@ray_tpu.remote(num_returns="dynamic")
def f_squares(n):
    for i in range(n):
        yield i * i


def test_dynamic_yields_survive_executor_borrow_roundtrip(ray_start):
    """The yields of a num_returns="dynamic" task stay alive from the
    task's reply to the caller's first reference to them.  Forced here:
    a borrower's borrow_add and borrow_remove for a yield land on the
    owner after the reply and before the generator is deserialised (the
    order the executor's own per-yield refs used to produce on a loaded
    machine), which freed the yield and left get() waiting for ever."""
    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.worker import get_core
    core = get_core()
    ref = f_squares.remote(4)
    ready, _ = ray_tpu.wait([ref], timeout=60)
    assert ready == [ref]   # reply stored: yields adopted, no ref to them yet
    for i in range(1, 5):
        msg = {"object_id": ObjectID.for_task_return(ref.id.task_id(),
                                                     i).hex(),
               "borrower": "late-borrower:1"}
        assert core._run(core._h_borrow_add(msg), timeout=30) == {"ok": True}
        core._run(core._h_borrow_remove(msg), timeout=30)
    gen = ray_tpu.get(ref, timeout=60)
    assert ray_tpu.get(list(gen), timeout=30) == [0, 1, 4, 9]


def test_dynamic_generator_can_be_got_twice(ray_start):
    """Return 0 lists the yields, so they live as long as it is owned: a
    second get() of the generator ref, after every ref of the first was
    dropped, still finds them."""
    import gc
    ref = f_squares.remote(3)
    gen = ray_tpu.get(ref, timeout=60)
    assert ray_tpu.get(list(gen), timeout=30) == [0, 1, 4]
    del gen
    gc.collect()
    ray_tpu.get(f_identity.remote(0), timeout=60)  # a loop turn: frees flushed
    assert ray_tpu.get(list(ray_tpu.get(ref, timeout=60)),
                       timeout=30) == [0, 1, 4]


def test_queued_free_spares_a_retaken_ref(ray_start):
    """A free queued at count zero frees nothing if a ref was taken again
    before the loop flushed it."""
    from ray_tpu._private.worker import get_core
    core = get_core()
    ref = ray_tpu.put("kept")

    async def drop_and_retake():   # one loop turn: the flush runs after it
        core.remove_local_ref(ref.id, ref.owner_address)
        core.add_local_ref(ref.id, ref.owner_address)

    core._run(drop_and_retake(), timeout=30)
    ray_tpu.get(f_identity.remote(0), timeout=60)
    assert ray_tpu.get(ref, timeout=30) == "kept"


@pytest.mark.timeout_s(30)
def test_ref_finalised_inside_a_refcount_section_does_not_deadlock(ray_start):
    """A collector pass can run ObjectRef.__del__ on a thread that is inside
    add_local_ref or remove_local_ref (seen in test_actor_ordering's
    submit loop on a loaded machine): the finaliser must not wait for the
    lock its own thread holds, and the reference must still go."""
    import time

    from ray_tpu._private.worker import get_core
    core = get_core()
    ref = ray_tpu.put("dropped inside the section")
    h = ref.hex()
    with core._ref_lock:   # where add_local_ref is when the collector runs
        del ref
    deadline = time.monotonic() + 30
    while h in core.owned and time.monotonic() < deadline:
        ray_tpu.get(f_identity.remote(0), timeout=60)   # loop turns
    assert h not in core.owned and h not in core._local_refs


def test_get_and_wait_of_a_freed_owned_ref_raise_at_once(ray_start):
    """A ref this process owns and no longer holds cannot become ready:
    get() and wait() raise ObjectLostError instead of waiting."""
    from ray_tpu._private.ids import ObjectID, TaskID
    from ray_tpu._private.object_ref import ObjectRef
    from ray_tpu._private.worker import get_core
    from ray_tpu.exceptions import ObjectLostError
    gone = ObjectRef(ObjectID.for_task_return(TaskID.from_random(), 1),
                     get_core().address)
    with pytest.raises(ObjectLostError):
        ray_tpu.get(gone, timeout=30)
    with pytest.raises(ObjectLostError):
        ray_tpu.wait([gone], timeout=30)


def test_get_runtime_context(ray_start):
    """ray.get_runtime_context() analog: driver vs task vs actor views."""
    ctx = ray_tpu.get_runtime_context()
    assert ctx.worker_mode == "driver"
    assert ctx.get_task_id() is None and ctx.get_actor_id() is None
    assert len(ctx.get_node_id()) > 8

    @ray_tpu.remote
    def probe():
        c = ray_tpu.get_runtime_context()
        return c.get()

    d = ray_tpu.get(probe.remote())
    assert d["worker_mode"] == "worker"
    assert d["task_id"] and d["actor_id"] is None
    assert d["node_id"] == ctx.get_node_id()   # single-node cluster

    @ray_tpu.remote
    class A:
        def who(self):
            return ray_tpu.get_runtime_context().get()

    a = A.remote()
    d = ray_tpu.get(a.who.remote())
    assert d["actor_id"]


def test_local_mode_inline_execution():
    """ray.init(local_mode=True) analog: tasks/actors run inline, errors
    surface at get(), dynamic returns work, named actors resolve."""
    import ray_tpu as rt
    rt.shutdown()
    info = rt.init(local_mode=True)
    try:
        assert info.get("local_mode") is True

        calls = []

        @rt.remote
        def f(x):
            calls.append(x)     # proof of in-process execution
            return x + 1

        r = f.remote(1)
        assert calls == [1]     # ran synchronously at .remote()
        assert rt.get(r) == 2
        assert rt.get(f.remote(rt.put(10))) == 11

        @rt.remote
        def boom():
            raise ValueError("inline boom")

        ref = boom.remote()
        with pytest.raises(ValueError, match="inline boom"):
            rt.get(ref)

        @rt.remote(num_returns="dynamic")
        def gen(n):
            yield from range(n)

        assert rt.get(list(rt.get(gen.remote(3)))) == [0, 1, 2]

        @rt.remote
        class Counter:
            def __init__(self):
                self.n = 0

            def inc(self):
                self.n += 1
                return self.n

        c = Counter.options(name="cnt").remote()
        assert rt.get(c.inc.remote()) == 1
        c2 = rt.get_actor("cnt")
        assert rt.get(c2.inc.remote()) == 2
        ready, rest = rt.wait([rt.put(1), rt.put(2)])
        assert len(ready) == 1 and len(rest) == 1

        @rt.remote(num_returns=2)
        def boom2():
            raise ValueError("boom2")

        a, b = boom2.remote()   # must unpack, same as cluster mode
        for r in (a, b):
            with pytest.raises(ValueError, match="boom2"):
                rt.get(r)
    finally:
        rt.shutdown()
