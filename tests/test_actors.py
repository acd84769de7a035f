"""Actor tests: creation, ordering, named actors, restarts, async.

Modeled on the reference's ``python/ray/tests/test_actor.py`` /
``test_actor_failures.py`` coverage.
"""

import time

import pytest


def test_actor_basic(ray_start_regular):
    ray = ray_start_regular

    @ray.remote
    class Counter:
        def __init__(self, start=0):
            self.value = start

        def incr(self, by=1):
            self.value += by
            return self.value

        def get(self):
            return self.value

    c = Counter.remote(10)
    assert ray.get(c.incr.remote()) == 11
    assert ray.get(c.incr.remote(5)) == 16
    assert ray.get(c.get.remote()) == 16


def test_actor_method_ordering(ray_start_regular):
    ray = ray_start_regular

    @ray.remote
    class Appender:
        def __init__(self):
            self.items = []

        def add(self, x):
            self.items.append(x)

        def get(self):
            return self.items

    a = Appender.remote()
    for i in range(50):
        a.add.remote(i)
    assert ray.get(a.get.remote()) == list(range(50))


def test_actor_error(ray_start_regular):
    ray = ray_start_regular

    @ray.remote
    class Bad:
        def boom(self):
            raise RuntimeError("actor method failed")

        def fine(self):
            return "ok"

    b = Bad.remote()
    with pytest.raises(RuntimeError, match="actor method failed"):
        ray.get(b.boom.remote())
    # actor survives method errors
    assert ray.get(b.fine.remote()) == "ok"


def test_actor_init_error(ray_start_regular):
    ray = ray_start_regular

    @ray.remote
    class BadInit:
        def __init__(self):
            raise ValueError("init failed")

        def f(self):
            return 1

    b = BadInit.remote()
    with pytest.raises(Exception):
        ray.get(b.f.remote())


def test_named_actor(ray_start_regular):
    ray = ray_start_regular

    @ray.remote
    class Store:
        def __init__(self):
            self.d = {}

        def put(self, k, v):
            self.d[k] = v

        def get(self, k):
            return self.d.get(k)

    Store.options(name="kv").remote()
    h = ray.get_actor("kv")
    ray.get(h.put.remote("a", 1))
    assert ray.get(h.get.remote("a")) == 1
    with pytest.raises(ValueError):
        ray.get_actor("missing")


def test_actor_handle_passing(ray_start_regular):
    ray = ray_start_regular

    @ray.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def incr(self):
            self.n += 1
            return self.n

    @ray.remote
    def bump(counter):
        import ray_tpu
        return ray_tpu.get(counter.incr.remote())

    c = Counter.remote()
    results = ray.get([bump.remote(c) for _ in range(4)])
    assert sorted(results) == [1, 2, 3, 4]


def test_kill_actor(ray_start_regular):
    ray = ray_start_regular

    @ray.remote
    class A:
        def ping(self):
            return "pong"

    a = A.remote()
    assert ray.get(a.ping.remote()) == "pong"
    ray.kill(a)
    from ray_tpu.exceptions import ActorError
    time.sleep(0.5)
    with pytest.raises(Exception):
        ray.get(a.ping.remote(), timeout=5)


def test_actor_restart(ray_start_regular):
    ray = ray_start_regular

    @ray.remote(max_restarts=1)
    class Flaky:
        def __init__(self):
            self.n = 0

        def incr(self):
            self.n += 1
            return self.n

        def pid(self):
            import os
            return os.getpid()

        def die(self):
            import os
            os._exit(1)

    f = Flaky.remote()
    assert ray.get(f.incr.remote()) == 1
    first = ray.get(f.pid.remote())
    f.die.remote()
    time.sleep(1.0)
    # wait for the restart on a call that changes nothing: a call that
    # times out here may still run later, and a retried ``incr`` that
    # did would be counted against the state check below
    deadline = time.time() + 60
    while time.time() < deadline:
        try:
            if ray.get(f.pid.remote(), timeout=10) != first:
                break
        except Exception:
            pass
        time.sleep(0.5)
    else:
        pytest.fail("actor did not restart")
    assert ray.get(f.incr.remote(), timeout=30) == 1    # state reset


def test_async_actor(ray_start_regular):
    ray = ray_start_regular

    @ray.remote
    class AsyncActor:
        async def slow(self, x):
            import asyncio
            await asyncio.sleep(0.05)
            return x * 2

    a = AsyncActor.remote()
    refs = [a.slow.remote(i) for i in range(8)]
    t0 = time.time()
    assert ray.get(refs, timeout=30) == [i * 2 for i in range(8)]
    # concurrent: 8 x 50ms should take far less than 400ms
    assert time.time() - t0 < 2.0


def test_threaded_actor_concurrency(ray_start_regular):
    ray = ray_start_regular

    @ray.remote(max_concurrency=4)
    class Blocking:
        def wait_a_bit(self):
            time.sleep(0.2)
            return 1

    b = Blocking.remote()
    t0 = time.time()
    assert sum(ray.get([b.wait_a_bit.remote() for _ in range(4)],
                       timeout=30)) == 4
    assert time.time() - t0 < 3.0


def test_actor_num_returns(ray_start_regular):
    ray = ray_start_regular

    @ray.remote
    class M:
        @ray.method(num_returns=2)
        def two(self):
            return 1, 2

    m = M.remote()
    a, b = m.two.remote()
    assert ray.get([a, b]) == [1, 2]


def test_detached_lifetime_named_get(ray_start_regular):
    ray = ray_start_regular

    @ray.remote
    class D:
        def hi(self):
            return "hi"

    D.options(name="d1", lifetime="detached").remote()
    assert ray.get(ray.get_actor("d1").hi.remote()) == "hi"


def test_direct_result_push_edge_cases(ray_start_regular):
    """Direct-channel result push: big results fall back to the CP
    flow, error results raise through the push, and entries never
    strand a get() (docs/PROTOCOL.md result push-back)."""
    import numpy as np

    ray = ray_start_regular

    @ray.remote
    class A:
        def small(self, x):
            return x * 2

        def big(self):
            # over inline_object_max_bytes: push sends the big marker
            return np.zeros(400_000, np.uint8)

        def boom(self):
            raise RuntimeError("pushed-error")

    a = A.remote()
    assert ray.get(a.small.remote(21), timeout=30) == 42
    arr = ray.get(a.big.remote(), timeout=30)
    assert arr.nbytes == 400_000
    with pytest.raises(RuntimeError, match="pushed-error"):
        ray.get(a.boom.remote(), timeout=30)
    # interleaving small/big/error keeps per-call results straight
    refs = [a.small.remote(i) for i in range(20)]
    assert ray.get(refs, timeout=30) == [i * 2 for i in range(20)]


def test_direct_push_survives_actor_kill(ray_start_regular):
    """A call in flight when the actor dies fails cleanly (the result
    stream drops; the waiter falls back to the CP flow and the death
    path resolves it)."""
    ray = ray_start_regular
    from ray_tpu.exceptions import ActorDiedError, TaskError

    @ray.remote(max_restarts=0)
    class Slow:
        def nap(self, s):
            time.sleep(s)
            return "done"

        def pid(self):
            import os
            return os.getpid()

    s = Slow.remote()
    assert ray.get(s.pid.remote(), timeout=30) > 0
    ref = s.nap.remote(30)
    time.sleep(0.3)
    ray.kill(s)
    with pytest.raises((ActorDiedError, TaskError)):
        ray.get(ref, timeout=60)
