"""Compiled DAG + mutable channel tests (parity:
``python/ray/dag/tests/experimental``)."""

import pytest


def test_channel_roundtrip_and_close(tmp_path):
    from ray_tpu.experimental.channel import (Channel, ChannelClosed)

    ch = Channel(str(tmp_path / "c0"), capacity=4096, num_readers=2)
    ch.write({"a": 1})
    assert ch.read(reader_index=0) == {"a": 1}
    # second reader has its own cursor
    assert ch.read(reader_index=1) == {"a": 1}
    ch.write([1, 2, 3])
    assert ch.read(reader_index=0) == [1, 2, 3]
    assert ch.read(reader_index=1) == [1, 2, 3]
    ch.close()
    with pytest.raises(ChannelClosed):
        ch.read(reader_index=0)
    ch.unlink()


def test_channel_capacity_enforced(tmp_path):
    from ray_tpu.experimental.channel import Channel
    ch = Channel(str(tmp_path / "c1"), capacity=128)
    with pytest.raises(ValueError):
        ch.write(b"x" * 1024)
    ch.unlink()


@pytest.mark.slow
def test_compiled_dag_pipeline(ray_start_regular):
    """3-stage pipeline over channels: correct, pipelined, and much
    faster than per-call task submission (gate kept conservative
    here)."""
    import time

    import ray_tpu
    from ray_tpu.dag import InputNode

    @ray_tpu.remote
    class Stage:
        def __init__(self, add):
            self.add = add

        def step(self, x):
            return x + self.add

    a, b, c = Stage.bind(1), Stage.bind(10), Stage.bind(100)
    with InputNode() as inp:
        dag = c.step.bind(b.step.bind(a.step.bind(inp)))
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute(5).get() == 116
        N = 200
        t0 = time.perf_counter()
        outs, futs = [], []
        for i in range(N):
            futs.append(compiled.execute(i))
            if len(futs) >= 3:
                outs.append(futs.pop(0).get())
        outs.extend(f.get() for f in futs)
        compiled_rate = N / (time.perf_counter() - t0)
        assert outs == [i + 111 for i in range(N)]

        s1, s2, s3 = Stage.remote(1), Stage.remote(10), Stage.remote(100)
        ray_tpu.get([s1.step.remote(0), s2.step.remote(0),
                     s3.step.remote(0)])
        t0 = time.perf_counter()
        M = 60
        for i in range(M):
            assert ray_tpu.get(
                s3.step.remote(s2.step.remote(s1.step.remote(i)))) \
                == i + 111
        task_rate = M / (time.perf_counter() - t0)
        assert compiled_rate > 2 * task_rate, (compiled_rate, task_rate)
    finally:
        compiled.teardown()


def test_compiled_dag_multi_output(ray_start_regular):
    import ray_tpu
    from ray_tpu.dag import InputNode, MultiOutputNode

    @ray_tpu.remote
    class Worker:
        def __init__(self, k):
            self.k = k

        def mul(self, x):
            return x * self.k

    w1, w2 = Worker.bind(2), Worker.bind(3)
    with InputNode() as inp:
        dag = MultiOutputNode([w1.mul.bind(inp), w2.mul.bind(inp)])
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute(7).get() == [14, 21]
        assert compiled.execute(2).get() == [4, 6]
    finally:
        compiled.teardown()


def test_compiled_dag_teardown_frees_actor(ray_start_regular):
    """After teardown the executor loop exits and the actor serves
    normal calls again."""
    import ray_tpu
    from ray_tpu.dag import InputNode

    @ray_tpu.remote
    class S:
        def step(self, x):
            return x - 1

    node = S.bind()
    with InputNode() as inp:
        dag = node.step.bind(inp)
    compiled = dag.experimental_compile()
    assert compiled.execute(3).get() == 2
    compiled.teardown()
    handle = node._get_handle({}, ())
    assert ray_tpu.get(handle.step.remote(10), timeout=30) == 9


def test_compiled_dag_surfaces_stage_exception(ray_start_regular):
    """A stage exception propagates to the driver's get (not a channel
    timeout) and the pipeline stays alive for later calls."""
    import pytest as _pytest

    import ray_tpu
    from ray_tpu.dag import InputNode

    @ray_tpu.remote
    class S:
        def step(self, x):
            if x < 0:
                raise ValueError("negative!")
            return x + 1

    node = S.bind()
    with InputNode() as inp:
        dag = node.step.bind(inp)
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute(1).get() == 2
        with _pytest.raises(RuntimeError, match="negative!"):
            compiled.execute(-1).get()
        assert compiled.execute(5).get() == 6   # loop survived
    finally:
        compiled.teardown()


def test_compiled_dag_rejects_kwargs(ray_start_regular):
    import pytest as _pytest

    import ray_tpu
    from ray_tpu.dag import InputNode

    @ray_tpu.remote
    class S:
        def step(self, x, scale=1):
            return x * scale

    node = S.bind()
    with InputNode() as inp:
        dag = node.step.bind(inp, scale=2)
    with _pytest.raises(TypeError, match="positional"):
        dag.experimental_compile()
