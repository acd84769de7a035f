"""Resilience-layer tests: deterministic fault injection, async
bit-exact train checkpoint/resume with corrupt-snapshot fallback, the
supervised RL loop's kill/recovery acceptance invariants, the replay
put timeout, and the engine watchdog."""

import glob
import os
import sys
import threading
import time

import numpy as np
import pytest


# --------------------------------------------------------------- fixtures
@pytest.fixture(scope="module")
def tiny_train():
    """Smallest GPT that exercises the full sharded TrainState."""
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig
    return GPTConfig(vocab_size=64, d_model=32, n_layers=1, n_heads=2,
                     max_seq=32, dtype=jnp.float32)


@pytest.fixture(scope="module")
def train_fns(tiny_train):
    """One compiled train step shared by every checkpoint test (the
    loops differ only in step counts/checkpoint plumbing — recompiling
    per test would dominate the suite's budget)."""
    import jax

    from ray_tpu.models import training
    from ray_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    return training.build_gpt_train(tiny_train, mesh, telemetry=False)


@pytest.fixture(scope="module")
def rl_learner_fns(tiny_rl):
    """One compiled policy-gradient step shared by every supervised-
    loop test (same lr/baseline everywhere; per-test seeds re-init the
    state, so determinism is untouched)."""
    import jax

    from ray_tpu.models import training
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.rl.learner import _rl_optimizer
    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    return training.build_gpt_rl_train(
        tiny_rl, mesh, baseline="rloo",
        optimizer=_rl_optimizer(1e-2, 1.0))


@pytest.fixture(scope="module")
def tiny_rl():
    """The test_rl.py tiny config: vocab 128 keeps the target-token
    task learnable in a handful of REINFORCE steps."""
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig
    return GPTConfig(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                     max_seq=64, dtype=jnp.float32)


@pytest.fixture(autouse=True)
def _no_faults():
    """Every test starts and ends with no armed fault plan."""
    from ray_tpu.util import chaos
    chaos.clear_faults()
    yield
    chaos.clear_faults()


# RL engines across tests share one executable cache (same geometry ->
# same AOT executables; the test_rl.py pattern)
_EXEC_CACHE = {}
_ENGINE_KW = {"slots": 6, "page_size": 16, "buckets": (16,),
              "telemetry": False, "executable_cache": _EXEC_CACHE}


def _rlcfg(**over):
    from ray_tpu.rl.config import RLConfig
    base = dict(actors=1, batch=6, horizon=8, queue=4, max_lag=2,
                overflow="drop", publish_every=1, baseline="rloo",
                temperature=1.0)
    base.update(over)
    return RLConfig(**base)


# ------------------------------------------------------------ fault plans
def test_fault_plan_spec_and_counters():
    from ray_tpu.util.chaos import FaultPlan, InjectedFault
    plan = FaultPlan("rl.rollout@3, infer.decode, ckpt.write@2")
    # fires exactly on the armed hit, once
    assert [plan.fires("rl.rollout") for _ in range(5)] == \
        [False, False, True, False, False]
    assert plan.fires("infer.decode") is True      # bare site = @1
    assert plan.fires("infer.decode") is False
    assert plan.fires("unarmed.site") is False
    assert plan.fired == [("rl.rollout", 3), ("infer.decode", 1)]
    assert plan.hits("rl.rollout") == 5
    with pytest.raises(ValueError, match="site@N"):
        FaultPlan("rl.rollout@x")
    with pytest.raises(ValueError, match=">= 1"):
        FaultPlan("rl.rollout@0")
    err = InjectedFault("s", 2)
    assert err.site == "s" and err.hit == 2
    # faults cross process boundaries: must pickle via constructor
    # args, not the default args-is-the-message replay
    import pickle
    back = pickle.loads(pickle.dumps(err))
    assert (back.site, back.hit) == ("s", 2)
    assert str(back) == str(err)


def test_fault_plan_delay_grammar():
    """r19 slowdown entries: ``site@N:delay=S`` sleeps one hit,
    ``site@N..M:delay=S`` a sustained window, both logged in
    ``plan.slowed`` and charged to ``slowdown_s`` — and the grammar
    rejects a hit range without a delay (a fault fires once)."""
    import time as _time

    from ray_tpu.util.chaos import FaultPlan
    plan = FaultPlan("a.b@2:delay=0.02, a.b@4..6:delay=0.01, c.d@2")
    t0 = _time.monotonic()
    fired = [plan.fires("a.b") for _ in range(7)]
    wall = _time.monotonic() - t0
    assert fired == [False] * 7          # delays never raise
    assert plan.slowed == [("a.b", 2, 0.02), ("a.b", 4, 0.01),
                           ("a.b", 5, 0.01), ("a.b", 6, 0.01)]
    assert plan.slowdown_s("a.b") == pytest.approx(0.05)
    assert plan.slowdown_s("c.d") == 0.0
    assert wall >= 0.05                  # the sleeps really happened
    # a delay window and an armed fault coexist on one site
    assert [plan.fires("c.d") for _ in range(3)] == \
        [False, True, False]
    # overlapping windows stack their delays on the shared hit
    both = FaultPlan("x.y@1..2:delay=0.01,x.y@2:delay=0.02")
    both.fires("x.y")
    both.fires("x.y")
    assert both.slowed == [("x.y", 1, 0.01), ("x.y", 2, 0.03)]
    with pytest.raises(ValueError, match="delay"):
        FaultPlan("a.b@1..3")            # range needs :delay=
    with pytest.raises(ValueError, match="number of seconds"):
        FaultPlan("a.b@1:delay=fast")
    with pytest.raises(ValueError, match=">= 0"):
        FaultPlan("a.b@1:delay=-1")
    with pytest.raises(ValueError, match="modifier"):
        FaultPlan("a.b@1:jitter=1")
    with pytest.raises(ValueError, match="N <= M"):
        FaultPlan("a.b@5..2:delay=0.1")


def test_fault_plan_counters_thread_safe():
    """Hit counters are lock-protected: N threads hammering one site
    count exactly N*K hits and the armed fault fires exactly once —
    the data-plane producer thread and hedged standby readers count
    sites concurrently with the main thread."""
    import threading

    from ray_tpu.util.chaos import FaultPlan
    plan = FaultPlan("t.s@1500")
    fired = []

    def worker():
        for _ in range(250):
            if plan.fires("t.s"):
                fired.append(1)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert plan.hits("t.s") == 2000
    assert len(fired) == 1 and plan.fired == [("t.s", 1500)]


# ------------------------------------------------- straggler supervisor
def test_straggler_supervisor_blip_vs_sustained():
    """r19 gray-failure detection: the rolling-median baseline forms
    from accepted steps only, a single slow step (GC pause, cold
    compile) never fires, and only ``dwell`` CONSECUTIVE slow steps
    raise the event — after which the streak resets."""
    from ray_tpu.resilience import StragglerSupervisor
    sup = StragglerSupervisor(factor=3.0, dwell=3, window=8)
    assert sup.enabled
    # baseline forming: even a wild outlier is accepted silently (the
    # cold-compile step) and the median stays robust to it
    assert not any(sup.observe(w) for w in (0.01, 0.5, 0.01, 0.012))
    assert sup.baseline_s() == pytest.approx(0.011)
    # a blip: two slow steps, then recovery — no event, and the slow
    # samples never entered the baseline
    assert sup.observe(0.2) is False
    assert sup.observe(0.2) is False
    assert sup.observe(0.011) is False          # streak broken
    assert sup.baseline_s() == pytest.approx(0.011)
    assert sup.events == 0 and sup.slow_steps == 2
    # sustained: dwell consecutive slow steps fire exactly one event
    assert [sup.observe(0.2) for _ in range(3)] == \
        [False, False, True]
    assert sup.events == 1
    assert sup.event_log[-1]["baseline_s"] == pytest.approx(0.011)
    # reset forgets baseline AND streak (topology changed)
    sup.reset()
    assert sup.baseline_s() == 0.0
    assert sup.observe(10.0) is False           # new normal, accepted
    # disabled: factor=0 never observes anything
    off = StragglerSupervisor(factor=0.0, dwell=1, window=8)
    assert not off.enabled
    assert not any(off.observe(100.0) for _ in range(10))
    with pytest.raises(ValueError, match="dwell"):
        StragglerSupervisor(factor=2.0, dwell=0)
    with pytest.raises(ValueError, match="min_samples"):
        StragglerSupervisor(factor=2.0, window=2)


def test_straggler_per_tier_baselines():
    """r22: multi-pod meshes keep a baseline PER fabric tier — a
    DCN-crossing step is legitimately slower than an ICI-only one, so
    it must be judged against its own tier's median, and slow streaks
    must not interleave across tiers into a phantom event."""
    from ray_tpu.resilience import StragglerSupervisor
    sup = StragglerSupervisor(factor=3.0, dwell=2, window=8)
    # two tiers, 10x apart in normal step wall
    for w in (0.01, 0.011, 0.01):
        assert sup.observe(w, tier="ici") is False
    for w in (0.1, 0.11, 0.1):
        assert sup.observe(w, tier="dcn") is False
    assert sup.baseline_s("ici") == pytest.approx(0.01)
    assert sup.baseline_s("dcn") == pytest.approx(0.1)
    # a 0.1s step is 10x the ICI baseline but NORMAL for the dcn tier:
    # judged against its own baseline, it is accepted silently
    assert sup.observe(0.1, tier="dcn") is False
    assert sup.slow_steps == 0
    # streaks are per-tier: slow-ici, slow-dcn, slow-ici must not fire
    # a dwell=2 event (no tier saw two CONSECUTIVE slow steps...)
    assert sup.observe(0.05, tier="ici") is False
    assert sup.observe(0.5, tier="dcn") is False
    assert sup.events == 0
    # ...but the second consecutive slow step on one tier does fire,
    # and the event names its tier
    assert sup.observe(0.05, tier="ici") is True
    assert sup.events == 1
    assert sup.event_log[-1]["tier"] == "ici"
    assert sup.event_log[-1]["baseline_s"] == pytest.approx(0.01)
    # the dcn tier's streak is still one: its own second slow step
    # completes its own event
    assert sup.observe(0.5, tier="dcn") is True
    assert sup.event_log[-1]["tier"] == "dcn"
    # reset forgets every tier
    sup.reset()
    assert sup.baseline_s("ici") == 0.0
    assert sup.baseline_s("dcn") == 0.0
    # tier-less callers land in one "default" bucket (back-compat)
    for w in (0.02, 0.02, 0.02):
        sup.observe(w)
    assert sup.baseline_s() == pytest.approx(0.02)


def test_straggler_config_env_knobs(monkeypatch):
    from ray_tpu.resilience import StragglerSupervisor
    from ray_tpu.resilience.config import resilience_config
    cfg = resilience_config(refresh=True)
    assert cfg.straggler_factor == 0.0          # default off
    assert cfg.straggler_dwell == 3
    assert cfg.straggler_window == 16
    monkeypatch.setenv("RAY_TPU_STRAGGLER_FACTOR", "2.5")
    monkeypatch.setenv("RAY_TPU_STRAGGLER_DWELL", "5")
    monkeypatch.setenv("RAY_TPU_STRAGGLER_WINDOW", "32")
    resilience_config(refresh=True)
    sup = StragglerSupervisor()
    assert (sup.factor, sup.dwell) == (2.5, 5)
    assert sup._tier_walls("default").maxlen == 32
    # out-of-range knobs clamp loudly instead of crashing the loop
    monkeypatch.setenv("RAY_TPU_STRAGGLER_FACTOR", "-1")
    monkeypatch.setenv("RAY_TPU_STRAGGLER_DWELL", "0")
    monkeypatch.setenv("RAY_TPU_STRAGGLER_WINDOW", "1")
    cfg = resilience_config(refresh=True)
    assert (cfg.straggler_factor, cfg.straggler_dwell,
            cfg.straggler_window) == (0.0, 1, 3)
    monkeypatch.delenv("RAY_TPU_STRAGGLER_FACTOR")
    monkeypatch.delenv("RAY_TPU_STRAGGLER_DWELL")
    monkeypatch.delenv("RAY_TPU_STRAGGLER_WINDOW")
    resilience_config(refresh=True)


def test_fault_plan_env_and_install(monkeypatch):
    from ray_tpu.util import chaos
    # env spec is read lazily, once
    monkeypatch.setenv("RAY_TPU_FAULTS", "a.b@2")
    chaos.clear_faults()
    chaos.maybe_fail("a.b")                        # hit 1: armed at 2
    with pytest.raises(chaos.InjectedFault):
        chaos.maybe_fail("a.b")
    chaos.maybe_fail("a.b")                        # fired once only
    # programmatic install wins over the env
    plan = chaos.install_faults("c.d@1")
    assert chaos.should_fire("c.d") is True
    assert plan.fired == [("c.d", 1)]
    chaos.clear_faults()
    monkeypatch.delenv("RAY_TPU_FAULTS")
    chaos.clear_faults()
    chaos.maybe_fail("c.d")                        # no plan: free


# ----------------------------------------------------- train checkpointing
def test_checkpoint_write_is_async(tmp_path, monkeypatch, tiny_train):
    """The step loop pays the host copy, never the disk write: with a
    deliberately slow writer the save call returns immediately and
    flush() observes the write."""
    import ray_tpu.resilience.checkpoint as rc

    slow, wrote = 0.25, []

    def slow_save(tree, path, *, name="state"):
        time.sleep(slow)
        wrote.append(path)
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, f"{name}.marker"), "w") as f:
            f.write("x")

    monkeypatch.setattr(rc, "save_pytree", slow_save)
    ck = rc.TrainCheckpointer(str(tmp_path), every=2, keep=2,
                              telemetry=True)
    state = {"w": np.zeros((4, 4), np.float32)}
    t0 = time.monotonic()
    assert ck.maybe_save(state, step=2) is True
    assert ck.maybe_save(state, step=3) is False   # off-cadence: no-op
    took = time.monotonic() - t0
    assert took < slow / 2, f"save blocked the caller for {took:.3f}s"
    ck.flush()
    assert len(wrote) == 1
    assert ck.telemetry.summary()["checkpoints"] == 1
    assert ck.telemetry.summary()["last_checkpoint_step"] == 2
    assert ck.telemetry.summary()["write_s"] >= slow
    ck.close()


def test_train_resume_is_bit_exact(tmp_path, tiny_train, train_fns):
    """The acceptance invariant: a run killed at step 4 and resumed
    from its checkpoint produces the identical loss sequence to an
    uninterrupted fixed-seed run — params, opt state, step counter and
    data cursor all survive the round trip."""
    from ray_tpu.resilience import TrainCheckpointer, run_train_ckpt_loop
    cfg = tiny_train
    full = run_train_ckpt_loop(cfg, steps=6, batch_size=2, seq_len=16,
                               seed=0, fns=train_fns)
    assert len(full["losses"]) == 6

    d = str(tmp_path / "ck")
    with TrainCheckpointer(d, every=2, keep=2, telemetry=True) as ck:
        part = run_train_ckpt_loop(cfg, steps=4, batch_size=2,
                                   seq_len=16, seed=0, fns=train_fns, ckpt=ck)
    assert part["losses"] == full["losses"][:4]
    assert part["checkpoint"]["checkpoints"] == 2
    assert part["checkpoint"]["last_checkpoint_step"] == 4

    with TrainCheckpointer(d, every=2, keep=2) as ck2:
        rest = run_train_ckpt_loop(cfg, steps=6, batch_size=2,
                                   seq_len=16, seed=0, fns=train_fns, ckpt=ck2,
                                   resume=True)
    assert rest["start_step"] == 4
    assert rest["restored_from"].endswith("checkpoint_000001")
    # bit-exact: float-equal losses, not allclose
    assert rest["losses"] == full["losses"][4:]
    assert rest["final_step"] == 6


def test_corrupt_checkpoint_falls_back_loudly(tmp_path, capfd,
                                              tiny_train, train_fns):
    """A truncated newest snapshot (torn write / ``ckpt.truncate``
    fault) must cost one checkpoint interval, not the run: restore
    warns on stderr and falls back to the previous retained one."""
    from ray_tpu.resilience import TrainCheckpointer, run_train_ckpt_loop
    cfg = tiny_train
    d = str(tmp_path / "ck")
    with TrainCheckpointer(d, every=2, keep=3) as ck:
        run_train_ckpt_loop(cfg, steps=4, batch_size=2, seq_len=16,
                            seed=0, fns=train_fns, ckpt=ck)
    dirs = sorted(glob.glob(os.path.join(d, "checkpoint_*")))
    assert len(dirs) == 2
    # gut the newest checkpoint's payload (keep one file so the dir
    # still "exists" for the manager)
    for root, _dirs, names in os.walk(dirs[-1]):
        for n in sorted(names)[1:]:
            os.remove(os.path.join(root, n))
    capfd.readouterr()
    with TrainCheckpointer(d, every=2, keep=3) as ck2:
        rest = run_train_ckpt_loop(cfg, steps=4, batch_size=2,
                                   seq_len=16, seed=0, fns=train_fns, ckpt=ck2,
                                   resume=True)
    assert rest["start_step"] == 2
    assert rest["restored_from"].endswith("checkpoint_000000")
    err = capfd.readouterr().err
    assert "falling back to the previous retained snapshot" in err


def test_npz_sidecar_mismatch_falls_back(tmp_path, monkeypatch, capfd,
                                         tiny_train, train_fns):
    """The npz fallback path can deserialize a *wrong* tree without
    erroring; restore validation must reject shape/dtype drift loudly
    instead of silently loading garbage params."""
    from ray_tpu.resilience import TrainCheckpointer, run_train_ckpt_loop
    from ray_tpu.train.checkpoint import load_pytree, save_pytree
    # force the npz writer: make `import orbax.checkpoint` fail
    monkeypatch.setitem(sys.modules, "orbax", None)
    monkeypatch.setitem(sys.modules, "orbax.checkpoint", None)
    cfg = tiny_train
    d = str(tmp_path / "ck")
    with TrainCheckpointer(d, every=2, keep=3) as ck:
        run_train_ckpt_loop(cfg, steps=4, batch_size=2, seq_len=16,
                            seed=0, fns=train_fns, ckpt=ck)
    dirs = sorted(glob.glob(os.path.join(d, "checkpoint_*")))
    assert os.path.exists(os.path.join(dirs[-1], "train_state.npz"))
    # rewrite the newest snapshot with one leaf's shape drifted (the
    # embed table loses a row): the npz+sidecar pair still loads
    # cleanly — only validation can tell it is not this model's state
    payload = load_pytree(dirs[-1], name="train_state")
    payload["state"].params["embed"] = \
        payload["state"].params["embed"][:-1]
    save_pytree(payload, dirs[-1], name="train_state")
    capfd.readouterr()
    with TrainCheckpointer(d, every=2, keep=3) as ck2:
        rest = run_train_ckpt_loop(cfg, steps=4, batch_size=2,
                                   seq_len=16, seed=0, fns=train_fns, ckpt=ck2,
                                   resume=True)
    assert rest["start_step"] == 2          # fell back to the older one
    assert rest["restored_from"].endswith("checkpoint_000000")
    err = capfd.readouterr().err
    assert "mismatch" in err and "falling back" in err


def test_ckpt_write_and_truncate_faults(tmp_path, capfd, tiny_train,
                                        train_fns):
    """``ckpt.write`` fails a write (counted, run continues);
    ``ckpt.truncate`` tears one on disk (restore falls back)."""
    from ray_tpu.resilience import TrainCheckpointer, run_train_ckpt_loop
    from ray_tpu.util import chaos
    cfg = tiny_train
    d = str(tmp_path / "ck")
    # write 1 dies at the ckpt.write site (so it never reaches the
    # truncate site); write 2 lands; write 3 lands then gets truncated
    plan = chaos.install_faults("ckpt.write@1,ckpt.truncate@2")
    with TrainCheckpointer(d, every=1, keep=4, telemetry=True) as ck:
        run_train_ckpt_loop(cfg, steps=3, batch_size=2, seq_len=16,
                            seed=0, fns=train_fns, ckpt=ck)
        ck.flush()
        summary = ck.telemetry.summary()
    assert summary["failed"] == 1
    assert summary["checkpoints"] == 2
    assert ("ckpt.write", 1) in plan.fired
    assert ("ckpt.truncate", 2) in plan.fired
    chaos.clear_faults()
    capfd.readouterr()
    with TrainCheckpointer(d, every=1, keep=4) as ck2:
        rest = run_train_ckpt_loop(cfg, steps=3, batch_size=2,
                                   seq_len=16, seed=0, fns=train_fns, ckpt=ck2,
                                   resume=True)
    # the truncated newest (step 3) falls back to the valid step-2 one
    assert rest["start_step"] == 2
    assert "falling back" in capfd.readouterr().err


# --------------------------------------------------------- replay timeout
def test_replay_put_timeout_typed_and_counted(tiny_rl):
    from ray_tpu.rl.replay import ReplayPutTimeout, ReplayQueue
    from ray_tpu.rl.rollout import TrajectoryBatch

    def batch(v):
        return TrajectoryBatch(
            tokens=np.zeros((1, 4), np.int32),
            targets=np.full((1, 4), -1, np.int32),
            rewards=np.zeros((1,), np.float32), logprobs=[[0.0]],
            completions=[[1]], param_version=v)

    q = ReplayQueue(1, max_lag=1, overflow="wait")
    assert q.put(batch(1)) is True
    # non-blocking rejection (timeout unset): False + counted
    assert q.put(batch(1)) is False
    assert q.backpressure_rejections == 1
    # timed rejection: typed error + counted
    t0 = time.monotonic()
    with pytest.raises(ReplayPutTimeout, match="RAY_TPU_RL_PUT_TIMEOUT") \
            as ei:
        q.put(batch(1), timeout=0.15)
    assert 0.1 < time.monotonic() - t0 < 5.0
    assert q.backpressure_rejections == 2
    import pickle             # crosses the object store: must rebuild
    assert pickle.loads(pickle.dumps(ei.value)).timeout_s == 0.15
    # a concurrent pop frees space: the blocked put completes
    popper = threading.Timer(0.1, lambda: q.pop(1))
    popper.start()
    assert q.put(batch(1), timeout=5.0) is True
    popper.join()
    assert q.backpressure_rejections == 2
    # the knob plumbs through rl_config
    os.environ["RAY_TPU_RL_PUT_TIMEOUT"] = "2.5"
    try:
        from ray_tpu.rl import rl_config
        assert rl_config(refresh=True).put_timeout == 2.5
        os.environ["RAY_TPU_RL_PUT_TIMEOUT"] = "-1"
        assert rl_config(refresh=True).put_timeout == 0.0
    finally:
        del os.environ["RAY_TPU_RL_PUT_TIMEOUT"]
        rl_config(refresh=True)


# --------------------------------------------------- supervised RL loop
def test_rl_kill_recovery_acceptance(tmp_path, tiny_rl, rl_learner_fns):
    """THE chaos acceptance test: kill a rollout actor mid-loop AND
    the learner mid-loop (restored from its checkpoint); the loop must
    complete with (a) the final-third reward mean within tolerance of
    an uninterrupted fixed-seed run, (b) zero steady-state recompiles
    after recovery (the restarted engine compiles nothing — shared
    executable cache), and (c) no leaked slots/pages/refs (the loop
    raises on leak at drain)."""
    from ray_tpu.resilience import (TrainCheckpointer,
                                    run_supervised_rl_loop)
    from ray_tpu.util import chaos
    cfg = tiny_rl
    steps, seed = 12, 3
    base = run_supervised_rl_loop(cfg, steps=steps, rlcfg=_rlcfg(),
                                  seed=seed, lr=1e-2,
                                  engine_kwargs=_ENGINE_KW,
                                  learner_fns=rl_learner_fns,
                                  telemetry=True)
    assert base["actor_restarts"] == 0 and base["learner_restarts"] == 0
    curve_b = base["reward_curve"]
    third = len(curve_b) // 3
    base_first = float(np.mean(curve_b[:third]))
    base_final = float(np.mean(curve_b[-third:]))
    assert base_final > base_first + 0.5     # the r14 reward-improves

    plan = chaos.install_faults("rl.rollout@4,rl.learner@7")
    with TrainCheckpointer(str(tmp_path / "rl"), every=0,
                           keep=3) as ck:
        rec = run_supervised_rl_loop(cfg, steps=steps, rlcfg=_rlcfg(),
                                     seed=seed, lr=1e-2,
                                     engine_kwargs=_ENGINE_KW,
                                     learner_fns=rl_learner_fns,
                                     ckpt=ck, ckpt_every=2,
                                     telemetry=True)
    chaos.clear_faults()
    # both faults actually landed
    assert ("rl.rollout", 4) in plan.fired
    assert ("rl.learner", 7) in plan.fired
    assert rec["actor_restarts"] == 1
    assert rec["learner_restarts"] == 1
    assert rec["telemetry"]["actor_restarts"] == 1
    assert rec["telemetry"]["learner_restarts"] == 1
    # (b) zero recompiles after recovery: the replacement actor's
    # engine compiled NOTHING — every executable came from the shared
    # cache (restart cost is construction, not XLA)
    assert rec["restart_compiles"] == [
        {"prefill": 0, "prefill_cached": 0, "decode": 0}]
    # steady state after recovery: the surviving engines also show no
    # new compiles vs the cache (all compile keys pre-existed)
    for st in rec["engine_stats"]:
        assert st["compiles"] == {"prefill": 0, "prefill_cached": 0,
                                  "decode": 0}
    # (a) recovery quality: the loop still learns — improvement over
    # its own first third AND final-third mean within tolerance of the
    # uninterrupted run (trajectories diverge after the kill by
    # construction, so this is a tolerance check, not bitwise)
    curve_r = rec["reward_curve"]
    third_r = len(curve_r) // 3
    rec_first = float(np.mean(curve_r[:third_r]))
    rec_final = float(np.mean(curve_r[-third_r:]))
    assert rec_final > rec_first + 0.25
    assert abs(rec_final - base_final) < 2.0, (
        f"recovered final-third {rec_final} vs uninterrupted "
        f"{base_final}")
    # the restore rolled the records back with the learner, so
    # curve[i] is exactly "the i-th counted learner step" even though
    # some steps re-ran after the restore
    assert len(curve_r) == steps
    # (c) is the loop's own drain-clean invariant: reaching here means
    # no slot/page/ref leaked (it raises otherwise) — cross-check one
    for st in rec["engine_stats"]:
        assert st["active"] == 0 and st["waiting"] == 0


def test_rl_killed_loop_resumes_with_bounded_loss(tmp_path, tiny_rl,
                                                  rl_learner_fns):
    """A loop whose learner death exceeds the in-place restart budget
    dies — and a rerun with ``resume=True`` restores the checkpointed
    learner and finishes; lost work is bounded by the checkpoint
    interval plus one queue, never the run."""
    from ray_tpu.resilience import (TrainCheckpointer,
                                    run_supervised_rl_loop)
    from ray_tpu.util import chaos
    cfg = tiny_rl
    d = str(tmp_path / "rl")
    kw = dict(rlcfg=_rlcfg(), seed=5, lr=1e-2,
              engine_kwargs=_ENGINE_KW, learner_fns=rl_learner_fns,
              telemetry=False)
    chaos.install_faults("rl.learner@5")
    with TrainCheckpointer(d, every=0, keep=3) as ck:
        with pytest.raises(chaos.InjectedFault):
            run_supervised_rl_loop(cfg, steps=6, ckpt=ck, ckpt_every=2,
                                   max_learner_restarts=0, **kw)
    chaos.clear_faults()
    with TrainCheckpointer(d, every=0, keep=3) as ck2:
        rec = run_supervised_rl_loop(cfg, steps=6, ckpt=ck2,
                                     ckpt_every=2, resume=True, **kw)
    assert rec["resumed_from"] is not None
    assert rec["steps"] == 6
    # killed at learner step 5 with ckpt_every=2 -> restored from the
    # step-4 snapshot: the resumed run re-ran at most ckpt_every steps
    assert len(rec["reward_curve"]) == 2


@pytest.mark.slow   # ~4s: the kill-recovery acceptance test already
                    # proves the supervised-publish path end-to-end
def test_publish_failure_is_survived(tiny_rl, rl_learner_fns):
    """An injected ``rl.publish`` failure skips one publication:
    actors keep rolling out on the previous version and the loop
    completes (no crash, failure counted)."""
    from ray_tpu.resilience import run_supervised_rl_loop
    from ray_tpu.util import chaos
    cfg = tiny_rl
    # the seed publish is hit 1 and must succeed; kill a later one
    plan = chaos.install_faults("rl.publish@3")
    res = run_supervised_rl_loop(cfg, steps=4, rlcfg=_rlcfg(),
                                 seed=7, lr=1e-2,
                                 engine_kwargs=_ENGINE_KW,
                                 learner_fns=rl_learner_fns,
                                 telemetry=False)
    chaos.clear_faults()
    assert ("rl.publish", 3) in plan.fired
    assert res["publish_failures"] == 1
    assert res["steps"] == 4
    # versions stay monotonic and consistent despite the gap
    assert res["param_version"] == res["publishes"]


def test_rollout_engine_ignores_serve_deadlines(monkeypatch, tiny_rl):
    """A rollout actor's engine must not inherit the serving fleet's
    deadline defaults: an expired rollout request would truncate a
    trajectory mid-flight (and its terminal error event would
    otherwise feed token -1 to the learner as a real action)."""
    from ray_tpu.inference import infer_config
    from ray_tpu.rl.rollout import RolloutActor
    import jax

    from ray_tpu.models.gpt import init_params
    monkeypatch.setenv("RAY_TPU_INFER_TTFT_DEADLINE", "0.001")
    monkeypatch.setenv("RAY_TPU_INFER_DEADLINE", "0.001")
    infer_config(refresh=True)
    try:
        params = init_params(tiny_rl, jax.random.PRNGKey(0))
        actor = RolloutActor(tiny_rl, params, engine_kwargs=_ENGINE_KW)
        assert actor.engine.ttft_deadline is None
        assert actor.engine.deadline is None
    finally:
        monkeypatch.delenv("RAY_TPU_INFER_TTFT_DEADLINE")
        monkeypatch.delenv("RAY_TPU_INFER_DEADLINE")
        infer_config(refresh=True)


# --------------------------------------------------------------- watchdog
class _FakeEngine:
    """Quacks like an engine for the watchdog: pure host state."""

    def __init__(self):
        self.ticks = 0
        self.last_tick_ts = time.monotonic()
        self._work = False

        class _S:
            waiting = ()
            active = {}
        self.scheduler = _S()

    def has_work(self):
        return self._work

    def tick(self):
        self.ticks += 1
        self.last_tick_ts = time.monotonic()


def test_watchdog_fires_once_per_stall_episode(capfd):
    from ray_tpu.resilience import EngineWatchdog
    eng = _FakeEngine()
    fired = []
    wd = EngineWatchdog(eng, timeout_s=0.1, poll_s=0.02,
                        on_wedge=lambda e: fired.append(e.ticks))
    # idle: never fires no matter how stale the tick stamp
    eng.last_tick_ts -= 10
    assert wd.check() is False and wd.wedges == 0
    # idle -> busy: the stale stamp must NOT fire a false wedge —
    # the stall clock restarts when the work arrives
    eng._work = True
    now = time.monotonic()
    assert wd.check(now=now) is False
    assert wd.check(now=now + 0.05) is False   # within budget
    # ... but a real stall past the budget fires, once per episode
    assert wd.check(now=now + 0.2) is True
    assert wd.check(now=now + 0.3) is False    # same episode
    assert wd.wedges == 1 and fired == [0]
    # progress re-arms; a fresh stall fires again
    eng.tick()
    assert wd.check() is False
    assert wd.check(now=time.monotonic() + 0.2) is True
    assert wd.wedges == 2
    # the background thread spots a stall on its own (engine already
    # busy: the thread's first poll is the idle->busy transition, the
    # later ones see no tick inside the budget)
    eng.tick()
    eng.last_tick_ts -= 10
    with EngineWatchdog(eng, timeout_s=0.05, poll_s=0.01) as wd2:
        time.sleep(0.25)
    assert wd2.wedges == 1
    assert "wedged" in capfd.readouterr().err


def test_watchdog_validates_timeout():
    from ray_tpu.resilience import EngineWatchdog
    with pytest.raises(ValueError, match="RAY_TPU_INFER_WATCHDOG"):
        EngineWatchdog(_FakeEngine(), timeout_s=0)


# ----------------------------------------------------------------- config
def test_resilience_config_env_knobs(monkeypatch):
    from ray_tpu.resilience import resilience_config
    cfg = resilience_config(refresh=True)
    assert (cfg.ckpt_every, cfg.ckpt_dir, cfg.ckpt_keep) == (0, None, 3)
    monkeypatch.setenv("RAY_TPU_CKPT_EVERY", "50")
    monkeypatch.setenv("RAY_TPU_CKPT_DIR", "/tmp/ckpts")
    monkeypatch.setenv("RAY_TPU_CKPT_KEEP", "5")
    cfg = resilience_config(refresh=True)
    assert (cfg.ckpt_every, cfg.ckpt_dir, cfg.ckpt_keep) == \
        (50, "/tmp/ckpts", 5)
    # invalid values fall back loudly, not crash
    monkeypatch.setenv("RAY_TPU_CKPT_EVERY", "-1")
    monkeypatch.setenv("RAY_TPU_CKPT_KEEP", "0")
    cfg = resilience_config(refresh=True)
    assert cfg.ckpt_every == 0 and cfg.ckpt_keep == 1
    for name in ("EVERY", "DIR", "KEEP"):
        monkeypatch.delenv(f"RAY_TPU_CKPT_{name}")
    resilience_config(refresh=True)
    # a checkpointer with no directory anywhere refuses loudly
    from ray_tpu.resilience import TrainCheckpointer
    with pytest.raises(ValueError, match="RAY_TPU_CKPT_DIR"):
        TrainCheckpointer()


def test_infer_deadline_env_knobs(monkeypatch):
    from ray_tpu.inference import infer_config
    cfg = infer_config(refresh=True)
    assert (cfg.ttft_deadline, cfg.deadline, cfg.watchdog) == (0, 0, 0)
    monkeypatch.setenv("RAY_TPU_INFER_TTFT_DEADLINE", "0.25")
    monkeypatch.setenv("RAY_TPU_INFER_DEADLINE", "30")
    monkeypatch.setenv("RAY_TPU_INFER_WATCHDOG", "10")
    cfg = infer_config(refresh=True)
    assert (cfg.ttft_deadline, cfg.deadline, cfg.watchdog) == \
        (0.25, 30.0, 10.0)
    monkeypatch.setenv("RAY_TPU_INFER_DEADLINE", "-3")
    assert infer_config(refresh=True).deadline == 0.0
    for name in ("TTFT_DEADLINE", "DEADLINE", "WATCHDOG"):
        monkeypatch.delenv(f"RAY_TPU_INFER_{name}")
    infer_config(refresh=True)


@pytest.mark.slow   # the r09 precedent: overhead-budget measurements
                    # are slow-marked (timing-sensitive under load)
def test_checkpoint_overhead_budget(tmp_path, tiny_train, train_fns):
    """The <1% steady-state claim, measured the way the telemetry
    overhead test measures (r09 precedent): the per-step cost the
    checkpointer adds — an off-cadence ``maybe_save`` (a modulo) plus
    the on-cadence host snapshot amortized over ``every`` — must be
    under 1% of the real steady step time at a realistic cadence."""
    import jax

    from ray_tpu.models import training
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.resilience import TrainCheckpointer
    cfg = tiny_train
    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    fns = training.build_gpt_train(cfg, mesh, telemetry=False)
    state = fns["init_fn"](jax.random.PRNGKey(0))
    batch = training.synthetic_lm_batch(jax.random.PRNGKey(1), 4, 32,
                                        cfg.vocab_size)
    walls = []
    for i in range(8):
        t0 = time.monotonic()
        state, m = fns["step_fn"](state, batch)
        jax.block_until_ready((state, m))
        if i > 1:
            walls.append(time.monotonic() - t0)
    walls.sort()
    steady = walls[len(walls) // 2]

    every = 200
    with TrainCheckpointer(str(tmp_path), every=every, keep=2) as ck:
        # off-cadence cost: N modulo checks
        n = 5000
        t0 = time.monotonic()
        for i in range(n):
            ck.maybe_save(state, step=every * 7 + 1 + (i % (every - 1)))
        off = (time.monotonic() - t0) / n
        # on-cadence cost: the host snapshot (the write is background)
        t0 = time.monotonic()
        ck.save(state, step=every)
        on = time.monotonic() - t0
        ck.flush()
    per_step = off + on / every
    assert per_step / steady < 0.01, (
        f"checkpointing costs {per_step*1e6:.0f}µs/step amortized "
        f"({per_step/steady:.2%} of the {steady*1e3:.1f}ms steady "
        f"step) — exceeds the 1% budget")
