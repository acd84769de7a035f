"""Parallel layer: mesh, sharding rules, ring attention, pipeline, MoE.

All on the virtual 8-device CPU mesh (conftest sets
xla_force_host_platform_device_count=8).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.parallel import moe, pipeline
from ray_tpu.parallel.mesh import MeshSpec, make_mesh, validate_divisibility
from ray_tpu.parallel.ring_attention import (local_attention,
                                             make_ring_attention_fn)
from ray_tpu.parallel.sharding import logical_to_spec, named_sharding


def test_mesh_spec_resolution():
    spec = MeshSpec.create(dp=-1, tp=2)
    resolved = spec.resolve(8)
    assert dict(resolved.axes) == {"dp": 4, "tp": 2}
    with pytest.raises(ValueError):
        MeshSpec.create(dp=3, tp=3).resolve(8)
    with pytest.raises(ValueError):
        MeshSpec.create(bogus=2)


def test_make_mesh_axes():
    mesh = make_mesh(dp=2, tp=4)
    assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 4
    mesh2 = make_mesh(dp=-1)
    assert mesh2.shape["dp"] == 8


def test_validate_divisibility():
    mesh = make_mesh(dp=2, sp=2, tp=2)
    validate_divisibility(mesh, batch=4, seq=64, n_heads=4, d_model=64)
    with pytest.raises(ValueError):
        validate_divisibility(mesh, n_heads=3)


def test_logical_to_spec_rules():
    mesh = make_mesh(dp=2, tp=4)
    spec = logical_to_spec(("batch", "seq", "heads", None), mesh=mesh)
    # fsdp absent from mesh -> batch maps to dp only; sp absent -> None
    assert spec == jax.sharding.PartitionSpec("dp", None, "tp")
    sh = named_sharding(mesh, ("batch", "embed"))
    assert sh.mesh is mesh


def test_ring_attention_matches_local():
    mesh = make_mesh(dp=2, sp=4)
    B, S, H, D = 4, 32, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D)) for kk in ks)
    ring = jax.jit(make_ring_attention_fn(mesh, causal=True))(q, k, v)
    ref = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(ring, ref, atol=2e-5)


def test_ring_attention_grads():
    mesh = make_mesh(sp=4)
    B, S, H, D = 2, 16, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D)) for kk in ks)
    ring_fn = make_ring_attention_fn(mesh, causal=True)

    g_ring = jax.jit(jax.grad(lambda q: (ring_fn(q, k, v) ** 2).sum()))(q)
    g_ref = jax.grad(
        lambda q: (local_attention(q, k, v, causal=True) ** 2).sum())(q)
    np.testing.assert_allclose(g_ring, g_ref, atol=5e-5)


def test_pipeline_matches_sequential():
    mesh = make_mesh(pp=4, dp=2)
    d = 16
    stages = [{"w": jax.random.normal(k, (d, d)) * 0.3}
              for k in jax.random.split(jax.random.PRNGKey(0), 4)]
    stacked = pipeline.stack_stage_params(stages)

    def stage_fn(p, x):
        return jax.nn.relu(x @ p["w"])

    x = jax.random.normal(jax.random.PRNGKey(1), (6, 8, d))
    out = jax.jit(lambda p, x: pipeline.pipeline_apply(
        stage_fn, p, x, mesh=mesh, num_microbatches=6))(stacked, x)
    ref = x
    for p in stages:
        ref = jax.nn.relu(ref @ p["w"])
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_moe_ep_matches_dense():
    mesh = make_mesh(ep=4)
    T, d, E, h = 64, 8, 8, 16
    params = moe.init_moe_params(jax.random.PRNGKey(2), d, h, E)
    x = jax.random.normal(jax.random.PRNGKey(3), (T, d))
    dense_out, _ = jax.jit(lambda p, x: moe.moe_layer(
        p, x, top_k=2, capacity_factor=8.0))(params, x)
    ep_out, _ = jax.jit(moe.make_moe_fn(mesh, top_k=2,
                                        capacity_factor=8.0))(params, x)
    np.testing.assert_allclose(dense_out, ep_out, atol=1e-5)


def test_moe_capacity_drops_tokens():
    # with tiny capacity most tokens are dropped -> output mostly zero
    T, d, E, h = 32, 4, 4, 8
    params = moe.init_moe_params(jax.random.PRNGKey(4), d, h, E)
    x = jax.random.normal(jax.random.PRNGKey(5), (T, d))
    out, aux = moe.moe_layer(params, x, top_k=1, capacity_factor=0.1)
    assert float(aux) > 0
    zero_rows = int((jnp.abs(out).sum(-1) == 0).sum())
    assert zero_rows > 0


@pytest.mark.slow
def test_gpt_pipeline_parallel_matches_dense():
    """build_gpt_train_pp over {pp,dp,tp} matches the non-pp loss exactly
    and trains (parity target: reference's DeepSpeed pipeline delegation,
    SURVEY.md §2.4)."""
    import optax

    from ray_tpu.models import training
    from ray_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(vocab_size=256, d_model=32, n_layers=4, n_heads=4,
                    max_seq=32, dtype=jnp.float32)
    batch = training.synthetic_lm_batch(jax.random.PRNGKey(1),
                                        batch_size=8, seq_len=16, vocab=256)

    pmesh = make_mesh(pp=2, dp=2, tp=2)
    fns_pp = training.build_gpt_train_pp(cfg, pmesh, num_microbatches=4)
    st_pp = fns_pp["init_fn"](jax.random.PRNGKey(0))
    l_pp = float(fns_pp["loss_fn"](st_pp.params, batch))

    mesh = make_mesh(dp=2, tp=2)
    fns = training.build_gpt_train(cfg, mesh)
    st = fns["init_fn"](jax.random.PRNGKey(0))
    l_ref = float(fns["loss_fn"](st.params, batch))
    # f32 reduction order moves this loss by ~1e-2 *between meshes* on
    # some XLA builds (measured: dense 5.539–5.553 over dp/tp/fsdp
    # layouts on the CPU backend, pp microbatch-count stable) — a real
    # pipeline bug (dropped microbatch, wrong stage order) shows up at
    # O(0.1+), so 2e-2 still guards the schedule
    assert abs(l_pp - l_ref) < 2e-2

    fns2 = training.build_gpt_train_pp(cfg, pmesh, num_microbatches=4,
                                       optimizer=optax.adam(1e-2))
    s = fns2["init_fn"](jax.random.PRNGKey(0))
    for _ in range(8):
        s, m = fns2["step_fn"](s, batch)
    assert float(m["loss"]) < l_ref - 0.5


def test_ulysses_attention_matches_local():
    """Ulysses all-to-all SP == unsharded attention, values and grads
    (SURVEY §2.4 'Ulysses' row)."""
    from ray_tpu.parallel.ulysses import make_ulysses_attention_fn

    mesh = make_mesh(dp=2, sp=4)
    B, S, H, D = 2, 64, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))

    fn = make_ulysses_attention_fn(mesh, causal=True)
    out = jax.jit(fn)(q, k, v)
    ref = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)

    g1 = jax.jit(jax.grad(lambda q: (fn(q, k, v) ** 2).sum()))(q)
    g2 = jax.grad(lambda q: (local_attention(q, k, v, causal=True) ** 2
                             ).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=2e-3, atol=2e-4)

    # sp=1 mesh degrades to plain attention
    fn1 = make_ulysses_attention_fn(make_mesh(dp=2), causal=True)
    np.testing.assert_allclose(np.asarray(fn1(q, k, v)),
                               np.asarray(ref), rtol=2e-4, atol=2e-5)


def test_zigzag_ring_attention_matches_local():
    """Causal load-balanced (zigzag) layout: each sp-rank holds chunks
    (i, 2n-1-i), fully-masked blocks are skipped, and the result —
    after undoing the host-side permutation — is exact."""
    from ray_tpu.parallel.ring_attention import zigzag_permutation

    mesh = make_mesh(dp=2, sp=4)
    B, S, H, D = 4, 32, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D)) for kk in ks)

    perm, inv = zigzag_permutation(S, 4)
    fn = jax.jit(make_ring_attention_fn(mesh, causal=True,
                                        layout="zigzag"))
    out = fn(q[:, perm], k[:, perm], v[:, perm])[:, inv]
    ref = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_zigzag_ring_attention_grads():
    from ray_tpu.parallel.ring_attention import zigzag_permutation

    mesh = make_mesh(sp=4)
    B, S, H, D = 2, 16, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D)) for kk in ks)
    perm, inv = zigzag_permutation(S, 4)
    fn = make_ring_attention_fn(mesh, causal=True, layout="zigzag")

    g = jax.jit(jax.grad(
        lambda q: (fn(q[:, perm], k[:, perm], v[:, perm])[:, inv]
                   ** 2).sum()))(q)
    g_ref = jax.grad(
        lambda q: (local_attention(q, k, v, causal=True) ** 2).sum())(q)
    np.testing.assert_allclose(g, g_ref, atol=5e-5)


# ---------------------------------------------------------------------------
# r08: overlap-scheduled FSDP/TP (parallel/overlap.py)
# ---------------------------------------------------------------------------

def test_ring_allgather_matmul_matches_gather():
    """ppermute ring AG-matmul == all_gather-then-matmul, values and
    grads, incl. the multi-weight (one ring, several matmuls) form."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.compat import shard_map
    from ray_tpu.parallel.overlap import ring_allgather_matmul

    mesh = make_mesh(tp=8)
    T, K, M = 16, 8, 12
    kx, kw1, kw2 = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (T, K))
    w1 = jax.random.normal(kw1, (K, M))
    w2 = jax.random.normal(kw2, (K, 2, 3))     # non-matrix out dims

    def ring(x, w1, w2):
        a, b = ring_allgather_matmul(x, [w1, w2], "tp")
        return a, b

    fn = jax.jit(shard_map(ring, mesh=mesh,
                           in_specs=(P("tp", None), P(), P()),
                           out_specs=(P(), P())))
    a, b = fn(x, w1, w2)
    np.testing.assert_allclose(a, x @ w1, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(b, np.einsum("tk,kab->tab", x, w2),
                               rtol=1e-5, atol=1e-5)

    # grads flow through the ring (transpose = ring matmul-accumulate)
    def loss(x):
        a, _ = fn(x, w1, w2)
        return (a ** 2).sum()
    g = jax.grad(loss)(x)
    g_ref = jax.grad(lambda x: ((x @ w1) ** 2).sum())(x)
    np.testing.assert_allclose(g, g_ref, rtol=1e-5, atol=1e-5)

    # no ring axis -> plain matmul
    np.testing.assert_allclose(ring_allgather_matmul(x, w1, None),
                               x @ w1, rtol=1e-6, atol=1e-6)


def _overlap_vs_gspmd(cfg, axes, *, batch_size=8, seq=32, masked=False,
                      rtol=2e-4, atol=2e-5, grad_atol=5e-5):
    """Loss + per-parameter grad parity of the overlap schedule against
    the GSPMD path on the same mesh, from identical (GSPMD-initialized)
    params."""
    from ray_tpu.models import gpt as gpt_mod, training
    from ray_tpu.parallel import overlap as ovl

    mesh = make_mesh(**axes)
    batch = training.synthetic_lm_batch(jax.random.PRNGKey(1),
                                        batch_size, seq, cfg.vocab_size)
    if masked:
        t = np.array(batch["targets"])
        t[:, : seq // 4] = -1
        batch["targets"] = jnp.asarray(t)
    fns_g = training.build_gpt_train(cfg, mesh, comm_mode="gspmd")
    st = fns_g["init_fn"](jax.random.PRNGKey(0))

    def gspmd_loss(p, b):
        return gpt_mod.loss_fn(p, b, cfg, attn_fn=fns_g["attn_fn"],
                               mesh=mesh)

    l_ref, g_ref = jax.jit(jax.value_and_grad(gspmd_loss))(st.params,
                                                           batch)
    o = ovl.build_overlap_step_fns(cfg, mesh)
    l_ovl, g_ovl = jax.jit(o["value_and_grad"])(
        st.params, batch["tokens"], batch["targets"])
    np.testing.assert_allclose(float(l_ovl), float(l_ref),
                               rtol=rtol, atol=atol)
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(g_ref),
            jax.tree.leaves(g_ovl)):
        np.testing.assert_allclose(
            np.asarray(b, np.float32), np.asarray(a, np.float32),
            rtol=5e-3, atol=grad_atol,
            err_msg=f"grad mismatch at {jax.tree_util.keystr(path)} "
                    f"on mesh {axes}")


@pytest.mark.slow
def test_overlap_fsdp_parity():
    """Pure-FSDP overlap schedule (prefetched per-block gathers,
    per-block grad reduce-scatters) matches GSPMD exactly in f32."""
    from ray_tpu.models.gpt import GPTConfig
    cfg = GPTConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                    max_seq=32, dtype=jnp.float32)
    _overlap_vs_gspmd(cfg, {"fsdp": 8})


@pytest.mark.slow
def test_overlap_fsdp_tp_parity():
    """fsdp x tp: ring all-gather-matmul TP + vocab-parallel CE, with
    masked targets and an odd layer count (the scan's double-buffer
    wraparound block)."""
    from ray_tpu.models.gpt import GPTConfig
    cfg = GPTConfig(vocab_size=256, d_model=64, n_layers=3, n_heads=4,
                    max_seq=32, dtype=jnp.float32)
    _overlap_vs_gspmd(cfg, {"fsdp": 4, "tp": 2}, masked=True)


@pytest.mark.slow
def test_overlap_uneven_shapes_parity():
    """Ragged shapes: d_ff/seq chunks far from lane multiples, batch
    that splits into odd-sized (3-row) shards over the batch axes."""
    from ray_tpu.models.gpt import GPTConfig
    cfg = GPTConfig(vocab_size=192, d_model=48, n_layers=3, n_heads=4,
                    d_ff=40, max_seq=24, dtype=jnp.float32)
    _overlap_vs_gspmd(cfg, {"fsdp": 2, "tp": 4}, batch_size=6, seq=24)


@pytest.mark.slow
def test_overlap_full_mesh_variants():
    """dp x fsdp x tp with unroll+remat, and the bf16 arm
    (bf16-gather-aware tolerances: gathered weights and ring chunks
    round per hop)."""
    from ray_tpu.models.gpt import GPTConfig
    cfg = GPTConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                    max_seq=32, dtype=jnp.float32, unroll_layers=True,
                    remat=True)
    _overlap_vs_gspmd(cfg, {"dp": 2, "fsdp": 2, "tp": 2})
    cfg16 = GPTConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                      max_seq=32, dtype=jnp.bfloat16)
    _overlap_vs_gspmd(cfg16, {"fsdp": 4, "tp": 2}, rtol=3e-2,
                      atol=3e-2, grad_atol=3e-2)


@pytest.mark.slow
def test_overlap_quantized_wire_grad_budget():
    """End-to-end grad-error budget for the int8 wire mode (r11): the
    quantized overlap schedule (deterministic-rounding weight AG,
    stochastic-rounding ring grad RS) against the unquantized overlap
    schedule, same params/batch, on the fsdp=4,tp=2 host-sim mesh.

    The documented budget (r11): per-parameter relative
    grad error ||g_q - g|| / ||g|| <= 5% in f32, loss within 1%.  The
    weight AG contributes <= 1/254 of each 128-block's amax per
    element; each of the fsdp-1 RS hops adds <= 1/127 stochastic-
    rounding noise that is unbiased by construction
    (test_quant.py::test_stochastic_rounding_unbiased)."""
    from ray_tpu.models import training
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.parallel import overlap as ovl

    cfg = GPTConfig(vocab_size=256, d_model=64, n_layers=3, n_heads=4,
                    max_seq=32, dtype=jnp.float32)
    mesh = make_mesh(fsdp=4, tp=2)
    fns = training.build_gpt_train(cfg, mesh, comm_mode="overlap")
    st = fns["init_fn"](jax.random.PRNGKey(0))
    batch = training.synthetic_lm_batch(jax.random.PRNGKey(1), 8, 32,
                                        cfg.vocab_size)

    base = ovl.build_overlap_step_fns(cfg, mesh, quant="none")
    quant = ovl.build_overlap_step_fns(cfg, mesh, quant="int8")
    l_ref, g_ref = jax.jit(base["value_and_grad"])(
        st.params, batch["tokens"], batch["targets"])
    l_q, g_q = jax.jit(quant["value_and_grad"])(
        st.params, batch["tokens"], batch["targets"])

    assert abs(float(l_q) - float(l_ref)) <= 0.01 * abs(float(l_ref))
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(g_ref),
            jax.tree.leaves(g_q)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        denom = np.linalg.norm(a)
        rel = np.linalg.norm(b - a) / max(denom, 1e-12)
        assert rel <= 0.05, (
            f"grad error {rel:.4f} over budget at "
            f"{jax.tree_util.keystr(path)}")

    # and the full jitted train step still trains under int8 wire
    import optax
    fns_q = training.build_gpt_train(cfg, mesh, comm_mode="overlap",
                                     comm_quant="int8",
                                     optimizer=optax.adam(1e-2))
    assert fns_q["comm_quant"] == "int8"
    stq = fns_q["init_fn"](jax.random.PRNGKey(0))
    l0 = None
    for _ in range(6):
        stq, m = fns_q["step_fn"](stq, batch)
        l0 = l0 if l0 is not None else float(m["loss"])
    assert float(m["loss"]) < l0 - 0.2
    assert float(m["grad_norm"]) == float(m["grad_norm"])  # not NaN


@pytest.mark.slow  # r08 budget: dryrun_multichip runs an overlap step too
def test_overlap_step_trains():
    """build_gpt_train(comm_mode='overlap'): the full jitted train step
    (optimizer + donation) runs and loss decreases."""
    import optax

    from ray_tpu.models import training
    from ray_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                    max_seq=32, dtype=jnp.float32)
    mesh = make_mesh(fsdp=4, tp=2)
    fns = training.build_gpt_train(cfg, mesh, comm_mode="overlap",
                                   optimizer=optax.adam(1e-2))
    assert fns["comm_mode"] == "overlap"
    st = fns["init_fn"](jax.random.PRNGKey(0))
    batch = training.synthetic_lm_batch(jax.random.PRNGKey(1), 8, 32,
                                        256)
    l0 = None
    for _ in range(6):
        st, m = fns["step_fn"](st, batch)
        l0 = l0 if l0 is not None else float(m["loss"])
    assert float(m["loss"]) < l0 - 0.3
    assert float(m["grad_norm"]) == float(m["grad_norm"])  # not NaN


def test_comm_config_and_fallback_dispatch(monkeypatch):
    """comm_config env resolution + the loud gspmd fallbacks for
    unsupported (cfg, mesh) combinations."""
    from ray_tpu.models import training
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.parallel import overlap as ovl

    monkeypatch.setenv("RAY_TPU_COMM", "overlap")
    assert ovl.comm_config(refresh=True).mode == "overlap"
    monkeypatch.setenv("RAY_TPU_COMM", "bogus")
    assert ovl.comm_config(refresh=True).mode == "gspmd"
    monkeypatch.delenv("RAY_TPU_COMM")
    assert ovl.comm_config(refresh=True).mode == "gspmd"
    # wire-quant knob: default none, int8, bogus -> loud none
    assert ovl.comm_config(refresh=True).quant == "none"
    monkeypatch.setenv("RAY_TPU_COMM_QUANT", "int8")
    assert ovl.comm_config(refresh=True).quant == "int8"
    monkeypatch.setenv("RAY_TPU_COMM_QUANT", "int4")
    assert ovl.comm_config(refresh=True).quant == "none"
    monkeypatch.delenv("RAY_TPU_COMM_QUANT")
    ovl.comm_config(refresh=True)

    cfg = GPTConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                    max_seq=32, dtype=jnp.float32)
    # sp mesh: outside overlap coverage -> falls back, says why
    assert "sp" in ovl.overlap_supported(cfg, make_mesh(dp=2, sp=4))
    fns = training.build_gpt_train(cfg, make_mesh(dp=2, sp=4),
                                   comm_mode="overlap")
    assert fns["comm_mode"] == "gspmd"
    # indivisible heads / moe all have reasons
    cfg3 = GPTConfig(vocab_size=256, d_model=66, n_layers=2, n_heads=3,
                     max_seq=32)
    assert "n_heads" in ovl.overlap_supported(cfg3, make_mesh(tp=2))
    assert "MoE" in ovl.overlap_supported(
        GPTConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                  n_experts=2), make_mesh(fsdp=2))
    assert ovl.overlap_supported(cfg, make_mesh(fsdp=4, tp=2)) is None
    # single device: nothing to schedule
    from ray_tpu.parallel.mesh import single_device_mesh
    fns1 = training.build_gpt_train(cfg, single_device_mesh(),
                                    comm_mode="overlap")
    assert fns1["comm_mode"] == "gspmd"
    # comm_quant needs the overlap schedule: dropped loudly once the
    # effective mode is gspmd (requested or fallen back to)
    fns2 = training.build_gpt_train(cfg, make_mesh(fsdp=4, tp=2),
                                    comm_mode="gspmd",
                                    comm_quant="int8")
    assert fns2["comm_quant"] == "none"
    fns3 = training.build_gpt_train(cfg, make_mesh(dp=2, sp=4),
                                    comm_mode="overlap",
                                    comm_quant="int8")
    assert fns3["comm_mode"] == "gspmd"
    assert fns3["comm_quant"] == "none"
    with pytest.raises(ValueError, match="comm_quant"):
        training.build_gpt_train(cfg, make_mesh(fsdp=4, tp=2),
                                 comm_mode="overlap",
                                 comm_quant="fp8")


def test_parse_mesh_axes():
    from ray_tpu.parallel.mesh import MeshAxisError, parse_mesh_axes

    assert parse_mesh_axes("fsdp=4,tp=2") == {"fsdp": 4, "tp": 2}
    assert parse_mesh_axes("dp=-1") == {"dp": -1}
    assert parse_mesh_axes("dcn=2,fsdp=4") == {"dcn": 2, "fsdp": 4}
    assert parse_mesh_axes(" dcn=2 , fsdp=4 ") == {"dcn": 2, "fsdp": 4}

    # every rejection is the typed MeshAxisError (a ValueError) and
    # names the offending axis, so CLI surfaces can point at the token
    def rejects(arg, axis, match):
        with pytest.raises(MeshAxisError, match=match) as e:
            parse_mesh_axes(arg)
        assert e.value.axis == axis
        assert isinstance(e.value, ValueError)

    rejects("bogus=2", "bogus", "unknown mesh axis")
    rejects("fsdp4", "fsdp4", "bad mesh axis")
    rejects("fsdp=four", "fsdp", "non-integer")
    rejects("fsdp=2,fsdp=4", "fsdp", "duplicate")
    rejects("fsdp=0", "fsdp", "non-positive")
    rejects("tp=-2", "tp", "only -1 is allowed")
    # dcn is the slow tier: it must be the outermost (first) axis or
    # make_mesh's per-pod device blocks would interleave pods
    rejects("fsdp=4,dcn=2", "dcn", "outermost")


def test_collective_bytes_accounting():
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.parallel import overlap as ovl
    from ray_tpu.parallel.mesh import single_device_mesh

    cfg = GPTConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                    max_seq=32, dtype=jnp.float32)
    for mode in ("gspmd", "overlap"):
        zero = ovl.collective_bytes_per_step(
            cfg, single_device_mesh(), batch=8, seq=32, comm_mode=mode)
        assert zero["total"] == 0
        multi = ovl.collective_bytes_per_step(
            cfg, make_mesh(fsdp=4, tp=2), batch=8, seq=32,
            comm_mode=mode)
        # per-tier structure: {"ici": {...}, "dcn": {...}, "total"};
        # every collective entry carries its own bytes and explicit
        # wire dtype (satellite: no more implicit cfg.dtype itemsize
        # everywhere)
        ici = multi["ici"]
        assert ici["weight_allgather"]["bytes"] > 0
        assert ici["grad_reduce_scatter"]["bytes"] > 0
        assert ici["tp_ring"]["bytes"] > 0
        for k, v in ici.items():
            if isinstance(v, dict):
                assert v["wire_dtype"] == "float32"
        assert ici["total"] == sum(v["bytes"] for v in ici.values()
                                   if isinstance(v, dict))
        # flat (single-pod) mesh: the dcn tier is idle and the top
        # total is just the ICI bytes
        assert multi["dcn"]["total"] == 0
        assert "reduction_vs_flat" not in multi["dcn"]
        assert multi["total"] == ici["total"]
        # each tier prices its bytes at its own analytic bandwidth
        assert ici["seconds"] == pytest.approx(
            ovl.tier_seconds(ici["total"], "ici"))
        assert multi["dcn"]["seconds"] == 0.0


def test_collective_bytes_quantized_wire():
    """quant='int8' halves the FSDP weight-AG / grad-RS wire bytes
    (>= 1.9x: int8 codes + one f32 scale per 128 elements = 1.03125
    B/elem vs bf16's 2) and labels the quantized collectives'
    wire_dtype; everything else — and the gspmd arm, which owns its
    own collectives — stays at cfg.dtype."""
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.parallel import overlap as ovl

    cfg = GPTConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                    max_seq=32, dtype=jnp.bfloat16)
    mesh = make_mesh(fsdp=4, tp=2)
    base = ovl.collective_bytes_per_step(cfg, mesh, batch=8, seq=32,
                                         comm_mode="overlap")["ici"]
    q = ovl.collective_bytes_per_step(cfg, mesh, batch=8, seq=32,
                                      comm_mode="overlap",
                                      quant="int8")["ici"]
    for name in ("weight_allgather", "grad_reduce_scatter"):
        ratio = base[name]["bytes"] / q[name]["bytes"]
        assert ratio >= 1.9, f"{name}: only {ratio:.3f}x lower"
        assert q[name]["wire_dtype"] == "int8+f32/128"
    # the unquantized streams are untouched
    assert q["tp_ring"] == base["tp_ring"]
    assert q["grad_allreduce_dp"] == base["grad_allreduce_dp"]
    assert q["total"] < base["total"]
    # GSPMD cannot honor the quant knob — charged unquantized
    g = ovl.collective_bytes_per_step(cfg, mesh, batch=8, seq=32,
                                      comm_mode="gspmd", quant="int8")
    assert g["ici"]["weight_allgather"]["wire_dtype"] == "bfloat16"


# -------------------------------------------------- r22: DCN hierarchy ----
def test_collective_bytes_per_tier_hierarchy():
    """On a nested dcn x fsdp mesh the hierarchical schedule's only
    cross-pod traffic is one shard-sized grad all-reduce — the dcn
    tier's bytes come out ~pod-size lower than charging the flat
    (dcn*fsdp)-way schedule to the same pod-boundary link."""
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.parallel import overlap as ovl

    cfg = GPTConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                    max_seq=32, dtype=jnp.float32)
    mesh = make_mesh(dcn=2, fsdp=4)
    cb = ovl.collective_bytes_per_step(cfg, mesh, batch=8, seq=32,
                                       comm_mode="overlap")
    dcn, ici = cb["dcn"], cb["ici"]
    assert dcn["grad_allreduce_dcn"]["bytes"] > 0
    assert cb["total"] == ici["total"] + dcn["total"]
    # the analytic comparator: flat schedule pushes full weight
    # gathers + grad reduce-scatters across the pod boundary, the
    # hierarchy one 1/fsdp shard all-reduce -> reduction ~ pod size
    pod = mesh.shape["fsdp"]
    assert dcn["flat_equivalent_bytes"] > dcn["total"]
    assert dcn["reduction_vs_flat"] >= pod  # measured 6.93 on this cfg
    assert dcn["seconds"] == pytest.approx(
        ovl.tier_seconds(dcn["total"], "dcn"))

    # quant="dcn": only the cross-pod leg moves int8 — ICI entries
    # stay at cfg.dtype, and the dcn wire shrinks ~4x vs f32
    qd = ovl.collective_bytes_per_step(cfg, mesh, batch=8, seq=32,
                                       comm_mode="overlap", quant="dcn")
    assert qd["dcn"]["grad_allreduce_dcn"]["wire_dtype"] == \
        "int8+f32/128"
    assert qd["ici"]["weight_allgather"]["wire_dtype"] == "float32"
    assert qd["ici"]["total"] == ici["total"]
    ratio = dcn["grad_allreduce_dcn"]["bytes"] / \
        qd["dcn"]["grad_allreduce_dcn"]["bytes"]
    assert ratio >= 3.5, f"dcn wire only {ratio:.2f}x lower"
    # the comparator is priced at the same wire so the ratio isolates
    # the schedule, not the quantizer
    assert qd["dcn"]["reduction_vs_flat"] >= pod
    # quant="int8" covers both tiers
    qa = ovl.collective_bytes_per_step(cfg, mesh, batch=8, seq=32,
                                       comm_mode="overlap",
                                       quant="int8")
    assert qa["ici"]["weight_allgather"]["wire_dtype"] == \
        "int8+f32/128"
    assert qa["dcn"]["grad_allreduce_dcn"]["wire_dtype"] == \
        "int8+f32/128"


@pytest.mark.slow
def test_hierarchical_overlap_parity():
    """Nested dcn x ici meshes: the hierarchical overlap schedule
    (pod-local weight gathers, ICI reduce-scatter + DCN shard
    all-reduce grad transpose) matches GSPMD on the same mesh within
    the r08 tolerances."""
    from ray_tpu.models.gpt import GPTConfig
    cfg = GPTConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                    max_seq=32, dtype=jnp.float32)
    _overlap_vs_gspmd(cfg, {"dcn": 2, "fsdp": 4})
    _overlap_vs_gspmd(cfg, {"dcn": 2, "fsdp": 2, "tp": 2}, masked=True)
    # bf16 arm: gathered weights and ring chunks round per hop (the
    # r08 bf16 tolerances)
    cfg16 = GPTConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                      max_seq=32, dtype=jnp.bfloat16)
    _overlap_vs_gspmd(cfg16, {"dcn": 2, "fsdp": 4}, rtol=3e-2,
                      atol=3e-2, grad_atol=3e-2)


@pytest.mark.slow
def test_hierarchical_dcn_quant_grad_budget():
    """quant='dcn' (int8 on the cross-pod leg only) against the
    unquantized overlap schedule on dcn=2,fsdp=4: same r11-style
    budget discipline, but only the DCN all-reduce is rounding."""
    from ray_tpu.models import training
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.parallel import overlap as ovl

    cfg = GPTConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                    max_seq=32, dtype=jnp.float32)
    mesh = make_mesh(dcn=2, fsdp=4)
    batch = training.synthetic_lm_batch(jax.random.PRNGKey(1), 8, 32,
                                        cfg.vocab_size)
    fns = training.build_gpt_train(cfg, mesh, comm_mode="overlap")
    st = fns["init_fn"](jax.random.PRNGKey(0))
    base = ovl.build_overlap_step_fns(cfg, mesh, quant="none")
    quant = ovl.build_overlap_step_fns(cfg, mesh, quant="dcn")
    l0, g0 = jax.jit(base["value_and_grad"])(
        st.params, batch["tokens"], batch["targets"])
    l1, g1 = jax.jit(quant["value_and_grad"])(
        st.params, batch["tokens"], batch["targets"])
    # loss is computed from unquantized weights: identical
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(g0),
            jax.tree.leaves(g1)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        denom = max(float(np.max(np.abs(a))), 1e-8)
        rel = float(np.max(np.abs(b - a))) / denom
        assert rel < 0.05, \
            f"dcn-quant grad error {rel:.4f} at " \
            f"{jax.tree_util.keystr(path)}"


def test_pipeline_schedule_stats():
    from ray_tpu.parallel.pipeline import pipeline_schedule_stats

    g = pipeline_schedule_stats(4, 8, "gpipe")
    assert g["ticks"] == 8 + 4 - 1
    assert g["bubble_fraction"] == pytest.approx(3 / 11)
    assert g["in_flight_microbatches"] == 8
    f = pipeline_schedule_stats(4, 8, "1f1b")
    assert f["ticks"] == 8 + 2 * 4 - 2
    assert f["bubble_fraction"] == pytest.approx(6 / 14)
    # the 1f1b win: in-flight activations bounded by 2*pp-1, not M
    assert f["in_flight_microbatches"] == 7
    assert pipeline_schedule_stats(4, 64, "1f1b")[
        "in_flight_microbatches"] == 7
    # degenerate single stage: sequential microbatching, no bubble
    s = pipeline_schedule_stats(1, 4, "1f1b")
    assert s["bubble_fraction"] == 0.0 and s["ticks"] == 4
    with pytest.raises(ValueError, match="schedule"):
        pipeline_schedule_stats(2, 4, "zb-h1")


@pytest.mark.slow
def test_1f1b_parity_with_non_pipelined():
    """1F1B (pp=2 x M=4) against the non-pipelined trainer at the same
    global batch: identical loss/grad_norm, identical post-step params,
    and one compile per topology (the jit cache holds a single entry
    after two steps)."""
    import optax

    from ray_tpu.models import training
    from ray_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(vocab_size=256, d_model=64, n_layers=4, n_heads=4,
                    max_seq=32, dtype=jnp.float32, remat=True)
    sgd = optax.sgd(1e-2)
    mesh_pp = make_mesh(pp=2, devices=jax.devices()[:2])
    fns = training.build_gpt_train_pp(cfg, mesh_pp, schedule="1f1b",
                                      num_microbatches=4,
                                      optimizer=sgd, telemetry=False)
    assert fns["schedule"] == "1f1b" and fns["stage_axis"] == "pp"
    assert fns["in_flight_microbatches"] == 3   # 2*pp-1 < M
    mesh_1 = make_mesh(dp=1, devices=jax.devices()[:1])
    ref = training.build_gpt_train(cfg, mesh_1, optimizer=sgd,
                                   telemetry=False)
    batch = training.synthetic_lm_batch(jax.random.PRNGKey(1), 8, 32,
                                        cfg.vocab_size)
    st_pp = fns["init_fn"](jax.random.PRNGKey(0))
    st_ref = ref["init_fn"](jax.random.PRNGKey(0))

    st_pp, m_pp = fns["step_fn"](st_pp, batch)
    st_ref, m_ref = ref["step_fn"](st_ref, batch)
    np.testing.assert_allclose(float(m_pp["loss"]),
                               float(m_ref["loss"]),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(float(m_pp["grad_norm"]),
                               float(m_ref["grad_norm"]),
                               rtol=2e-4, atol=2e-5)
    # post-step params agree leaf-by-leaf (stage dim folded back)
    pp_layers = jax.tree.map(
        lambda t: np.asarray(t, np.float32).reshape((-1,) + t.shape[2:]),
        jax.device_get(st_pp.params["layers"]))
    ref_layers = jax.device_get(st_ref.params["layers"])
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(ref_layers),
            jax.tree.leaves(pp_layers)):
        np.testing.assert_allclose(
            b, np.asarray(a, np.float32), rtol=1e-4, atol=1e-5,
            err_msg=f"param drift at {jax.tree_util.keystr(path)}")
    # second step reuses the trace: exactly one compile per topology
    st_pp, _ = fns["step_fn"](st_pp, batch)
    assert fns["step_fn"]._cache_size() == 1


@pytest.mark.slow
def test_1f1b_stages_over_dcn_axis():
    """1F1B staged over the dcn axis itself (one stage per pod): the
    slow tier carries one microbatch activation boundary per tick
    instead of a grad all-reduce, and the loss matches gpipe-on-pp at
    the same global batch."""
    import optax

    from ray_tpu.models import training
    from ray_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                    max_seq=32, dtype=jnp.float32)
    sgd = optax.sgd(1e-2)
    mesh_dcn = make_mesh(dcn=2, devices=jax.devices()[:2])
    fns = training.build_gpt_train_pp(cfg, mesh_dcn, schedule="1f1b",
                                      num_microbatches=2,
                                      optimizer=sgd, telemetry=False)
    assert fns["stage_axis"] == "dcn"
    mesh_pp = make_mesh(pp=2, devices=jax.devices()[:2])
    gp = training.build_gpt_train_pp(cfg, mesh_pp, schedule="gpipe",
                                     num_microbatches=2,
                                     optimizer=sgd, telemetry=False)
    batch = training.synthetic_lm_batch(jax.random.PRNGKey(1), 4, 32,
                                        cfg.vocab_size)
    st = fns["init_fn"](jax.random.PRNGKey(0))
    st_g = gp["init_fn"](jax.random.PRNGKey(0))
    l_1f1b = float(fns["loss_fn"](st.params, batch))
    l_gpipe = float(gp["loss_fn"](st_g.params, batch))
    np.testing.assert_allclose(l_1f1b, l_gpipe, rtol=2e-5, atol=2e-6)
