"""Model tests: GPT forward/train under various meshes, graft entry."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import training
from ray_tpu.models.gpt import (GPTConfig, forward, init_params, loss_fn,
                                num_params, param_logical_axes)
from ray_tpu.parallel.mesh import make_mesh


def test_gpt_forward_shapes():
    cfg = GPTConfig.tiny(dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits, aux = forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_gpt_logical_axes_match_params():
    cfg = GPTConfig.tiny(n_experts=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    axes = param_logical_axes(cfg)
    pl = jax.tree.leaves_with_path(params)
    al = jax.tree.leaves_with_path(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    assert len(pl) == len(al)
    for (ppath, leaf), (apath, ax) in zip(pl, al):
        assert ppath == apath
        assert leaf.ndim == len(ax), f"{ppath}: {leaf.shape} vs {ax}"


def test_gpt_causality():
    cfg = GPTConfig.tiny(dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    t1 = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, 100)
    t2 = t1.at[0, -1].set((t1[0, -1] + 1) % 100)
    l1, _ = forward(params, t1, cfg)
    l2, _ = forward(params, t2, cfg)
    # changing the last token must not affect earlier logits
    np.testing.assert_allclose(l1[0, :-1], l2[0, :-1], atol=1e-5)
    assert float(jnp.abs(l1[0, -1] - l2[0, -1]).max()) > 1e-6


@pytest.mark.slow
def test_gpt_train_loss_decreases_dp_tp_sp():
    mesh = make_mesh(dp=2, sp=2, tp=2)
    cfg = GPTConfig.tiny(dtype=jnp.float32)
    fns = training.build_gpt_train(
        cfg, mesh, optimizer=training.default_optimizer(lr=1e-2, warmup=1))
    state = fns["init_fn"](jax.random.PRNGKey(0))
    batch = training.synthetic_lm_batch(jax.random.PRNGKey(1), 8, 64,
                                        cfg.vocab_size)
    first = None
    for i in range(8):
        state, m = fns["step_fn"](state, batch)
        if first is None:
            first = float(m["loss"])
    assert float(m["loss"]) < first


@pytest.mark.slow  # r08 --durations re-profile: tier-1 crossed the 870s budget (moe parity stays tier-1)
def test_gpt_moe_trains():
    mesh = make_mesh(dp=2, ep=2, tp=2)
    cfg = GPTConfig.tiny(n_experts=4, dtype=jnp.float32)
    fns = training.build_gpt_train(cfg, mesh)
    state = fns["init_fn"](jax.random.PRNGKey(0))
    batch = training.synthetic_lm_batch(jax.random.PRNGKey(1), 8, 32,
                                        cfg.vocab_size)
    state, m = fns["step_fn"](state, batch)
    assert np.isfinite(float(m["loss"]))


@pytest.mark.slow
def test_ring_vs_local_full_model():
    """Same params, sp mesh vs single device: identical loss."""
    cfg = GPTConfig.tiny(dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = training.synthetic_lm_batch(jax.random.PRNGKey(1), 4, 64,
                                        cfg.vocab_size)
    loss_local = float(loss_fn(params, batch, cfg))
    mesh = make_mesh(sp=4)
    from ray_tpu.parallel.ring_attention import make_ring_attention_fn
    attn = make_ring_attention_fn(mesh, causal=True)
    loss_ring = float(loss_fn(params, batch, cfg, attn_fn=attn))
    assert abs(loss_local - loss_ring) < 1e-4


@pytest.mark.slow
def test_graft_entry():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "graft_entry", os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
            "__graft_entry__.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    fn, args = mod.entry()
    out = jax.eval_shape(fn, *args)
    assert out.shape[-1] == 32768
    mod.dryrun_multichip(8)


@pytest.mark.slow
def test_unrolled_layers_match_scan():
    """cfg.unroll_layers + ce_chunk are pure perf knobs: identical loss
    to the scan path."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt, training
    from ray_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    batch = training.synthetic_lm_batch(jax.random.PRNGKey(1), 4, 32, 256)
    losses = []
    for unroll, chunk in [(False, 4096), (True, 0), (True, 64)]:
        cfg = gpt.GPTConfig(vocab_size=256, d_model=32, n_layers=3,
                            n_heads=4, max_seq=32, dtype=jnp.float32,
                            unroll_layers=unroll, ce_chunk=chunk)
        params = gpt.init_params(cfg, jax.random.PRNGKey(0))
        losses.append(float(gpt.loss_fn(params, batch, cfg)))
    assert abs(losses[0] - losses[1]) < 1e-4
    assert abs(losses[0] - losses[2]) < 1e-4


def _fuse_norm_parity_cfg():
    """A shape where BOTH r13 fusions engage (d_model % 128 == 0 so
    the out-proj epilogue tiles, flash-CE supported so ln_f fuses into
    the vocab-matmul prologue) — asserted, or the parity tests prove
    nothing."""
    from ray_tpu.ops import flash_ce, fused_norm

    cfg = GPTConfig(vocab_size=512, d_model=128, n_layers=2, n_heads=4,
                    max_seq=32, dtype=jnp.float32)
    batch = training.synthetic_lm_batch(jax.random.PRNGKey(1), 2, 32,
                                        cfg.vocab_size)
    assert fused_norm.out_proj_norm_plan(2 * 32, 128, 128, seq=32,
                                         enabled=True)
    assert flash_ce.uses_flash_ce_norm(2 * 32, 128, 512, enabled=True,
                                       ce_chunk=cfg.ce_chunk)
    return cfg, batch


def _assert_every_grad_close(got, want, tol=1e-4):
    """Each parameter's gradient, to ``tol`` of its largest element."""
    import numpy as np

    for (path, a), b in zip(jax.tree.leaves_with_path(got),
                            jax.tree.leaves(want)):
        na, nb = np.asarray(a), np.asarray(b)
        denom = max(1e-8, float(np.abs(nb).max()))
        err = float(np.abs(na - nb).max()) / denom
        assert err < tol, (jax.tree_util.keystr(path), err)


def test_gpt_train_fuse_norm_parity():
    """r13 acceptance: loss/grad parity of the exact loss closure
    build_gpt_train compiles — including the norm-scale grads
    (ln1/ln2/ln_f; ln_f's comes back through the flash-CE prologue's
    per-row-block partials) — with ``fuse_norm`` pinned on vs off."""
    from ray_tpu.models import gpt

    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    cfg, batch = _fuse_norm_parity_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    grads, losses = {}, {}
    for fuse in (True, False):
        losses[fuse], grads[fuse] = jax.value_and_grad(
            lambda p: gpt.loss_fn(p, batch, cfg, mesh=mesh,
                                  fuse_norm=fuse))(params)
    assert float(losses[True]) == pytest.approx(float(losses[False]),
                                                abs=2e-5)
    _assert_every_grad_close(grads[True], grads[False])


@pytest.mark.parametrize("layers", [
    dict(unroll_layers=True),               # the train cells' recipe
    dict(unroll_layers=True, remat=True),
    dict(remat=True),                       # scanned, under checkpoint
])
def test_gpt_grad_lowers_as_xla_epilogue(layers):
    """PR 53: the out-proj epilogue decides by whether it is
    differentiated.  With the gate engaged (asserted), the gradient of
    ``loss_fn`` lowers to the text it lowers to with the gate pinned
    off (locations stripped, the counters jax appends to its private
    functions' names too): the rule's einsum + add + norm is
    ``layer_apply``'s declined branch.  The forward-only
    ``forward_hidden`` at the same shapes keeps one kernel a layer."""
    import dataclasses
    import re

    from ray_tpu.models import gpt

    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    cfg, batch = _fuse_norm_parity_cfg()
    cfg = dataclasses.replace(cfg, ce_chunk=-1, **layers)
    params = init_params(cfg, jax.random.PRNGKey(0))
    texts = {}
    for fuse in (None, False):
        grad = jax.jit(jax.value_and_grad(
            lambda p: gpt.loss_fn(p, batch, cfg, mesh=mesh,
                                  fuse_norm=fuse)))
        texts[fuse] = re.sub(r"@([A-Za-z_]+?)_\d+\b", r"@\1",
                             grad.lower(params).as_text())
    assert texts[None] == texts[False]

    def kernels(fuse):
        return str(jax.make_jaxpr(lambda p: gpt.forward_hidden(
            p, batch["tokens"], cfg, mesh=mesh, fuse_norm=fuse))(params)
        ).count("pallas_call")
    # one a layer, or one in the scanned body
    assert kernels(None) == (cfg.n_layers if cfg.unroll_layers else 1)
    assert kernels(False) == 0


def test_gpt_train_keep_logits_matches_flash_ce():
    """PR 49: a recipe that keeps its logits (``ce_chunk=-1``: XLA's
    saved-logits head, ``ln_f`` in XLA) and the default one
    (``ce_chunk=4096``: flash-CE with the norm in its prologue,
    interpret mode here) are the same loss and the same gradient of
    every parameter, through the loss closure and the step that
    ``build_gpt_train`` compiles."""
    import dataclasses

    from ray_tpu.models import gpt
    from ray_tpu.ops import flash_ce

    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    flash_cfg, batch = _fuse_norm_parity_cfg()
    keep_cfg = dataclasses.replace(flash_cfg, ce_chunk=-1)
    gate = flash_ce.uses_flash_ce_norm(2 * 32, 128, 512, ce_chunk=-1)
    assert not gate and "keeps its logits" in gate.reason
    params = init_params(flash_cfg, jax.random.PRNGKey(0))
    grads, losses, gnorm = {}, {}, {}
    for name, cfg in (("flash", flash_cfg), ("keep", keep_cfg)):
        losses[name], grads[name] = jax.value_and_grad(
            lambda p: gpt.loss_fn(p, batch, cfg, mesh=mesh))(params)
        fns = training.build_gpt_train(cfg, mesh)
        _, metrics = fns["step_fn"](fns["init_fn"](jax.random.PRNGKey(0)),
                                    batch)
        assert float(metrics["loss"]) == pytest.approx(
            float(losses[name]), abs=2e-5)
        gnorm[name] = float(metrics["grad_norm"])
        assert fns["telemetry"].records[0]["ce_path"] == (
            "flash" if name == "flash" else "xla_saved")
    assert float(losses["keep"]) == pytest.approx(float(losses["flash"]),
                                                  abs=2e-5)
    assert gnorm["keep"] == pytest.approx(gnorm["flash"], rel=1e-4)
    _assert_every_grad_close(grads["keep"], grads["flash"])


@pytest.mark.slow  # two extra full train-step jits; grads covered above
def test_gpt_train_fuse_norm_parity_through_builder():
    """The same on/off parity through build_gpt_train(fuse_norm=...)'s
    jitted step: identical loss and grad-norm metrics from the same
    init."""
    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    cfg, batch = _fuse_norm_parity_cfg()
    metrics = {}
    for fuse in (True, False):
        fns = training.build_gpt_train(cfg, mesh, fuse_norm=fuse,
                                       telemetry=False)
        state = fns["init_fn"](jax.random.PRNGKey(0))
        _, metrics[fuse] = fns["step_fn"](state, batch)
    assert float(metrics[True]["loss"]) == pytest.approx(
        float(metrics[False]["loss"]), abs=2e-5)
    assert float(metrics[True]["grad_norm"]) == pytest.approx(
        float(metrics[False]["grad_norm"]), rel=1e-4)
