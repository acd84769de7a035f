"""Sharded training-step builders for the in-tree models.

One function turns (config, mesh) into a fully-sharded jitted train step:
params/optimizer sharded by the logical-axis rules, batch sharded over
(dcn, dp, fsdp) × sp, gradients reduced by XLA from the shardings alone —
the TPU-native equivalent of the reference's DDP/FSDP wrapper selection
(``train/torch/train_loop_utils.py`` prepare_model).  On a multi-pod
``dcn`` mesh the params stay pod-replicated (pure DP across pods), so
only the post-reduction gradient shard crosses the slow tier.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu._private.compile_cache import enable_compile_cache
from ray_tpu.models import gpt as gpt_mod
from ray_tpu.parallel import sharding as shd
from ray_tpu.parallel.ring_attention import make_ring_attention_fn


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jnp.ndarray


def default_accum_steps() -> int:
    """``RAY_TPU_ACCUM`` (default 1): gradient-accumulation microbatch
    count the train builders use when ``accum_steps`` is not pinned —
    the global-batch-invariance knob of the elastic story (an 8->4
    mesh shrink doubles it so the optimization trajectory, not just
    the arithmetic, survives the topology change)."""
    import sys
    raw = os.environ.get("RAY_TPU_ACCUM", "1")
    try:
        k = int(raw)
    except ValueError:
        print(f"RAY_TPU_ACCUM={raw!r} is not an integer; using 1",
              file=sys.stderr)
        return 1
    if k < 1:
        print(f"RAY_TPU_ACCUM={k} must be >= 1; using 1",
              file=sys.stderr)
        return 1
    return k


def _split_microbatches(batch: Dict[str, Any], accum_steps: int):
    """Reshape every batch leaf ``[B, ...] -> [k, B/k, ...]`` for the
    accumulation scan; loud on an indivisible batch (the
    ``validate_divisibility`` suggestion names the fix)."""
    sizes = {k: v.shape[0] for k, v in batch.items()}
    bad = {k: b for k, b in sizes.items() if b % accum_steps}
    if bad:
        raise ValueError(
            f"batch dims {bad} not divisible by accum_steps="
            f"{accum_steps}: gradient accumulation scans whole "
            "microbatches (see parallel.mesh.suggest_accum_steps "
            "for a legal factor)")
    return {k: v.reshape((accum_steps, v.shape[0] // accum_steps)
                         + v.shape[1:])
            for k, v in batch.items()}


def _accum_value_and_grad(value_and_grad, params, batch,
                          accum_steps: int):
    """``value_and_grad`` over ``accum_steps`` microbatches with f32
    gradient accumulation inside a ``lax.scan`` — the backward runs
    per microbatch (activation memory is one microbatch's, the whole
    point), partial gradients accumulate in f32 regardless of the
    model dtype (bf16 partial sums would drift with ``k``), and the
    mean loss/grads match the unaccumulated full-batch step to fp32
    tolerance when microbatches carry equal valid-token counts (the
    synthetic and packed batches here do; the residual difference is
    reduction order only)."""
    micro = _split_microbatches(batch, accum_steps)

    def body(carry, mb):
        loss_sum, grad_acc = carry
        loss, grads = value_and_grad(params, mb)
        grad_acc = jax.tree.map(
            lambda a, g: a + g.astype(jnp.float32), grad_acc, grads)
        return (loss_sum + loss.astype(jnp.float32), grad_acc), None

    zeros = jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss_sum, grad_acc), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), zeros), micro)
    inv_k = 1.0 / accum_steps
    grads = jax.tree.map(
        lambda g, p: (g * inv_k).astype(p.dtype), grad_acc, params)
    return loss_sum * inv_k, grads


def default_optimizer(lr: float = 3e-4, weight_decay: float = 0.1,
                      warmup: int = 100, total_steps: int = 10000,
                      grad_clip: float = 1.0):
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup, max(total_steps, warmup + 1), lr * 0.1)
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(schedule, b1=0.9, b2=0.95,
                    weight_decay=weight_decay),
    )


def _state_shardings(init, param_sh, mesh) -> TrainState:
    """Shard the full state by structure: params by rules; opt_state leaves
    that match a param shape inherit that param's sharding; scalars
    replicate."""
    example = jax.eval_shape(init, jax.random.PRNGKey(0))
    shape_to_sh = {}
    for leaf, sh in zip(jax.tree.leaves(example.params),
                        jax.tree.leaves(param_sh)):
        shape_to_sh[leaf.shape] = sh
    replicated = NamedSharding(mesh, P())
    opt_sh = jax.tree.map(lambda leaf: shape_to_sh.get(leaf.shape,
                                                       replicated),
                          example.opt_state)
    return TrainState(param_sh, opt_sh, replicated)


def _batch_sharding(mesh):
    seq_axis = "sp" if mesh.shape.get("sp", 1) > 1 else None
    return NamedSharding(mesh, P(shd.data_axes(mesh), seq_axis))


def _recorded_init(init_jit):
    """``init_jit`` with its call in the start-up record as
    ``setup/weights`` (``util/tracing.py``): the dispatch of the one
    jitted call that draws the state, its trace and its executable's
    load with it, and not the device's work, which nothing here waits
    for."""
    from ray_tpu.util import tracing

    def init_fn(key):
        with tracing.span("setup/weights") as sp:
            state = init_jit(key)
            leaves = jax.tree.leaves(state)
            sp.set(leaves=len(leaves),
                   bytes=sum(x.size * x.dtype.itemsize for x in leaves))
        return state

    return init_fn


def _maybe_instrument(fns: Dict[str, Callable], cfg, mesh, *,
                      comm_mode: Optional[str] = None,
                      comm_quant: Optional[str] = None,
                      ce_mode: Optional[str] = None,
                      label: str = "train",
                      telemetry: Optional[bool] = None):
    """Wrap ``fns["step_fn"]`` with a :class:`StepTelemetry` recorder.

    ``telemetry``: ``None`` follows ``RAY_TPU_TELEMETRY`` (default on),
    ``False`` skips, ``True`` forces on (A/B drivers).  When on, the
    dict gains ``telemetry`` (the recorder) and ``raw_step_fn``."""
    if telemetry is False:
        return fns
    from ray_tpu import telemetry as tel_mod
    config = None
    if telemetry is True:
        config = tel_mod.TelemetryConfig(
            enabled=True,
            profile_dir=tel_mod.telemetry_config().profile_dir)
    return tel_mod.instrument(fns, cfg, mesh, comm_mode=comm_mode,
                              comm_quant=comm_quant, ce_mode=ce_mode,
                              label=label, config=config)


def _resolve_lora(lora, base_params):
    """``lora=`` kwarg -> effective LoraConfig (None when off), with
    the base-params requirement enforced up front: adapter-only
    training differentiates *through* a frozen base, so there must be
    one to freeze."""
    if not lora:
        return None
    from ray_tpu.adapters import LoraConfig, lora_config
    lcfg = lora if isinstance(lora, LoraConfig) else lora_config()
    if base_params is None:
        raise ValueError(
            "trainable-adapter mode (lora=...) needs base_params — the "
            "frozen base weights the adapter is trained against (e.g. "
            "gpt.init_params(...) or a served checkpoint)")
    return lcfg


def _adapter_fns(cfg, lcfg, base_params, mesh, base_sh):
    """The trainable-adapter plumbing shared by both builders:
    -> (sharded frozen base, replicated adapter param shardings,
    init(key) -> adapter tree, lora_tree(adapter) -> forward kwarg)."""
    from ray_tpu.adapters import lora as lora_mod
    base = jax.device_put(base_params, base_sh)
    replicated = NamedSharding(mesh, P())
    adapter_shapes = jax.eval_shape(
        lambda k: lora_mod.init_adapter(cfg, lcfg, k),
        jax.random.PRNGKey(0))
    param_sh = jax.tree.map(lambda _: replicated, adapter_shapes)
    scale = jnp.asarray(lcfg.scale, jnp.float32)

    def init_adapter(key):
        return lora_mod.init_adapter(cfg, lcfg, key)

    def lora_tree(adapter):
        return {**adapter, "scale": scale}

    return base, param_sh, init_adapter, lora_tree


def build_gpt_train(cfg: "gpt_mod.GPTConfig", mesh, *,
                    optimizer=None,
                    sp_impl: str = "ring",
                    attn_pack2: Optional[bool] = None,
                    ce_mode: Optional[str] = None,
                    comm_mode: Optional[str] = None,
                    comm_quant: Optional[str] = None,
                    fuse_norm: Optional[bool] = None,
                    accum_steps: Optional[int] = None,
                    telemetry: Optional[bool] = None,
                    lora=None,
                    base_params=None) -> Dict[str, Callable]:
    """Returns dict(init_fn, step_fn, loss_eval_fn, shardings).

    init_fn(key) -> TrainState (sharded); step_fn(state, batch) ->
    (state, metrics); batch = dict(tokens, targets) [B, S] int32.
    ``sp_impl``: how sequence parallelism moves data on sp>1 meshes —
    "ring" (ring attention) or "ulysses" (all-to-all head resharding).
    ``attn_pack2`` pins the two-head lane-packed attention schedule for
    A/B drivers (default: ``ray_tpu.ops.attention.attention_config``);
    ``ce_mode`` pins the loss head for tests and A/B drivers ("flash"
    / "xla"); the default, ``None``, follows the recipe's
    ``cfg.ce_chunk`` through ``ray_tpu.ops.flash_ce.uses_flash_ce``:
    ``< 0`` keeps the f32 logits for the backward (three vocabulary
    matmuls), ``>= 0`` recomputes them, in flash-CE on one device and
    in row chunks under ``jax.checkpoint`` on a sharded mesh.
    ``comm_mode`` pins the multi-chip collective schedule ("gspmd" /
    "overlap"; default: ``ray_tpu.parallel.overlap.comm_config``) —
    "overlap" runs the explicit shard_map schedule (prefetched
    per-block FSDP gathers, as-you-go grad reduce-scatters, ring
    all-gather-matmul TP) and falls back to "gspmd" loudly when the
    (cfg, mesh) is outside its dp/fsdp/tp dense coverage; the chosen
    mode is returned as ``fns["comm_mode"]``.  ``comm_quant`` pins the
    overlap schedule's collective wire dtype ("none" / "int8" / "dcn";
    default: ``comm_config().quant`` from ``RAY_TPU_COMM_QUANT``) —
    "int8" moves the FSDP weight all-gathers and grad reduce-scatters
    (and, on a multi-pod mesh, the cross-pod grad all-reduce) as
    block-scaled int8 (``ray_tpu.quant``, stochastic-rounding ring RS);
    "dcn" quantizes ONLY the cross-pod leg — the recommended multi-pod
    setting: DCN is where bandwidth is scarce, the ICI legs stay exact,
    and it is a plain-wire no-op on a single-pod mesh.  Either is
    dropped loudly when the effective comm_mode is "gspmd"
    (GSPMD owns its collectives), and the effective value is returned
    as ``fns["comm_quant"]``.  ``fuse_norm=False`` pins the fused norm
    epilogues off (``None`` is on; no environment variable decides):
    the out-proj residual/norm epilogue of every block, which a
    differentiated step runs in XLA's formulation either way (its
    Pallas kernel is the forward-only call's, ``ops/fused_norm.py``),
    and the ``ln_f``-in-flash-CE prologue; both decline loudly
    (reasoned gates) on sharded meshes and unsupported shapes.
    The overlap step/loss
    use their own block formulation (einsum attention, vocab-parallel
    CE), so ``attn_pack2``/``ce_mode`` only affect the GSPMD-side
    ``forward_fn`` there.  ``accum_steps`` (default: env
    ``RAY_TPU_ACCUM``, 1) runs the step as ``k`` sequential
    microbatches of ``B/k`` rows under a ``lax.scan`` with f32
    gradient accumulation and ONE optimizer update — the global batch
    (and with it the optimization trajectory) is invariant to the
    device count, which is what lets an elastic 8->4 mesh shrink keep
    training the *same* run (``resilience/elastic.py``); loss and
    per-param grads match the unaccumulated full-batch step to fp32
    tolerance (reduction order is the only difference), and the
    effective value is returned as ``fns["accum_steps"]``.
    ``accum_steps > 1`` declines the overlap schedule loudly (the
    shard_map schedule has its own scan carry; nesting the microbatch
    scan inside it is untested) and falls back to gspmd.
    ``telemetry`` (default: env
    ``RAY_TPU_TELEMETRY``) wraps ``step_fn`` with a per-step
    :class:`ray_tpu.telemetry.StepTelemetry` recorder — the returned
    dict then also carries ``telemetry`` and ``raw_step_fn``.

    ``lora`` (a :class:`ray_tpu.adapters.LoraConfig`, or ``True`` for
    the env-resolved one) switches the builder to **trainable-adapter
    mode** (r25): ``TrainState.params`` becomes the LoRA A/B factor
    tree only, the frozen ``base_params`` (required) is closed over as
    a jit constant, and gradients flow exclusively through the
    adapters — the optimizer state, donation, checkpoints and
    ``publish`` payloads all shrink to adapter size
    (``adapters.adapter_nbytes``).  ``init_fn`` uses the standard LoRA
    init (A gaussian, B zero), so step 0 is exactly the base model.
    The overlap schedule has no adapter formulation and declines
    loudly to gspmd; the returned dict carries the effective config as
    ``fns["lora"]`` (``None`` when off).
    """
    from ray_tpu.ops.attention import make_flash_attention_fn
    from ray_tpu.parallel import overlap as ovl

    enable_compile_cache()
    tx = optimizer or default_optimizer(warmup=cfg.warmup_steps)
    if accum_steps is None:
        accum_steps = default_accum_steps()
    accum_steps = int(accum_steps)
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps} "
                         "(check RAY_TPU_ACCUM)")
    lcfg = _resolve_lora(lora, base_params)
    if comm_mode is None:
        comm_mode = ovl.comm_config().mode
    if comm_mode not in ("gspmd", "overlap"):
        raise ValueError(f"unknown comm_mode {comm_mode!r}; "
                         "expected 'gspmd' or 'overlap'")
    if comm_mode == "overlap":
        if lcfg is not None:
            import sys
            print("comm_mode=overlap has no trainable-adapter "
                  "formulation (the shard_map schedule gathers base "
                  "weights per block); falling back to gspmd",
                  file=sys.stderr)
            comm_mode = "gspmd"
        elif getattr(mesh, "size", 1) <= 1:
            comm_mode = "gspmd"   # single device: nothing to schedule
        elif accum_steps > 1:
            import sys
            print(f"comm_mode=overlap does not support accum_steps="
                  f"{accum_steps} (the schedule's prefetch scan would "
                  "nest inside the microbatch scan); falling back to "
                  "gspmd", file=sys.stderr)
            comm_mode = "gspmd"
        else:
            reason = ovl.overlap_supported(cfg, mesh)
            if reason is not None:
                import sys
                print(f"comm_mode=overlap unsupported ({reason}); "
                      "falling back to gspmd", file=sys.stderr)
                comm_mode = "gspmd"
    if comm_quant is None:
        comm_quant = ovl.comm_config().quant
    if comm_quant not in ("none", "int8", "dcn"):
        raise ValueError(f"unknown comm_quant {comm_quant!r}; "
                         "expected 'none', 'int8' or 'dcn'")
    if comm_quant != "none" and comm_mode != "overlap":
        import sys
        print(f"comm_quant={comm_quant} needs the overlap schedule "
              f"(comm_mode is {comm_mode!r}); wire stays "
              f"{jnp.dtype(cfg.dtype).name}", file=sys.stderr)
        comm_quant = "none"
    logical = gpt_mod.param_logical_axes(cfg)
    param_sh = shd.tree_shardings(mesh, logical)
    base = init_adapter = lora_tree = None
    if lcfg is not None:
        base, param_sh, init_adapter, lora_tree = _adapter_fns(
            cfg, lcfg, base_params, mesh, param_sh)
    routed = cfg.dropless
    if routed and (accum_steps > 1 or lcfg is not None):
        raise NotImplementedError(
            "a config with held_experts (the dropless expert layer) "
            "trains with accum_steps=1 and no LoRA adapter: the "
            "microbatch scan and the adapter forward carry no expert "
            "counts (models/training.py:_accum_value_and_grad)")
    if not cfg.plain_attention:
        if mesh.shape.get("sp", 1) > 1 or comm_mode == "overlap":
            raise NotImplementedError(
                "a config with window layers or grouped K/V heads has "
                "no sequence-parallel (ring, ulysses) or overlap-"
                "schedule attention: build on an sp=1 mesh with "
                "comm_mode='gspmd'")
        attn_fn = gpt_mod.attention_fns(cfg, mesh, pack2=attn_pack2)
    elif mesh.shape.get("sp", 1) > 1:
        if sp_impl == "ulysses":
            from ray_tpu.parallel.ulysses import make_ulysses_attention_fn
            attn_fn = make_ulysses_attention_fn(mesh, causal=True)
        elif sp_impl == "ring":
            attn_fn = make_ring_attention_fn(mesh, causal=True)
        else:
            raise ValueError(f"unknown sp_impl {sp_impl!r}; "
                             "expected 'ring' or 'ulysses'")
    else:
        attn_fn = make_flash_attention_fn(
            mesh, causal=True,
            rope_theta=cfg.rope_theta if cfg.pos == "rope" else None,
            pack2=attn_pack2)
    batch_sh = _batch_sharding(mesh)

    def loss(params, batch):
        if "segment_ids" in batch and mesh.shape.get("sp", 1) > 1:
            # the ring/ulysses hooks have no segment_ids kwarg: the
            # partial would die as an opaque trace-time TypeError, and
            # silently dropping the mask would let co-packed documents
            # attend to each other (same guard as overlap/pipeline)
            raise ValueError(
                "sample-packed batches (segment_ids) are not "
                "supported by sequence-parallel attention (sp>1) yet "
                "— stream unpacked (RAY_TPU_DATA_PACK=0) or use an "
                "sp=1 mesh")
        if lcfg is not None:
            return gpt_mod.loss_fn(base, batch, cfg, attn_fn=attn_fn,
                                   mesh=mesh, ce_mode=ce_mode,
                                   fuse_norm=fuse_norm,
                                   lora=lora_tree(params))
        return gpt_mod.loss_fn(params, batch, cfg, attn_fn=attn_fn,
                               mesh=mesh, ce_mode=ce_mode,
                               fuse_norm=fuse_norm, with_counts=routed)

    overlap_fns = (ovl.build_overlap_step_fns(cfg, mesh, quant=comm_quant)
                   if comm_mode == "overlap" else None)

    def value_and_grad(params, batch):
        if overlap_fns is not None:
            if "segment_ids" in batch:
                # silently training a packed batch without its mask
                # would let co-packed documents attend to each other
                raise ValueError(
                    "sample-packed batches (segment_ids) are not "
                    "supported by the overlap schedule yet — build "
                    "with comm_mode='gspmd' for streamed packed input")
            return overlap_fns["value_and_grad"](
                params, batch["tokens"], batch["targets"])
        return jax.value_and_grad(loss, has_aux=routed)(params, batch)

    def init(key) -> TrainState:
        params = init_adapter(key) if lcfg is not None \
            else gpt_mod.init_params(cfg, key)
        return TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))

    st_sh = _state_shardings(init, param_sh, mesh)
    init_jit = jax.jit(init, out_shardings=st_sh)

    @functools.partial(jax.jit, in_shardings=(st_sh, batch_sh),
                       out_shardings=(st_sh, None), donate_argnums=(0,))
    def step(state: TrainState, batch):
        if accum_steps > 1:
            loss_val, grads = _accum_value_and_grad(
                value_and_grad, state.params, batch, accum_steps)
        else:
            loss_val, grads = value_and_grad(state.params, batch)
        metrics = {}
        if routed:
            # the expert layers' counts, summed over layers, ride on the
            # step's one fetch with the loss
            loss_val, metrics["moe_counts"] = loss_val
        updates, opt_state = tx.update(grads, state.opt_state,
                                       state.params)
        params = optax.apply_updates(state.params, updates)
        # a routed step's gradient norm in float32: bfloat16's 2^-8
        # would be the resolution of the check that reads it
        gnorm = optax.global_norm(
            jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            if routed else grads)
        return (TrainState(params, opt_state, state.step + 1),
                {"loss": loss_val, "grad_norm": gnorm,
                 "step": state.step + 1, **metrics})

    @functools.partial(jax.jit, in_shardings=(st_sh.params, batch_sh))
    def loss_eval(params, batch):
        if overlap_fns is not None:
            return overlap_fns["loss"](params, batch["tokens"],
                                       batch["targets"])
        out = loss(params, batch)
        return out[0] if routed else out

    @functools.partial(jax.jit, in_shardings=(st_sh.params, batch_sh),
                       out_shardings=None)
    def forward_logits(params, batch):
        if lcfg is not None:
            logits, _ = gpt_mod.forward(base, batch["tokens"], cfg,
                                        attn_fn=attn_fn, mesh=mesh,
                                        fuse_norm=fuse_norm,
                                        lora=lora_tree(params))
            return logits
        logits, _ = gpt_mod.forward(params, batch["tokens"], cfg,
                                    attn_fn=attn_fn, mesh=mesh,
                                    fuse_norm=fuse_norm)
        return logits

    fns = {
        "init_fn": _recorded_init(init_jit),
        "step_fn": step,
        "loss_fn": loss_eval,
        "forward_fn": forward_logits,
        "state_shardings": st_sh,
        "batch_sharding": batch_sh,
        "attn_fn": attn_fn,
        "comm_mode": comm_mode,
        "comm_quant": comm_quant,
        "accum_steps": accum_steps,
        "lora": lcfg,
    }
    return _maybe_instrument(fns, cfg, mesh, comm_mode=comm_mode,
                             comm_quant=comm_quant,
                             ce_mode=ce_mode, telemetry=telemetry)


def rl_advantages(rewards, baseline: str = "rloo"):
    """Per-trajectory advantages from scalar rewards ([B] -> [B]).

    - ``rloo``: leave-one-out baseline (RLOO): each trajectory's
      baseline is the mean reward of the *other* B-1 trajectories in
      its batch — unbiased, variance-reduced, no value network
      (``adv_b = (B * r_b - sum r) / (B - 1)``; falls back to ``none``
      at B=1, where there is no "other").
    - ``mean``: batch-mean baseline (biased at small B — the sample
      mean includes r_b — but the familiar REINFORCE-with-baseline).
    - ``none``: raw rewards (plain REINFORCE).
    """
    B = rewards.shape[0]
    r = rewards.astype(jnp.float32)
    if baseline == "rloo" and B > 1:
        return (B * r - jnp.sum(r)) / (B - 1)
    if baseline == "mean":
        return r - jnp.mean(r)
    if baseline in ("rloo", "none"):
        return r
    raise ValueError(f"unknown baseline {baseline!r}; "
                     "expected 'rloo', 'mean' or 'none'")


def build_gpt_rl_train(cfg: "gpt_mod.GPTConfig", mesh, *,
                       optimizer=None,
                       baseline: str = "rloo",
                       attn_pack2: Optional[bool] = None,
                       accum_steps: int = 1,
                       lora=None,
                       base_params=None
                       ) -> Dict[str, Callable]:
    """Policy-gradient (REINFORCE/RLOO) step builder for the GPT family
    — the learner half of the ``ray_tpu.rl`` actor/learner split,
    derived from :func:`build_gpt_train`: same param/optimizer
    shardings, same attention dispatch, same donated
    :class:`TrainState`, but the loss is the score-function policy
    gradient over sampled trajectories instead of teacher-forced CE.

    Batch (fixed shapes -> one compile):

    - ``tokens``  [B, S] int32 — prompt + sampled completion, padded;
    - ``targets`` [B, S] int32 — the *action* labels: ``targets[b, t]``
      is the token sampled at position ``t+1`` when that token is part
      of the completion, else ``-1`` (the CE masking convention — only
      generated tokens carry gradient, prompt/pad positions do not);
    - ``rewards`` [B] f32 — one scalar per trajectory.

    Loss: ``-(1/B) * sum_b adv_b * sum_t logp(targets[b,t])`` — the
    per-sequence-sum REINFORCE estimator with :func:`rl_advantages`
    baselines computed inside the jitted step.  Logprobs come from a
    plain f32 ``log_softmax`` over the forward logits, the same
    distribution the actors' sampler reports (``inference.sampling``),
    so actor-side logprobs and learner-side gradients price the same
    policy; the flash-CE streamed-logits formulation has no
    advantage-weighted variant yet, so the [B, S, V] logits
    materialize here (fine at rollout batch sizes — an on-chip
    follow-up can fuse the weighted gather).

    Metrics per step: ``pg_loss``, ``reward_mean``/``reward_max``,
    ``logp_mean`` (per action token), ``entropy`` (mean action-position
    entropy — a collapse canary), ``grad_norm``, ``action_tokens``,
    ``step``.  The returned dict also carries ``pg_grad_fn`` (jitted
    ``(params, batch) -> ((loss, metrics), grads)``) for the
    hand-computed-gradient parity test and for LearnerGroup hosting
    (gradients leave jit, get allreduced, come back through
    ``apply_grads_fn``).

    ``accum_steps > 1`` microbatches the trajectories ``B -> k x B/k``
    under a ``lax.scan`` with f32 grad accumulation, mirroring
    :func:`build_gpt_train` — crucially the RLOO/mean **baseline is
    computed over the FULL batch first** (the r14 LearnerGroup lesson:
    per-microbatch leave-one-out is a different, worse estimator), so
    the accumulated step is the same policy gradient to reduction
    order: the score-function loss is a plain sum over trajectories
    and decomposes exactly across microbatches.

    ``lora``/``base_params`` switch to trainable-adapter mode exactly
    as in :func:`build_gpt_train`: the TrainState carries only LoRA
    A/B factors, the frozen base is a jit constant, and
    ``params_host()`` snapshots — the RL *publish* payload — shrink
    from full-model to adapter bytes, which is what makes per-tenant
    RL publication through the :class:`~ray_tpu.adapters.AdapterStore`
    cheap enough to do every few steps.
    """
    from ray_tpu.ops.attention import make_flash_attention_fn

    rl_advantages(jnp.zeros((2,)), baseline)   # validate loudly, once
    accum_steps = int(accum_steps)
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    enable_compile_cache()
    # NOT default_optimizer(): its warmup schedule starts at lr 0, so
    # an RL run's first (often only) handful of steps would be no-ops
    tx = optimizer or optax.chain(optax.clip_by_global_norm(1.0),
                                  optax.adam(3e-4))
    lcfg = _resolve_lora(lora, base_params)
    logical = gpt_mod.param_logical_axes(cfg)
    param_sh = shd.tree_shardings(mesh, logical)
    base = init_adapter = lora_tree = None
    if lcfg is not None:
        base, param_sh, init_adapter, lora_tree = _adapter_fns(
            cfg, lcfg, base_params, mesh, param_sh)
    if mesh.shape.get("sp", 1) > 1:
        attn_fn = make_ring_attention_fn(mesh, causal=True)
    else:
        attn_fn = make_flash_attention_fn(
            mesh, causal=True,
            rope_theta=cfg.rope_theta if cfg.pos == "rope" else None,
            pack2=attn_pack2)
    seq_sh = _batch_sharding(mesh)                      # [B, S] leaves
    traj_sh = NamedSharding(mesh, P(shd.data_axes(mesh)))  # [B] leaves
    batch_sh = {"tokens": seq_sh, "targets": seq_sh,
                "rewards": traj_sh}

    def policy_forward(p, tokens):
        if lcfg is not None:
            return gpt_mod.forward(base, tokens, cfg, attn_fn=attn_fn,
                                   mesh=mesh, lora=lora_tree(p))
        return gpt_mod.forward(p, tokens, cfg, attn_fn=attn_fn,
                               mesh=mesh)

    def pg_loss(params, batch):
        tokens, targets = batch["tokens"], batch["targets"]
        B, S = tokens.shape
        logits, _aux = policy_forward(params, tokens)
        logp = jax.nn.log_softmax(logits, axis=-1)      # [B, S, V] f32
        chosen = jnp.take_along_axis(
            logp, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
        mask = (targets >= 0).astype(jnp.float32)
        adv = rl_advantages(batch["rewards"], baseline)
        seq_logp = jnp.sum(chosen * mask, axis=-1)      # [B]
        loss = -jnp.mean(adv * seq_logp)
        n_act = jnp.maximum(jnp.sum(mask), 1.0)
        ent = -jnp.sum(jnp.sum(jnp.exp(logp) * logp, -1) * mask) / n_act
        metrics = {
            "pg_loss": loss,
            "reward_mean": jnp.mean(batch["rewards"]),
            "reward_max": jnp.max(batch["rewards"]),
            "logp_mean": jnp.sum(chosen * mask) / n_act,
            "entropy": ent,
            "action_tokens": jnp.sum(mask),
        }
        return loss, metrics

    def _accum_pg_value_and_grad(params, batch):
        """The accumulated policy-gradient step: advantages over the
        FULL batch, then the score-function loss — a plain sum over
        trajectories — split exactly across ``accum_steps``
        microbatches whose grads accumulate in f32 (each microbatch's
        partial is already ``/B``-scaled, so the accumulated sum IS
        the full-batch gradient, no mean at the end)."""
        B = batch["tokens"].shape[0]
        adv = rl_advantages(batch["rewards"], baseline)
        micro = _split_microbatches(
            {"tokens": batch["tokens"], "targets": batch["targets"],
             "adv": adv}, accum_steps)

        def micro_loss(p, mb):
            tokens, targets = mb["tokens"], mb["targets"]
            logits, _aux = policy_forward(p, tokens)
            logp = jax.nn.log_softmax(logits, axis=-1)
            chosen = jnp.take_along_axis(
                logp, jnp.maximum(targets, 0)[..., None],
                axis=-1)[..., 0]
            mask = (targets >= 0).astype(jnp.float32)
            seq_logp = jnp.sum(chosen * mask, axis=-1)
            part = -jnp.sum(mb["adv"] * seq_logp) / B
            ent_sum = -jnp.sum(
                jnp.sum(jnp.exp(logp) * logp, -1) * mask)
            sums = jnp.stack([jnp.sum(chosen * mask), ent_sum,
                              jnp.sum(mask)])
            return part, sums

        def body(carry, mb):
            loss_sum, grad_acc, sums = carry
            (part, s), grads = jax.value_and_grad(
                micro_loss, has_aux=True)(params, mb)
            grad_acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), grad_acc,
                grads)
            return (loss_sum + part.astype(jnp.float32),
                    grad_acc, sums + s), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss, grad_acc, sums), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zeros,
                   jnp.zeros((3,), jnp.float32)), micro)
        grads = jax.tree.map(lambda g, p: g.astype(p.dtype),
                             grad_acc, params)
        n_act = jnp.maximum(sums[2], 1.0)
        metrics = {
            "pg_loss": loss,
            "reward_mean": jnp.mean(batch["rewards"]),
            "reward_max": jnp.max(batch["rewards"]),
            "logp_mean": sums[0] / n_act,
            "entropy": sums[1] / n_act,
            "action_tokens": sums[2],
        }
        return (loss, metrics), grads

    def pg_value_and_grad(params, batch):
        if accum_steps > 1:
            return _accum_pg_value_and_grad(params, batch)
        return jax.value_and_grad(pg_loss, has_aux=True)(params, batch)

    def init(key) -> TrainState:
        params = init_adapter(key) if lcfg is not None \
            else gpt_mod.init_params(cfg, key)
        return TrainState(params, tx.init(params),
                          jnp.zeros((), jnp.int32))

    st_sh = _state_shardings(init, param_sh, mesh)
    init_jit = jax.jit(init, out_shardings=st_sh)

    @functools.partial(jax.jit, in_shardings=(st_sh, batch_sh),
                       out_shardings=(st_sh, None), donate_argnums=(0,))
    def step(state: TrainState, batch):
        (loss_val, metrics), grads = pg_value_and_grad(state.params,
                                                       batch)
        updates, opt_state = tx.update(grads, state.opt_state,
                                       state.params)
        params = optax.apply_updates(state.params, updates)
        metrics.update(step=state.step + 1,
                       grad_norm=optax.global_norm(grads))
        return (TrainState(params, opt_state, state.step + 1), metrics)

    @functools.partial(jax.jit,
                       in_shardings=(st_sh.params, batch_sh))
    def grad_fn(params, batch):
        return pg_value_and_grad(params, batch)

    # split apply for the LearnerGroup DDP path (grads leave jit for
    # the host allreduce ring and come back — the PPOLearner pattern)
    @jax.jit
    def apply_grads(params, opt_state, grads):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    @functools.partial(jax.jit,
                       in_shardings=(st_sh.params, batch_sh))
    def loss_eval(params, batch):
        return pg_loss(params, batch)[0]

    return {
        "init_fn": _recorded_init(init_jit),
        "step_fn": step,
        "loss_fn": loss_eval,
        "pg_grad_fn": grad_fn,
        "apply_grads_fn": apply_grads,
        "optimizer": tx,
        "state_shardings": st_sh,
        "batch_sharding": batch_sh,
        "attn_fn": attn_fn,
        "baseline": baseline,
        "accum_steps": accum_steps,
        "lora": lcfg,
    }


def default_pp_schedule() -> str:
    """``RAY_TPU_PP_SCHEDULE`` (default ``gpipe``): the pipeline
    microbatch schedule ``build_gpt_train_pp`` uses when ``schedule``
    is not pinned — ``gpipe`` (all-forward-then-backward, in-flight =
    M) or ``1f1b`` (one-forward-one-backward, in-flight bounded at
    ``2*stages - 1``)."""
    import sys
    raw = os.environ.get("RAY_TPU_PP_SCHEDULE", "gpipe").strip().lower()
    if raw not in ("gpipe", "1f1b"):
        print(f"RAY_TPU_PP_SCHEDULE={raw!r} unknown (want 'gpipe' or "
              "'1f1b'); using gpipe", file=sys.stderr)
        return "gpipe"
    return raw


def default_pp_microbatches() -> Optional[int]:
    """``RAY_TPU_PP_MICROBATCH`` (default unset): microbatch count for
    ``build_gpt_train_pp`` when ``num_microbatches`` is not pinned;
    unset falls back to ``2 * stages``."""
    import sys
    raw = os.environ.get("RAY_TPU_PP_MICROBATCH", "").strip()
    if not raw:
        return None
    try:
        m = int(raw)
    except ValueError:
        print(f"RAY_TPU_PP_MICROBATCH={raw!r} is not an integer; "
              "ignoring", file=sys.stderr)
        return None
    if m < 1:
        print(f"RAY_TPU_PP_MICROBATCH={m} must be >= 1; ignoring",
              file=sys.stderr)
        return None
    return m


def _pp_batch_sharding(mesh, exclude: Optional[str]):
    """Batch sharding for the pipeline trainers: the usual data axes
    minus the stage axis (a dcn-staged pipeline must not ALSO shard the
    batch over dcn — each microbatch visits every stage whole)."""
    axes = tuple(a for a in ("dcn", "dp", "fsdp")
                 if a != exclude and mesh.shape.get(a, 1) > 1)
    data = None if not axes else (axes[0] if len(axes) == 1 else axes)
    seq_axis = "sp" if mesh.shape.get("sp", 1) > 1 else None
    return NamedSharding(mesh, P(data, seq_axis))


def build_gpt_train_pp(cfg: "gpt_mod.GPTConfig", mesh, *,
                       num_microbatches: Optional[int] = None,
                       schedule: Optional[str] = None,
                       optimizer=None,
                       telemetry: Optional[bool] = None
                       ) -> Dict[str, Callable]:
    """Pipeline-parallel GPT training over a ``pp`` (or ``dcn``) axis.

    The layer stack ``[L, ...]`` is reshaped to ``[stages, L/stages,
    ...]`` and sharded stage-wise; two schedules
    (``parallel/pipeline.py``):

    * ``gpipe`` (default; ``pp`` axis only): forward sweep through
      :func:`pipeline_apply`, autodiff's mirrored backward.  Embedding/
      loss run outside the pipeline (replicated over pp, sharded over
      dp/tp as usual); dp/fsdp/tp compose inside each stage via the
      partial-manual shard_map.
    * ``1f1b``: hand-scheduled one-forward-one-backward
      (:func:`pipeline_1f1b_value_and_grad`), in-flight activations
      bounded at ``2*stages - 1`` regardless of the microbatch count.
      Stages ride the ``pp`` axis when it is >1, else the ``dcn`` axis
      — one stage per pod, so the only cross-pod traffic is one
      microbatch activation boundary per tick instead of a full grad
      all-reduce.  Embedding and loss head are *inside* the (uniform)
      stage program, masked to the first/last stage.

    ``schedule`` defaults to env ``RAY_TPU_PP_SCHEDULE`` (gpipe);
    ``num_microbatches`` to env ``RAY_TPU_PP_MICROBATCH``, else
    ``2 * stages``.  The returned dict reports ``schedule``,
    ``stage_axis``, ``bubble_fraction`` and ``in_flight_microbatches``
    (analytic, :func:`pipeline_schedule_stats`).  TPU-native
    counterpart of the reference's DeepSpeed-delegated pipeline
    parallelism (SURVEY §2.4).
    """
    from jax import lax

    from ray_tpu.parallel import pipeline as pipe
    from ray_tpu.parallel.ring_attention import local_attention

    if schedule is None:
        schedule = default_pp_schedule()
    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}; "
                         "expected 'gpipe' or '1f1b'")
    if schedule == "gpipe":
        if "pp" not in dict(mesh.shape):
            raise ValueError("schedule='gpipe' needs a 'pp' mesh axis "
                             "(1f1b can also stage over 'dcn')")
        stage_axis = "pp"
    elif mesh.shape.get("pp", 1) > 1 or "pp" in dict(mesh.shape):
        stage_axis = "pp"
    elif mesh.shape.get("dcn", 1) > 1:
        stage_axis = "dcn"
    else:
        raise ValueError(
            "schedule='1f1b' needs a 'pp' axis or a 'dcn' axis > 1 to "
            f"stage over; mesh has {dict(mesh.shape)}")
    pp = mesh.shape[stage_axis]
    if cfg.n_layers % pp:
        raise ValueError(f"n_layers={cfg.n_layers} not divisible by "
                         f"stages={pp} (axis {stage_axis!r})")
    if cfg.n_experts > 0:
        raise ValueError("MoE + pipeline parallelism not supported yet")
    Ls = cfg.n_layers // pp
    M = num_microbatches or default_pp_microbatches() or 2 * pp
    enable_compile_cache()
    tx = optimizer or default_optimizer(warmup=cfg.warmup_steps)
    stats = pipe.pipeline_schedule_stats(pp, M, schedule)

    # one rule table for both schedules: "stage" follows the stage
    # axis, and the batch never shards over it (identical to
    # DEFAULT_RULES when staging over pp)
    rules = tuple(
        ("stage", stage_axis) if k == "stage" else
        (("batch", tuple(a for a in ("dcn", "dp", "fsdp")
                         if a != stage_axis)) if k == "batch"
         else (k, v))
        for k, v in shd.DEFAULT_RULES)

    logical = gpt_mod.param_logical_axes(cfg)
    is_axes = lambda x: (isinstance(x, tuple) and all(  # noqa: E731
        isinstance(a, (str, type(None))) for a in x))
    logical["layers"] = jax.tree.map(lambda axes: ("stage",) + axes,
                                     logical["layers"], is_leaf=is_axes)
    param_sh = shd.tree_shardings(mesh, logical, rules)
    batch_sh = _pp_batch_sharding(mesh, stage_axis)
    attn = functools.partial(local_attention, causal=True)
    # stage params enter the shard_map split on dim 0 (stage) only;
    # their within-stage tp/fsdp sharding flows through the auto axes.
    stage_spec = jax.tree.map(lambda leaf: P(stage_axis),
                              logical["layers"], is_leaf=is_axes)

    def init(key) -> TrainState:
        params = gpt_mod.init_params(cfg, key)
        params["layers"] = jax.tree.map(
            lambda leaf: leaf.reshape((pp, Ls) + leaf.shape[1:]),
            params["layers"])
        return TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))

    def _check_batch(batch):
        B = batch["tokens"].shape[0]
        if B % M:
            raise ValueError(f"batch={B} not divisible by microbatches={M}")
        if "segment_ids" in batch:
            # silently dropping the mask would let co-packed documents
            # attend to each other (same guard as the overlap schedule)
            raise ValueError(
                "sample-packed batches (segment_ids) are not supported "
                "by the pipeline-parallel trainer yet — stream unpacked "
                "(RAY_TPU_DATA_PACK=0) or use build_gpt_train")

    def _stack_body(sp, a, positions):
        """Scan this stage's local layers over the activation."""
        def body(c, lp):
            # fuse_norm pinned off: this body traces inside the
            # pipeline shard_map with no mesh in scope, so the epilogue
            # gate would see n_devices=1 and put a pallas_call (no SPMD
            # rule) under the multi-chip pipeline at aligned shapes
            y, _aux = gpt_mod.layer_apply(lp, c, cfg,
                                          positions=positions,
                                          attn_fn=attn,
                                          fuse_norm=False)
            return y, None
        if cfg.remat:
            body = jax.checkpoint(body)
        if cfg.unroll_layers:
            for i in range(Ls):
                a, _ = body(a, jax.tree.map(lambda t: t[i], sp))
            return a
        a, _ = jax.lax.scan(body, a, sp)
        return a

    # ------------------------------------------------------- gpipe ----
    def loss(params, batch):
        tokens, targets = batch["tokens"], batch["targets"]
        B, S = tokens.shape
        _check_batch(batch)
        positions = jnp.arange(S)
        x = gpt_mod.embed_tokens(params, tokens, cfg, mesh=mesh)
        d = x.shape[-1]
        xs = x.reshape(M, B // M, S, d)

        def stage_fn(sp, a):
            return _stack_body(sp, a, positions)

        out = pipe.pipeline_apply(stage_fn, params["layers"], xs,
                                  mesh=mesh, num_microbatches=M,
                                  params_spec=stage_spec)
        h = out.reshape(B, S, d)
        h = gpt_mod._norm(h, params["ln_f"], cfg.norm,
                          bias=params.get("ln_f_b"),
                          eps=gpt_mod.norm_eps(cfg))
        return gpt_mod.loss_from_hidden(params, h, targets, cfg,
                                        mesh=mesh)

    # -------------------------------------------------------- 1f1b ----
    # Uniform stage program: embed masked to the first stage, loss head
    # computed everywhere but seeded (cot_weights) only on the last.
    # The embed is inlined — gpt.embed_tokens' sharding constraints map
    # "batch" to the data axes, which on a dcn-staged mesh would fight
    # the stage partitioning from inside the shard_map.
    def stage_fn_1f1b(sp, shared, a, mb):
        s_idx = lax.axis_index(stage_axis)
        tok, tgt = mb["tokens"], mb["targets"]
        S = tok.shape[1]
        emb = shared["embed"].astype(cfg.dtype)[tok]
        if cfg.pos == "learned":
            emb = emb + shared["pos_embed"].astype(cfg.dtype)[None, :S]
        h = jnp.where(s_idx == 0, emb, a)
        h = _stack_body(sp, h, jnp.arange(S))
        hn = gpt_mod._norm(h, shared["ln_f"], cfg.norm,
                           bias=shared.get("ln_f_b"),
                           eps=gpt_mod.norm_eps(cfg))
        # mesh=None: single-device formulation — the CE runs per stage
        # inside the manual region
        loss_u = gpt_mod.loss_from_hidden(shared, hn, tgt, cfg,
                                          mesh=None)
        return h, loss_u

    def value_and_grad_1f1b(params, batch):
        tokens, targets = batch["tokens"], batch["targets"]
        B, S = tokens.shape
        _check_batch(batch)
        mbs = {"tokens": tokens.reshape(M, B // M, S),
               "targets": targets.reshape(M, B // M, S)}
        # per-microbatch valid-token weights: stage_fn returns each
        # microbatch's own mean, so w_u = n_u / n_total makes the
        # weighted sum the exact global masked mean
        n_u = jnp.sum(mbs["targets"] >= 0, axis=(1, 2)
                      ).astype(jnp.float32)
        w = n_u / jnp.maximum(jnp.sum(n_u), 1.0)
        act_example = jnp.zeros((B // M, S, cfg.d_model), cfg.dtype)
        shared = {k: v for k, v in params.items() if k != "layers"}
        loss_val, g_stage, g_shared = pipe.pipeline_1f1b_value_and_grad(
            stage_fn_1f1b, params["layers"], shared, mbs, mesh=mesh,
            axis=stage_axis, num_microbatches=M,
            act_example=act_example, cot_weights=w,
            stage_spec=stage_spec)
        grads = dict(g_shared)
        grads["layers"] = g_stage
        grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads,
                             params)
        return loss_val, grads

    st_sh = _state_shardings(init, param_sh, mesh)
    init_jit = jax.jit(init, out_shardings=st_sh)

    def value_and_grad(params, batch):
        if schedule == "1f1b":
            return value_and_grad_1f1b(params, batch)
        return jax.value_and_grad(loss)(params, batch)

    @functools.partial(jax.jit, in_shardings=(st_sh, batch_sh),
                       out_shardings=(st_sh, None), donate_argnums=(0,))
    def step(state: TrainState, batch):
        loss_val, grads = value_and_grad(state.params, batch)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return (TrainState(params, opt_state, state.step + 1),
                {"loss": loss_val, "grad_norm": optax.global_norm(grads),
                 "step": state.step + 1})

    @functools.partial(jax.jit, in_shardings=(st_sh.params, batch_sh))
    def loss_eval(params, batch):
        if schedule == "1f1b":
            return value_and_grad_1f1b(params, batch)[0]
        return loss(params, batch)

    fns = {
        "init_fn": _recorded_init(init_jit),
        "step_fn": step,
        "loss_fn": loss_eval,
        "state_shardings": st_sh,
        "batch_sharding": batch_sh,
        "num_microbatches": M,
        "schedule": schedule,
        "stage_axis": stage_axis,
        "bubble_fraction": stats["bubble_fraction"],
        "in_flight_microbatches": stats["in_flight_microbatches"],
    }
    return _maybe_instrument(fns, cfg, mesh, label="train_pp",
                             telemetry=telemetry)


def synthetic_lm_batch(key, batch_size: int, seq_len: int,
                       vocab: int) -> Dict[str, jnp.ndarray]:
    tokens = jax.random.randint(key, (batch_size, seq_len + 1), 0, vocab)
    return {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
