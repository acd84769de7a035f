"""Pipeline parallelism over a ``pp`` (or ``dcn``) mesh axis.

Absent natively in the reference (SURVEY.md §2.4 — delegated to DeepSpeed
et al.).  TPU-native design: every stage is the *same* jitted SPMD program
(one shard_map over the stage axis); stage weights are the per-device
shard of a stacked param tree; activations move stage-to-stage with
``ppermute``.  Two schedules:

* GPipe (:func:`pipeline_apply`): all forwards, then autodiff's mirrored
  backward sweep.  Simple, but every microbatch's activations are live at
  the steady-state peak (in-flight = M).
* 1F1B (:func:`pipeline_1f1b_value_and_grad`, arXiv:2011.03641): each
  stage alternates one forward with one backward once warm, so at most
  ``2*pp - 1`` microbatches are in flight regardless of M — the
  activation footprint is bounded by the *depth*, not the *batch*.  The
  backward is hand-scheduled (recompute + ``jax.vjp`` per tick) because
  autodiff of a scan cannot interleave ticks.

Both compose with the other axes: within a stage the layer math can be
tp/fsdp-sharded as usual (the shard_map here only manages the stage
axis).  Staging over ``dcn`` is the natural multi-pod layout: one stage
per pod, only the microbatch activation boundary crossing the slow tier
per tick instead of a gradient all-reduce of the whole model.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel.compat import shard_map


def pipeline_schedule_stats(pp: int, num_microbatches: int,
                            schedule: str = "1f1b") -> Dict[str, Any]:
    """Analytic schedule figures: bubble fraction and peak in-flight
    microbatches (the activation-memory driver).

    GPipe idles ``pp - 1`` of ``M + pp - 1`` ticks per sweep and holds
    all ``M`` microbatches' activations at peak; 1F1B idles
    ``2*pp - 2`` of ``M + 2*pp - 2`` ticks (same asymptotic fraction)
    but holds at most ``2*pp - 1``.  Reported by ``build_gpt_train_pp``
    and the r22 scratch driver so the bubble is a number in the run
    record, not a vibe."""
    M = int(num_microbatches)
    pp = int(pp)
    if schedule == "gpipe":
        ticks = M + pp - 1
        bubble = (pp - 1) / ticks
        in_flight = M
    elif schedule == "1f1b":
        ticks = M + 2 * pp - 2
        bubble = (2 * pp - 2) / max(ticks, 1)
        in_flight = min(M, 2 * pp - 1)
    else:
        raise ValueError(
            f"unknown pipeline schedule {schedule!r} "
            "(want 'gpipe' or '1f1b')")
    return {"schedule": schedule, "stages": pp, "num_microbatches": M,
            "ticks": ticks, "bubble_fraction": bubble,
            "in_flight_microbatches": in_flight}


def pipeline_apply(stage_fn: Callable, stacked_params, x, *, mesh,
                   num_microbatches: int, params_spec=None,
                   axis: str = "pp"):
    """Run a GPipe pipeline.

    Args:
      stage_fn: ``(params_slice, activation) -> activation`` for ONE stage;
        activation shapes must match across stages.
      stacked_params: pytree whose leaves have leading dim ``pp`` (stage).
      x: ``[M, mb, ...]`` microbatched input (M = num_microbatches).
      mesh: mesh containing the stage axis.
      params_spec: optional pytree of PartitionSpecs for stacked_params
        (defaults to sharding dim 0 over the stage axis, rest replicated).
      axis: mesh axis to stage over (``"pp"``, or ``"dcn"`` for
        one-stage-per-pod layouts).

    Returns the last stage's outputs, ``[M, mb, ...]``.

    The shard_map is *partial-manual*: only the stage axis is
    manual, so dp/fsdp/tp shardings inside ``stage_fn`` compose
    automatically (XLA partitions the within-stage math as usual).
    """
    pp = mesh.shape[axis]
    xs_m = jax.tree.leaves(x)[0].shape[0]
    if xs_m != num_microbatches:
        raise ValueError(f"x leading dim {xs_m} != "
                         f"num_microbatches {num_microbatches}")
    if params_spec is None:
        params_spec = jax.tree.map(
            lambda leaf: P(axis, *([None] * (leaf.ndim - 1))),
            stacked_params)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(params_spec, P()), out_specs=P(),
        axis_names={axis})
    def run(params, xs):
        # params leaves: [1, ...] local stage slice -> squeeze
        params = jax.tree.map(lambda p: jnp.squeeze(p, 0), params)
        my = lax.axis_index(axis)
        M = xs.shape[0]
        T = M + pp - 1
        act0 = jnp.zeros_like(xs[0])
        out0 = jnp.zeros_like(xs)
        perm_fwd = [(i, (i + 1) % pp) for i in range(pp)]

        def tick(carry, t):
            act, outs = carry
            # receive from previous stage (stage 0 receives garbage ring
            # wrap, replaced by injection below)
            received = lax.ppermute(act, axis, perm_fwd)
            inject = xs[jnp.minimum(t, M - 1)]
            act_in = jnp.where(my == 0, inject, received)
            act_out = stage_fn(params, act_in)
            out_idx = t - (pp - 1)
            write = jnp.logical_and(my == pp - 1, out_idx >= 0)
            idx = jnp.maximum(out_idx, 0)
            updated = lax.dynamic_update_index_in_dim(
                outs, jnp.where(write, act_out, outs[idx]), idx, 0)
            return (act_out, updated), None

        (act, outs), _ = lax.scan(tick, (act0, out0), jnp.arange(T))
        # broadcast the last stage's buffer to all stages
        mask = (my == pp - 1).astype(outs.dtype)
        outs = lax.psum(outs * mask, axis)
        return outs

    return run(stacked_params, x)


def pipeline_1f1b_value_and_grad(
        stage_fn: Callable, stage_params, shared_params, mb_inputs, *,
        mesh, num_microbatches: int, act_example,
        axis: str = "pp", cot_weights=None, stage_spec=None):
    """One-forward-one-backward pipeline step: loss AND grads in a
    single hand-scheduled sweep (arXiv:2011.03641).

    The schedule: microbatch ``u`` runs forward on stage ``s`` at tick
    ``u + s`` and backward at tick ``u + 2*pp - 2 - s`` — the last
    stage's forward and backward of the same microbatch share a tick,
    which is what bounds in-flight activations at ``2*pp - 1``.  Each
    stage keeps a ring buffer of its ``min(M, 2*pp - 1)`` most recent
    stage *inputs*; the backward recomputes the stage forward from the
    saved input under ``jax.vjp`` (remat — the same memory/flops trade
    the non-pipelined path makes) and ppermutes the input-cotangent
    upstream.  Bubble ticks compute on zeros/clamped indices and are
    masked out of every accumulator with ``where`` *selects* (never
    multiplies), so garbage — even a NaN — cannot reach a live value.

    Args:
      stage_fn: ``(stage_params_local, shared_params, act_in, mb) ->
        (act_out, loss)`` for ONE stage, uniform across stages (mask
        internally on the stage index: first stage ignores ``act_in``
        and embeds from ``mb``; ``loss`` is read only on the last
        stage).  ``loss`` must be this microbatch's *mean* over its own
        valid tokens — the runner weights it by ``cot_weights[u]``.
      stage_params: pytree, leaves ``[pp, ...]`` (stage-stacked).
      shared_params: pytree replicated across stages (embedding table,
        final norm, head); grads are psum'd over the stage axis.
      mb_inputs: pytree, leaves ``[M, ...]`` — per-microbatch inputs
        (tokens, targets), replicated over the stage axis (the last
        stage needs every microbatch's targets).
      act_example: activation template (``[mb_rows, ...]``) used to
        shape the carries; zeros of it must be a legal stage input.
      cot_weights: ``[M]`` f32 loss weights (default uniform ``1/M``).
        For masked targets pass ``n_u / n_total`` so the weighted sum
        equals the global masked mean exactly.
      stage_spec: PartitionSpec tree for ``stage_params`` (default: dim
        0 over ``axis``, rest replicated).

    Returns ``(loss, stage_grads, shared_grads)``; grads are f32,
    ``stage_grads`` stage-stacked like ``stage_params``.
    """
    pp = int(mesh.shape[axis])
    M = int(num_microbatches)
    if M < 1:
        raise ValueError(f"num_microbatches={M} must be >= 1")
    for leaf in jax.tree.leaves(mb_inputs):
        if leaf.shape[0] != M:
            raise ValueError(
                f"mb_inputs leading dim {leaf.shape[0]} != "
                f"num_microbatches {M}")
    if stage_spec is None:
        stage_spec = jax.tree.map(
            lambda leaf: P(axis, *([None] * (leaf.ndim - 1))),
            stage_params)
    if cot_weights is None:
        cot_weights = jnp.full((M,), 1.0 / M, jnp.float32)

    T = M + 2 * pp - 2
    K = min(M, 2 * pp - 1)     # ring-buffer depth = peak in-flight

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(stage_spec, P(), P(), P(), P()),
        out_specs=(P(), stage_spec, P()),
        axis_names={axis})
    def run(p_stage, p_shared, mbs, w, act0):
        p_stage = jax.tree.map(lambda p: jnp.squeeze(p, 0), p_stage)
        s = lax.axis_index(axis)
        is_last = s == pp - 1
        perm_fwd = [(i, (i + 1) % pp) for i in range(pp)]
        perm_bwd = [(i, (i - 1) % pp) for i in range(pp)]

        zero_act = jnp.zeros_like(act0)
        saved0 = jnp.zeros((K,) + act0.shape, act0.dtype)
        gs0 = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), p_stage)
        gh0 = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), p_shared)

        def mb_at(u):
            return jax.tree.map(
                lambda leaf: lax.dynamic_index_in_dim(
                    leaf, u, 0, keepdims=False), mbs)

        def tick(carry, t):
            act_fwd, cot_bwd, saved, gs, gh, loss_acc = carry
            received = lax.ppermute(act_fwd, axis, perm_fwd)
            cot_recv = lax.ppermute(cot_bwd, axis, perm_bwd)

            # ---- forward: microbatch u_f = t - s
            u_f = t - s
            f_valid = jnp.logical_and(u_f >= 0, u_f < M)
            u_fc = jnp.clip(u_f, 0, M - 1)
            act_in = jnp.where(f_valid, received, zero_act)
            # save the stage INPUT for the remat backward; the slot is
            # free again by construction (K = 2*pp - 1 covers the
            # longest fwd->bwd gap, at stage 0)
            slot_f = jnp.mod(u_fc, K)
            prev = lax.dynamic_index_in_dim(saved, slot_f, 0,
                                            keepdims=False)
            saved = lax.dynamic_update_index_in_dim(
                saved, jnp.where(f_valid, act_in, prev), slot_f, 0)
            act_out, loss_u = stage_fn(p_stage, p_shared, act_in,
                                       mb_at(u_fc))
            act_fwd_next = jnp.where(f_valid, act_out, zero_act)
            loss_acc = loss_acc + jnp.where(
                jnp.logical_and(is_last, f_valid),
                loss_u.astype(jnp.float32) * w[u_fc], 0.0)

            # ---- backward: microbatch u_b = t - (2*pp - 2 - s).
            # The last stage's same-tick read of `saved` happens after
            # the write above, so u_b == u_f there is safe.
            u_b = t - (2 * pp - 2 - s)
            b_valid = jnp.logical_and(u_b >= 0, u_b < M)
            u_bc = jnp.clip(u_b, 0, M - 1)
            act_in_b = lax.dynamic_index_in_dim(
                saved, jnp.mod(u_bc, K), 0, keepdims=False)
            mb_b = mb_at(u_bc)

            def fwd(ps, ph, a):
                return stage_fn(ps, ph, a, mb_b)

            (out_b, loss_b), vjp_fn = jax.vjp(fwd, p_stage, p_shared,
                                              act_in_b)
            # cotangent seeds: downstream act-cotangent everywhere but
            # the last stage (whose act_out feeds nothing); the loss
            # seed w[u] only there
            cot_act = jnp.where(is_last, zero_act,
                                cot_recv).astype(out_b.dtype)
            cot_loss = jnp.where(is_last, w[u_bc],
                                 0.0).astype(loss_b.dtype)
            g_stage, g_shared, cot_in = vjp_fn((cot_act, cot_loss))
            gs = jax.tree.map(
                lambda acc, g: acc + jnp.where(
                    b_valid, g.astype(jnp.float32), 0.0), gs, g_stage)
            gh = jax.tree.map(
                lambda acc, g: acc + jnp.where(
                    b_valid, g.astype(jnp.float32), 0.0), gh, g_shared)
            cot_next = jnp.where(b_valid, cot_in,
                                 jnp.zeros_like(cot_in))
            return (act_fwd_next, cot_next, saved, gs, gh,
                    loss_acc), None

        carry0 = (zero_act, zero_act, saved0, gs0, gh0,
                  jnp.zeros((), jnp.float32))
        (_, _, _, gs, gh, loss_acc), _ = lax.scan(
            tick, carry0, jnp.arange(T))
        loss = lax.psum(loss_acc, axis)
        gh = lax.psum(gh, axis)
        gs = jax.tree.map(lambda g: jnp.expand_dims(g, 0), gs)
        return loss, gs, gh

    return run(stage_params, shared_params, mb_inputs,
               jnp.asarray(cot_weights, jnp.float32), act_example)


def pipeline_loss_fn(stage_fn: Callable, loss_fn: Callable):
    """Compose a pipeline forward with a loss on the final activations."""
    def fn(stacked_params, x, targets, *, mesh, num_microbatches):
        out = pipeline_apply(stage_fn, stacked_params, x, mesh=mesh,
                             num_microbatches=num_microbatches)
        return loss_fn(out, targets)
    return fn


def stack_stage_params(per_stage_params):
    """[{...}, {...}] -> single pytree with leading stage dim."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves),
                        *per_stage_params)
