"""Flash-CE: streamed-logits Pallas cross-entropy (fused vocab matmul
+ online logsumexp).

The loss block is the largest serialized chunk of the GPT-2 step after
the r06 attention rework: the no-remat CE writes a resident 4.9 GB f32
``[24576, 50304]`` logits tensor, reads it back for the lse/true-logit
reduces (~17 ms at HBM rate), keeps it alive across the whole backward,
and reads it a third time for the grad matmuls.  None of those passes
do MXU work — they only exist because XLA cannot compute a reduction
*inside* a matmul epilogue.

This kernel can.  Forward is a blocked matmul over the vocab dimension
whose epilogue maintains flash-attention-style online row statistics:

    for each vocab tile j:                    # [block_n, block_v] VMEM
        s    = x_blk @ head_blk               # MXU, f32 accumulation
        m    = max(m, rowmax(s))              # online max
        l    = l * exp(m_prev - m) + rowsum(exp(s - m))
        true += s[row, target[row]]           # one-hot dot, VPU select

so the ``[N, V]`` logits exist only as VMEM tiles — forward emits
``(sum_nll, n_valid)`` with only ``[N]``-sized residuals (lse and the
inputs), never touching HBM with anything vocab-sized.  Backward is
strip-mined the same way: each logits tile is recomputed in the input
dtype (bf16 on chip, f32 accumulation), ``dl = (p - onehot) * g·mask``
is formed in VMEM and fused straight into *both* grad matmuls:

    dx_blk   += dl @ head_blk^T               # accumulated in VMEM
    dhead[j] += x_blk^T @ dl                  # per-(row,vocab) partial

dx accumulates across the sequential vocab sweep in VMEM scratch (the
``ops/attention.py`` strip/accumulator idiom); dhead contributions are
emitted as per-row-block partials ``[N/block_n, d, V]`` and summed in
one XLA pass — the only vocab-sized HBM tensor in the whole path, a
write-once/read-once transient at ~1/13th the traffic of the logits
residual it replaces (and it vanishes from the *resident* footprint:
batch 32 x 1024 fits on a v5e, 12.67 GiB of temporaries, though it
buys no more tokens a second than batch 24 does — the readings are
below).

The bet and its outcome.  Total matmul work is 4 vocab-matmul-
equivalents (fwd, bwd recompute, dx, dhead) vs the saved-logits
path's 3; the bet was that one extra matmul at MXU rate beats the
serialized HBM-rate reduces.  On a v5e at ``[24576, 768] x [768,
50304]`` it is lost on the count, not on the kernels: the forward runs
at 79 % and the backward at 97 % of the bf16 peak, and 4 x 9.6 ms is
still more than XLA's three matmuls plus 6.6 ms of reductions (45.0
against 38.5 ms a step on one chip: the paired reading of
``PERF.md`` section 6, PR 49).  So the kernel serves the recipes that
recompute anyway, and only those:

- ``ce_chunk >= 0`` (the default ``GPTConfig``; the logits may not be
  kept): flash-CE, with the final norm in its prologue.  Both XLA
  alternatives pay the fourth matmul there too, and this one never
  writes a vocab-sized residual.  Measured on a v5e, GPT-2 124M at
  24 x 1024 tokens, ``ce_chunk=4096`` (``PERF.md`` section 6, PR 49):
  the step takes 186.1 ms with flash-CE against 201.6 ms with the
  row-chunked XLA head (199.9 as one chunk), 8.3 % more tokens a
  second; at 28 and 32 x 1024 the same (215.9 against 234.0 ms, 247.2
  against 267.1), and there it also beats the saved-logits head, which
  still fits at 28 but runs 226.1 ms a step.
- ``ce_chunk < 0`` (the recipe keeps its logits): the saved-logits XLA
  formulation of ``models.gpt._chunked_ce``; the gate declines with
  that reason.
- a sharded mesh, or a shape :func:`supports` declines: the XLA
  formulations, as ``ce_chunk`` says.

:func:`uses_flash_ce` is the one place that decides; the model
(``models.gpt.ce_path`` names the head it and the XLA branches of
``_chunked_ce`` come to), the trainer's ``ce_mode=`` pin and the step
telemetry all ask it.

Handles: masked ``-1`` targets (excluded from both loss and grads),
vocab sizes that are not a multiple of the block (lane-aligned padding
with in-kernel column masking — V=50304 pads to the block grid, padded
columns contribute exp(-inf)=0), and row counts that are not a multiple
of ``block_n`` (zero-padded rows with ``-1`` targets).

Blocking is owned by :func:`ce_config` — the single home for CE env
knobs.  Called directly, the ops fall back to the dense XLA
formulation for shapes :func:`supports` declines; a Mosaic compile
failure is a failure
(``tests/test_tpu_aot.py`` compiles the kernels for a v5e ahead of any
chip run).

Reference role: the loss path of the reference's torch trainers
(``F.cross_entropy`` in ``train/torch/train_loop_utils.py``); the
streamed-logits design is TPU-first.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one home for the Pallas infrastructure: the interpret-mode policy,
# lane-padded row-stats convention, block resolution and env-knob
# readers are shared with the attention / fused-norm kernels via the
# substrate
from ray_tpu.ops.substrate import (NEG_INF as _NEG_INF, STATS_LANES,
                                   CompilerParams as _CompilerParams,
                                   Support, env_int,
                                   resolve_blocks,
                                   stats_in as _stats_in, supported,
                                   unsupported,
                                   use_interpret as _use_interpret)


@dataclasses.dataclass(frozen=True)
class CEConfig:
    """Flash-CE blocking knobs, resolved once from the environment.

    The single home for CE env flags (consolidation precedent: r06's
    ``attention_config``).  Which loss head runs is no knob: it follows
    the recipe's ``ce_chunk`` (:func:`uses_flash_ce`).

    - ``RAY_TPU_CE_BN`` / ``RAY_TPU_CE_BV`` (default 1024/1024):
      forward row/vocab blocking.
    - ``RAY_TPU_CE_BWD_BN`` / ``RAY_TPU_CE_BWD_BV`` (default
      1024/512): backward blocking — the bwd tile also carries the
      [bn, d] f32 dx accumulator, so it wants a narrower vocab block.
    """
    block_n: int = 1024
    block_v: int = 1024
    bwd_block_n: int = 1024
    bwd_block_v: int = 512


_CONFIG: Optional[CEConfig] = None


def ce_config(refresh: bool = False) -> CEConfig:
    """The process-wide :class:`CEConfig` (env read once, cached).

    ``refresh=True`` re-reads the environment — for tests and A/B
    drivers that flip flags after import."""
    global _CONFIG
    if _CONFIG is None or refresh:
        _CONFIG = CEConfig(
            block_n=env_int("RAY_TPU_CE_BN", 1024),
            block_v=env_int("RAY_TPU_CE_BV", 1024),
            bwd_block_n=env_int("RAY_TPU_CE_BWD_BN", 1024),
            bwd_block_v=env_int("RAY_TPU_CE_BWD_BV", 512),
        )
    return _CONFIG


def supports(N: int, d: int, V: int) -> bool:
    """Shapes the kernel grid can handle (callers fall back otherwise).

    N and V are padded to the block grid by the wrappers, so the only
    hard constraints are on the model dimension: it is the contraction
    lane dimension of every tile matmul and the dx accumulator width,
    so it must be lane-aligned and VMEM-sized."""
    return d % 128 == 0 and 0 < d <= 2048 and N > 0 and V > 1


def uses_flash_ce(N: int, d: int, V: int, *, ce_chunk: int,
                  n_devices: int = 1,
                  mode: Optional[str] = None) -> Support:
    """Whether a loss head of this shape, recipe and mesh takes
    flash-CE, with the reason — the one gate the model's dispatch, the
    trainer and the step telemetry ask, so a summary can't claim a
    schedule the dispatch declined.

    ``ce_chunk`` is the recipe's (``GPTConfig.ce_chunk``): negative
    says the logits may be kept, and the saved-logits formulation is
    then one vocabulary matmul cheaper than this kernel's recompute
    (183.4 against 186.1 ms a step at the cells' shape); where they
    are recomputed anyway the kernel is 15.5 ms a step ahead of XLA's
    chunks (the module's header has the readings).
    ``n_devices`` is the mesh size the loss head will run under (a
    ``pallas_call`` has no SPMD rule).  ``mode`` pins the choice for
    tests and A/B drivers: ``"flash"`` takes the kernel whatever
    ``ce_chunk`` says, ``"xla"`` never does."""
    if mode not in (None, "flash", "xla"):
        raise ValueError(f"ce_mode={mode!r}: expected None, 'flash' or "
                         "'xla'")
    if mode == "xla":
        return unsupported("pinned to the XLA loss head (ce_mode='xla')")
    if n_devices > 1:
        return unsupported(f"sharded mesh (n_devices={n_devices}): a "
                           "pallas_call has no SPMD rule")
    if not supports(N, d, V):
        return unsupported(f"shape outside the kernel grid (N={N}, "
                           f"d={d}, V={V}: d must be a multiple of 128, "
                           "at most 2048)")
    if mode is None and ce_chunk < 0:
        return unsupported(f"the recipe keeps its logits "
                           f"(ce_chunk={ce_chunk}): three vocabulary "
                           "matmuls against flash-CE's four")
    return supported("flash-CE: the logits are recomputed tile by tile")


# Mosaic's default scoped-VMEM budget is 16 MiB; at the default blocks
# the backward's double-buffered [bn, d] / [d, bv] tiles, the f32 dx
# accumulator and the [bn, bv] f32 temporaries need 16.5 MiB (18 MiB
# with the norm prologue) at d=768 — "Ran out of memory in memory space
# vmem ... limit 16.00M" when compiled for v5e.  The widest shape
# `supports` admits (d = 2048) needs 36 MiB; 48 MiB covers it inside a
# v5e's 128 MiB of VMEM.
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024

# block resolution and the lane-broadcast stats layout are the
# substrate's resolve_blocks/stats_in (this module wrote the originals;
# the alias keeps the call sites unchanged)
_blocks = resolve_blocks


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, h_ref, tgt_ref, *rest, block_n: int, block_v: int,
                num_v: int, v_real: Optional[int],
                norm_eps: Optional[float] = None):
    """``norm_eps`` (static): the final-norm prologue — ``x_ref`` holds
    the *raw* residual stream and the kernel computes
    ``y = rmsnorm(x) * scale`` once per row block (at ``j == 0``, into
    VMEM scratch every vocab tile then reuses), emitting the ``rstd``
    statistics as an extra ``[N]``-sized residual.  The norm work rides
    the matmul sweep instead of running as its own XLA fusion."""
    if norm_eps is not None:
        s_ref, lse_ref, true_ref, rstd_ref, m_sc, l_sc, t_sc, y_sc = rest
    else:
        lse_ref, true_ref, m_sc, l_sc, t_sc = rest
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        t_sc[:] = jnp.zeros_like(t_sc)
        if norm_eps is not None:
            r32 = x_ref[...].astype(jnp.float32)
            rstd = jax.lax.rsqrt(
                jnp.mean(r32 * r32, -1, keepdims=True) + norm_eps)
            y_sc[...] = (r32 * rstd * s_ref[...].astype(jnp.float32)
                         ).astype(y_sc.dtype)
            rstd_ref[0] = jnp.broadcast_to(rstd, rstd_ref.shape[1:])

    x = x_ref[...] if norm_eps is None else y_sc[...]
    s = jax.lax.dot_general(
        x, h_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # [bn, bv]
    col = (j * block_v
           + jax.lax.broadcasted_iota(jnp.int32, (block_n, block_v), 1))
    if v_real is not None:
        s = jnp.where(col < v_real, s, _NEG_INF)
    # true-logit gather: exactly one column matches the row's target
    # (none for masked -1 targets), so a select+rowsum is the gather
    tgt = tgt_ref[0][:, 0:1]                             # [bn, 1] int32
    t_sc[:] += jnp.sum(jnp.where(col == tgt, s, 0.0), 1, keepdims=True)
    m_prev = m_sc[:]                                     # [bn, 128]
    m_new = jnp.maximum(m_prev, jnp.max(s, 1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :1])
    l_sc[:] = l_sc[:] * alpha + jnp.sum(p, 1, keepdims=True)
    m_sc[:] = m_new

    @pl.when(j == num_v - 1)
    def _finalize():
        l = jnp.maximum(l_sc[:, :1], 1e-30)
        lse = m_sc[:, :1] + jnp.log(l)                   # [bn, 1]
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])
        true_ref[0] = jnp.broadcast_to(t_sc[:, :1], true_ref.shape[1:])


def _fwd_pallas(x, head, targets, *, block_n: int, block_v: int,
                norm=None):
    """x [N, d], head [d, V], targets [N] int32 (-1 = masked) ->
    (lse [N] f32, true_logit [N] f32) with no [N, V] materialization.

    ``norm``: optional ``(scale [d], eps)`` — the final-norm prologue;
    ``x`` is then the raw residual stream and the return gains
    ``rstd [N] f32``."""
    N, d = x.shape
    V = head.shape[1]
    bn, bv, Np, Vp = _blocks(N, V, block_n, block_v)
    num_n, num_v = Np // bn, Vp // bv
    if Np != N:
        x = jnp.pad(x, ((0, Np - N), (0, 0)))
        targets = jnp.pad(targets, (0, Np - N), constant_values=-1)
    if Vp != V:
        head = jnp.pad(head, ((0, 0), (0, Vp - V)))
    tstats = _stats_in(targets.astype(jnp.int32), num_n, bn)

    stats_spec = pl.BlockSpec((1, bn, STATS_LANES), lambda i, j: (i, 0, 0))
    stats_shape = jax.ShapeDtypeStruct((num_n, bn, STATS_LANES),
                                       jnp.float32)
    norm_args, norm_in, norm_out, norm_shape, norm_sc = \
        (), [], [], [], []
    if norm is not None:
        scale, eps = norm
        norm_args = (scale[None, :],)
        norm_in = [pl.BlockSpec((1, d), lambda i, j: (0, 0))]
        norm_out = [stats_spec]
        norm_shape = [stats_shape]
        norm_sc = [pltpu.VMEM((bn, d), x.dtype)]
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, block_n=bn, block_v=bv,
                          num_v=num_v,
                          v_real=V if Vp != V else None,
                          norm_eps=norm[1] if norm else None),
        grid=(num_n, num_v),
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bv), lambda i, j: (0, j)),
            stats_spec,
            *norm_in,
        ],
        out_specs=[stats_spec, stats_spec, *norm_out],
        out_shape=[stats_shape, stats_shape, *norm_shape],
        scratch_shapes=[
            pltpu.VMEM((bn, 128), jnp.float32),
            pltpu.VMEM((bn, 128), jnp.float32),
            pltpu.VMEM((bn, 128), jnp.float32),
            *norm_sc,
        ],
        interpret=_use_interpret(),
    )(x, head, tstats, *norm_args)
    flat = tuple(o[:, :, 0].reshape(Np)[:N] for o in out)
    return flat          # (lse, true[, rstd])


# ---------------------------------------------------------------------------
# backward kernel
# ---------------------------------------------------------------------------

def _bwd_kernel(x_ref, h_ref, tgt_ref, lse_ref, srow_ref,
                *rest, block_n: int, block_v: int,
                num_v: int, v_real: Optional[int],
                norm_eps: Optional[float] = None):
    """``norm_eps`` (static): the final-norm prologue's backward —
    ``x_ref`` holds the raw residual stream, the normed ``y`` is
    recomputed into VMEM scratch from the saved ``rstd`` (both matmuls
    contract against it), and at the end of the vocab sweep the
    accumulated ``dy`` takes the norm backward *in-kernel*: ``dx``
    becomes the residual-stream gradient and the norm-scale gradient
    is emitted as a per-row-block ``[d]`` partial (summed in one XLA
    pass by the wrapper) — no standalone ``[d]``-output reduction
    dispatch survives."""
    if norm_eps is not None:
        (s_ref, rstd_ref, dx_ref, dhp_ref, dsp_ref,
         dx_sc, y_sc) = rest
    else:
        dx_ref, dhp_ref, dx_sc = rest
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dx_sc[:] = jnp.zeros_like(dx_sc)
        if norm_eps is not None:
            r32 = x_ref[...].astype(jnp.float32)
            rstd = rstd_ref[0][:, 0:1]
            y_sc[...] = (r32 * rstd * s_ref[...].astype(jnp.float32)
                         ).astype(y_sc.dtype)

    x = x_ref[...] if norm_eps is None else y_sc[...]    # [bn, d]
    h = h_ref[...]                                       # [d, bv]
    s = jax.lax.dot_general(
        x, h, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # recompute tile
    col = (j * block_v
           + jax.lax.broadcasted_iota(jnp.int32, (block_n, block_v), 1))
    if v_real is not None:
        s = jnp.where(col < v_real, s, _NEG_INF)
    p = jnp.exp(s - lse_ref[0][:, 0:1])   # padded cols: exp(-inf) = 0
    onehot = jnp.where(col == tgt_ref[0][:, 0:1], 1.0, 0.0)
    # (p - onehot) scaled by the incoming cotangent x row mask, cast to
    # the input dtype, fused straight into BOTH grad matmuls — the tile
    # never leaves VMEM
    dl = ((p - onehot) * srow_ref[0][:, 0:1]).astype(h.dtype)
    dx_sc[:] += jax.lax.dot_general(
        dl, h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # [bn, d]
    dhp_ref[0] = jax.lax.dot_general(
        x, dl, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dhp_ref.dtype)

    @pl.when(j == num_v - 1)
    def _finalize():
        if norm_eps is None:
            dx_ref[...] = dx_sc[:].astype(dx_ref.dtype)
        else:
            dy = dx_sc[:]                                # [bn, d] f32
            r32 = x_ref[...].astype(jnp.float32)
            rstd = rstd_ref[0][:, 0:1]
            xhat = r32 * rstd
            dxhat = dy * s_ref[...].astype(jnp.float32)
            m = jnp.mean(dxhat * xhat, -1, keepdims=True)
            dx_ref[...] = (rstd * (dxhat - xhat * m)).astype(dx_ref.dtype)
            dsp_ref[0] = jnp.sum(dy * xhat, 0, keepdims=True)


def _bwd_pallas(x, head, targets, lse, gs, *, block_n: int,
                block_v: int, norm=None):
    """Strip-mined backward: (residuals, d(sum_nll)) -> (dx, dhead).

    dx accumulates across the vocab sweep in VMEM scratch; dhead is
    emitted as ``[num_n, d, V]`` per-row-block partials (each written
    exactly once, at matmul rate) and summed in one XLA pass — the
    write-once/read-once analogue of attention's dk/dv scratch, sized
    for a head too large to ride along in VMEM.

    ``norm``: optional ``(scale [d], eps, rstd [N] f32)`` — the
    final-norm prologue's backward; the return gains ``dscale [d]``
    (from per-row-block partials, same one-XLA-pass sum as dhead) and
    ``dx`` is the *residual-stream* gradient."""
    N, d = x.shape
    V = head.shape[1]
    bn, bv, Np, Vp = _blocks(N, V, block_n, block_v)
    num_n, num_v = Np // bn, Vp // bv
    rstd = norm[2] if norm is not None else None
    if Np != N:
        x = jnp.pad(x, ((0, Np - N), (0, 0)))
        targets = jnp.pad(targets, (0, Np - N), constant_values=-1)
        lse = jnp.pad(lse, (0, Np - N))
        if rstd is not None:
            rstd = jnp.pad(rstd, (0, Np - N))
    if Vp != V:
        head = jnp.pad(head, ((0, 0), (0, Vp - V)))
    targets = targets.astype(jnp.int32)
    # per-row scale: the sum_nll cotangent where the target is live
    srow = jnp.where(targets >= 0, gs.astype(jnp.float32), 0.0)
    tstats = _stats_in(targets, num_n, bn)
    lstats = _stats_in(lse.astype(jnp.float32), num_n, bn)
    sstats = _stats_in(srow, num_n, bn)

    stats_spec = pl.BlockSpec((1, bn, STATS_LANES), lambda i, j: (i, 0, 0))
    norm_args, norm_in, norm_out, norm_shape, norm_sc = \
        (), [], [], [], []
    if norm is not None:
        scale, eps = norm[0], norm[1]
        norm_args = (scale[None, :], _stats_in(rstd, num_n, bn))
        norm_in = [pl.BlockSpec((1, d), lambda i, j: (0, 0)),
                   stats_spec]
        # [num_n, 1, d] partials: the (1, d) block is then the full
        # extent of the last two dims (the TPU tiling rule refuses it
        # over [num_n, d])
        norm_out = [pl.BlockSpec((1, 1, d), lambda i, j: (i, 0, 0))]
        norm_shape = [jax.ShapeDtypeStruct((num_n, 1, d), jnp.float32)]
        norm_sc = [pltpu.VMEM((bn, d), x.dtype)]
    out = pl.pallas_call(
        functools.partial(_bwd_kernel, block_n=bn, block_v=bv,
                          num_v=num_v,
                          v_real=V if Vp != V else None,
                          norm_eps=norm[1] if norm else None),
        grid=(num_n, num_v),
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bv), lambda i, j: (0, j)),
            stats_spec,
            stats_spec,
            stats_spec,
            *norm_in,
        ],
        out_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((1, d, bv), lambda i, j: (i, 0, j)),
            *norm_out,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Np, d), x.dtype),
            jax.ShapeDtypeStruct((num_n, d, Vp), head.dtype),
            *norm_shape,
        ],
        scratch_shapes=[pltpu.VMEM((bn, d), jnp.float32), *norm_sc],
        interpret=_use_interpret(),
    )(x, head, tstats, lstats, sstats, *norm_args)
    dx, dhp = out[0], out[1]
    dhead = jnp.sum(dhp.astype(jnp.float32), axis=0)[:, :V]
    if norm is None:
        return dx[:N], dhead.astype(head.dtype)
    # per-row-block dscale partials summed in ONE XLA pass — this sum
    # replaces the standalone [d]-output reduction dispatch
    dscale = jnp.sum(out[2], axis=(0, 1))
    return dx[:N], dhead.astype(head.dtype), dscale


# ---------------------------------------------------------------------------
# custom VJP + public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_ce(x, head, targets, block_n, block_v, bwd_block_n,
              bwd_block_v):
    out, _ = _flash_ce_fwd(x, head, targets, block_n, block_v,
                           bwd_block_n, bwd_block_v)
    return out


def _flash_ce_fwd(x, head, targets, block_n, block_v, bwd_block_n,
                  bwd_block_v):
    lse, true = _fwd_pallas(x, head, targets, block_n=block_n,
                            block_v=block_v)
    mask = (targets >= 0).astype(jnp.float32)
    out = (jnp.sum((lse - true) * mask), jnp.sum(mask))
    # residuals are [N]-sized (plus the inputs the grads contract
    # against) — nothing vocab-shaped survives the forward
    return out, (x, head, targets, lse)


def _flash_ce_bwd(block_n, block_v, bwd_block_n, bwd_block_v, res, g):
    x, head, targets, lse = res
    gs, _ = g                                  # d/d(sum_nll); n is count
    dx, dhead = _bwd_pallas(x, head, targets, lse, jnp.asarray(gs),
                            block_n=bwd_block_n, block_v=bwd_block_v)
    return dx, dhead, None


_flash_ce.defvjp(_flash_ce_fwd, _flash_ce_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_ce_norm(x, head, targets, scale, eps, block_n, block_v,
                   bwd_block_n, bwd_block_v):
    out, _ = _flash_ce_norm_fwd(x, head, targets, scale, eps, block_n,
                                block_v, bwd_block_n, bwd_block_v)
    return out


def _flash_ce_norm_fwd(x, head, targets, scale, eps, block_n, block_v,
                       bwd_block_n, bwd_block_v):
    lse, true, rstd = _fwd_pallas(x, head, targets, block_n=block_n,
                                  block_v=block_v, norm=(scale, eps))
    mask = (targets >= 0).astype(jnp.float32)
    out = (jnp.sum((lse - true) * mask), jnp.sum(mask))
    # residuals stay [N]-sized: the raw residual stream, the stats
    # (lse + rstd) and the operands the grads contract against — the
    # normed hidden is recomputed per tile, never saved
    return out, (x, head, targets, scale, lse, rstd)


def _flash_ce_norm_bwd(eps, block_n, block_v, bwd_block_n, bwd_block_v,
                       res, g):
    x, head, targets, scale, lse, rstd = res
    gs, _ = g                                  # d/d(sum_nll); n is count
    dx, dhead, dscale = _bwd_pallas(
        x, head, targets, lse, jnp.asarray(gs),
        block_n=bwd_block_n, block_v=bwd_block_v,
        norm=(scale, eps, rstd))
    return dx, dhead, None, dscale.astype(scale.dtype)


_flash_ce_norm.defvjp(_flash_ce_norm_fwd, _flash_ce_norm_bwd)


def uses_flash_ce_norm(N: int, d: int, V: int, *, ce_chunk: int,
                       n_devices: int = 1,
                       mode: Optional[str] = None,
                       norm: str = "rmsnorm",
                       has_bias: bool = False,
                       enabled: Optional[bool] = None) -> Support:
    """Dispatch gate (with reason) for the final-norm-fused CE path.

    The single source of the decision ``models.gpt.loss_fn`` makes
    before skipping the XLA final norm — also the reporting mirror.
    Requires the flash-CE path itself (:func:`uses_flash_ce`, whose
    reason it passes on) plus a norm the prologue can fuse;
    ``enabled=False`` pins it off (the tests' pin; ``None`` is on)."""
    if enabled is not None and not enabled:
        return unsupported("disabled (enabled=False)")
    if norm != "rmsnorm":
        return unsupported(f"norm={norm!r}: only rmsnorm fuses")
    if has_bias:
        return unsupported("bias norms (GPT-2 exact-architecture mode) "
                           "stay on the XLA path")
    flash = uses_flash_ce(N, d, V, ce_chunk=ce_chunk,
                          n_devices=n_devices, mode=mode)
    if not flash:
        return unsupported(f"flash-CE path declined: {flash.reason}")
    return supported("flash-CE with fused final-norm prologue")


def flash_ce_norm_sum(x, head, targets, norm_scale, *,
                      eps: float = 1e-6,
                      block_n: Optional[int] = None,
                      block_v: Optional[int] = None,
                      bwd_block_n: Optional[int] = None,
                      bwd_block_v: Optional[int] = None):
    """Final-norm-fused streamed-logits CE: ``(sum_nll, n_valid)``.

    x [N, d] is the *raw* residual stream (the model's final hidden,
    before its last norm); the kernel computes
    ``rmsnorm(x) * norm_scale`` in the vocab matmul's prologue — the
    normed tensor is never materialized in HBM, the norm statistics
    ride as ``[N]``-sized residuals, and the norm-scale gradient comes
    back through per-row-block partials.  Differentiable in
    (x, head, norm_scale).  Shapes :func:`supports` declines fall back
    to the unfused XLA formulation (norm then dense CE, same
    numerics)."""
    cfg = ce_config()
    N, d = x.shape
    V = head.shape[1]
    if not supports(N, d, V):
        with jax.named_scope("ce/norm_xla"):
            x32 = x.astype(jnp.float32)
            x32 = x32 * jax.lax.rsqrt(
                jnp.mean(x32 * x32, -1, keepdims=True) + eps)
            y = (x32 * norm_scale.astype(jnp.float32)).astype(x.dtype)
            return _xla_ce_sum(y, head, targets)
    with jax.named_scope("ce/flash_norm"):
        return _flash_ce_norm(x, head.astype(x.dtype), targets,
                              norm_scale, eps,
                              block_n or cfg.block_n,
                              block_v or cfg.block_v,
                              bwd_block_n or cfg.bwd_block_n,
                              bwd_block_v or cfg.bwd_block_v)


def _xla_ce_sum(x, head, targets):
    """Dense XLA reference (fallback for unsupported shapes; also the
    parity oracle in tests/test_ops.py)."""
    logits = jax.lax.dot_general(
        x, head, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(
        logits, jnp.maximum(targets, 0)[:, None], axis=-1)[:, 0]
    mask = (targets >= 0).astype(jnp.float32)
    return jnp.sum((lse - true) * mask), jnp.sum(mask)


def flash_ce_sum(x, head, targets, *, block_n: Optional[int] = None,
                 block_v: Optional[int] = None,
                 bwd_block_n: Optional[int] = None,
                 bwd_block_v: Optional[int] = None):
    """Streamed-logits cross-entropy: ``(sum_nll, n_valid)``.

    x [N, d] (bf16 ok), head [d, V], targets [N] int32 (-1 = masked).
    Differentiable in (x, head); the [N, V] logits are never
    materialized in either pass.  Blocks default to :func:`ce_config`;
    shapes :func:`supports` declines fall back to the dense XLA
    formulation (same numerics, no streaming)."""
    cfg = ce_config()
    N, d = x.shape
    V = head.shape[1]
    if not supports(N, d, V):
        with jax.named_scope("ce/xla"):
            return _xla_ce_sum(x, head, targets)
    with jax.named_scope("ce/flash"):
        return _flash_ce(x, head, targets,
                         block_n or cfg.block_n,
                         block_v or cfg.block_v,
                         bwd_block_n or cfg.bwd_block_n,
                         bwd_block_v or cfg.bwd_block_v)
