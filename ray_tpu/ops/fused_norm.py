"""Fused norm epilogue: out-proj matmul + residual add + RMSNorm in
one Pallas kernel (the attention family's epilogue member).

PERF.md's remaining-headroom analysis pins ~18 ms/step of the GPT-2
single-chip gap on work XLA cannot fuse across custom-call boundaries:
the attention out-proj's residual/norm fusions (~13 ms) and the
``[768]``-output reductions that compute the norm-scale gradients
(~10.7 ms of the backward tail).  Once attention itself is a custom
call, the neighbouring norm is orphaned — XLA schedules it as
standalone HBM-rate fusions on either side of the kernel boundary.

This kernel moves the whole residual/norm block *inside* the boundary.
Forward, per ``block_n`` row block (one grid sweep, everything
VMEM-resident):

    p    = attn_blk @ wo            # MXU, f32 accumulation
    r    = resid_blk + p            # the residual stream, written once
    rstd = rsqrt(mean(r^2) + eps)   # norm statistics in the epilogue
    y    = r * rstd * scale         # the next block's normed input

emitting ``(r, y)`` plus an ``[N]``-sized ``rstd`` residual — the norm
statistics are never re-derived from a re-materialized tensor.  The
custom-vjp backward recomputes ``xhat = r * rstd`` from the saved
stats and fuses the norm backward into the matmul grads:

    dr       = rstd * (dy*scale - xhat * mean(dy*scale * xhat)) + dr_in
    da_blk   = dr @ wo^T                      # back into attention
    dwo[i]   = attn_blk^T @ dr                # per-row-block partial
    dscale[i]= sum_rows(dy * xhat)            # per-row-block partial

``dwo``/``dscale`` partials are emitted per row block and summed in
one XLA pass — the ``flash_ce`` dhead idiom — which is what deletes
the standalone ``[768]``-reduction dispatches from the step.

Dispatch is a reasoned gate (:func:`out_proj_norm_plan`): rmsnorm
only, no biases, single-device mesh (``pallas_call`` has no SPMD
rule), lane-aligned ``K``/``d``, and a real sequence (the S=1 decode
step keeps the XLA epilogue — per-token kernel launches lose there).
``RAY_TPU_FUSE_NORM=0`` reverts everything.  Built directly on
``ops/substrate.py``; numerics tests vs the unfused formulation live
in ``tests/test_ops.py``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ray_tpu.ops.substrate import (STATS_LANES, CompilerParams, Support,
                                   env_flag, env_int, resolve_blocks,
                                   stats_in, supported, unsupported,
                                   use_interpret)


@dataclasses.dataclass(frozen=True)
class FuseNormConfig:
    """Fused-norm-epilogue knobs, resolved once from the environment.

    - ``RAY_TPU_FUSE_NORM`` (default on; ``0`` disables): fold the
      attention out-proj residual/norm and the final-norm CE prologue
      into their neighbouring Pallas kernels wherever the dispatch
      gates pass.
    - ``RAY_TPU_FUSE_NORM_BN`` (default 256): row blocking — the
      backward tile carries ``[bn, K]`` + ``[bn, d]`` f32 work plus
      the ``[K, d]`` weight-grad partial, so it wants a narrower row
      block than the attention kernels' 512/1024.
    """
    enabled: bool = True
    block_n: int = 256


_CONFIG: Optional[FuseNormConfig] = None


def fuse_config(refresh: bool = False) -> FuseNormConfig:
    """The process-wide :class:`FuseNormConfig` (env read once, cached).

    ``refresh=True`` re-reads the environment — for tests and A/B
    drivers that flip flags after import."""
    global _CONFIG
    if _CONFIG is None or refresh:
        _CONFIG = FuseNormConfig(
            enabled=env_flag("RAY_TPU_FUSE_NORM"),
            block_n=env_int("RAY_TPU_FUSE_NORM_BN", 256),
        )
    return _CONFIG


def supports(N: int, K: int, d: int) -> Support:
    """Shapes the matmul+norm grid can tile (XLA epilogue otherwise).

    ``K`` (contraction) and ``d`` (output/norm) are both lane
    dimensions of VMEM-resident tiles, so they must be lane-aligned
    and small enough that the weight block plus its grad partial fit
    VMEM alongside the row blocks."""
    if N <= 0:
        return unsupported(f"N={N} has no rows")
    if K % 128:
        return unsupported(f"K={K} not lane-aligned (128)")
    if d % 128:
        return unsupported(f"d={d} not lane-aligned (128)")
    if K > 1536 or d > 1536:
        return unsupported(f"K={K}, d={d}: weight block + grad partial "
                           "exceed the VMEM budget (cap 1536)")
    return supported("pallas fused out-proj epilogue")


def out_proj_norm_plan(N: int, K: int, d: int, *, norm: str = "rmsnorm",
                       has_bias: bool = False, n_devices: int = 1,
                       seq: Optional[int] = None,
                       enabled: Optional[bool] = None) -> Support:
    """The full out-proj epilogue dispatch gate, with reasons.

    The single source of the fused-vs-XLA decision — shared by
    ``models.gpt.layer_apply`` and whatever reports the schedule, so a
    summary can't claim a fusion the dispatch declined.
    ``enabled`` pins the knob for A/B drivers (default:
    :func:`fuse_config`)."""
    if enabled is None:
        enabled = fuse_config().enabled
    if not enabled:
        return unsupported("disabled (RAY_TPU_FUSE_NORM=0)")
    if norm != "rmsnorm":
        return unsupported(f"norm={norm!r}: only rmsnorm fuses")
    if has_bias:
        return unsupported("bias projections/norms (GPT-2 exact-"
                           "architecture mode) stay on the XLA path")
    if n_devices > 1:
        return unsupported(f"mesh size {n_devices}: pallas_call has "
                           "no SPMD rule")
    if seq is not None and seq <= 1:
        return unsupported("decode step (S=1): per-token kernel "
                           "launches lose to the XLA epilogue")
    return supports(N, K, d)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(a_ref, w_ref, r_ref, s_ref, rout_ref, y_ref, rstd_ref,
                *, eps: float):
    p = jax.lax.dot_general(
        a_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # [bn, d]
    # the residual add runs in the storage dtype (matching the unfused
    # bf16 einsum + add), the norm statistics in f32 (matching _norm)
    r = r_ref[...] + p.astype(r_ref.dtype)
    rout_ref[...] = r
    r32 = r.astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(r32 * r32, -1, keepdims=True) + eps)
    y_ref[...] = (r32 * rstd * s_ref[...].astype(jnp.float32)
                  ).astype(y_ref.dtype)
    rstd_ref[0] = jnp.broadcast_to(rstd, rstd_ref.shape[1:])


def _bwd_kernel(a_ref, w_ref, rout_ref, s_ref, rstd_ref, drout_ref,
                dy_ref, da_ref, dresid_ref, dwp_ref, dsp_ref):
    # (no eps here: the saved rstd already bakes it in — xhat is
    # reconstructed as rout * rstd, never re-derived from statistics)
    r32 = rout_ref[...].astype(jnp.float32)              # [bn, d]
    rstd = rstd_ref[0][:, 0:1]                           # [bn, 1]
    xhat = r32 * rstd
    dy = dy_ref[...].astype(jnp.float32)
    dxhat = dy * s_ref[...].astype(jnp.float32)
    m = jnp.mean(dxhat * xhat, -1, keepdims=True)
    # total cotangent into the residual stream: the norm backward plus
    # whatever flowed in from downstream consumers of r
    dr32 = rstd * (dxhat - xhat * m) + drout_ref[...].astype(jnp.float32)
    dsp_ref[0] = jnp.sum(dy * xhat, 0, keepdims=True)    # [1, d] partial
    dresid_ref[...] = dr32.astype(dresid_ref.dtype)
    dp = dr32.astype(w_ref.dtype)
    da_ref[...] = jax.lax.dot_general(
        dp, w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(da_ref.dtype)
    dwp_ref[0] = jax.lax.dot_general(
        a_ref[...], dp, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dwp_ref.dtype)


# ---------------------------------------------------------------------------
# custom VJP + public API
# ---------------------------------------------------------------------------

def _pad_rows(x, Np: int):
    return x if x.shape[0] == Np else \
        jnp.pad(x, ((0, Np - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))


def _row_blocks(N: int, block_n: int):
    """(bn, Np, num_n) — the substrate's resolve_blocks row half (the
    16-row alignment is the tree-wide bf16-safe sublane tile)."""
    bn, _, Np, _ = resolve_blocks(N, 1, block_n, 1, lane_align=1)
    return bn, Np, Np // bn


def _run_fwd(a, w, resid, scale, eps, block_n):
    N, K = a.shape
    d = w.shape[1]
    bn, Np, num_n = _row_blocks(N, block_n)
    a, resid = _pad_rows(a, Np), _pad_rows(resid, Np)
    rout, y, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(num_n,),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel",)),
        in_specs=[
            pl.BlockSpec((bn, K), lambda i: (i, 0)),
            pl.BlockSpec((K, d), lambda i: (0, 0)),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, bn, STATS_LANES), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Np, d), resid.dtype),
            jax.ShapeDtypeStruct((Np, d), resid.dtype),
            jax.ShapeDtypeStruct((num_n, bn, STATS_LANES), jnp.float32),
        ],
        interpret=use_interpret(),
    )(a, w, resid, scale[None, :])
    return rout[:N], y[:N], rstd[:, :, 0].reshape(Np)[:N]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _mrn(a, w, resid, scale, eps, block_n):
    (rout, y), _ = _mrn_fwd(a, w, resid, scale, eps, block_n)
    return rout, y


def _mrn_fwd(a, w, resid, scale, eps, block_n):
    rout, y, rstd = _run_fwd(a, w, resid, scale, eps, block_n)
    # residuals are [N]-sized stats plus the inputs the grads contract
    # against — the residual stream is saved once (rout), never both
    # sides of the add
    return (rout, y), (a, w, rout, scale, rstd)


def _mrn_bwd(eps, block_n, res, cts):
    a, w, rout, scale, rstd = res
    drout, dy = cts
    N, K = a.shape
    d = w.shape[1]
    bn, Np, num_n = _row_blocks(N, block_n)
    a, rout = _pad_rows(a, Np), _pad_rows(rout, Np)
    drout, dy = _pad_rows(drout, Np), _pad_rows(dy, Np)
    rstd_b = stats_in(_pad_rows(rstd[:, None], Np)[:, 0], num_n, bn)
    da, dresid, dwp, dsp = pl.pallas_call(
        _bwd_kernel,
        grid=(num_n,),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel",)),
        in_specs=[
            pl.BlockSpec((bn, K), lambda i: (i, 0)),
            pl.BlockSpec((K, d), lambda i: (0, 0)),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, bn, STATS_LANES), lambda i: (i, 0, 0)),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, K), lambda i: (i, 0)),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, K, d), lambda i: (i, 0, 0)),
            # [num_n, 1, d]: a (1, d) block over [num_n, d] breaks the
            # TPU tiling rule (sublane dim 1 neither 8-aligned nor the
            # array's); over a unit middle dim it is the full extent
            pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Np, K), a.dtype),
            jax.ShapeDtypeStruct((Np, d), rout.dtype),
            jax.ShapeDtypeStruct((num_n, K, d), w.dtype),
            jax.ShapeDtypeStruct((num_n, 1, d), jnp.float32),
        ],
        interpret=use_interpret(),
    )(a, w, rout, scale[None, :], rstd_b, drout, dy)
    # per-row-block partials summed in ONE XLA pass each — these sums
    # replace the standalone [d]-output reduction dispatches
    dw = jnp.sum(dwp.astype(jnp.float32), 0).astype(w.dtype)
    dscale = jnp.sum(dsp, (0, 1)).astype(scale.dtype)
    return da[:N], dw, dresid[:N], dscale


_mrn.defvjp(_mrn_fwd, _mrn_bwd)


def matmul_residual_norm(a, w, resid, scale, *, eps: float = 1e-6,
                         block_n: Optional[int] = None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(resid + a @ w, rmsnorm(resid + a @ w) * scale)`` — fused.

    a [N, K] (bf16 ok), w [K, d], resid [N, d], scale [d].  Returns
    ``(r, y)``: the updated residual stream and the normed/scaled
    hidden, with only ``[N]``-sized norm statistics saved between the
    passes.  Differentiable in all four operands; ``dscale``/``dw``
    come back through per-row-block partials (see module docstring).
    Shapes :func:`supports` declines raise — dispatch is the caller's
    job (:func:`out_proj_norm_plan`)."""
    ok = supports(a.shape[0], a.shape[1], w.shape[1])
    if not ok:
        raise ValueError(f"matmul_residual_norm cannot tile: {ok.reason}")
    if block_n is None:
        block_n = fuse_config().block_n
    with jax.named_scope("norm/fused_epilogue"):
        return _mrn(a, w, resid, scale, eps, block_n)


def xla_matmul_residual_norm(a, w, resid, scale, *, eps: float = 1e-6):
    """Unfused XLA reference (the fallback formulation and the parity
    oracle in tests/test_ops.py) — numerics mirror of
    ``models.gpt.layer_apply``'s einsum + add + ``_norm`` path."""
    r = resid + jax.lax.dot_general(
        a, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(resid.dtype)
    r32 = r.astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(r32 * r32, -1, keepdims=True) + eps)
    y = (r32 * rstd * scale.astype(jnp.float32)).astype(r.dtype)
    return r, y
