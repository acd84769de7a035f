"""Out-proj epilogue: out-proj matmul + residual add + RMSNorm, one
computation with two implementations (the attention family's epilogue
member).

    r = resid + attn @ wo          # the residual stream, written once
    y = rmsnorm(r) * scale         # the next block's normed input

The bet (r13): once attention is a custom call its neighbouring norm
is orphaned, and XLA schedules the out-proj's residual/norm work as
standalone HBM-rate fusions on either side of the boundary; a Pallas
kernel that keeps the whole block VMEM-resident per ``block_n`` rows
(MXU matmul with f32 accumulation, the add in the storage dtype, the
statistics in f32) would take them off the step.

Its outcome on the chip (PERF.md, PRs 49 and 53): the *forward* kernel
holds at a prefill's 32-1024 rows (0.0020 s of a traced chat piece
against XLA's 0.0041 + 0.0010).  The *backward* lost at a train step's
24,576 rows: a ``[768, 768]`` weight-grad partial per row block, 96 of
them summed afterwards, and a 2-D reshape of the attention kernel's
output on each side cost the one-chip GPT-2 step 10.9 ms of 182 against
the compiler's own einsum + add + norm and their gradients.

So the call decides by whether it is differentiated.  ``_mrn`` is a
``jax.custom_vjp`` whose primal is the Pallas forward kernel and whose
rule is XLA's: JAX runs the primal only where no gradient is taken
(the engine's prefill, a forward-only evaluation) and the rule
wherever one is (under ``jax.checkpoint`` too), where the forward is
:func:`xla_matmul_residual_norm` and the backward is ``jax.vjp`` of
it, with the compiler's own residuals.  A differentiated step so
compiles to what ``models.gpt.layer_apply``'s declined branch
compiles to.

Dispatch is a reasoned gate (:func:`out_proj_norm_plan`): rmsnorm
only, no biases, single-device mesh (``pallas_call`` has no SPMD
rule), lane-aligned ``K``/``d``, and a real sequence (the S=1 decode
step keeps the XLA epilogue: per-token kernel launches lose there).
Built directly on ``ops/substrate.py``; numerics tests against the XLA
formulation live in ``tests/test_ops.py``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ray_tpu.ops.substrate import (STATS_LANES, CompilerParams, Support,
                                   resolve_blocks, supported, unsupported,
                                   use_interpret)

# the forward's row block (rows of attn/resid resident per grid step)
BLOCK_N = 256


def supports(N: int, K: int, d: int) -> Support:
    """Shapes the matmul+norm grid can tile (XLA epilogue otherwise).

    ``K`` (contraction) and ``d`` (output/norm) are both lane
    dimensions of VMEM-resident tiles, so they must be lane-aligned
    and small enough that the weight block fits VMEM alongside the
    row blocks."""
    if N <= 0:
        return unsupported(f"N={N} has no rows")
    if K % 128:
        return unsupported(f"K={K} not lane-aligned (128)")
    if d % 128:
        return unsupported(f"d={d} not lane-aligned (128)")
    if K > 1536 or d > 1536:
        return unsupported(f"K={K}, d={d}: weight block exceeds the "
                           "VMEM budget (cap 1536)")
    return supported("pallas fused out-proj epilogue")


def out_proj_norm_plan(N: int, K: int, d: int, *, norm: str = "rmsnorm",
                       has_bias: bool = False, n_devices: int = 1,
                       seq: Optional[int] = None,
                       enabled: Optional[bool] = None) -> Support:
    """The full out-proj epilogue dispatch gate, with reasons.

    The single source of the fused-vs-XLA decision — shared by
    ``models.gpt.layer_apply`` and whatever reports the schedule, so a
    summary can't claim a fusion the dispatch declined.
    ``enabled=False`` pins the XLA formulation (the tests' A/B pin;
    ``None`` is on: no environment variable decides)."""
    if enabled is not None and not enabled:
        return unsupported("disabled (enabled=False)")
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
# kernel
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


def _pad_rows(x, Np: int):
    return x if x.shape[0] == Np else \
        jnp.pad(x, ((0, Np - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))


def _run_fwd(a, w, resid, scale, eps, block_n):
    """a [N, K], w [K, d], resid [N, d] -> (r, y), each [N, d]."""
    N, K = a.shape
    d = w.shape[1]
    # the substrate's resolve_blocks row half (the 16-row alignment is
    # the tree-wide bf16-safe sublane tile)
    bn, _, Np, _ = resolve_blocks(N, 1, block_n, 1, lane_align=1)
    num_n = Np // bn
    a, resid = _pad_rows(a, Np), _pad_rows(resid, Np)
    # the third output is the rows' rstd, which the Pallas backward
    # read until PR 53; nothing reads it now (PERF.md, open questions)
    rout, y, _ = pl.pallas_call(
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
    return rout[:N], y[:N]


# ---------------------------------------------------------------------------
# the two implementations + public API
# ---------------------------------------------------------------------------

def xla_matmul_residual_norm(attn, wo, resid, scale, *, eps: float = 1e-6):
    """The XLA formulation: what a differentiated call runs, and the
    parity oracle in tests/test_ops.py.  Step for step what
    ``models.gpt.layer_apply``'s declined branch writes (its einsum on
    the 4-D attention output, its add in the storage dtype, ``_norm``'s
    rmsnorm with f32 statistics); tests/test_models.py holds the two to
    one lowering."""
    r = resid + jnp.einsum("bshk,hkd->bsd", attn, wo)
    r32 = r.astype(jnp.float32)
    r32 = r32 * jax.lax.rsqrt(jnp.mean(r32 * r32, -1, keepdims=True) + eps)
    r32 = r32 * scale.astype(jnp.float32)
    return r, r32.astype(r.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _mrn(attn, wo, resid, scale, eps, block_n):
    B, S, H, hd = attn.shape
    d = wo.shape[-1]
    with jax.named_scope("norm/fused_epilogue"):
        r, y = _run_fwd(attn.reshape(B * S, H * hd), wo.reshape(H * hd, d),
                        resid.reshape(B * S, d), scale, eps, block_n)
    return r.reshape(B, S, d), y.reshape(B, S, d)


def _mrn_fwd(attn, wo, resid, scale, eps, block_n):
    # the residuals are whatever XLA's own differentiation keeps
    return jax.vjp(functools.partial(xla_matmul_residual_norm, eps=eps),
                   attn, wo, resid, scale)


def _mrn_bwd(eps, block_n, vjp_fn, cts):
    return vjp_fn(cts)


_mrn.defvjp(_mrn_fwd, _mrn_bwd)


def matmul_residual_norm(attn, wo, resid, scale, *, eps: float = 1e-6,
                         block_n: int = BLOCK_N
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(resid + attn @ wo, rmsnorm(resid + attn @ wo) * scale)``.

    attn [B, S, H, hd] (bf16 ok), wo [H, hd, d], resid [B, S, d],
    scale [d].  Returns ``(r, y)``: the updated residual stream and the
    normed/scaled hidden.  A call nobody differentiates is one Pallas
    kernel over ``block_n`` rows of ``B * S``; a differentiated call is
    :func:`xla_matmul_residual_norm` and XLA's gradient of it, in all
    four operands (see module docstring).  Shapes :func:`supports`
    declines raise: dispatch is the caller's job
    (:func:`out_proj_norm_plan`)."""
    B, S, H, hd = attn.shape
    ok = supports(B * S, H * hd, wo.shape[-1])
    if not ok:
        raise ValueError(f"matmul_residual_norm cannot tile: {ok.reason}")
    return _mrn(attn, wo, resid, scale, eps, block_n)
