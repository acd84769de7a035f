"""Pallas TPU flash attention (forward + custom-VJP backward).

The compute heart of the flagship model path.  The reference delegates
fused attention to torch/CUDA inside the user's train fn; here it is a
first-class TPU kernel: blockwise online-softmax attention that never
materializes the [S, S] score matrix in HBM.  Backward recomputes scores
per block from the saved (o, logsumexp) residuals — activation memory is
O(B*S*H*D) instead of O(B*H*S^2).

Layouts: public API takes ``[B, S, H, D]`` (model layout, matches
``ray_tpu.parallel.ring_attention``); kernels run over ``[B, H, S, D]``.

Two-head lane packing (``pack2``): at head_dim 64 the score and
probability·V matmuls drive the 128-wide MXU at half rate (the
contraction or output dimension fills only 64 of 128 lanes).  When
head_dim == 64 and the head count is even, pairs of heads are
concatenated along the lane dimension — ``[B, H, S, 64]`` becomes
``[B, H/2, S, 128]``, a pure reshape in the model layout — and the
packed kernels keep the two heads' scores from mixing with a
block-diagonal K/V arrangement: every MXU op is then
``[block, 128] x [128, block]``-shaped (full-width contraction or
full-width output) and the op *count* halves.  Controlled by
``attention_config()`` (env ``RAY_TPU_ATTN_PACK2=0`` to disable); odd
head counts, head_dim 128 and shapes the packed grid cannot tile take
the single-head schedule.

The single-head schedule (``_fwd``, ``_bwd``) is the schedule of
grouped-query and window layers, and the first a benchmark cell times
(the routed 8k train cell: 32 query heads on 4 K/V heads of 128, three
window layers to one full).  Its grids carry the K/V head in their index
maps (query head ``h`` reads K/V head ``h // group``), the backward sums
``dk`` / ``dv`` over a K/V head's query heads in VMEM (the strip-mined
kernel holds the head's whole K and V once for all of them), and a
``window`` joins the causal edge in the schedule: a kv block wholly
behind a q block's window is skipped like one wholly above its
diagonal, neither computed nor fetched (its index is clamped to a live
block's), and every block that runs is masked whole, with both edges.

Causal structure: the packed kernels carry it in the schedule, not in
a mask over whole blocks (see "the causal structure, as a schedule"
below): blocks above the diagonal are skipped, blocks below it run with
no mask, and a block the diagonal crosses is walked in row sub-blocks
over the columns each can see, with a mask on the one sub-tile the
diagonal crosses.  :func:`causal_coverage` counts what that executes
of the square (0.625 at 1024 tokens, 0.5005 needed; 0.75 when whole
blocks of 512 were masked).  The single-head kernels skip and mask by
whole block: walked the same way their forward read slower on the chip
(0.58 -> 0.67 ms a layer at head_dim 128, PR 54).  For them
``coverage`` (``make_flash_attention_fn(...).coverage(S, H, D)``) counts
the blocks that run, each whole, as a share of the ``S x S`` square,
the mean over the seven score-sized matmuls of a train step, beside the
share the layer needs (:func:`needed_coverage`): at 8192 tokens 0.54 of
0.50 for a full layer, 0.19 of 0.117 for a window of 1024.

Numerics: scores/stats in f32 regardless of input dtype; probability
blocks are cast back to the value dtype for the MXU matmuls.  Numerics
tests vs the einsum path (packed and unpacked) live in
``tests/test_ops.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# shared kernel infrastructure lives in ops/substrate.py (one home for
# the interpret policy, the lane-padded row-stats convention, and
# env-knob readers); the historical private names stay importable —
# flash_ce/tests grew up on them
from ray_tpu.ops.substrate import (NEG_INF as _NEG_INF, STATS_LANES,
                                   CompilerParams as _CompilerParams,
                                   env_flag, env_int,
                                   use_interpret as _use_interpret)


@dataclasses.dataclass(frozen=True)
class AttentionConfig:
    """Kernel-schedule knobs, resolved once from the environment.

    The single home for attention env flags (scattered module-level
    ``os.environ`` reads grew dead ends in round 5 — ``RAY_TPU_ATTN_EXP2``
    was removed after A/B showed VPU exp is not the bottleneck):

    - ``RAY_TPU_ATTN_BWD_BQ`` / ``RAY_TPU_ATTN_BWD_BK`` (default 512):
      causal-backward blocking of the single-head schedule, whose only
      causal skip is of whole blocks, profiled on v5e at GPT-2 shapes.
    - ``RAY_TPU_ATTN_PACK2`` (default on; ``0`` disables): two-head lane
      packing for head_dim-64 even-head attention (see module docstring).
    - ``RAY_TPU_ATTN_PACK2_BQ`` / ``RAY_TPU_ATTN_PACK2_BK`` (default
      1024): packed-kernel blocking, forward and backward.  The packed
      kernels walk every block in row sub-blocks and skip what lies
      above the diagonal by sub-tile, so a large block costs no
      coverage and saves grid steps, re-fetched K/V and repeated RoPE:
      at 24 x 1024 x 12 x 64 on a v5e, 1024 reads 1.26 + 2.25 ms a
      layer forward + backward where 512 reads 1.55 + 2.75 (PR 54).
    """
    bwd_block_q: int = 512
    bwd_block_k: int = 512
    pack2: bool = True
    pack2_block_q: int = 1024
    pack2_block_k: int = 1024


_CONFIG: Optional[AttentionConfig] = None


def attention_config(refresh: bool = False) -> AttentionConfig:
    """The process-wide :class:`AttentionConfig` (env read once, cached).

    ``refresh=True`` re-reads the environment — for tests and A/B
    drivers that flip flags after import."""
    global _CONFIG
    if _CONFIG is None or refresh:
        _CONFIG = AttentionConfig(
            bwd_block_q=env_int("RAY_TPU_ATTN_BWD_BQ", 512),
            bwd_block_k=env_int("RAY_TPU_ATTN_BWD_BK", 512),
            pack2=env_flag("RAY_TPU_ATTN_PACK2"),
            pack2_block_q=env_int("RAY_TPU_ATTN_PACK2_BQ", 1024),
            pack2_block_k=env_int("RAY_TPU_ATTN_PACK2_BK", 1024),
        )
    return _CONFIG


# ---------------------------------------------------------------------------
# fused RoPE
#
# Applied outside the kernel, the rotation is 4+ HBM passes over q and k
# per layer in a lane-32 layout XLA handles badly (~18 ms/step on the
# GPT-2 bench).  Fused, the rotation is a few VPU ops on VMEM-resident
# blocks.  Formulation that avoids lane-32 slicing: with duplicated
# tables cos2 = [cos, cos], sinm = [-sin, sin] (each [S, D]),
#   rot(x)  = x * cos2 + roll(x, D/2) * sinm       (the RoPE rotation)
#   rotT(g) = g * cos2 - roll(g, D/2) * sinm       (its transpose)
# since roll(x, D/2) swaps halves and the sign pattern folds into sinm.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Rope:
    """One layer kind's rotary frequencies.  ``factor == 1`` is the plain
    ``theta ** (-2c / D)``; above it, static YaRN as transformers'
    ``_compute_yarn_parameters`` has it: with ``low, high`` the
    correction dims of ``beta_fast`` and ``beta_slow`` rotations over
    ``original_max`` positions (floored and ceiled), channel ``c``
    blends the plain frequency (below ``low``) into the plain one over
    ``factor`` (above ``high``) along ``ramp_c = clip((c - low) / (high
    - low), 0, 1)``, and cos and sin are both multiplied by
    ``attention_factor``."""
    theta: float = 10000.0
    factor: float = 1.0
    original_max: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def inv_freq(self, D: int) -> np.ndarray:
        half = D // 2
        base = np.exp(-np.log(self.theta) * np.arange(half) / half)
        if self.factor == 1.0:
            return base.astype(np.float32)

        def dim(rotations):
            return (D * math.log(self.original_max
                                 / (rotations * 2 * math.pi))
                    / (2 * math.log(self.theta)))

        low = max(math.floor(dim(self.beta_fast)), 0)
        high = min(math.ceil(dim(self.beta_slow)), D - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(half) - low) / (high - low), 0, 1)
        return ((1 - ramp) * base + ramp * base / self.factor
                ).astype(np.float32)


def as_rope(rope) -> Rope:
    """A :class:`Rope`, from one or from a plain ``theta``."""
    return rope if isinstance(rope, Rope) else Rope(theta=float(rope))


def rope_tables(positions, D: int, theta, dtype):
    """positions [S] (or any leading shape) -> (cos2, sinm) each
    [*positions.shape, D] for the fused kernels.  ``theta``: a plain
    base, or a layer kind's :class:`Rope`."""
    rope = as_rope(theta)
    if rope.factor == 1.0:
        half = D // 2
        freqs = jnp.exp(-jnp.log(rope.theta) * jnp.arange(half) / half)
    else:
        freqs = jnp.asarray(rope.inv_freq(D))
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)
    sin = jnp.sin(angles)
    if rope.attention_factor != 1.0:
        cos, sin = cos * rope.attention_factor, sin * rope.attention_factor
    cos2 = jnp.concatenate([cos, cos], -1).astype(dtype)
    sinm = jnp.concatenate([-sin, sin], -1).astype(dtype)
    return cos2, sinm


def rope_rotate(x, positions, theta):
    """XLA-side RoPE: x [B, S, H, D] rotated per-position.

    ``positions`` is [S] (one schedule shared across the batch — the
    training path) or [B, S] (per-sequence absolute positions — the
    decode path of the inference engine, where co-batched sequences sit
    at different lengths).

    The single source of truth for the rotation outside the kernels —
    ``ray_tpu.models.gpt._rope`` and the ``flash_attention`` fallback
    both call this, so it stays numerically identical to the in-kernel
    ``_rot`` (same duplicated-table formulation)."""
    D = x.shape[-1]
    cos2, sinm = rope_tables(positions, D, theta, x.dtype)
    if positions.ndim == 2:                  # [B, S] -> [B, S, 1, D]
        cos2, sinm = cos2[:, :, None, :], sinm[:, :, None, :]
    else:                                    # [S] -> [1, S, 1, D]
        cos2, sinm = cos2[None, :, None, :], sinm[None, :, None, :]
    return x * cos2 + jnp.roll(x, D // 2, -1) * sinm


def _roll_half(x, D: int):
    # Mosaic's lane rotate is 32-bit only; callers pass f32.  (The
    # interpreter runs pltpu.roll too, so the tests check this code.)
    return pltpu.roll(x, D // 2, 1)


def _rot(x, cos2, sinm, D: int):
    xf = x.astype(jnp.float32)
    out = (xf * cos2.astype(jnp.float32)
           + _roll_half(xf, D) * sinm.astype(jnp.float32))
    return out.astype(x.dtype)


def _rot_t(g, cos2, sinm, D: int):
    gf = g.astype(jnp.float32)
    out = (gf * cos2.astype(jnp.float32)
           - _roll_half(gf, D) * sinm.astype(jnp.float32))
    return out.astype(g.dtype)


def _masked_scores(q, k, i, j, *, scale: float, causal: bool,
                   block_q: int, block_k: int,
                   window: Optional[int] = None):
    """f32 scaled q@k^T for blocks (i, j) with the causal mask applied,
    and the window's (key ``c`` is visible to row ``r`` iff ``c <= r``
    and ``r - c < window``)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale       # [bq, bk]
    if causal:
        q_idx = (i * block_q
                 + jax.lax.broadcasted_iota(jnp.int32,
                                            (block_q, block_k), 0))
        k_idx = (j * block_k
                 + jax.lax.broadcasted_iota(jnp.int32,
                                            (block_q, block_k), 1))
        keep = q_idx >= k_idx
        if window is not None:
            keep = keep & (q_idx - k_idx < window)
        s = jnp.where(keep, s, _NEG_INF)
    return s


def _block_live(i, j, *, causal: bool, block_q: int, block_k: int,
                window: Optional[int] = None):
    """Whether kv block j contributes anything to q block i: not wholly
    above the diagonal, nor wholly behind the window's lower edge."""
    if not causal:
        return True
    live = j * block_k <= i * block_q + block_q - 1
    if window is not None:
        live = live & (j * block_k + block_k - 1 >= i * block_q - window + 1)
    return live


def _live_range(i, *, causal: bool, block_q: int, block_k: int,
                window: Optional[int], num_kv: int):
    """(first, last) kv block that :func:`_block_live` passes for q
    block ``i``: what an index map clamps to, so that a dead block is a
    block already fetched."""
    if not causal:
        return 0, num_kv - 1
    last = jnp.minimum((i * block_q + block_q - 1) // block_k, num_kv - 1)
    if window is None:
        return 0, last
    return jnp.maximum(i * block_q - window + 1, 0) // block_k, last


def _live_kv_block(i, j, **schedule):
    """``j`` clamped to :func:`_live_range` of q block ``i``: what a K/V
    index map returns, so that a dead step fetches nothing new."""
    if not schedule["causal"]:
        return j
    return jnp.clip(j, *_live_range(i, **schedule))


def _grad_blocks(q, k, v, do, lse, delta, i, j, *, scale: float,
                 causal: bool, block_q: int, block_k: int,
                 window: Optional[int] = None):
    """Shared backward block math: (p [bq,bk] f32, ds [bq,bk] f32).

    p = exp(s - lse) recomputed from the block scores; ds is the score
    gradient.  dq/dk/dv follow as single matmuls against k/q/do in the
    caller (which differ per kernel in what they accumulate)."""
    s = _masked_scores(q, k, i, j, scale=scale, causal=causal,
                       block_q=block_q, block_k=block_k, window=window)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # [bq, bk]
    ds = p * (dp - delta) * scale
    return p, ds


# ---------------------------------------------------------------------------
# two-head lane packing helpers
#
# Packed blocks are [rows, 2*Ds] with head A on lanes :Ds and head B on
# lanes Ds: (Ds = 64, so 2*Ds = 128 = the MXU/VPU lane width).  The
# block-diagonal arrangement
#     kd = [[kA, 0], [0, kB]]        ([2*rows, 128])
# makes one full-width matmul compute both heads without mixing:
#     qp @ kd^T = [sA | sB]          ([bq, 2*bk], lanes annihilate the
#                                     other head's q half)
#     [pA | pB] @ vd = [pA@vA | pB@vB]  (packed output, one matmul)
# ---------------------------------------------------------------------------

def _lane_ids(rows: int, lanes: int = 128):
    return jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)


def _half_mask(rows: int, sub_d: int):
    """bool [rows, 2*sub_d]: True on the first head's lanes."""
    return _lane_ids(rows, 2 * sub_d) < sub_d


def _fold2(t, bk: int, sub_d: int):
    """Inverse of the block-diagonal output: [2*bk, 128] -> [bk, 128].

    Row r of the top half carries head A's useful lanes :sub_d (the rest
    is the cross-head product the packing must discard); row r of the
    bottom half carries head B's lanes sub_d:."""
    return jnp.where(_half_mask(bk, sub_d), t[:bk], t[bk:])


def _roll_sub(x, sub_d: int):
    """Lane roll by sub_d//2 *within* each sub_d-lane group of a packed
    [rows, 2*sub_d] block (the per-sub-head RoPE half-swap).

    A plain 128-lane rotate crosses the head boundary; two full rotates
    select-combined per quarter implement the grouped rotate:
    destination lane l wants source (l - sub_d/2) mod sub_d within its
    group, which is roll(sub_d/2) for the upper half-group and
    roll(sub_d/2 + sub_d) for the lower half-group."""
    lo = pltpu.roll(x, sub_d // 2, 1)
    hi = pltpu.roll(x, sub_d // 2 + sub_d, 1)
    return jnp.where(_lane_ids(x.shape[0]) % sub_d < sub_d // 2, hi, lo)


def _rot2(x, cos2, sinm, sub_d: int):
    """Per-sub-head RoPE on a packed [rows, 2*sub_d] block (tables are
    the D=sub_d tables duplicated along lanes)."""
    xf = x.astype(jnp.float32)
    out = (xf * cos2.astype(jnp.float32)
           + _roll_sub(xf, sub_d) * sinm.astype(jnp.float32))
    return out.astype(x.dtype)


def _rot2_t(g, cos2, sinm, sub_d: int):
    gf = g.astype(jnp.float32)
    out = (gf * cos2.astype(jnp.float32)
           - _roll_sub(gf, sub_d) * sinm.astype(jnp.float32))
    return out.astype(g.dtype)


def _heads2(x, sub_d: int):
    """Packed rows [r, 2*sub_d] -> (head A's rows, head B's rows), each
    with the other head's lanes zeroed: rows ``[lo:hi]`` of the two,
    stacked, are the block-diagonal arrangement of rows ``[lo:hi]``."""
    m = _half_mask(x.shape[0], sub_d)
    z = jnp.zeros_like(x)
    return jnp.where(m, x, z), jnp.where(m, z, x)


def _mask_tail(s, keep):
    """Scores [n, L] with their last ``w`` columns masked by ``keep``
    (bool [n, w], None: nothing to mask) — the only columns a causal
    piece's diagonal crosses; the columns before them are not touched."""
    if keep is None:
        return s
    L, w = s.shape[1], keep.shape[1]
    if w == L:
        return jnp.where(keep, s, _NEG_INF)
    return jnp.concatenate(
        [s[:, :L - w], jnp.where(keep, s[:, L - w:], _NEG_INF)], 1)


def _scores2(q, kd, *, scale: float, keep=None):
    """Packed f32 scores of q rows [n, 128] against L kv rows in their
    block-diagonal arrangement ``kd`` [2*L, 128] (:func:`_heads2`,
    stacked): (head A's [n, L], head B's [n, L]) from one
    [n, 128] x [128, 2*L] matmul — the zeros in ``kd`` annihilate the
    other head's q lanes.  ``keep`` (bool [n, w]) masks the last ``w``
    columns of each head, the only ones a causal tile's diagonal
    crosses; columns before them are not touched."""
    L = kd.shape[0] // 2
    s = jax.lax.dot_general(
        q, kd, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale       # [n, 2*L]
    return _mask_tail(s[:, :L], keep), _mask_tail(s[:, L:], keep)


# ---------------------------------------------------------------------------
# the causal structure, as a schedule
#
# A block (i, j) sits at offset d = i*block_q - j*block_k from the
# diagonal: local row r sees local column c iff r + d >= c.  It is dead
# (d <= -block_q: never computed), interior (d >= block_k - 1: every
# element live, computed with no mask) or diagonal.  The packed kernels
# walk every block in row sub-blocks of ``sub`` rows, so a piece of work
# is [sub, 2*L] scores whatever the block sizes are; a row sub-block of a
# diagonal block (:func:`_diag_rows`) computes the block's columns
# [0, L) it can see, and of those only the last ``sub`` — the one
# sub-tile the diagonal crosses — are masked; sub-tiles above the
# diagonal are never computed.  Everything here is a static shape fact,
# so the kernels unroll the walk at trace time.
# ---------------------------------------------------------------------------

def _causal_sub(block_q: int, block_k: int) -> Optional[int]:
    """The sub-tile edge of the walk: the largest of 256, 128 that
    divides both block sizes (None: a block is one piece, and a
    diagonal block is masked whole)."""
    for t in (256, 128):
        if block_q % t == 0 and block_k % t == 0:
            return t
    return None


def _is_interior(d, block_k: int):
    return d >= block_k - 1


def _is_diagonal(d, block_q: int, block_k: int):
    return (d > -block_q) & (d < block_k - 1)


def _full_rows(block_q: int, block_k: int, sub: Optional[int]):
    """The walk of a block with nothing to mask: ``(r0, r1, L, w)`` as
    :func:`_diag_rows` gives them."""
    sub = sub or block_q
    return [(r0, r0 + sub, block_k, 0) for r0 in range(0, block_q, sub)]


def _diag_rows(block_q: int, block_k: int, sub: Optional[int], d: int):
    """The walk of a diagonal block at offset ``d``: ``(r0, r1, L, w)``
    — rows [r0, r1) against the block's columns [0, L), of which the
    last ``w`` are masked (0: none).  Rows that see nothing have no
    entry."""
    if sub is None:
        return [(0, block_q, block_k, block_k)]
    rows = []
    for r0 in range(0, block_q, sub):
        edge = r0 + d                  # the crossed sub-tile's column
        if 0 <= edge < block_k:
            rows.append((r0, r0 + sub, edge + sub, sub))
        elif edge >= block_k:
            rows.append((r0, r0 + sub, block_k, 0))
    return rows


def _causal_keep(rows: int, cols: int, shift):
    """bool [rows, cols]: local row r sees local column c."""
    return (jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) + shift
            >= jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1))


def _offsets(num_q: int, block_q: int, kv_starts):
    """Every offset from the diagonal at which one of ``num_q`` q blocks
    meets a kv block that starts at one of the columns ``kv_starts``."""
    return [i * block_q - c0 for i in range(num_q) for c0 in kv_starts]


def _causal_walk(d, offsets, begin, *, causal: bool, block_q: int,
                 block_k: int):
    """Walk what a q block sees of a kv block at the (traced) offset
    ``d``, one of the static ``offsets`` the grid can put there.
    ``begin()`` prepares the pair's operands once and returns
    ``update(r0, r1, L, keep)``, which is run over :func:`_full_rows`
    for an interior block and over :func:`_diag_rows` for a diagonal
    one (each such offset is its own branch, so every slice is static);
    a dead block begins nothing."""
    sub = _causal_sub(block_q, block_k)

    def _unmasked():
        update = begin()
        for r0, r1, L, _ in _full_rows(block_q, block_k, sub):
            update(r0, r1, L, None)

    if not causal:
        _unmasked()
        return
    if any(_is_interior(d0, block_k) for d0 in offsets):
        pl.when(_is_interior(d, block_k))(_unmasked)
    if sub is None:
        @pl.when(_is_diagonal(d, block_q, block_k))
        def _masked_whole():
            begin()(0, block_q, block_k,
                    _causal_keep(block_q, block_k, d))
        return
    for d0 in sorted(set(offsets)):
        if not _is_diagonal(d0, block_q, block_k):
            continue

        @pl.when(d == d0)
        def _diagonal(d0=d0):
            update, tri = begin(), _causal_keep(sub, sub, 0)
            for r0, r1, L, w in _diag_rows(block_q, block_k, sub, d0):
                update(r0, r1, L, tri if w else None)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale: float, causal: bool,
                block_q: int, block_k: int, num_kv: int,
                has_rope: bool, window: Optional[int] = None):
    if has_rope:
        (cq_ref, sq_ref, ck_ref, sk_ref,
         o_ref, lse_ref, acc_sc, m_sc, l_sc) = rest
    else:
        o_ref, lse_ref, acc_sc, m_sc, l_sc = rest
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    @pl.when(_block_live(i, j, causal=causal, block_q=block_q,
                         block_k=block_k, window=window))
    def _compute():
        q = q_ref[0, 0]                      # [bq, D]
        k = k_ref[0, 0]                      # [bk, D]
        v = v_ref[0, 0]
        if has_rope:
            D = q.shape[-1]
            q = _rot(q, cq_ref[...], sq_ref[...], D)
            k = _rot(k, ck_ref[...], sk_ref[...], D)
        s = _masked_scores(q, k, i, j, scale=scale, causal=causal,
                           block_q=block_q, block_k=block_k,
                           window=window)
        m_prev = m_sc[:]                      # [bq, 128] (col-bcast)
        m_cur = jnp.max(s, axis=1, keepdims=True)          # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)                 # [bq, 128]
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])                      # [bq, bk]
        l_sc[:] = l_sc[:] * alpha + jnp.sum(p, 1, keepdims=True)
        acc_sc[:] = (acc_sc[:] * alpha[:, :1]
                     + jax.lax.dot_general(
                         p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32))
        m_sc[:] = m_new

    @pl.when(j == num_kv - 1)
    def _finalize():
        l = l_sc[:, :1]
        o_ref[0, 0] = (acc_sc[:]
                       / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse = m_sc[:, :1] + jnp.log(jnp.maximum(l, 1e-30))  # [bq, 1]
        lse_ref[0, 0, 0] = jnp.broadcast_to(lse, lse_ref.shape[3:])


def _fwd(q, k, v, *, scale: float, causal: bool,
         block_q: int, block_k: int, rope=None,
         window: Optional[int] = None):
    """q: [B, H, S, D]; k, v: [B, Hkv, Sk, D], query head ``h`` reading
    K/V head ``h // (H // Hkv)`` -> (o [B, H, S, D],
    lse [B, H, S // bq, bq, STATS_LANES] f32 — lane-padded row stats).

    ``rope``: optional (cos2 [S, D], sinm [S, D]) tables from
    ``rope_tables``; q/k blocks are rotated in-kernel.  ``window``: a
    row sees the ``window`` keys up to its own.  A kv block that is
    dead for a q block (above the diagonal, behind the window) is not
    computed, and not fetched either: its index is clamped to the
    nearest live block's, which is in VMEM already."""
    B, H, S, D = q.shape
    Sk = k.shape[2]
    group = H // k.shape[1]
    bq, bk = min(block_q, S), min(block_k, Sk)
    grid = (B, H, S // bq, Sk // bk)
    num_kv = grid[3]

    kv_block = functools.partial(_live_kv_block, causal=causal, block_q=bq,
                                 block_k=bk, window=window, num_kv=num_kv)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        num_kv=num_kv, has_rope=rope is not None, window=window)
    rope_args, rope_specs = (), []
    if rope is not None:
        cos2, sinm = rope
        rope_args = (cos2, sinm, cos2, sinm)
        rope_specs = [
            pl.BlockSpec((bq, D), lambda b, h, i, j: (i, 0)),
            pl.BlockSpec((bq, D), lambda b, h, i, j: (i, 0)),
            pl.BlockSpec((bk, D), lambda b, h, i, j: (kv_block(i, j), 0)),
            pl.BlockSpec((bk, D), lambda b, h, i, j: (kv_block(i, j), 0)),
        ]
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (
                b, h // group, kv_block(i, j), 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (
                b, h // group, kv_block(i, j), 0)),
            *rope_specs,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            # row stats as [B, H, num_q, bq, STATS_LANES]: a
            # (.., bq, STATS_LANES) block satisfies the TPU tiling rule
            # ((bq, 8): sublane div 8, lane equal to array dim) where a
            # 1-D (.., bq) row cannot
            pl.BlockSpec((1, 1, 1, bq, STATS_LANES),
                         lambda b, h, i, j: (b, h, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, S // bq, bq, STATS_LANES),
                                 jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        interpret=_use_interpret(),
    )(q, k, v, *rope_args)
    return o, lse


# What the packed kernels may take of a v5e's 128 MiB of VMEM.  The
# backward keeps the whole kv sequence resident (k, v, their RoPE
# tables, dk and dv double-buffered, two f32 accumulators, the rotated
# k) beside its q block and a walk step's [256, 2*1024] f32
# temporaries.  Under the default 16 MiB limit Mosaic refused it at
# 2048 rows with q blocks of 1024, and at 4096 and 8192 with any blocks
# ("Ran out of memory in memory space vmem", compiled for a described
# v5e, PR 54), though the gate admits 8192; under this one every such
# shape compiles.
_PACK2_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _fwd_pack2_kernel(q_ref, k_ref, v_ref, *rest, scale: float,
                      causal: bool, block_q: int, block_k: int,
                      num_q: int, num_kv: int, has_rope: bool,
                      sub_d: int):
    """Packed forward: blocks are [bq, 128] head pairs; scores/stats run
    per half while both matmuls go through the MXU at full lane width
    (one [n, 128] x [128, 2*L] score op, one [n, 2*L] x [2*L, 128]
    accumulate op — half the op count of the unpacked pair).  The
    causal structure is the schedule's (:func:`_causal_walk`): one
    update per row sub-block over the columns it sees, each with its
    own slice of the online-softmax state."""
    if has_rope:
        (cq_ref, sq_ref, ck_ref, sk_ref,
         o_ref, lse0_ref, lse1_ref, acc_sc, m_sc, l_sc) = rest
    else:
        o_ref, lse0_ref, lse1_ref, acc_sc, m_sc, l_sc = rest
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    def _begin():
        qp = q_ref[0, 0]                     # [bq, 128] packed pair
        kp = k_ref[0, 0]                     # [bk, 128]
        if has_rope:
            qp = _rot2(qp, cq_ref[...], sq_ref[...], sub_d)
            kp = _rot2(kp, ck_ref[...], sk_ref[...], sub_d)
        ka, kb = _heads2(kp, sub_d)
        va, vb = _heads2(v_ref[0, 0], sub_d)

        def _update(r0, r1, L, keep):
            kd = jnp.concatenate([ka[:L], kb[:L]], 0)      # [2*L, 128]
            s0, s1 = _scores2(qp[r0:r1], kd, scale=scale, keep=keep)
            m0_prev, m1_prev = m_sc[0, r0:r1], m_sc[1, r0:r1]  # [n, 128]
            m0 = jnp.maximum(m0_prev, jnp.max(s0, axis=1, keepdims=True))
            m1 = jnp.maximum(m1_prev, jnp.max(s1, axis=1, keepdims=True))
            a0 = jnp.exp(m0_prev - m0)
            a1 = jnp.exp(m1_prev - m1)
            p0 = jnp.exp(s0 - m0[:, :1])
            p1 = jnp.exp(s1 - m1[:, :1])
            l_sc[0, r0:r1] = (l_sc[0, r0:r1] * a0
                              + jnp.sum(p0, 1, keepdims=True))
            l_sc[1, r0:r1] = (l_sc[1, r0:r1] * a1
                              + jnp.sum(p1, 1, keepdims=True))
            pd = jnp.concatenate([p0, p1], 1).astype(va.dtype)
            vd = jnp.concatenate([va[:L], vb[:L]], 0)      # [2*L, 128]
            alpha = jnp.where(_half_mask(r1 - r0, sub_d), a0, a1)
            acc_sc[r0:r1] = (acc_sc[r0:r1] * alpha
                             + jax.lax.dot_general(
                                 pd, vd, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32))
            m_sc[0, r0:r1] = m0
            m_sc[1, r0:r1] = m1
        return _update

    _causal_walk(i * block_q - j * block_k,
                 _offsets(num_q, block_q,
                          range(0, num_kv * block_k, block_k)),
                 _begin, causal=causal, block_q=block_q, block_k=block_k)

    @pl.when(j == num_kv - 1)
    def _finalize():
        l0 = jnp.maximum(l_sc[0][:, :1], 1e-30)
        l1 = jnp.maximum(l_sc[1][:, :1], 1e-30)
        den = jnp.where(_half_mask(block_q, sub_d), l0, l1)
        o_ref[0, 0] = (acc_sc[:] / den).astype(o_ref.dtype)
        lse0 = m_sc[0][:, :1] + jnp.log(l0)               # [bq, 1]
        lse1 = m_sc[1][:, :1] + jnp.log(l1)
        lse0_ref[0, 0, 0] = jnp.broadcast_to(lse0, lse0_ref.shape[3:])
        lse1_ref[0, 0, 0] = jnp.broadcast_to(lse1, lse1_ref.shape[3:])


@functools.partial(jax.jit, inline=True, static_argnames=(
    "scale", "causal", "block_q", "block_k", "sub_d", "interpret"))
def _fwd_pack2(q, k, v, *, scale: float, causal: bool, block_q: int,
               block_k: int, interpret: bool, rope=None, sub_d: int = 64):
    """Packed q,k,v: [B, Hp, S, 2*sub_d] -> (o packed, lse0, lse1 each
    [B, Hp, S // bq, bq, STATS_LANES] f32 — per-sub-head row stats).

    ``rope``: optional packed tables (cos2 [S, 128], sinm [S, 128] —
    the D=sub_d tables duplicated along lanes).

    An inlined ``jit``: the compiled program is the same to the op name,
    and an unrolled model's layers, which all make this call, trace and
    lower the kernel's walk once, not once a layer (the 24 kernels of
    the 12-layer train step lower in 5 s without it and in 1.5 s with
    it, on the sandbox's CPU for a described v5e, PR 54; every run pays
    that in its set-up).  What the body reads besides its arguments has
    to be constant, so ``interpret`` (``substrate.use_interpret()``: a
    context decides it) is the caller's to pass."""
    B, Hp, S, Dp = q.shape
    Sk = k.shape[2]
    bq, bk = min(block_q, S), min(block_k, Sk)
    grid = (B, Hp, S // bq, Sk // bk)
    num_kv = grid[3]

    kernel = functools.partial(
        _fwd_pack2_kernel, scale=scale, causal=causal, block_q=bq,
        block_k=bk, num_q=grid[2], num_kv=num_kv,
        has_rope=rope is not None, sub_d=sub_d)
    rope_args, rope_specs = (), []
    if rope is not None:
        cos2, sinm = rope
        rope_args = (cos2, sinm, cos2, sinm)
        rope_specs = [
            pl.BlockSpec((bq, Dp), lambda b, h, i, j: (i, 0)),
            pl.BlockSpec((bq, Dp), lambda b, h, i, j: (i, 0)),
            pl.BlockSpec((bk, Dp), lambda b, h, i, j: (j, 0)),
            pl.BlockSpec((bk, Dp), lambda b, h, i, j: (j, 0)),
        ]
    stats_spec = pl.BlockSpec((1, 1, 1, bq, STATS_LANES),
                              lambda b, h, i, j: (b, h, i, 0, 0))
    stats_shape = jax.ShapeDtypeStruct((B, Hp, S // bq, bq, STATS_LANES),
                                       jnp.float32)
    o, lse0, lse1 = pl.pallas_call(
        kernel,
        grid=grid,
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_PACK2_VMEM_LIMIT_BYTES),
        in_specs=[
            pl.BlockSpec((1, 1, bq, Dp), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, Dp), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, Dp), lambda b, h, i, j: (b, h, j, 0)),
            *rope_specs,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, Dp), lambda b, h, i, j: (b, h, i, 0)),
            stats_spec,
            stats_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hp, S, Dp), q.dtype),
            stats_shape,
            stats_shape,
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, Dp), jnp.float32),
            pltpu.VMEM((2, bq, 128), jnp.float32),
            pltpu.VMEM((2, bq, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, *rope_args)
    return o, lse0, lse1


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_sc, *, scale: float, causal: bool,
                   block_q: int, block_k: int, num_kv: int,
                   window: Optional[int] = None):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    @pl.when(_block_live(i, j, causal=causal, block_q=block_q,
                         block_k=block_k, window=window))
    def _compute():
        k = k_ref[0, 0]
        _, ds = _grad_blocks(
            q_ref[0, 0], k, v_ref[0, 0], do_ref[0, 0],
            lse_ref[0, 0, 0][:, 0:1], delta_ref[0, 0, 0][:, 0:1], i, j,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            window=window)
        dq_sc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == num_kv - 1)
    def _finalize():
        dq_ref[0, 0] = dq_sc[:].astype(dq_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                      *rest, scale: float, causal: bool, block_q: int,
                      block_k: int, num_q: int, num_kv: int,
                      has_rope: bool, group: int = 1,
                      window: Optional[int] = None):
    """Strip-mined fused backward: dq, dk, dv in one pass over (b, K/V
    head, query head of its group, i).

    The two-kernel backward (`_bwd_dq_kernel` + `_bwd_dkv_kernel`)
    recomputes the score block and dp in each kernel — 2 extra
    K=head_dim matmuls per block pair, the expensive kind on the MXU
    (contraction = 64 runs the systolic array at half rate).  Here the
    whole kv sequence rides along as one [Sk, D] block and the kernel
    walks it in ``block_k`` strips: s/p/dp are computed once per strip
    and feed all three gradients.  Causal masking goes from "compute
    the full square then mask" to *skipping dead strips outright*
    (``_block_live``) — at bq=bk=256 over S=1024 that's 37.5% of the
    score matmuls and, just as importantly on TPU, of the VPU
    exp/mask work that otherwise rivals the MXU time at head_dim 64.
    dq accumulates in VMEM scratch per q block; dk/dv accumulate in
    [Sk, D] scratch across the sequential sweep over the ``group``
    query heads that share the K/V head and their q blocks, so K and V
    are fetched, and the rotated K made, once a K/V head
    (VMEM-bounded: the `_bwd` dispatcher falls back to the two-kernel
    path for long Sk).  With ``window``, the strips wholly behind a q
    block's window are skipped like the ones above its diagonal.

    With ``has_rope``, q/k are rotated in-kernel for the score
    recompute; score-gradients land on the *rotated* q/k, so dq takes
    the transposed rotation before its store and dk takes it at
    finalize (the rotation is per-row, so it commutes with the
    accumulation over q blocks).  A row's ``delta = sum(do * o)`` is
    made here from the ``do`` and ``o`` blocks of the same rows: the
    grid visits a (query head, q block) once, so it is made once.
    """
    if has_rope:
        (cq_ref, sq_ref, ck_ref, sk_ref,
         dq_ref, dk_ref, dv_ref, dq_sc, dk_sc, dv_sc, krot_sc) = rest
    else:
        dq_ref, dk_ref, dv_ref, dq_sc, dk_sc, dv_sc = rest
    g, i = pl.program_id(2), pl.program_id(3)   # query head, q block

    @pl.when((g == 0) & (i == 0))
    def _init_kv():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)
        if has_rope and num_kv > 1:
            # rotate k ONCE per (b, h): every q block's strips reuse the
            # cached rotation instead of re-rotating per (i, strip)
            krot_sc[:] = _rot(k_ref[0, 0], ck_ref[...], sk_ref[...],
                              k_ref.shape[-1])

    q = q_ref[0, 0]
    do = do_ref[0, 0]
    D = q.shape[-1]
    if has_rope:
        q = _rot(q, cq_ref[...], sq_ref[...], D)
    lse = lse_ref[0, 0, 0][:, 0:1]
    delta = jnp.sum(do.astype(jnp.float32)
                    * o_ref[0, 0].astype(jnp.float32),
                    axis=-1, keepdims=True)               # [bq, 1]

    if num_kv == 1:
        # single strip: every block pair is live under causal masking,
        # so no liveness guard — and dq/k go straight through values
        # instead of VMEM scratch round-trips (this is the exact hot
        # path of the S<=block_k case, keep it lean)
        k = k_ref[0, 0]
        if has_rope:
            k = _rot(k, ck_ref[...], sk_ref[...], D)
        p, ds = _grad_blocks(
            q, k, v_ref[0, 0], do, lse, delta, i, 0,
            scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, window=window)
        dv_sc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bk, D]
        dk_sc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bk, D]
        dq = jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        dq_sc[:] = jnp.zeros_like(dq_sc)
        for j in range(num_kv):
            lo, hi = j * block_k, (j + 1) * block_k

            @pl.when(_block_live(i, j, causal=causal, block_q=block_q,
                                 block_k=block_k, window=window))
            def _strip(j=j, lo=lo, hi=hi):
                if has_rope:
                    k = krot_sc[lo:hi, :]
                else:
                    k = k_ref[0, 0, lo:hi, :]
                p, ds = _grad_blocks(
                    q, k, v_ref[0, 0, lo:hi, :], do, lse, delta, i, j,
                    scale=scale, causal=causal, block_q=block_q,
                    block_k=block_k, window=window)
                dv_sc[lo:hi, :] += jax.lax.dot_general(
                    p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [bk, D]
                dk_sc[lo:hi, :] += jax.lax.dot_general(
                    ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [bk, D]
                dq_sc[:] += jax.lax.dot_general(
                    ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
        dq = dq_sc[:]
    if has_rope:
        dq = _rot_t(dq, cq_ref[...], sq_ref[...], D)
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)

    @pl.when((g == group - 1) & (i == num_q - 1))
    def _finalize():
        dk = dk_sc[:]
        if has_rope:
            dk = _rot_t(dk, ck_ref[...], sk_ref[...], D)
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[:].astype(dv_ref.dtype)


def _bwd_pack2_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse0_ref,
                      lse1_ref, *rest, scale: float, causal: bool,
                      block_q: int, block_k: int, num_q: int,
                      num_kv: int, has_rope: bool, sub_d: int):
    """Packed strip-mined fused backward: the packed analogue of
    `_bwd_fused_kernel` (same grid, same rope-at-the-boundary
    structure), with every matmul full-width.  For n q rows against L
    kv rows of a strip:

        s  = qp @ kd^T          [n, 128] x [128, 2*L]
        dp = do @ vd^T          [n, 128] x [128, 2*L]
        dv = fold(pd^T @ do)    [2*L, n] x [n, 128]
        dk = fold(dsd^T @ qp)   [2*L, n] x [n, 128]
        dq = dsd @ kd           [n, 2*L] x [2*L, 128]

    — 5 ops for a head *pair* vs 10 half-width ops on the unpacked
    schedule.  ``fold`` keeps each half's own lanes and drops the
    cross-head lanes the widened transpose matmuls produce.  Which
    (n, L) pieces of a strip run, and which of them are masked, is the
    schedule's (:func:`_causal_walk`): dq accumulates by row sub-block,
    dk/dv into the rows of their scratch the piece covers; a sub-head's
    ``delta = sum(do * o)`` over its own lanes, once a grid step."""
    if has_rope:
        (cq_ref, sq_ref, ck_ref, sk_ref,
         dq_ref, dk_ref, dv_ref, dq_sc, dk_sc, dv_sc, krot_sc) = rest
    else:
        dq_ref, dk_ref, dv_ref, dq_sc, dk_sc, dv_sc = rest
    i = pl.program_id(2)                        # q block index

    @pl.when(i == 0)
    def _init_kv():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)
        if has_rope:
            # rotate k ONCE per (b, h): every q block's strips reuse it
            krot_sc[:] = _rot2(k_ref[0, 0], ck_ref[...], sk_ref[...],
                               sub_d)

    qp = q_ref[0, 0]                             # [bq, 128]
    do = do_ref[0, 0]
    if has_rope:
        qp = _rot2(qp, cq_ref[...], sq_ref[...], sub_d)
    lse0 = lse0_ref[0, 0, 0][:, 0:1]
    lse1 = lse1_ref[0, 0, 0][:, 0:1]
    prod0, prod1 = _heads2(
        do.astype(jnp.float32) * o_ref[0, 0].astype(jnp.float32), sub_d)
    delta0 = jnp.sum(prod0, axis=-1, keepdims=True)       # [bq, 1]
    delta1 = jnp.sum(prod1, axis=-1, keepdims=True)
    dq_sc[:] = jnp.zeros_like(dq_sc)

    for j in range(num_kv):
        lo, hi = j * block_k, (j + 1) * block_k

        def _begin(lo=lo, hi=hi):
            k_rows = krot_sc if has_rope else k_ref.at[0, 0]
            ka, kb = _heads2(k_rows[lo:hi, :], sub_d)
            va, vb = _heads2(v_ref[0, 0, lo:hi, :], sub_d)

            def _update(r0, r1, L, keep):
                q, g = qp[r0:r1], do[r0:r1]
                kd = jnp.concatenate([ka[:L], kb[:L]], 0)  # [2*L, 128]
                vd = jnp.concatenate([va[:L], vb[:L]], 0)
                s0, s1 = _scores2(q, kd, scale=scale, keep=keep)
                p0 = jnp.exp(s0 - lse0[r0:r1])
                p1 = jnp.exp(s1 - lse1[r0:r1])
                dp = jax.lax.dot_general(
                    g, vd, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)    # [n, 2*L]
                ds0 = p0 * (dp[:, :L] - delta0[r0:r1]) * scale
                ds1 = p1 * (dp[:, L:] - delta1[r0:r1]) * scale
                pd = jnp.concatenate([p0, p1], 1)
                dsd = jnp.concatenate([ds0, ds1], 1)
                dv_sc[lo:lo + L, :] += _fold2(jax.lax.dot_general(
                    pd.astype(g.dtype), g, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32), L, sub_d)
                dk_sc[lo:lo + L, :] += _fold2(jax.lax.dot_general(
                    dsd.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32), L, sub_d)
                dq_sc[r0:r1, :] += jax.lax.dot_general(
                    dsd.astype(kd.dtype), kd, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            return _update

        _causal_walk(i * block_q - lo,
                     _offsets(num_q, block_q, [lo]),
                     _begin, causal=causal, block_q=block_q,
                     block_k=block_k)
    dq = dq_sc[:]
    if has_rope:
        dq = _rot2_t(dq, cq_ref[...], sq_ref[...], sub_d)
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)

    @pl.when(i == num_q - 1)
    def _finalize():
        dk = dk_sc[:]
        if has_rope:
            dk = _rot2_t(dk, ck_ref[...], sk_ref[...], sub_d)
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[:].astype(dv_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_sc, dv_sc, *, scale: float,
                    causal: bool, block_q: int, block_k: int,
                    num_q: int, group: int = 1,
                    window: Optional[int] = None):
    # kv block outer; the group's query heads, then their q blocks, inner
    j, g, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when((g == 0) & (i == 0))
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    @pl.when(_block_live(i, j, causal=causal, block_q=block_q,
                         block_k=block_k, window=window))
    def _compute():
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        p, ds = _grad_blocks(
            q, k_ref[0, 0], v_ref[0, 0], do, lse_ref[0, 0, 0][:, 0:1],
            delta_ref[0, 0, 0][:, 0:1], i, j,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            window=window)
        dv_sc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bk, D]
        dk_sc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bk, D]

    @pl.when((g == group - 1) & (i == num_q - 1))
    def _finalize():
        dk_ref[0, 0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[:].astype(dv_ref.dtype)


# The strip-mined backward at its gate's edge (8192 x 128: K, V, their
# RoPE tables and dk, dv double-buffered, two f32 accumulators and the
# rotated K) needs 34 MiB of VMEM, over Mosaic's default 16; shapes
# under _BWD_VMEM_DEFAULT_ROWS keep the default limit and lower as they
# always have.
_BWD_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# the two f32 [Sk, D] accumulators the strip-mined backward may hold
_FUSED_BWD_SCRATCH_BYTES = 8 * 1024 * 1024
_BWD_VMEM_DEFAULT_ROWS = 2048 * 128


def _bwd(q, k, v, o, lse, do, *, scale: float, causal: bool,
         block_q: int, block_k: int, rope=None,
         window: Optional[int] = None):
    """-> (dq [B, H, S, D], dk, dv [B, Hkv, Sk, D]): the K/V gradients
    are summed over the ``H // Hkv`` query heads of a K/V head inside
    the kernels' grids, so they are written once, at K/V's size."""
    B, H, S, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    group = H // Hkv
    bq, bk = min(block_q, S), min(block_k, Sk)
    num_q, num_kv = S // bq, Sk // bk
    # the forward's stats arrive one value a row ([B, H, S // fwd bq,
    # fwd bq]): regroup to this pass's blocking and pad to the lanes
    lse = jnp.broadcast_to(lse.reshape(B, H, num_q, bq, 1),
                           (B, H, num_q, bq, STATS_LANES))

    # strip-mined fused path: the whole kv sequence rides as one block
    # and the kernel walks it in bk strips (skipping causally-dead
    # ones).  [Sk, D] f32 scratch x2 bounds it to moderate Sk; longer
    # sequences take the two-kernel path below.  Its grid visits a q
    # block once, so it takes ``o`` and makes delta itself.
    if Sk * D * 4 * 2 <= _FUSED_BWD_SCRATCH_BYTES:
        qs = pl.BlockSpec((1, 1, bq, D),
                          lambda b, h, g, i: (b, h * group + g, i, 0))
        ks = pl.BlockSpec((1, 1, Sk, D), lambda b, h, g, i: (b, h, 0, 0))
        rs = pl.BlockSpec((1, 1, 1, bq, STATS_LANES),
                          lambda b, h, g, i: (b, h * group + g, i, 0, 0))
        rope_args, rope_specs = (), []
        if rope is not None:
            cos2, sinm = rope
            rope_args = (cos2, sinm, cos2, sinm)
            rope_specs = [
                pl.BlockSpec((bq, D), lambda b, h, g, i: (i, 0)),
                pl.BlockSpec((bq, D), lambda b, h, g, i: (i, 0)),
                pl.BlockSpec((Sk, D), lambda b, h, g, i: (0, 0)),
                pl.BlockSpec((Sk, D), lambda b, h, g, i: (0, 0)),
            ]
        limit = ({} if Sk * D <= _BWD_VMEM_DEFAULT_ROWS
                 else {"vmem_limit_bytes": _BWD_VMEM_LIMIT_BYTES})
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, scale=scale,
                              causal=causal, block_q=bq, block_k=bk,
                              num_q=num_q, num_kv=num_kv,
                              has_rope=rope is not None, group=group,
                              window=window),
            grid=(B, Hkv, group, num_q),
            compiler_params=_CompilerParams(
                dimension_semantics=("parallel", "parallel",
                                     "arbitrary", "arbitrary"),
                **limit),
            in_specs=[qs, ks, ks, qs, qs, rs, *rope_specs],
            out_specs=[qs, ks, ks],
            out_shape=[jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
                       jax.ShapeDtypeStruct((B, Hkv, Sk, D), k.dtype),
                       jax.ShapeDtypeStruct((B, Hkv, Sk, D), v.dtype)],
            scratch_shapes=(
                [pltpu.VMEM((bq, D), jnp.float32),
                 pltpu.VMEM((Sk, D), jnp.float32),
                 pltpu.VMEM((Sk, D), jnp.float32)]
                + ([pltpu.VMEM((Sk, D), q.dtype)]
                   if rope is not None else [])),
            interpret=_use_interpret(),
        )(q, k, v, do, o, lse, *rope_args)
        return dq, dk, dv
    assert rope is None, \
        "fused rope requires the strip-mined backward (moderate Sk)"
    # each kernel below visits a q block once a kv block: delta =
    # sum(do * o), one value a row, comes to them as a slab made here
    delta = jnp.broadcast_to(
        jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                axis=-1).reshape(B, H, num_q, bq, 1),
        (B, H, num_q, bq, STATS_LANES))

    kv_block = functools.partial(_live_kv_block, causal=causal, block_q=bq,
                                 block_k=bk, window=window, num_kv=num_kv)

    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0))
    k_spec = pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (
        b, h // group, kv_block(i, j), 0))
    r_spec = pl.BlockSpec((1, 1, 1, bq, STATS_LANES),
                          lambda b, h, i, j: (b, h, i, 0, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, num_kv=num_kv,
                          window=window),
        grid=(B, H, num_q, num_kv),
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        in_specs=[q_spec, k_spec, k_spec, q_spec, r_spec, r_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=_use_interpret(),
    )(q, k, v, do, lse, delta)

    # kv-outer grid: index maps see (b, K/V head, j, query head, i)
    q_spec2 = pl.BlockSpec(
        (1, 1, bq, D), lambda b, h, j, g, i: (b, h * group + g, i, 0))
    k_spec2 = pl.BlockSpec((1, 1, bk, D),
                           lambda b, h, j, g, i: (b, h, j, 0))
    r_spec2 = pl.BlockSpec(
        (1, 1, 1, bq, STATS_LANES),
        lambda b, h, j, g, i: (b, h * group + g, i, 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, num_q=num_q,
                          group=group, window=window),
        grid=(B, Hkv, num_kv, group, num_q),
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, r_spec2, r_spec2],
        out_specs=[k_spec2, k_spec2],
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, Sk, D), k.dtype),
                   jax.ShapeDtypeStruct((B, Hkv, Sk, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=_use_interpret(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.jit, inline=True, static_argnames=(
    "scale", "causal", "block_q", "block_k", "sub_d", "interpret"))
def _bwd_pack2(q, k, v, o, lse0, lse1, do, *, scale: float, causal: bool,
               block_q: int, block_k: int, interpret: bool, rope=None,
               sub_d: int = 64):
    """Packed backward dispatcher (strip-mined fused path only — the
    `flash_attention` gate keeps pack2 off for kv sequences whose
    [Sk, 128] f32 dk/dv scratch would not fit VMEM); an inlined ``jit``
    as :func:`_fwd_pack2` is."""
    B, Hp, S, Dp = q.shape
    Sk = k.shape[2]
    bq, bk = min(block_q, S), min(block_k, Sk)
    num_q, num_kv = S // bq, Sk // bk
    assert Sk * Dp * 4 * 2 <= 8 * 1024 * 1024, \
        "packed backward needs the strip-mined fused path (moderate Sk)"
    if lse0.shape[3] != bq:
        lse0 = lse0.reshape(B, Hp, num_q, bq, STATS_LANES)
        lse1 = lse1.reshape(B, Hp, num_q, bq, STATS_LANES)

    qs = pl.BlockSpec((1, 1, bq, Dp), lambda b, h, i: (b, h, i, 0))
    ks = pl.BlockSpec((1, 1, Sk, Dp), lambda b, h, i: (b, h, 0, 0))
    rs = pl.BlockSpec((1, 1, 1, bq, STATS_LANES),
                      lambda b, h, i: (b, h, i, 0, 0))
    rope_args, rope_specs = (), []
    if rope is not None:
        cos2, sinm = rope
        rope_args = (cos2, sinm, cos2, sinm)
        rope_specs = [
            pl.BlockSpec((bq, Dp), lambda b, h, i: (i, 0)),
            pl.BlockSpec((bq, Dp), lambda b, h, i: (i, 0)),
            pl.BlockSpec((Sk, Dp), lambda b, h, i: (0, 0)),
            pl.BlockSpec((Sk, Dp), lambda b, h, i: (0, 0)),
        ]
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_pack2_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, num_q=num_q,
                          num_kv=num_kv, has_rope=rope is not None,
                          sub_d=sub_d),
        grid=(B, Hp, num_q),
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_PACK2_VMEM_LIMIT_BYTES),
        in_specs=[qs, ks, ks, qs, qs, rs, rs, *rope_specs],
        out_specs=[qs, ks, ks],
        out_shape=[jax.ShapeDtypeStruct((B, Hp, S, Dp), q.dtype),
                   jax.ShapeDtypeStruct((B, Hp, Sk, Dp), k.dtype),
                   jax.ShapeDtypeStruct((B, Hp, Sk, Dp), v.dtype)],
        scratch_shapes=(
            [pltpu.VMEM((bq, Dp), jnp.float32),
             pltpu.VMEM((Sk, Dp), jnp.float32),
             pltpu.VMEM((Sk, Dp), jnp.float32)]
            + ([pltpu.VMEM((Sk, Dp), q.dtype)]
               if rope is not None else [])),
        interpret=interpret,
    )(q, k, v, do, o, lse0, lse1, *rope_args)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_bhsd(q, k, v, scale, causal, block_q, block_k,
                bwd_block_q, bwd_block_k, window=None):
    o, _ = _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q,
                block_k=block_k, window=window)
    return o


def _flash_bhsd_fwd(q, k, v, scale, causal, block_q, block_k,
                    bwd_block_q, bwd_block_k, window=None):
    o, lse = _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, window=window)
    # the row stats are kept one value a row ([B, H, S]), not the
    # kernel's lane-padded block: at 8192 x 32 heads the padding is
    # 134 MB a sequence a layer held from forward to backward
    return o, (q, k, v, o, lse[..., 0])


def _flash_bhsd_bwd(scale, causal, block_q, block_k, bwd_block_q,
                    bwd_block_k, window, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _bwd(q, k, v, o, lse, do, scale=scale, causal=causal,
                      block_q=bwd_block_q, block_k=bwd_block_k,
                      window=window)
    return dq, dk, dv


_flash_bhsd.defvjp(_flash_bhsd_fwd, _flash_bhsd_bwd)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash_bhsd_rope(q, k, v, cos2, sinm, scale, causal, block_q,
                     block_k, bwd_block_q, bwd_block_k, window=None):
    o, _ = _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q,
                block_k=block_k, rope=(cos2, sinm), window=window)
    return o


def _flash_bhsd_rope_fwd(q, k, v, cos2, sinm, scale, causal, block_q,
                         block_k, bwd_block_q, bwd_block_k, window=None):
    o, lse = _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, rope=(cos2, sinm), window=window)
    return o, (q, k, v, cos2, sinm, o, lse[..., 0])


def _flash_bhsd_rope_bwd(scale, causal, block_q, block_k, bwd_block_q,
                         bwd_block_k, window, res, do):
    q, k, v, cos2, sinm, o, lse = res
    dq, dk, dv = _bwd(q, k, v, o, lse, do, scale=scale, causal=causal,
                      block_q=bwd_block_q, block_k=bwd_block_k,
                      rope=(cos2, sinm), window=window)
    return dq, dk, dv, None, None


_flash_bhsd_rope.defvjp(_flash_bhsd_rope_fwd, _flash_bhsd_rope_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_pack2(q, k, v, scale, causal, block_q, block_k,
                 bwd_block_q, bwd_block_k):
    o, _, _ = _fwd_pack2(q, k, v, scale=scale, causal=causal,
                         block_q=block_q, block_k=block_k,
                         interpret=_use_interpret())
    return o


def _flash_pack2_fwd(q, k, v, scale, causal, block_q, block_k,
                     bwd_block_q, bwd_block_k):
    o, lse0, lse1 = _fwd_pack2(q, k, v, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               interpret=_use_interpret())
    return o, (q, k, v, o, lse0, lse1)


def _flash_pack2_bwd(scale, causal, block_q, block_k, bwd_block_q,
                     bwd_block_k, res, do):
    q, k, v, o, lse0, lse1 = res
    dq, dk, dv = _bwd_pack2(q, k, v, o, lse0, lse1, do, scale=scale,
                            causal=causal, block_q=bwd_block_q,
                            block_k=bwd_block_k,
                            interpret=_use_interpret())
    return dq, dk, dv


_flash_pack2.defvjp(_flash_pack2_fwd, _flash_pack2_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_pack2_rope(q, k, v, cos2, sinm, scale, causal, block_q,
                      block_k, bwd_block_q, bwd_block_k):
    o, _, _ = _fwd_pack2(q, k, v, scale=scale, causal=causal,
                         block_q=block_q, block_k=block_k,
                         interpret=_use_interpret(), rope=(cos2, sinm))
    return o


def _flash_pack2_rope_fwd(q, k, v, cos2, sinm, scale, causal, block_q,
                          block_k, bwd_block_q, bwd_block_k):
    o, lse0, lse1 = _fwd_pack2(q, k, v, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               interpret=_use_interpret(),
                               rope=(cos2, sinm))
    return o, (q, k, v, cos2, sinm, o, lse0, lse1)


def _flash_pack2_rope_bwd(scale, causal, block_q, block_k, bwd_block_q,
                          bwd_block_k, res, do):
    q, k, v, cos2, sinm, o, lse0, lse1 = res
    dq, dk, dv = _bwd_pack2(q, k, v, o, lse0, lse1, do, scale=scale,
                            causal=causal, block_q=bwd_block_q,
                            block_k=bwd_block_k,
                            interpret=_use_interpret(),
                            rope=(cos2, sinm))
    return dq, dk, dv, None, None


_flash_pack2_rope.defvjp(_flash_pack2_rope_fwd, _flash_pack2_rope_bwd)


def segment_attention(q, k, v, segment_ids, *, causal: bool = True,
                      scale: Optional[float] = None):
    """Packed-batch attention: block-diagonal masking by segment.

    q, k, v: ``[B, S, H, D]``; ``segment_ids``: ``[B, S]`` int32, the
    sample packer's per-row document index (1-based; ``0`` = padding).
    Position ``i`` attends to ``j`` iff ``seg[i] == seg[j]``, both are
    nonzero, and (``causal``) ``j <= i`` — co-packed documents never
    see each other, which is what makes a packed forward equal the
    per-document unpacked forward (asserted in
    ``tests/test_data_plane.py``).

    XLA formulation (f32 scores/stats, masked online-softmax-free):
    the per-batch ``[B, S, S]`` mask has no Pallas kernel yet — the
    flash/pack2 schedules decline packed batches through
    :func:`flash_attention`'s reasoned gate and land here.  Fully
    masked rows (padding queries) normalize against a floor so they
    produce zeros, not NaNs; their targets are ``-1`` so no loss or
    gradient flows through them.
    """
    B, S, H, D = q.shape
    if scale is None:
        scale = D ** -0.5
    seg = segment_ids.astype(jnp.int32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    mask = (seg[:, None, :, None] == seg[:, None, None, :]) \
        & (seg[:, None, :, None] > 0)
    if causal:
        causal_m = jnp.tril(jnp.ones((S, S), bool))
        mask = mask & causal_m[None, None]
    s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, -1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, -1, keepdims=True)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    l_q = jnp.swapaxes(l, 1, 2)              # [B, S, H, 1]
    return (o / jnp.maximum(l_q, 1e-30)).astype(q.dtype)


def xla_attention(q, k, v, *, causal: bool = True,
                  scale: Optional[float] = None,
                  window: Optional[int] = None):
    """The einsum formulation of what the single-head kernels compute:
    q [B, S, H, D] against k, v [B, Sk, Hkv, D], query head ``h`` on K/V
    head ``h // (H // Hkv)``, key ``c`` visible to row ``r`` iff ``c <=
    r`` (``causal``) and ``r - c < window``; float32 scores and softmax.
    What the kernels are tested against, and what runs where no grid
    tiles the shape."""
    B, S, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    if scale is None:
        scale = D ** -0.5
    qg = q.reshape(B, S, Hkv, H // Hkv, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        r = jnp.arange(S)[:, None] + (Sk - S)
        c = jnp.arange(Sk)[None, :]
        keep = c <= r
        if window is not None:
            keep = keep & (r - c < window)
        s = jnp.where(keep, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, S, H, D).astype(q.dtype)


def supports(S: int, Sk: int, D: int, *, block_q: int = 1024,
             block_k: int = 1024) -> bool:
    """Shapes the kernel grid can tile (fallback to einsum otherwise)."""
    bq, bk = min(block_q, S), min(block_k, Sk)
    return (S % bq == 0 and Sk % bk == 0 and D <= 256
            and bq % 8 == 0 and bk % 128 == 0)


def _tiling_block(block: int, n: int) -> int:
    """``block``, halved while it does not tile ``n`` rows (1024 -> 512
    at 1536), never under the 128 a block needs."""
    block = min(block, n)
    while block > 128 and n % block:
        block //= 2
    return block


def _pack2_plan(S, Sk, H, D, causal, block_q, block_k, bwd_block_q,
                bwd_block_k, pack2):
    """(pbq, pbk, pbwq, pbwk) if the packed schedule applies, else None.

    The single source of the pack2 dispatch decision — shared by
    ``flash_attention`` and the reporting helper ``uses_pack2`` so the
    bench can't claim a schedule the kernel silently declined."""
    cfg = attention_config()
    if pack2 is None:
        pack2 = cfg.pack2
    if not (pack2 and D == 64 and H % 2 == 0 and H > 0):
        return None
    Dp = 2 * D
    pbq = _tiling_block(min(block_q, cfg.pack2_block_q), S)
    pbk = _tiling_block(min(block_k, cfg.pack2_block_k), Sk)
    # the backward walks the same schedule, so it takes the same blocks
    # unless the call pins its own
    pbwq = pbq if bwd_block_q is None else min(pbq, bwd_block_q)
    pbwk = pbk if bwd_block_k is None else min(pbk, bwd_block_k)
    # packed backward only has the strip-mined fused path: dk/dv ride
    # in [Sk, 128] f32 VMEM scratch
    ok = (supports(S, Sk, Dp, block_q=pbq, block_k=pbk)
          and supports(S, Sk, Dp, block_q=pbwq, block_k=pbwk)
          and Sk * Dp * 4 * 2 <= 8 * 1024 * 1024)
    return (pbq, pbk, pbwq, pbwk) if ok else None


def uses_pack2(S: int, Sk: int, H: int, D: int, *, causal: bool = True,
               block_q: int = 1024, block_k: int = 1024,
               pack2: Optional[bool] = None) -> bool:
    """Whether :func:`flash_attention` takes the packed schedule for
    this shape under the current :func:`attention_config`."""
    return _pack2_plan(S, Sk, H, D, causal, block_q, block_k, None,
                       None, pack2) is not None


def causal_coverage(S: int, Sk: int, block_q: int, block_k: int,
                    sub: Optional[int],
                    window: Optional[int] = None) -> float:
    """The share of the ``S x Sk`` score square a causal schedule of
    ``block_q x block_k`` blocks executes when its diagonal blocks are
    walked in ``sub``-edged sub-tiles (None: masked whole) — counted by
    the predicates and the walk the packed kernels unroll with.  Causal
    attention needs ``1/2 + 1/(2*S)`` of a square.  With ``window``
    (the single-head schedule's: whole blocks, ``sub`` None) the blocks
    :func:`_block_live` passes are counted, each whole."""
    bq, bk = min(block_q, S), min(block_k, Sk)
    if window is not None:
        live = sum(bool(_block_live(i, j, causal=True, block_q=bq,
                                    block_k=bk, window=window))
                   for i in range(S // bq) for j in range(Sk // bk))
        return live * bq * bk / (S * Sk)
    done = 0
    for i in range(S // bq):
        for j in range(Sk // bk):
            d = i * bq - j * bk
            if _is_interior(d, bk):
                done += bq * bk
            elif _is_diagonal(d, bq, bk):
                done += sum((r1 - r0) * L
                            for r0, r1, L, _ in _diag_rows(bq, bk, sub, d))
    return done / (S * Sk)


def needed_coverage(S: int, window: Optional[int] = None) -> float:
    """The share of the ``S x S`` square causal attention needs: the
    pairs a row sees, its own key and at most ``window - 1`` before."""
    if window is None or window >= S:
        return (S + 1) / (2 * S)
    return (window * (window + 1) / 2 + (S - window) * window) / (S * S)


def train_causal_coverage(S: int, H: int, D: int, *, block_q: int = 1024,
                          block_k: int = 1024,
                          pack2: Optional[bool] = None,
                          window: Optional[int] = None,
                          grouped: bool = False) -> float:
    """:func:`causal_coverage` of the schedule :func:`flash_attention`
    takes for a causal train step at this shape, the mean over its seven
    score-sized matmuls (two forward, five backward, each under its own
    blocks).  The single-head schedule, which is also the one a
    ``window`` or ``grouped`` K/V heads take, skips the blocks wholly
    above the diagonal or behind the window and masks every block it
    runs, whole; a shape no grid tiles runs the einsum, the whole
    square."""
    plan = None if (window is not None or grouped) else _pack2_plan(
        S, S, H, D, True, block_q, block_k, None, None, pack2)
    if plan is not None:
        fwd, bwd = plan[:2], plan[2:]
        subs = _causal_sub(*fwd), _causal_sub(*bwd)
    else:
        cfg = attention_config()
        fwd = block_q, block_k
        bwd = min(block_q, cfg.bwd_block_q), min(block_k, cfg.bwd_block_k)
        if not (supports(S, S, D, block_q=fwd[0], block_k=fwd[1])
                and supports(S, S, D, block_q=bwd[0], block_k=bwd[1])):
            return 1.0
        subs = None, None
    return (2 * causal_coverage(S, S, *fwd, subs[0], window)
            + 5 * causal_coverage(S, S, *bwd, subs[1], window)) / 7


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None, block_q: int = 1024,
                    block_k: int = 1024,
                    bwd_block_q: Optional[int] = None,
                    bwd_block_k: Optional[int] = None,
                    positions=None,
                    rope_theta=10000.0,
                    pack2: Optional[bool] = None,
                    segment_ids=None,
                    window: Optional[int] = None):
    """Fused causal attention.  q: [B, S, H, D]; k, v: [B, Sk, Hkv, D]
    with ``H`` a multiple of ``Hkv`` (query head ``h`` reads K/V head
    ``h // (H // Hkv)``) -> [B, S, H, D].

    Drop-in for ``ray_tpu.parallel.ring_attention.local_attention``;
    falls back to the einsum path for shapes the grid cannot tile.

    ``block_q``/``block_k`` tile the forward grid; ``bwd_block_q``/
    ``bwd_block_k`` tile the strip-mined backward independently.  The
    packed schedule carries the causal structure itself (interior
    blocks unmasked, diagonal blocks walked by sub-tile), so a big
    block costs it no coverage and it takes 1024 forward and backward
    (fewer grid steps, K/V fetched and rotated once: measured on a
    v5e, PR 54).  The single-head schedule (head_dim 128, odd head
    counts, grouped K/V heads, window layers) skips whole blocks and
    masks every block it runs: its forward takes blocks of 1024 (one
    block at 1024 tokens: per-grid-step overhead outweighs the causal
    skip there, and so did the walk's smaller matmuls when it was
    tried, PR 54; 36 of 64 block pairs at 8192, 15 with a window of
    1024), its backward walks kv strips of 512 inside the kernel and
    skips the dead ones, above the diagonal or behind the window.

    ``positions`` [S] enables fused RoPE: q/k are rotated inside the
    kernels (zero extra HBM passes) when the kv sequence fits one
    block; otherwise the rotation is applied here before dispatch
    (same math as ``ray_tpu.models.gpt._rope``).

    ``pack2`` (default: :func:`attention_config`) selects the two-head
    lane-packed schedule for head_dim 64 / even head counts; odd head
    counts, other head dims and untileable shapes use the single-head
    schedule regardless.

    ``window`` (a row sees its own key and the ``window - 1`` before
    it) and K/V heads fewer than query heads take the single-head
    schedule: its grids carry the K/V head in their index maps, skip the
    blocks behind the window like the ones above the diagonal, and sum
    ``dk`` / ``dv`` over a K/V head's query heads in VMEM.
    ``rope_theta`` may be a layer kind's :class:`Rope`.

    ``segment_ids`` [B, S] (sample-packed batches) is a reasoned
    decline of every Pallas schedule: the per-batch block-diagonal
    mask has no kernel yet, so RoPE (when ``positions`` is given) is
    applied here and the XLA :func:`segment_attention` formulation
    runs — loud in timelines as ``attn/segment_xla``.
    """
    B, S, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    grouped = Hkv != H
    if H % Hkv:
        raise ValueError(f"{H} query heads do not divide into {Hkv} "
                         "K/V heads")
    cfg = attention_config()
    if scale is None:
        scale = D ** -0.5
    if positions is not None and S != Sk:
        raise ValueError(f"rope needs q and kv positions to match: "
                         f"S={S} vs Sk={Sk}")
    if window is not None and not causal:
        raise ValueError("a window is a causal layer's: causal=False "
                         "with window= has no kernel or formulation")
    if segment_ids is not None:
        if window is not None or grouped:
            raise NotImplementedError(
                "segment_ids (sample-packed batches) with a window or "
                "grouped K/V heads: ops/attention.py:segment_attention "
                "has neither")
        if positions is not None:
            q = rope_rotate(q, positions, rope_theta)
            k = rope_rotate(k, positions, rope_theta)
        with jax.named_scope("attn/segment_xla"):
            return segment_attention(q, k, v, segment_ids,
                                     causal=causal, scale=scale)

    plan = None if (window is not None or grouped) else _pack2_plan(
        S, Sk, H, D, causal, block_q, block_k, bwd_block_q, bwd_block_k,
        pack2)
    if plan is not None:
        pbq, pbk, pbwq, pbwk = plan
        Dp = 2 * D
        fuse_rope = (positions is not None and S == Sk
                     and Sk * Dp * 8 <= 8 * 1024 * 1024)
        if positions is not None and not fuse_rope:
            q = rope_rotate(q, positions, rope_theta)
            k = rope_rotate(k, positions, rope_theta)
        # pairing heads (2h, 2h+1) along lanes is a pure reshape in
        # the [B, S, H, D] model layout
        qp = jnp.swapaxes(q.reshape(B, S, H // 2, Dp), 1, 2)
        kp = jnp.swapaxes(k.reshape(B, Sk, H // 2, Dp), 1, 2)
        vp = jnp.swapaxes(v.reshape(B, Sk, H // 2, Dp), 1, 2)
        with jax.named_scope("attn/pack2"):
            if fuse_rope:
                cos2, sinm = rope_tables(positions, D, rope_theta,
                                         q.dtype)
                cos2 = jnp.concatenate([cos2, cos2], -1)  # [S, 128]
                sinm = jnp.concatenate([sinm, sinm], -1)
                op = _flash_pack2_rope(qp, kp, vp, cos2, sinm, scale,
                                       causal, pbq, pbk, pbwq, pbwk)
            else:
                op = _flash_pack2(qp, kp, vp, scale, causal, pbq, pbk,
                                  pbwq, pbwk)
            return jnp.swapaxes(op, 1, 2).reshape(B, S, H, D)

    if bwd_block_q is None:
        bwd_block_q = cfg.bwd_block_q if causal else block_q
        bwd_block_q = min(block_q, bwd_block_q)
    if bwd_block_k is None:
        bwd_block_k = cfg.bwd_block_k if causal else block_k
        bwd_block_k = min(block_k, bwd_block_k)
    kernel_ok = (supports(S, Sk, D, block_q=block_q, block_k=block_k)
                 and supports(S, Sk, D, block_q=bwd_block_q,
                              block_k=bwd_block_k))
    # in-kernel rope needs the strip-mined fused backward (kv rides as
    # one block; bound matches _bwd's VMEM-scratch budget)
    fuse_rope = (positions is not None and kernel_ok
                 and S == Sk and Sk * D * 8 <= 8 * 1024 * 1024)
    if positions is not None and not fuse_rope:
        q = rope_rotate(q, positions, rope_theta)
        k = rope_rotate(k, positions, rope_theta)
    if not kernel_ok:
        with jax.named_scope("attn/xla"):
            if window is not None or grouped:
                return xla_attention(q, k, v, causal=causal, scale=scale,
                                     window=window)
            from ray_tpu.parallel.ring_attention import local_attention
            return local_attention(q, k, v, causal=causal, scale=scale)
    with jax.named_scope("attn/flash"):
        qt = jnp.swapaxes(q, 1, 2)
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        if fuse_rope:
            cos2, sinm = rope_tables(positions, D, rope_theta, q.dtype)
            o = _flash_bhsd_rope(qt, kt, vt, cos2, sinm, scale, causal,
                                 block_q, block_k, bwd_block_q,
                                 bwd_block_k, window)
        else:
            o = _flash_bhsd(qt, kt, vt, scale, causal, block_q,
                            block_k, bwd_block_q, bwd_block_k, window)
        return jnp.swapaxes(o, 1, 2)


# ---------------------------------------------------------------------------
# decode attention over the paged KV pool (inference engine)
#
# One query token per sequence against the pages its slot holds, read
# where they lie: K and V are the cache's whole stacked pools
# ``[L, P, H, D, page]`` (the page offset minor, so a page of one layer
# is ``H`` lane-dense ``[D, page]`` tiles), and the kernel's K/V block
# is one page of one layer, all heads.  The grid is the decode's work
# list, one step per *live* page: ``(slot, page)`` pairs in slot order,
# compacted outside the kernel from ``lengths`` and the page table and
# read from scalar-prefetch SMEM; its bound is the list's (traced)
# length, so a decode costs what is live, not what the table could
# hold.  A slot's steps are consecutive (its output block stays in VMEM
# across them, the pipeline fetches the next page meanwhile); a slot
# that holds nothing is never visited and reads as zeros.  The single
# query row per head is broadcast to 8 sublanes, which the TPU tiling
# can block (row 0 is returned); online softmax as in ``_fwd_kernel``.
# ---------------------------------------------------------------------------

_DECODE_QROWS = 8      # sublane-pad the single query row to a tileable block


def _decode_kernel(slot_ref, j_ref, page_ref, layer_ref, len_ref, q_ref,
                   k_ref, v_ref, *rest, scale: float, page: int,
                   quantized: bool = False):
    """q [1, H, QROWS, D]; k, v [1, 1, H, D, page] (one live page, all
    heads).  ``quantized`` (static): K/V arrive as int8 codes plus
    per-(head, position) f32 scale blocks ``[1, 1, H, page]`` and are
    dequantized *inside* the page (the scales applied to the page's
    score/probability rows).  One body for both modes so the scratch
    discipline cannot diverge."""
    del page_ref, layer_ref                  # the index maps read them
    if quantized:
        ks_ref, vs_ref, _zeros, o_ref, acc_sc, m_sc, l_sc = rest
    else:
        _zeros, o_ref, acc_sc, m_sc, l_sc = rest
    g = pl.program_id(0)
    j, n = j_ref[g], len_ref[slot_ref[g]]    # this slot's j-th page of n rows

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    q = q_ref[0]                             # [H, QROWS, D]
    k = k_ref[0, 0]                          # [H, D, page]
    v = v_ref[0, 0]
    if quantized:
        # one scale per (head, position) = per column of k/v: it
        # commutes out of both matmuls onto the [H, QROWS, page] score /
        # probability rows (lane-major scales, page*D fewer multiplies)
        q = q.astype(jnp.float32)
        k = k.astype(jnp.float32)
        v = v.astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale      # [H, QROWS, page]
    if quantized:
        s = s * ks_ref[0, 0][:, None, :]
    col = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(col < n, s, _NEG_INF)
    m_prev = m_sc[:]                         # [H, QROWS, 128] (col-bcast)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :, :1])
    l_sc[:] = l_sc[:] * alpha + jnp.sum(p, 2, keepdims=True)
    if quantized:
        p = p * vs_ref[0, 0][:, None, :]
    acc_sc[:] = (acc_sc[:] * alpha[:, :, :1]
                 + jax.lax.dot_general(
                     p.astype(v.dtype), v, (((2,), (2,)), ((0,), (0,))),
                     preferred_element_type=jnp.float32))
    m_sc[:] = m_new

    @pl.when((j + 1) * page >= n)            # the slot's last live page
    def _finalize():
        o_ref[0] = (acc_sc[:]
                    / jnp.maximum(l_sc[:, :, :1], 1e-30)).astype(
                        o_ref.dtype)


def _live_pages(lengths, page_table, page: int):
    """The work list of a decode: ``(count, slot, j, page)`` — for each
    of the ``count`` live pages, slot by slot in order, its slot, its
    index in the slot's row of ``page_table`` and the pool page there
    (lists ``[B * max_pages]`` long, meaningless past ``count``)."""
    B, max_pages = page_table.shape
    n = jnp.minimum((lengths + page - 1) // page, max_pages)
    ends = jnp.cumsum(n)
    g = jnp.arange(B * max_pages, dtype=jnp.int32)
    slot = jnp.minimum((g[:, None] >= ends[None, :]).sum(1), B - 1)
    j = jnp.clip(g - (ends - n)[slot], 0, max_pages - 1)
    return ends[-1], slot, j, page_table[slot, j]


def _decode_supports(D: int, page: int, quantized: bool) -> bool:
    """Pool shapes the decode kernel can block: whole 128-lane pages,
    and a head_dim that fills the sublane tiles of the pool's dtype."""
    return page % 128 == 0 and D % (32 if quantized else 16) == 0


def decode_uses_pallas(D: int, page: int, *, quantized: bool = False,
                       impl: str = "auto") -> bool:
    """Whether :func:`decode_attention` runs the Pallas kernel for this
    pool geometry and ``impl`` — the single source of the decision (the
    engine reports it).  ``"auto"``: the kernel wherever kernels are
    compiled (a TPU) and the pool blocks, the einsum where the CPU was
    asked for; ``use_interpret`` refuses a backend nobody asked for."""
    if impl == "pallas":
        return True
    return (impl == "auto" and not _use_interpret()
            and _decode_supports(D, page, quantized))


def decode_attention(q, k, v, lengths, page_table, layer=0, *,
                     scale: Optional[float] = None, impl: str = "auto",
                     k_scale=None, v_scale=None):
    """Single-token decode attention over the paged KV pool, in place.

    q: [B, H, D] — the current token's (already-rotated) queries;
    k, v: [L, P, H, D, page] — the cache's whole stacked pools;
    lengths: [B] int32 — valid context length per sequence (including
    the current token, whose K/V the caller has already written; 0: the
    slot holds nothing and reads as zeros); page_table: [B, max_pages]
    int32 — the pages of each sequence in order (entries past
    ``ceil(lengths / page)`` are never read); layer: int32 scalar,
    traced or not.  Returns [B, H, D] in q's dtype.

    ``k_scale``/``v_scale`` ([L, P, H, page] f32, both or neither): the
    pool is block-scaled int8 (``kv_dtype="int8"`` caches) and is
    dequantized here — inside the kernel's page blocks on the Pallas
    path, as a fused ``codes * scale`` element-wise on the XLA path —
    so the int8 cache is never materialized wide.

    ``impl``: "pallas" (one live page a grid step; raises for pools it
    cannot block), "xla" (masked einsum over the gathered pages, runs
    anywhere), or "auto" (:func:`decode_uses_pallas`; interpret-mode
    parity for the kernel lives in ``tests/test_ops.py``)."""
    B, H, D = q.shape
    page = k.shape[-1]
    max_pages = page_table.shape[1]
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale/v_scale must be passed together")
    quantized = k_scale is not None
    if scale is None:
        scale = D ** -0.5
    lengths = lengths.astype(jnp.int32)
    page_table = page_table.astype(jnp.int32)
    if impl == "pallas" and not _decode_supports(D, page, quantized):
        raise ValueError(f"decode kernel cannot block page={page}, D={D} "
                         f"of a {k.dtype} pool")
    if not decode_uses_pallas(D, page, quantized=quantized, impl=impl):
        with jax.named_scope("attn/decode_xla"):
            k, v = k[layer, page_table], v[layer, page_table]
            if quantized:
                # masked-einsum fallback: dequantize as one fused
                # elementwise (XLA folds it into the gather consumers)
                k = (k.astype(jnp.float32)
                     * k_scale[layer, page_table][:, :, :, None]
                     ).astype(q.dtype)
                v = (v.astype(jnp.float32)
                     * v_scale[layer, page_table][:, :, :, None]
                     ).astype(q.dtype)
            s = jnp.einsum("bhd,bphdk->bhpk", q, k,
                           preferred_element_type=jnp.float32) * scale
            pos = (jnp.arange(max_pages)[:, None] * page
                   + jnp.arange(page)[None, :])
            mask = pos[None, None] < lengths[:, None, None, None]
            s = jnp.where(mask, s, _NEG_INF)
            m = jnp.max(s, (2, 3), keepdims=True)
            p = jnp.where(mask, jnp.exp(s - m), 0.0)
            l = jnp.sum(p, (2, 3))[..., None]              # [B, H, 1]
            o = jnp.einsum("bhpk,bphdk->bhd", p.astype(v.dtype), v,
                           preferred_element_type=jnp.float32)
            return (o / jnp.maximum(l, 1e-30)).astype(q.dtype)
    # everything this path does is under its name: the work list, the
    # query's rows, the zeros the output starts as (aliased in: a slot
    # with no live page is no step of the grid and keeps them)
    with jax.named_scope("attn/decode_pallas" + "_int8" * quantized):
        count, slot, j, pages = _live_pages(lengths, page_table, page)
        qp = jnp.broadcast_to(q[:, :, None, :], (B, H, _DECODE_QROWS, D))
        q_spec = pl.BlockSpec((1, H, _DECODE_QROWS, D),
                              lambda g, slot, *_: (slot[g], 0, 0, 0))
        kv_spec = pl.BlockSpec(
            (1, 1, H, D, page),
            lambda g, slot, j, pages, lay, lens: (lay[0], pages[g], 0, 0, 0))
        in_specs, args = [q_spec, kv_spec, kv_spec], [qp, k, v]
        if quantized:
            in_specs += [pl.BlockSpec(
                (1, 1, H, page), lambda g, slot, j, pages, lay, lens:
                (lay[0], pages[g], 0, 0))] * 2
            args += [k_scale, v_scale]
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        args.append(jnp.zeros(qp.shape, q.dtype))
        out = pl.pallas_call(
            functools.partial(_decode_kernel, scale=scale, page=page,
                              quantized=quantized),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(count,),
                in_specs=in_specs,
                out_specs=q_spec,
                scratch_shapes=[
                    pltpu.VMEM((H, _DECODE_QROWS, D), jnp.float32),
                    pltpu.VMEM((H, _DECODE_QROWS, 128), jnp.float32),
                    pltpu.VMEM((H, _DECODE_QROWS, 128), jnp.float32),
                ],
            ),
            compiler_params=_CompilerParams(
                dimension_semantics=("arbitrary",)),
            out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
            input_output_aliases={5 + len(args) - 1: 0},
            interpret=_use_interpret(),
        )(slot, j, pages, jnp.asarray(layer, jnp.int32).reshape(1),
          lengths, *args)
        return out[:, :, 0]


def make_flash_attention_fn(mesh=None, *, causal: bool = True,
                            block_q: int = 1024, block_k: int = 1024,
                            rope_theta=None,
                            pack2: Optional[bool] = None,
                            window: Optional[int] = None,
                            kv_heads: Optional[int] = None,
                            rope=None):
    """Mesh-aware flash attention (drop-in for ``make_ring_attention_fn``).

    A ``pallas_call`` has no SPMD partitioning rule, so on a >1-device
    mesh the kernel runs under ``shard_map``: batch over (dp, fsdp),
    heads over tp — each device runs the kernel on its local shard.
    Sequence stays unsharded (sp>1 uses ring attention instead).

    With ``rope_theta`` the returned fn accepts ``positions`` and
    applies RoPE inside the kernels (``fn.fused_rope`` marks this so
    the model skips its own rotation).

    ``pack2`` pins the two-head lane-packing choice (default: the
    process-wide :func:`attention_config`); note a tp-sharded mesh
    hands each device its *local* head count, which is what the
    even-head gate sees.

    One hook serves one kind of layer: ``window`` (its rows see that
    many keys), ``kv_heads`` (K/V heads, where fewer than the query
    heads; it is what the hook's counts are told, the kernels read it
    from the shapes) and ``rope`` (the kind's :class:`Rope`, in place
    of ``rope_theta``) are that kind's, and the hook carries them as
    attributes with ``coverage(S, H, D)``: the share of the ``S x S``
    square its schedule executes beside the share the layer needs.
    On a mesh that shards heads (tp > 1) a K/V group has no rule here
    and is refused.
    """
    if rope is not None:
        rope_theta = rope
    fn = functools.partial(flash_attention, causal=causal,
                           block_q=block_q, block_k=block_k,
                           pack2=pack2)
    if window is not None:
        fn = functools.partial(fn, window=window)
    if rope_theta is not None:
        fn = functools.partial(fn, rope_theta=rope_theta)
    one_device = mesh is None or getattr(mesh, "size", 1) <= 1
    tp_size = 1 if one_device else mesh.shape.get("tp", 1)
    if kv_heads is not None and tp_size > 1:
        raise NotImplementedError(
            "grouped K/V heads on a mesh that shards heads (tp > 1): "
            "make_flash_attention_fn's shard_map gives each device its "
            "query heads' slice and knows no K/V head's")

    def causal_coverage(S: int, H: int, D: int) -> float:
        """The share of the score square the schedule this fn takes for
        ``H`` global heads executes (:func:`train_causal_coverage`)."""
        return train_causal_coverage(
            S, H // tp_size, D, block_q=block_q, block_k=block_k,
            pack2=pack2, window=window,
            grouped=kv_heads not in (None, H)) if causal else 1.0

    def coverage(S: int, H: int, D: int) -> dict:
        return {"executed": causal_coverage(S, H, D),
                "needed": needed_coverage(S, window) if causal else 1.0}

    def marked(hook, fused: bool):
        hook.fused_rope = fused
        hook.causal_coverage = causal_coverage
        hook.coverage = coverage
        hook.window = window
        hook.kv_heads = kv_heads
        return hook

    if one_device:
        return marked(fn, rope_theta is not None)

    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.compat import shard_map
    from ray_tpu.parallel.sharding import data_axes

    tp = "tp" if mesh.shape.get("tp", 1) > 1 else None
    spec = P(data_axes(mesh), None, tp, None)
    bseq = P(data_axes(mesh), None)     # [B, S] leaves (packed batches)

    # packed (segment_ids) batches shard over batch like q/k/v; rope —
    # when fused — is applied per-shard from the per-row positions
    # before the XLA segment formulation (pallas declines anyway)
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(spec,) * 3 + (bseq, bseq),
                       out_specs=spec)
    def sharded_seg_rope(q, k, v, positions, segment_ids):
        q = rope_rotate(q, positions, rope_theta or 10000.0)
        k = rope_rotate(k, positions, rope_theta or 10000.0)
        return segment_attention(q, k, v, segment_ids, causal=causal)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(spec,) * 3 + (bseq,), out_specs=spec)
    def sharded_seg(q, k, v, segment_ids):
        return segment_attention(q, k, v, segment_ids, causal=causal)

    if rope_theta is not None:
        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(spec,) * 3 + (P(None),),
                           out_specs=spec)
        def sharded(q, k, v, positions):
            return fn(q, k, v, positions=positions)

        def wrapped(q, k, v, positions, segment_ids=None):
            if segment_ids is not None:
                if positions.ndim == 1:      # one spec: always [B, S]
                    positions = jnp.broadcast_to(
                        positions[None], segment_ids.shape)
                return sharded_seg_rope(q, k, v, positions,
                                        segment_ids)
            return sharded(q, k, v, positions)

        return marked(wrapped, True)

    @functools.partial(shard_map, mesh=mesh, in_specs=(spec,) * 3,
                       out_specs=spec)
    def sharded(q, k, v):
        return fn(q, k, v)

    def sharded_fn(q, k, v, segment_ids=None):
        if segment_ids is not None:
            return sharded_seg(q, k, v, segment_ids)
        return sharded(q, k, v)

    return marked(sharded_fn, False)


# ---------------------------------------------------------------------------
# the decode's cache write, in place
#
# One new K and V row per live slot, laid into the slot's tail page of
# one layer where the page lies.  The page offset is the pool's minor
# dimension, so a row is one lane of every ``[D, page]`` tile of its
# page and cannot be written narrower than the page: a grid step moves
# one tail page of K and of V in, lays the row over lane ``offset`` and
# moves them out, and the pools are aliased in and out, so the pages no
# step visits keep what they held and nothing of a pool is copied.  The
# grid is the live slots, compacted outside the kernel as
# :func:`_live_pages` compacts the live pages; a decode pays for the
# slots that hold a sequence, not for the table's rows.
# ---------------------------------------------------------------------------

def _write_kernel(slot_ref, page_ref, off_ref, layer_ref, kn_ref, vn_ref,
                  k_ref, v_ref, ko_ref, vo_ref):
    """kn, vn [1, H, D] (the slot's new rows); k, v, ko, vo
    [1, 1, H, D, page] (its tail page, in and out).  A row arrives with
    head_dim on the lanes and leaves as one lane of ``[D, page]`` tiles:
    it is turned once, ``[H, D] -> [D, H]`` in float32 (exact for every
    pool dtype), and each head's column is spread over the lanes."""
    del slot_ref, page_ref, layer_ref        # the index maps read them
    H, D, page = k_ref.shape[2:]
    off = off_ref[pl.program_id(0)]
    hit = jax.lax.broadcasted_iota(jnp.int32, (D, page), 1) == off
    for new_ref, in_ref, out_ref in ((kn_ref, k_ref, ko_ref),
                                     (vn_ref, v_ref, vo_ref)):
        cols = new_ref[0].astype(jnp.float32).T              # [D, H]
        for h in range(H):
            col = jnp.broadcast_to(cols[:, h:h + 1], (D, page))
            out_ref[0, 0, h] = jnp.where(hit, col.astype(out_ref.dtype),
                                         in_ref[0, 0, h])


def _live_rows(lengths, page_table, page: int, skip_page: int):
    """The work list of a decode's write: ``(count, slot, page, offset)``
    — for each of the ``count`` slots whose tail page is not
    ``skip_page``, in slot order, the slot, the pool page its next row
    lands in and the row's offset there (lists ``[B]`` long, meaningless
    past ``count``).  A slot whose length has run off its row of the
    table (a full sequence that sits this decode out) is no step."""
    B, max_pages = page_table.shape
    j = lengths // page
    tail = jnp.where(j < max_pages,
                     page_table[jnp.arange(B), jnp.minimum(j, max_pages - 1)],
                     skip_page)
    ends = jnp.cumsum(tail != skip_page)
    g = jnp.arange(B, dtype=jnp.int32)
    slot = jnp.minimum((g[:, None] >= ends[None, :]).sum(1), B - 1)
    return ends[-1], slot, tail[slot], (lengths % page)[slot]


def decode_write_uses_pallas(D: int, page: int, dtype) -> bool:
    """Whether :func:`decode_write` can lay a decode's rows into a
    ``dtype`` pool of this geometry — the single source of the decision
    (the cache's writer asks, the engine reports it): wherever kernels
    are compiled (a TPU) and the pool blocks, as for the attention
    (:func:`decode_uses_pallas`).  Where the CPU was asked for the
    cache keeps its whole-page blend; ``use_interpret`` refuses a
    backend nobody asked for."""
    return not _use_interpret() and _decode_supports(
        D, page, jnp.dtype(dtype).itemsize == 1)


def decode_write(k, v, k_new, v_new, lengths, page_table, layer, *,
                 skip_page: int):
    """Lay one new row per live slot into the paged KV pools, in place.

    k, v: [L, P, H, D, page] — the cache's whole stacked pools; k_new,
    v_new: [B, H, D] — each slot's new row; lengths: [B] int32 — the
    row's absolute position in its slot; page_table: [B, max_pages]
    int32; layer: int32 scalar, traced or not.  A slot whose tail page
    (``page_table[b, lengths[b] // page]``) is ``skip_page`` holds no
    sequence and is no step of the grid.  No two live slots may share a
    tail page.  Returns the two pools, which alias the arguments."""
    B, H, D = k_new.shape
    page = k.shape[-1]
    if not _decode_supports(D, page, k.dtype.itemsize == 1):
        raise ValueError(f"write kernel cannot block page={page}, D={D} "
                         f"of a {k.dtype} pool")
    # the work list runs under the kernel's name, and not under the
    # attention's: a trace reads each alone
    with jax.named_scope("attn/write_pallas"):
        count, slot, pages, offs = _live_rows(
            lengths.astype(jnp.int32), page_table.astype(jnp.int32), page,
            skip_page)
        row_spec = pl.BlockSpec((1, H, D),
                                lambda g, slot, *_: (slot[g], 0, 0))
        page_spec = pl.BlockSpec(
            (1, 1, H, D, page),
            lambda g, slot, pages, offs, lay: (lay[0], pages[g], 0, 0, 0))
        return pl.pallas_call(
            _write_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(count,),
                in_specs=[row_spec, row_spec, page_spec, page_spec],
                out_specs=[page_spec, page_spec],
            ),
            compiler_params=_CompilerParams(
                dimension_semantics=("arbitrary",)),
            out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                       jax.ShapeDtypeStruct(v.shape, v.dtype)],
            input_output_aliases={6: 0, 7: 1},
            interpret=_use_interpret(),
        )(slot, pages, offs, jnp.asarray(layer, jnp.int32).reshape(1),
          k_new.astype(k.dtype), v_new.astype(v.dtype), k, v)


# ---------------------------------------------------------------------------
# decode over a latent pool (latent attention: one row a token, shared by
# every head), in place
#
# The pool is ``[L, P, R, page]``: a row is ``R = rank + rope`` values, the
# latent vector and the rotary key part side by side, the page offset minor,
# so a page of one layer is one lane-dense ``[R, page]`` block.  A decode's
# query arrives *absorbed* (``absorb_query`` below): ``[B, H,
# R]``, so that a head's score against a token is its dot product with the
# row as it is stored, and the values are the rows' first ``value_dim``
# entries; every head reads the same block, so a step is two plain matrix
# products, ``[H, R] x [R, page]`` and ``[H, page] x [page, value_dim]``:
# compute and bandwidth side by side, where the K/V decode above is
# bandwidth alone.  A page is 147 KB at 576 values, which the chip moves in
# less time than a grid step costs, so a step takes ``_LATENT_GROUP`` of a
# slot's pages: the pool is handed to the kernel that many times, each with
# its own index map over the work list (``_live_page_groups``: a slot's
# pages in groups, the last group filled up with the garbage page, whose
# positions the length masks).  The write's work list and the aliased zeros
# are the K/V kernels' (``_live_rows``).
# ---------------------------------------------------------------------------

_LATENT_GROUP = 4      # pages of one slot a grid step of the decode takes


def _live_page_groups(lengths, page_table, page: int, group: int):
    """The work list of a latent decode: ``(count, slot, j, pages)`` —
    for each of the ``count`` live groups of ``group`` pages, slot by
    slot in order, its slot, its index among the slot's groups and the
    pool pages it holds (``[n * group]``, page 0 where the slot's pages
    end inside the group; lists meaningless past ``count``)."""
    B, max_pages = page_table.shape
    n_pages = jnp.minimum((lengths + page - 1) // page, max_pages)
    n = (n_pages + group - 1) // group
    max_groups = -(-max_pages // group)
    ends = jnp.cumsum(n)
    g = jnp.arange(B * max_groups, dtype=jnp.int32)
    slot = jnp.minimum((g[:, None] >= ends[None, :]).sum(1), B - 1)
    j = jnp.clip(g - (ends - n)[slot], 0, max_groups - 1)
    idx = j[:, None] * group + jnp.arange(group, dtype=jnp.int32)[None, :]
    pages = jnp.where(
        idx < n_pages[slot][:, None],
        page_table[slot[:, None], jnp.minimum(idx, max_pages - 1)], 0)
    return ends[-1], slot, j, pages.reshape(-1)

def latent_decode_uses_pallas(R: int, page: int, dtype) -> bool:
    """Whether a latent pool of this geometry is read
    (:func:`latent_decode_attention`) and written
    (:func:`latent_decode_write`) by the kernels — the single source of
    the decision, as :func:`decode_uses_pallas` is for K and V: wherever
    kernels are compiled (a TPU) and the pool blocks (whole 128-lane
    pages, rows that fill the 16-bit sublane tiles)."""
    return (not _use_interpret() and page % 128 == 0 and R % 16 == 0
            and jnp.dtype(dtype).itemsize == 2)


def _latent_decode_kernel(slot_ref, j_ref, page_ref, layer_ref, len_ref,
                          q_ref, *rest, scale: float, page: int,
                          value_dim: int, group: int):
    """q [1, H, R]; ``group`` blocks c [1, 1, R, page] (a slot's live
    pages, one group of them); o [1, H, value_dim].  Online softmax over
    a slot's consecutive steps, as ``_decode_kernel``, one update a
    group."""
    del page_ref, layer_ref                  # the index maps read them
    c_refs, (_zeros, o_ref, acc_sc, m_sc, l_sc) = rest[:group], rest[group:]
    g = pl.program_id(0)
    j, n = j_ref[g], len_ref[slot_ref[g]]    # this slot's j-th group, n rows

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    q = q_ref[0]
    cs = [c_ref[0, 0] for c_ref in c_refs]                   # [R, page]
    s = jnp.concatenate(
        [jnp.dot(q, c, preferred_element_type=jnp.float32) for c in cs],
        axis=1) * scale                                  # [H, group * page]
    col = j * group * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < n, s, _NEG_INF)
    m_prev = m_sc[:]                         # [H, 128] (col-bcast)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :1])
    l_sc[:] = l_sc[:] * alpha + jnp.sum(p, 1, keepdims=True)
    acc = acc_sc[:] * alpha[:, :1]
    for t, c in enumerate(cs):
        acc = acc + jax.lax.dot_general(
            p[:, t * page:(t + 1) * page].astype(c.dtype), c[:value_dim],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    acc_sc[:] = acc
    m_sc[:] = m_new

    @pl.when((j + 1) * group * page >= n)    # the slot's last live group
    def _finalize():
        o_ref[0] = (acc_sc[:] / jnp.maximum(l_sc[:, :1], 1e-30)).astype(
            o_ref.dtype)


def latent_decode_attention(q, rows, lengths, page_table, layer=0, *,
                            scale: float, value_dim: int):
    """Single-token decode attention over a latent pool, in place.

    q: [B, H, R] — the current token's absorbed queries; rows: [L, P, R,
    page] — the cache's whole stacked pool; lengths, page_table, layer:
    as :func:`decode_attention`; ``scale``: the softmax scale (of the
    unabsorbed head width); ``value_dim``: the leading part of a row
    that is the value.  Returns [B, H, value_dim] in q's dtype, zeros
    for a slot that holds nothing.  The kernel where
    :func:`latent_decode_uses_pallas` says so, a masked einsum over the
    gathered pages elsewhere."""
    B, H, R = q.shape
    page = rows.shape[-1]
    max_pages = page_table.shape[1]
    lengths = lengths.astype(jnp.int32)
    page_table = page_table.astype(jnp.int32)
    if not latent_decode_uses_pallas(R, page, rows.dtype):
        with jax.named_scope("attn/decode_xla"):
            c = rows[layer, page_table]                  # [B, mp, R, page]
            s = jnp.einsum("bhr,bprk->bhpk", q, c,
                           preferred_element_type=jnp.float32) * scale
            pos = (jnp.arange(max_pages)[:, None] * page
                   + jnp.arange(page)[None, :])
            mask = pos[None, None] < lengths[:, None, None, None]
            s = jnp.where(mask, s, _NEG_INF)
            m = jnp.max(s, (2, 3), keepdims=True)
            p = jnp.where(mask, jnp.exp(s - m), 0.0)
            l = jnp.sum(p, (2, 3))[..., None]              # [B, H, 1]
            o = jnp.einsum("bhpk,bprk->bhr", p.astype(c.dtype),
                           c[:, :, :value_dim],
                           preferred_element_type=jnp.float32)
            return (o / jnp.maximum(l, 1e-30)).astype(q.dtype)
    with jax.named_scope("attn/decode_pallas"):
        group = min(_LATENT_GROUP, max_pages)
        count, slot, j, pages = _live_page_groups(lengths, page_table, page,
                                                  group)
        q_spec = pl.BlockSpec((1, H, R), lambda g, slot, *_: (slot[g], 0, 0))
        o_spec = pl.BlockSpec((1, H, value_dim),
                              lambda g, slot, *_: (slot[g], 0, 0))
        c_specs = [pl.BlockSpec(
            (1, 1, R, page),
            lambda g, slot, j, pages, lay, lens, t=t:
            (lay[0], pages[g * group + t], 0, 0)) for t in range(group)]
        return pl.pallas_call(
            functools.partial(_latent_decode_kernel, scale=scale, page=page,
                              value_dim=value_dim, group=group),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(count,),
                in_specs=[q_spec, *c_specs,
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=o_spec,
                scratch_shapes=[
                    pltpu.VMEM((H, value_dim), jnp.float32),
                    pltpu.VMEM((H, 128), jnp.float32),
                    pltpu.VMEM((H, 128), jnp.float32),
                ],
            ),
            compiler_params=_CompilerParams(
                dimension_semantics=("arbitrary",)),
            out_shape=jax.ShapeDtypeStruct((B, H, value_dim), q.dtype),
            input_output_aliases={6 + group: 0},
            interpret=_use_interpret(),
        )(slot, j, pages, jnp.asarray(layer, jnp.int32).reshape(1),
          lengths, q, *([rows] * group),
          jnp.zeros((B, H, value_dim), q.dtype))


def _latent_write_kernel(slot_ref, page_ref, off_ref, layer_ref, new_ref,
                         c_ref, o_ref):
    """new [1, R, page] (the slot's new row, on every lane); c, o [1, 1,
    R, page] (its tail page, in and out): the row is laid over lane
    ``offset``."""
    del slot_ref, page_ref, layer_ref        # the index maps read them
    off = off_ref[pl.program_id(0)]
    hit = jax.lax.broadcasted_iota(jnp.int32, new_ref.shape[1:], 1) == off
    o_ref[0, 0] = jnp.where(hit, new_ref[0], c_ref[0, 0])


def latent_decode_write(rows, new, lengths, page_table, layer, *,
                        skip_page: int):
    """Lay one new row per live slot into a latent pool, in place.

    rows: [L, P, R, page] — the cache's whole stacked pool; new: [B, R]
    — each slot's new row; lengths, page_table, layer, ``skip_page``:
    as :func:`decode_write`.  A row is one lane of its page's block, so
    a step moves the slot's tail page in, lays the row over lane
    ``offset`` and moves it out; the pool is aliased in and out.  The
    row comes spread over a page's lanes (``[B, R, page]``, a few
    megabytes a decode): the kernel then selects, and turns nothing.
    Returns the pool, which aliases the argument."""
    B, R = new.shape
    page = rows.shape[-1]
    with jax.named_scope("attn/write_pallas"):
        count, slot, pages, offs = _live_rows(
            lengths.astype(jnp.int32), page_table.astype(jnp.int32), page,
            skip_page)
        spread = jnp.broadcast_to(new.astype(rows.dtype)[:, :, None],
                                  (B, R, page))
        page_spec = pl.BlockSpec(
            (1, 1, R, page),
            lambda g, slot, pages, offs, lay: (lay[0], pages[g], 0, 0))
        return pl.pallas_call(
            _latent_write_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(count,),
                in_specs=[pl.BlockSpec((1, R, page),
                                       lambda g, slot, *_: (slot[g], 0, 0)),
                          page_spec],
                out_specs=page_spec,
            ),
            compiler_params=_CompilerParams(
                dimension_semantics=("arbitrary",)),
            out_shape=jax.ShapeDtypeStruct(rows.shape, rows.dtype),
            input_output_aliases={5: 0},
            interpret=_use_interpret(),
        )(slot, pages, offs, jnp.asarray(layer, jnp.int32).reshape(1),
          spread, rows)


# ---------------------------------------------------------------------------
# a prefill's attention over a slot's gathered latent context
#
# A bucket's queries sit at absolute positions ``start .. start + S`` and
# attend over the slot's whole gathered context (positions ``0 .. C``, the
# new tokens' own rows among them), each over the keys not past its own
# position.  K and V are materialised from the latent rows by the caller
# (``latent_kv`` below), head-major; the rotary key part is one
# ``[C, rope]`` array every head shares, so a score is two products,
# ``q_nope . k_nope + q_rot . k_rot``, and no ``[C, H, nope + rope]`` key is
# ever laid down.  The kernel is a flash forward with the causal edge moved
# by ``start`` (scalar prefetch): the scores of a ``[block_q, block_k]``
# tile live in VMEM alone, and the key blocks past a query block's last
# position are neither fetched (the index map stops at the edge) nor
# computed.  The masked einsum it replaces wrote and re-read ``[H, S, C]``
# float32 scores through HBM for every softmax pass.
# ---------------------------------------------------------------------------

_PREFILL_BLOCKS_Q = (768, 640, 512, 384, 256, 128)   # the widest that divides S
_PREFILL_BLOCK_K = 512


def latent_prefill_uses_pallas(S: int, C: int, dtype) -> bool:
    """Whether :func:`latent_prefill_attention` runs the kernel for a
    bucket of ``S`` queries over ``C`` gathered positions: wherever
    kernels are compiled (a TPU) and both block."""
    return (not _use_interpret() and S % _PREFILL_BLOCKS_Q[-1] == 0
            and C % _PREFILL_BLOCK_K == 0
            and jnp.dtype(dtype).itemsize == 2)


def _latent_prefill_kernel(start_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref,
                           o_ref, acc_sc, m_sc, l_sc, *, scale: float,
                           block_q: int, block_k: int, num_kv: int):
    """qn [1, bq, nope], qr [1, bq, rope]; kn [1, bk, nope], kr [bk,
    rope], v [1, bk, dv]; o [1, bq, dv]."""
    i, j = pl.program_id(1), pl.program_id(2)
    first = start_ref[0] + i * block_q       # the block's first position

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    @pl.when(j * block_k <= first + block_q - 1)
    def _compute():
        nt = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(qn_ref[0], kn_ref[0], nt,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr_ref[0], kr_ref[...], nt,
                                   preferred_element_type=jnp.float32)
             ) * scale                                       # [bq, bk]
        row = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col <= row, s, _NEG_INF)
        m_prev = m_sc[:]                      # [bq, 128] (col-bcast)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_sc[:] = l_sc[:] * alpha + jnp.sum(p, 1, keepdims=True)
        v = v_ref[0]
        acc_sc[:] = (acc_sc[:] * alpha[:, :1]
                     + jax.lax.dot_general(
                         p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32))
        m_sc[:] = m_new

    @pl.when(j == num_kv - 1)
    def _finalize():
        o_ref[0] = (acc_sc[:] / jnp.maximum(l_sc[:, :1], 1e-30)).astype(
            o_ref.dtype)


def latent_prefill_attention(q_nope, q_rot, k_nope, k_rot, v, start, *,
                             scale: float):
    """Attention of a bucket's queries over a slot's gathered context.

    q_nope [H, S, nope], q_rot [H, S, rope]: the queries, at absolute
    positions ``start + (0 .. S)``; k_nope [H, C, nope], v [H, C, dv]:
    K's unrotated part and V of positions ``0 .. C``, materialised from
    the latent rows; k_rot [C, rope]: the rows' rotary part, shared by
    the heads; start: int32 scalar, traced or not.  A query sees the
    keys at positions not past its own, which also hides what the
    slot's pages hold beyond the prompt.  -> [H, S, dv].  The kernel
    where :func:`latent_prefill_uses_pallas` says so; elsewhere a masked
    einsum, a chunk of queries at a time, so that the scores held at
    once are ``[H, chunk, C]`` whatever the bucket."""
    H, S, _ = q_nope.shape
    C = k_nope.shape[1]
    start = jnp.asarray(start, jnp.int32)
    if not latent_prefill_uses_pallas(S, C, q_nope.dtype):
        with jax.named_scope("attn/prefill_xla"):
            chunk = min(_PREFILL_BLOCKS_Q[-1], S)
            pad = -S % chunk

            def one(args):
                qn, qr, first = args                    # [H, chunk, *]
                s = (jnp.einsum("hqd,hkd->hqk", qn, k_nope,
                                preferred_element_type=jnp.float32)
                     + jnp.einsum("hqd,kd->hqk", qr, k_rot,
                                  preferred_element_type=jnp.float32)
                     ) * scale
                seen = (jnp.arange(C)[None, :]
                        <= first + jnp.arange(chunk)[:, None])
                p = jax.nn.softmax(jnp.where(seen[None], s, _NEG_INF), -1)
                return jnp.einsum("hqk,hkd->hqd", p.astype(v.dtype), v,
                                  preferred_element_type=jnp.float32
                                  ).astype(q_nope.dtype)

            def chunks(q):
                q = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
                return jnp.moveaxis(
                    q.reshape(H, -1, chunk, q.shape[-1]), 1, 0)

            firsts = start + chunk * jnp.arange((S + pad) // chunk)
            o = jax.lax.map(one, (chunks(q_nope), chunks(q_rot), firsts))
            return jnp.moveaxis(o, 0, 1).reshape(H, S + pad, -1)[:, :S]
    # a query block streams its head's K and V once, so the widest
    # block re-reads them least
    bq = next(b for b in _PREFILL_BLOCKS_Q if S % b == 0)
    bk = _PREFILL_BLOCK_K
    num_kv = C // bk

    def kv_block(i, j, start):
        # the last key block a query block reaches; later steps name it
        # again, so nothing past the edge is fetched
        return jnp.minimum(j, (start[0] + (i + 1) * bq - 1) // bk)

    with jax.named_scope("attn/prefill_pallas"):
        q_spec = lambda d: pl.BlockSpec(                    # noqa: E731
            (1, bq, d), lambda h, i, j, start: (h, i, 0))
        kv_spec = lambda d: pl.BlockSpec(                   # noqa: E731
            (1, bk, d), lambda h, i, j, start: (h, kv_block(i, j, start), 0))
        dv = v.shape[-1]
        return pl.pallas_call(
            functools.partial(_latent_prefill_kernel, scale=scale,
                              block_q=bq, block_k=bk, num_kv=num_kv),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(H, S // bq, num_kv),
                in_specs=[q_spec(q_nope.shape[-1]), q_spec(q_rot.shape[-1]),
                          kv_spec(k_nope.shape[-1]),
                          pl.BlockSpec(
                              (bk, k_rot.shape[-1]),
                              lambda h, i, j, start:
                              (kv_block(i, j, start), 0)),
                          kv_spec(dv)],
                out_specs=q_spec(dv),
                scratch_shapes=[
                    pltpu.VMEM((bq, dv), jnp.float32),
                    pltpu.VMEM((bq, 128), jnp.float32),
                    pltpu.VMEM((bq, 128), jnp.float32),
                ],
            ),
            compiler_params=_CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            out_shape=jax.ShapeDtypeStruct((H, S, dv), q_nope.dtype),
            interpret=_use_interpret(),
        )(start.reshape(1), q_nope, q_rot, k_nope, k_rot, v)


# ---------------------------------------------------------------------------
# a latent row's algebra: what joins a model's projections to the two paths
# above.  ``w_kvb`` is a sublayer's ``(W_kb [H, rank, nope], W_vb [H, rank,
# v])``: the K half and the V half of the matrix that expands a latent row
# into a head's key and value, head-major.

def latent_kv(c, w_kvb):
    """Materialise K's unrotated part [H, C, nope] and V [H, C, v],
    head-major, from the cached rows' latent part ``c`` [C, rank]: what a
    prefill attends over (the rows' rotary part is K's other part as it
    is stored, shared by every head)."""
    wk, wv = w_kvb
    return (jnp.einsum("cr,hrk->hck", c, wk),
            jnp.einsum("cr,hrk->hck", c, wv))


def absorb_query(q_nope, q_rot, w_kvb):
    """A decode's query against the latent rows themselves: ``q_nope .
    k_nope = (q_nope W_k^T) . c``, so q [..., H, nope | rope] becomes
    [..., H, rank + rope] and meets a row as it is stored."""
    wk, _ = w_kvb
    return jnp.concatenate(
        [jnp.einsum("...hk,hrk->...hr", q_nope, wk), q_rot], -1)


def expand_output(o_latent, w_kvb):
    """The other half of the absorption: attention's output over the
    latent rows [..., H, rank] -> [..., H, v]."""
    _, wv = w_kvb
    return jnp.einsum("...hr,hrk->...hk", o_latent, wv)
