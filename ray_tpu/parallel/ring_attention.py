"""Ring attention — sequence/context parallelism over an ICI ring.

The reference has NO native sequence parallelism (verified in SURVEY.md
§2.4: Ray delegates long-context to DeepSpeed/Lightning inside the user
fn).  Here it is first-class: K/V shards rotate around the ``sp`` mesh
axis via ``ppermute`` while each device accumulates blockwise attention
for its resident Q shard with an online (streaming) softmax — attention
over sequences of length ``sp * S_local`` with O(S_local^2) memory.

Design (Liu et al. ring attention + flash-attention online softmax):
- one ring step per sp-rank; compute for the resident block overlaps the
  ppermute of the next K/V block (XLA schedules the collective async);
- numerics: scores/stats accumulate in f32 regardless of input dtype;
  masked logits use a large-negative finite value so fully-masked blocks
  stay NaN-free (every causal row owns its diagonal, so the final result
  is exact);
- the per-block kernel is pluggable: defaults to an einsum path XLA fuses
  well; ``ray_tpu.ops.attention`` provides the Pallas flash kernel for the
  resident-block case.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e9


def _block_attn(q, k, v, mask, scale):
    """One blockwise attention step returning (out, row_max, row_sum).

    q: [B, Sq, H, D]  k/v: [B, Sk, H, D]  mask: [Sq, Sk] bool or None.
    Stats in f32: out [B, Sq, H, D], m/l [B, Sq, H].
    """
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask[None, None, :, :], scores, _NEG_INF)
    m = jnp.max(scores, axis=-1)                      # [B, H, Sq]
    p = jnp.exp(scores - m[..., None])                # [B, H, Sq, Sk]
    l = jnp.sum(p, axis=-1)                           # [B, H, Sq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    # reshape stats to [B, Sq, H]
    return o, jnp.transpose(m, (0, 2, 1)), jnp.transpose(l, (0, 2, 1))


def zigzag_positions(rank, n, s_local):
    """Global token positions of rank ``rank``'s shard under the ZIGZAG
    layout: the sequence is cut into ``2n`` chunks and rank i holds
    chunks ``(i, 2n-1-i)`` — one early + one late chunk, so every rank
    carries the same share of the causal triangle (reference idea:
    striped/zigzag context parallelism; the plain contiguous layout
    gives rank n-1 the whole triangle while rank 0 sits masked).
    """
    c = s_local // 2
    early = rank * c + jnp.arange(c)
    late = (2 * n - 1 - rank) * c + jnp.arange(c)
    return jnp.concatenate([early, late])


def zigzag_permutation(seq_len: int, n: int):
    """Host-side index map: ``x[:, perm]`` reorders a ``[B, S, ...]``
    global sequence so an even split over ``n`` ranks gives each rank
    its zigzag shard.  Returns (perm, inverse_perm) as numpy arrays."""
    import numpy as np
    if seq_len % (2 * n):
        raise ValueError(
            f"zigzag needs seq_len divisible by 2*sp (got seq_len="
            f"{seq_len}, sp={n})")
    s_local = seq_len // n
    perm = np.concatenate([
        np.asarray(zigzag_positions(r, n, s_local)) for r in range(n)])
    inv = np.empty_like(perm)
    inv[perm] = np.arange(seq_len)
    return perm, inv


def ring_attention(q, k, v, *, axis_name: str = "sp", causal: bool = True,
                   scale: Optional[float] = None,
                   block_attn: Callable = _block_attn,
                   layout: str = "contiguous"):
    """Ring attention over a sharded sequence axis.

    Must run inside ``shard_map`` (or pjit-manual) with ``axis_name``
    bound.  q, k, v: ``[B, S_local, H, D]`` — the local sequence shard.
    Returns ``[B, S_local, H, D]`` in q's dtype.

    ``layout="zigzag"``: shards follow :func:`zigzag_positions` (feed
    the model a :func:`zigzag_permutation`-reordered sequence).  With
    chunks ``(r, 2n-1-r)`` every off-diagonal ring step reduces to an
    UNMASKED half-block — ``src < my``: all of q attends only the
    source's early chunk; ``src > my``: only q's late chunk attends the
    full source — so each step costs half the contiguous layout's
    block, identical on every rank: causal work is balanced AND ~halved
    (striped/zigzag context parallelism).
    """
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, S, H, D = q.shape
    if scale is None:
        scale = D ** -0.5
    if layout == "zigzag" and S % 2:
        raise ValueError(
            f"zigzag layout needs an even local shard, got S_local={S} "
            "(global seq_len must divide by 2*sp)")

    if layout == "zigzag":
        q_pos = zigzag_positions(my, n, S)
    else:
        q_pos = my * S + jnp.arange(S)                # global q positions

    c = S // 2

    def zz_diag(q, k_blk, v_blk, src):
        # own block: the zigzag causal mask (half true by structure)
        kv_pos = zigzag_positions(src, n, S)
        mask = q_pos[:, None] >= kv_pos[None, :]
        return block_attn(q, k_blk, v_blk, mask, scale)

    def zz_lower(q, k_blk, v_blk, src):
        # src strictly "earlier": every q position sees the source's
        # EARLY chunk completely and its late chunk not at all
        bo, bm, bl = block_attn(q, k_blk[:, :c], v_blk[:, :c], None,
                                scale)
        return bo, bm, bl

    def zz_upper(q, k_blk, v_blk, src):
        # src strictly "later": only q's LATE chunk sees the source
        # (all of it); early q rows contribute nothing this step
        bo, bm, bl = block_attn(q[:, c:], k_blk, v_blk, None, scale)
        pad_o = jnp.zeros((B, c, H, D), jnp.float32)
        pad_m = jnp.full((B, c, H), _NEG_INF, jnp.float32)
        pad_l = jnp.zeros((B, c, H), jnp.float32)
        return (jnp.concatenate([pad_o, bo], axis=1),
                jnp.concatenate([pad_m, bm], axis=1),
                jnp.concatenate([pad_l, bl], axis=1))

    def step(carry, step_idx):
        o, m, l, k_blk, v_blk = carry
        src = (my - step_idx) % n
        if causal and layout == "zigzag":
            # per-rank branch (no collective inside): each step costs
            # one half-block on every rank
            bo, bm, bl = lax.cond(
                src == my,
                lambda args: zz_diag(*args),
                lambda args: lax.cond(
                    args[3] < my,
                    lambda a: zz_lower(*a),
                    lambda a: zz_upper(*a),
                    args),
                (q, k_blk, v_blk, src))
        elif causal:
            kv_pos = src * S + jnp.arange(S)
            mask = q_pos[:, None] >= kv_pos[None, :]
            bo, bm, bl = block_attn(q, k_blk, v_blk, mask, scale)
        else:
            bo, bm, bl = block_attn(q, k_blk, v_blk, None, scale)
        m_new = jnp.maximum(m, bm)
        alpha = jnp.exp(m - m_new)                    # rescale old state
        beta = jnp.exp(bm - m_new)                    # rescale new block
        l_new = l * alpha + bl * beta
        o_new = (o * alpha[..., None]
                 + bo * beta[..., None])
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return (o_new, m_new, l_new, k_next, v_next), None

    o0 = jnp.zeros((B, S, H, D), jnp.float32)
    m0 = jnp.full((B, S, H), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, S, H), jnp.float32)
    (o, m, l, _, _), _ = lax.scan(step, (o0, m0, l0, k, v),
                                  jnp.arange(n))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def local_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None, segment_ids=None):
    """Single-device reference attention (same signature, no ring).

    ``segment_ids`` [B, S] (sample-packed batches, 0 = pad) delegates
    to the block-diagonal-masked formulation — co-packed documents
    never attend to each other."""
    if segment_ids is not None:
        from ray_tpu.ops.attention import segment_attention
        return segment_attention(q, k, v, segment_ids, causal=causal,
                                 scale=scale)
    B, S, H, D = q.shape
    if scale is None:
        scale = D ** -0.5
    mask = (jnp.tril(jnp.ones((S, S), bool)) if causal else None)
    o, m, l = _block_attn(q, k, v, mask, scale)
    return (o / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)


def make_ring_attention_fn(mesh, *, causal: bool = True,
                           rules=None, layout: str = "contiguous"):
    """shard_map-wrapped ring attention for a given mesh.

    Shards: batch over (dp, fsdp), seq over sp, heads over tp.  Falls back
    to plain local attention when the mesh has no sp axis.
    ``layout="zigzag"`` enables causal load balancing — the caller feeds
    sequences pre-permuted with :func:`zigzag_permutation`.
    """
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.compat import shard_map

    sp = mesh.shape.get("sp", 1)
    if sp <= 1:
        return functools.partial(local_attention, causal=causal)

    def drop_missing(spec_axes):
        out = []
        for a in spec_axes:
            if isinstance(a, tuple):
                a = tuple(x for x in a if mesh.shape.get(x, 1) >= 1
                          and x in mesh.axis_names) or None
            elif a is not None and a not in mesh.axis_names:
                a = None
            out.append(a)
        return P(*out)

    spec = drop_missing([("dp", "fsdp"), "sp", "tp", None])

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(spec, spec, spec), out_specs=spec)
    def fn(q, k, v):
        return ring_attention(q, k, v, axis_name="sp", causal=causal,
                              layout=layout)

    return fn
