"""Overlap-scheduled FSDP/TP training path — explicit collectives via
``shard_map`` instead of GSPMD auto-sharding.

The GSPMD path (``models/training.py``) expresses FSDP/TP purely as
shardings and lets XLA insert the collectives.  That leaves the
*schedule* to the compiler: weight all-gathers are emitted where SPMD
partitioning needs them, gradient reduce-scatters coalesce into an
end-of-step pass, and the TP all-reduces around each MLP block on the
critical path.  Both "Exploring the limits of Concurrency in ML
Training on Google TPUs" (arxiv 2011.03641) and "The Big Send-off"
(arxiv 2504.18658) locate the multi-chip headroom in decomposed,
software-pipelined collectives that hide each hop behind compute —
a schedule that cannot be forced out of GSPMD from shardings alone.

This module builds that schedule explicitly, as an opt-in path beside
the GSPMD one (env ``RAY_TPU_COMM=overlap``, see :func:`comm_config`):

1. **Prefetched per-block FSDP weight all-gathers.**  Master shards
   stay resident in the optimizer layout; the layer scan carries a
   double buffer: at block ``i`` the gather of block ``i+1``'s weights
   is issued *before* block ``i``'s compute, so the ICI transfer of the
   next block overlaps the matmuls of the current one.  With
   ``cfg.remat`` the gather instead moves inside the rematted block
   (only the small local shard is a residual), trading the forward
   prefetch for ZeRO-3 peak memory: gathered weights are transient in
   both passes and the backward re-gathers per block.

2. **As-you-go per-block gradient reduce-scatters.**  The backward of
   the same scan is generated by autodiff: the transpose of each
   per-block ``all_gather`` is a per-block ``psum_scatter``, so grad
   reduce-scatters are emitted as the backward completes each block —
   each one overlaps the previous block's backward matmuls — instead
   of one fused end-of-step pass over the whole gradient tree.

3. **Ring all-gather-matmul for TP.**  Activations travel the tp axis
   sequence-sharded (megatron sequence parallelism); each TP-entry
   projection (qkv, MLP up, vocab head) is a :func:`ring_allgather_matmul`:
   ``lax.ppermute`` circulates sequence chunks around the tp ring while
   each resident chunk is multiplied with the local weight shard, so
   every ICI hop overlaps one matmul chunk.  The TP-exit projections
   (attention out, MLP down) reduce-scatter back to sequence shards
   (``psum_scatter``), replacing GSPMD's blocking all-reduces.
   Backward re-uses the same machinery through the transpose rules
   (the transpose of the ring gather is a ring matmul-accumulate).

Correctness story in this CPU-only environment: the path is proved by
loss + per-parameter grad parity against the GSPMD path on an
8-host-device mesh (``tests/test_parallel.py``); the on-chip A/B is
ROADMAP S7.  Dispatch consolidates into
:func:`comm_config` (default ``gspmd`` until that A/B lands), matching
the ``attention_config()`` / ``ce_config()`` precedent in ``ops/``.

Scope: dp/fsdp/tp meshes, dense (non-MoE) GPT blocks.  sp/pp/ep meshes
and MoE fall back to GSPMD loudly (:func:`overlap_supported`).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel import sharding as shd
from ray_tpu.parallel.compat import shard_map
from ray_tpu.parallel.ring_attention import local_attention


# ---------------------------------------------------------------------------
# dispatch config — the single home for comm-schedule env knobs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CommConfig:
    """Multi-chip comm-schedule knobs, resolved once from the environment.

    - ``RAY_TPU_COMM`` (default ``gspmd``): which collective schedule
      the sharded GPT train step uses on a >1-device mesh —
      ``gspmd`` (XLA auto-sharding inserts the collectives) or
      ``overlap`` (this module's explicit shard_map schedule:
      prefetched per-block FSDP gathers, as-you-go grad
      reduce-scatters, ring all-gather-matmul TP).  The default stays
      ``gspmd`` until the on-chip A/B (ROADMAP S7) lands, per the
      r05 rule: flip it here once the measured delta has a sign.
    - ``RAY_TPU_COMM_QUANT`` (default ``none``): wire dtype of the
      overlap schedule's collectives, **per fabric tier** —

      * ``none`` (alias ``off``): weights/grads move at ``cfg.dtype``
        on every tier;
      * ``int8`` (alias ``all``): block-scaled int8 wire payloads on
        both tiers (``ray_tpu.quant``: weight all-gathers quantize the
        local shard deterministically and gather codes + per-128-block
        f32 scales; the transposed grad reduce-scatters — and the DCN
        all-reduce legs on a ``dcn`` mesh — requantize each ring hop
        with stochastic rounding, EQuARX-style, arXiv:2506.17615);
      * ``dcn``: quantize **only the cross-pod DCN leg** (the
        hierarchical grad all-reduce of the 1/fsdp shard); the ICI
        weight gathers / reduce-scatters stay at ``cfg.dtype``.  This
        is the recommended multi-pod setting — the bandwidth the int8
        wire buys matters most where the wire is ~30x slower, and the
        ICI legs keep full precision.

      ~1.94x fewer wire bytes on each quantized stream
      (``collective_bytes_per_step`` proves it per tier) at a bounded
      grad error (``tests/test_parallel.py`` budgets).  Only the
      overlap schedule can honor it — GSPMD owns its own collectives —
      so ``build_gpt_train`` drops it loudly under ``gspmd``.  Default
      stays ``none`` until an on-chip A/B.
    """
    mode: str = "gspmd"
    quant: str = "none"


_CONFIG: Optional[CommConfig] = None


def comm_config(refresh: bool = False) -> CommConfig:
    """The process-wide :class:`CommConfig` (env read once, cached).

    ``refresh=True`` re-reads the environment — for tests and A/B
    drivers that flip flags after import."""
    global _CONFIG
    if _CONFIG is None or refresh:
        mode = os.environ.get("RAY_TPU_COMM", "gspmd")
        if mode not in ("gspmd", "overlap"):
            print(f"RAY_TPU_COMM={mode!r} unknown; using 'gspmd'",
                  file=sys.stderr)
            mode = "gspmd"
        quant = os.environ.get("RAY_TPU_COMM_QUANT", "none")
        # tier aliases: "off" / "all" read better next to "dcn" in a
        # launch script; canonical values stay "none" / "int8"
        quant = {"off": "none", "all": "int8"}.get(quant, quant)
        if quant not in ("none", "int8", "dcn"):
            print(f"RAY_TPU_COMM_QUANT={quant!r} unknown; using 'none'",
                  file=sys.stderr)
            quant = "none"
        _CONFIG = CommConfig(mode=mode, quant=quant)
    return _CONFIG


def overlap_supported(cfg, mesh) -> Optional[str]:
    """None if the overlap schedule can run this (cfg, mesh); else the
    reason string the caller should print when falling back to GSPMD."""
    for axis in ("sp", "pp", "ep"):
        if mesh.shape.get(axis, 1) > 1:
            return f"mesh has {axis}>1 (overlap path covers dp/fsdp/tp)"
    if getattr(cfg, "n_experts", 0) > 0 or getattr(cfg, "dropless", False):
        return "MoE blocks not supported by the overlap schedule"
    if not getattr(cfg, "plain_attention", True):
        return ("window layers and grouped K/V heads not supported by "
                "the overlap schedule's einsum attention")
    fsdp = mesh.shape.get("fsdp", 1)
    tp = mesh.shape.get("tp", 1)
    if cfg.d_model % fsdp:
        return f"d_model={cfg.d_model} not divisible by fsdp={fsdp}"
    if cfg.n_heads % tp:
        return f"n_heads={cfg.n_heads} not divisible by tp={tp}"
    if cfg.ff_dim % tp:
        return f"d_ff={cfg.ff_dim} not divisible by tp={tp}"
    if cfg.vocab_size % tp:
        return f"vocab={cfg.vocab_size} not divisible by tp={tp}"
    return None


# ---------------------------------------------------------------------------
# ring all-gather-matmul
# ---------------------------------------------------------------------------

def ring_allgather_matmul(x, ws, axis_name: Optional[str], *,
                          out_dtype=None):
    """All-gather ``x``'s token dim around a ring, fused with matmuls.

    ``x``: ``[..., T, K]`` with the token dim sharded over ``axis_name``;
    each ``w`` in ``ws``: ``[K, *rest]`` (full K — already fsdp-gathered;
    tp-local on its output dims).  Returns ``[..., W*T, *rest]`` per
    ``w`` — numerically ``all_gather(x, tokens) @ w``, computed as W
    ring steps: multiply the resident chunk with every local weight
    shard, write it at its source rank's token offset, and
    ``ppermute`` the chunk onward so the next hop's transfer overlaps
    this hop's matmuls.  Passing several ``ws`` (qkv; MLP up+gate)
    moves each token chunk over ICI once for all of them.

    Backward needs no custom rule: the transpose of the chunked
    gather is a ring matmul-accumulate (dx chunks ppermute back while
    each hop's grad matmul runs), and the weight grads fall out as
    per-chunk contractions — the same overlap, mirrored.

    ``axis_name=None`` (or a 1-sized axis) degrades to plain matmuls.
    ``out_dtype`` forces f32 accumulation (the vocab head matches the
    GSPMD CE's ``preferred_element_type=f32``).
    """
    single = not isinstance(ws, (list, tuple))
    ws_list: List[Any] = [ws] if single else list(ws)

    def mm(chunk, w):
        wf = w.reshape(w.shape[0], -1)
        o = jnp.einsum("...tk,km->...tm", chunk, wf,
                       preferred_element_type=out_dtype)
        if out_dtype is not None:
            o = o.astype(out_dtype)
        return o.reshape(chunk.shape[:-1] + w.shape[1:])

    if axis_name is None:
        outs = [mm(x, w) for w in ws_list]
        return outs[0] if single else outs

    W = lax.axis_size(axis_name)
    if W == 1:
        outs = [mm(x, w) for w in ws_list]
        return outs[0] if single else outs

    rank = lax.axis_index(axis_name)
    T = x.shape[-2]
    tok_dim = x.ndim - 2
    outs = [jnp.zeros(x.shape[:-2] + (W * T,) + w.shape[1:],
                      out_dtype or x.dtype) for w in ws_list]
    perm = [(i, (i + 1) % W) for i in range(W)]
    chunk = x
    for step in range(W):
        # after `step` hops the resident chunk originated at rank-step
        src = (rank - step) % W
        for j, w in enumerate(ws_list):
            outs[j] = lax.dynamic_update_slice_in_dim(
                outs[j], mm(chunk, w), src * T, axis=tok_dim)
        if step != W - 1:
            chunk = lax.ppermute(chunk, axis_name, perm)
    return outs[0] if single else outs


def psum_scatter_tokens(x, axis_name: Optional[str]):
    """Reduce a tp-partial ``[..., W*T, M]`` and scatter token chunks
    back to their owning rank (``[..., T, M]``) — the decomposed half
    of the all-reduce GSPMD would emit after a TP-exit matmul.  Chunk
    ordering matches :func:`ring_allgather_matmul` (chunk r = rank r's
    tokens).  No-op without a ring."""
    if axis_name is None:
        return x
    return lax.psum_scatter(x, axis_name, scatter_dimension=x.ndim - 2,
                            tiled=True)


# ---------------------------------------------------------------------------
# spec plumbing
# ---------------------------------------------------------------------------

def _spec_axes(spec) -> set:
    out = set()
    for entry in spec:
        if isinstance(entry, tuple):
            out.update(a for a in entry if a)
        elif entry:
            out.add(entry)
    return out


def _param_specs(cfg, mesh):
    """PartitionSpec per param leaf (the same mapping the GSPMD path's
    NamedShardings use, so shard_map in_specs and jit in_shardings can
    never disagree)."""
    from ray_tpu.models import gpt as gpt_mod
    return shd.tree_specs(mesh, gpt_mod.param_logical_axes(cfg))


def _gather_axis_dims(spec, axis_name: str) -> List[int]:
    return [d for d, entry in enumerate(spec)
            if entry == axis_name
            or (isinstance(entry, tuple) and axis_name in entry)]


# ---------------------------------------------------------------------------
# wire-quantized FSDP collectives (EQuARX-style, arXiv:2506.17615)
# ---------------------------------------------------------------------------

#: Wire-quantization block: one f32 scale per 128 int8 codes, i.e.
#: 1.03125 bytes/element vs 2 in bf16 — a 1.94x wire-byte reduction.
#: 128 is also the TPU lane width, so on-chip the (de)quantize is a
#: per-lane-vector amax/multiply.  ``collective_bytes_per_step`` and
#: the actual payloads share this constant.
WIRE_BLOCK = 128


def _to2d(x, dim: int):
    """Move ``dim`` to the front and flatten the rest -> ``[n, rest]``.

    Canonical layout for wire quantization: scale blocks run along the
    flattened non-collective axes, so block geometry is independent of
    which dim the collective tiles and the tail-padding waste is at
    most one block per collective row (not one per trailing-dim row)."""
    xm = jnp.moveaxis(x, dim, 0)
    return xm.reshape(xm.shape[0], -1), xm.shape


def _from2d(x2, moved_shape, dim: int):
    """Inverse of :func:`_to2d`; ``x2``'s leading dim may have grown
    (post-gather) or shrunk (post-scatter) vs ``moved_shape[0]``."""
    x = x2.reshape((x2.shape[0],) + moved_shape[1:])
    return jnp.moveaxis(x, 0, dim)


def _quant_ring_reduce_scatter(g2, axis_name: str, *, key_base: int):
    """Wire-quantized ring reduce-scatter of ``[W*T, rest]`` -> the
    local ``[T, rest]`` chunk sum — the transpose of a tiled all-gather
    along dim 0, with every ICI hop carrying int8 codes + per-block
    scales instead of ``cfg.dtype``.

    Classic W-1-hop ring: the partial destined for rank ``b`` starts
    at rank ``b+1`` and accumulates one local contribution per hop.
    Each hop **requantizes the partial sum** with *stochastic* rounding
    (``floor(y + u)``, E[q] = y): a biased rounding here would compound
    over ranks and steps, while unbiased noise averages out across the
    batch — the EQuARX argument.  Accumulation stays f32 between hops.
    The per-hop PRNG key folds (rank, hop, payload salt) so no two
    hops — and no two steps — reuse a rounding pattern."""
    from ray_tpu.quant import (data_salt, dequantize_block,
                               quantize_block, stochastic_key)
    W = lax.axis_size(axis_name)
    if W == 1:
        return g2
    rank = lax.axis_index(axis_name)
    T = g2.shape[0] // W
    gf = g2.astype(jnp.float32)

    def chunk(i):
        return lax.dynamic_slice_in_dim(gf, i * T, T, axis=0)

    perm = [(i, (i + 1) % W) for i in range(W)]
    acc = chunk((rank - 1) % W)
    for hop in range(1, W):
        key = stochastic_key(key_base, rank, hop, data_salt(acc))
        q, s = quantize_block(acc, block=WIRE_BLOCK, axis=-1,
                              mode="stochastic", key=key)
        q = lax.ppermute(q, axis_name, perm)
        s = lax.ppermute(s, axis_name, perm)
        acc = (dequantize_block(q, s, block=WIRE_BLOCK, axis=-1)
               + chunk((rank - 1 - hop) % W))
    return acc


def quant_all_gather(x, axis_name: str, dim: int, *, key_base: int = 0):
    """Wire-quantized tiled ``all_gather`` with a wire-quantized ring
    reduce-scatter as its autodiff transpose (``custom_vjp``).

    Forward: quantize the *local* shard (deterministic rounding —
    gathered weights are read many times, bias does not accumulate),
    all-gather the int8 codes and the per-block f32 scales, dequantize
    to ``x.dtype``.  Scale blocks never cross shard boundaries because
    quantization happens pre-gather on local data.  Backward: the
    cotangent reduce-scatters through :func:`_quant_ring_reduce_scatter`
    (stochastic rounding per hop).  Both directions move ~1.03 bytes
    per element instead of ``cfg.dtype``'s 2 (bf16) — the halving
    ``collective_bytes_per_step(..., quant="int8")`` accounts for."""
    from ray_tpu.quant import dequantize_block, quantize_block
    out_dtype = x.dtype

    @jax.custom_vjp
    def qag(x):
        x2, moved = _to2d(x, dim)
        q, s = quantize_block(x2.astype(jnp.float32), block=WIRE_BLOCK,
                              axis=-1, mode="nearest")
        qg = lax.all_gather(q, axis_name, axis=0, tiled=True)
        sg = lax.all_gather(s, axis_name, axis=0, tiled=True)
        full2 = dequantize_block(qg, sg, block=WIRE_BLOCK, axis=-1)
        return _from2d(full2, moved, dim).astype(out_dtype)

    def fwd(x):
        return qag(x), None

    def bwd(_, g):
        g2, moved = _to2d(g, dim)
        local2 = _quant_ring_reduce_scatter(g2, axis_name,
                                            key_base=key_base)
        return (_from2d(local2, moved, dim).astype(out_dtype),)

    qag.defvjp(fwd, bwd)
    return qag(x)


def quant_psum(x, axis_name: str, *, key_base: int = 0):
    """Wire-quantized all-reduce over ``axis_name``, decomposed as a
    quantized ring reduce-scatter + quantized all-gather of the reduced
    shard — the DCN leg of the hierarchical grad reduction
    (``RAY_TPU_COMM_QUANT=dcn|all`` on a ``dcn`` mesh).

    Runs in grad space (after ``value_and_grad``), so no custom
    transpose is needed.  The payload flattens to ``[W, chunk]`` with
    ``chunk`` padded to a multiple of :data:`WIRE_BLOCK`, rides the
    stochastic-rounding ring of :func:`_quant_ring_reduce_scatter`
    (each hop requantizes a partial *sum* — unbiased rounding, the
    EQuARX argument), and the fully-reduced shard gathers back as int8
    codes + per-block f32 scales with deterministic rounding (read-many
    data, like the weight gathers)."""
    from ray_tpu.quant import dequantize_block, quantize_block
    W = lax.axis_size(axis_name)
    if W == 1:
        return x
    shape, dtype = x.shape, x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    n = flat.shape[0]
    chunk = -(-n // (W * WIRE_BLOCK)) * WIRE_BLOCK
    pad = W * chunk - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    acc = _quant_ring_reduce_scatter(flat.reshape(W, chunk), axis_name,
                                     key_base=key_base)     # [1, chunk]
    q, s = quantize_block(acc, block=WIRE_BLOCK, axis=-1, mode="nearest")
    qg = lax.all_gather(q, axis_name, axis=0, tiled=True)
    sg = lax.all_gather(s, axis_name, axis=0, tiled=True)
    full = dequantize_block(qg, sg, block=WIRE_BLOCK,
                            axis=-1).reshape(-1)
    if pad:
        full = full[:n]
    return full.reshape(shape).astype(dtype)


def _gather_leaf(x, spec, axis_name: str, *, quant: str = "none",
                 key_base: int = 0):
    for dim in _gather_axis_dims(spec, axis_name):
        if quant == "int8" and x.ndim >= 2:
            # 1-D leaves (biases, norm scales) would skip quantization
            # here, but no 1-D leaf is fsdp-sharded in the GPT tree —
            # the guard is for generality, not a live byte stream
            x = quant_all_gather(x, axis_name, dim, key_base=key_base)
        else:
            x = lax.all_gather(x, axis_name, axis=dim, tiled=True)
    return x


def _drop_lead(spec):
    """Spec of a stacked [L, ...] leaf after indexing one layer."""
    return P(*tuple(spec)[1:])


# ---------------------------------------------------------------------------
# the overlap-scheduled forward (runs inside shard_map)
# ---------------------------------------------------------------------------

def _block_apply(lp, x, cfg, *, positions, tp_axis: Optional[str]):
    """One transformer block on sequence-sharded activations.

    ``lp``: layer params, fsdp-gathered (full d / full f per tp shard),
    heads/mlp dims tp-local.  ``x``: ``[B_local, S/tp, d]``.  Mirrors
    ``gpt.layer_apply`` numerics with the TP collectives made explicit:
    ring all-gather-matmul into the block, psum_scatter out of it.
    """
    from ray_tpu.models import gpt as gpt_mod
    eps = gpt_mod.norm_eps(cfg)
    h = gpt_mod._norm(x, lp["ln1"], cfg.norm, bias=lp.get("ln1_b"),
                      eps=eps)
    q, k, v = ring_allgather_matmul(h, [lp["wq"], lp["wk"], lp["wv"]],
                                    tp_axis)
    if "bq" in lp:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    if cfg.pos == "rope":
        q = gpt_mod._rope(q, positions, cfg.rope_theta)
        k = gpt_mod._rope(k, positions, cfg.rope_theta)
    attn = local_attention(q, k, v, causal=True)
    proj = jnp.einsum("bshk,hkd->bsd", attn, lp["wo"])
    proj = psum_scatter_tokens(proj, tp_axis)
    if "bo" in lp:
        proj = proj + lp["bo"]     # after the reduce: added once per token
    x = x + proj

    h2 = gpt_mod._norm(x, lp["ln2"], cfg.norm, bias=lp.get("ln2_b"),
                       eps=eps)
    if cfg.act == "swiglu":
        hu, g = ring_allgather_matmul(h2, [lp["w1"], lp["w3"]], tp_axis)
        if "b1" in lp:
            hu = hu + lp["b1"]
            g = g + lp["b3"]
        hmid = jax.nn.silu(hu) * g
    else:
        hu = ring_allgather_matmul(h2, lp["w1"], tp_axis)
        if "b1" in lp:
            hu = hu + lp["b1"]
        hmid = jax.nn.gelu(hu)
    down = jnp.einsum("bsf,fd->bsd", hmid, lp["w2"])
    down = psum_scatter_tokens(down, tp_axis)
    if "b2" in lp:
        down = down + lp["b2"]
    return x + down


def _embed_local(tokens, cfg, *, d_full_table, pos_table,
                 tp_axis: Optional[str], tp_rank, seq_offset,
                 s_local: int):
    """Vocab-parallel embedding -> this rank's sequence shard.

    ``d_full_table``: the embed shard with d all-gathered over fsdp —
    ``[V/tp, d]``.  Every tp rank looks up the *full* local-batch
    token set in its vocab slice (out-of-range ids contribute zero)
    and the partial rows psum_scatter back to sequence shards
    (megatron-style; the GSPMD path instead all-gathers the whole
    table).  Without a tp ring this is a plain lookup."""
    table = d_full_table.astype(cfg.dtype)
    if tp_axis is None:
        x = table[tokens]
    else:
        v_local = table.shape[0]
        rel = tokens - tp_rank * v_local
        ok = (rel >= 0) & (rel < v_local)
        x = table[jnp.clip(rel, 0, v_local - 1)]
        x = jnp.where(ok[..., None], x, jnp.zeros((), x.dtype))
        x = psum_scatter_tokens(x, tp_axis)
    if cfg.pos == "learned":
        pos = pos_table.astype(cfg.dtype)
        x = x + lax.dynamic_slice_in_dim(pos, seq_offset, s_local,
                                         0)[None]
    return x


def _vocab_parallel_ce(logits_local, targets, *, tp_axis: Optional[str],
                       tp_rank):
    """Per-token nll ``[N]`` from tp-column-sharded f32 logits
    ``[N, V/tp]`` (every rank holds all N tokens x its vocab slice).

    lse combines across the ring with the online-max trick (``pmax``
    of the ``stop_gradient``'d row maxes — exact grads, lse is
    shift-invariant in m); the true logit lives on exactly one rank
    and psums over.  Masked (-1) targets are the caller's business —
    the returned nll is unmasked."""
    V_local = logits_local.shape[-1]
    if tp_axis is None:
        m = lax.stop_gradient(jnp.max(logits_local, axis=-1))
        z = jnp.sum(jnp.exp(logits_local - m[:, None]), axis=-1)
        true = jnp.take_along_axis(
            logits_local, jnp.maximum(targets, 0)[:, None], axis=-1)[:, 0]
    else:
        m_local = lax.stop_gradient(jnp.max(logits_local, axis=-1))
        m = lax.pmax(m_local, tp_axis)
        z = lax.psum(
            jnp.sum(jnp.exp(logits_local - m[:, None]), axis=-1), tp_axis)
        rel = targets - tp_rank * V_local
        ok = (rel >= 0) & (rel < V_local)
        picked = jnp.take_along_axis(
            logits_local, jnp.clip(rel, 0, V_local - 1)[:, None],
            axis=-1)[:, 0]
        true = lax.psum(jnp.where(ok, picked, 0.0), tp_axis)
    return m + jnp.log(z) - true


def _overlap_local_obj(params, tokens, targets, cfg, *, mesh,
                       layer_specs, quant: str = "none"):
    """This device's *distinct share* of the global mean NLL.

    ``params``/``tokens``/``targets`` are the local shards; the return
    value sums to the global loss over every device (batch shards over
    dp/fsdp, sequence chunks over tp).  That convention is load-bearing
    for autodiff: jax transposes ``psum`` to ``psum``, which computes
    the true cotangent exactly when the implicit objective is the sum
    of all ranks' local outputs — so every explicit collective here
    (all_gather/psum_scatter/ppermute/psum) transposes to the correct
    cross-rank gradient flow, and only leaves *replicated* over an axis
    need the explicit grad psum in :func:`build_overlap_step_fns`.
    """
    from ray_tpu.models import gpt as gpt_mod
    tp_axis = "tp" if mesh.shape.get("tp", 1) > 1 else None
    fsdp_on = mesh.shape.get("fsdp", 1) > 1
    tp_rank = lax.axis_index(tp_axis) if tp_axis else 0
    tp_size = mesh.shape.get("tp", 1)

    B, S = tokens.shape
    if S % tp_size:
        raise ValueError(
            f"overlap path needs seq={S} divisible by tp={tp_size}")
    S_local = S // tp_size
    seq_offset = tp_rank * S_local
    positions = jnp.arange(S)

    def gather_fsdp(tree, specs):
        if not fsdp_on:
            return tree
        # per-leaf key_base (flattened index): distinct stochastic
        # rounding streams per leaf; rank/hop/payload fold in later
        leaves, treedef = jax.tree.flatten(tree)
        spec_leaves = jax.tree.leaves(specs,
                                      is_leaf=lambda s: isinstance(s, P))
        if len(spec_leaves) != len(leaves):
            # the old jax.tree.map raised on structure drift; keep that
            # loud instead of letting zip truncate into mis-sharding
            raise ValueError(
                f"param/spec tree mismatch: {len(leaves)} leaves vs "
                f"{len(spec_leaves)} specs")
        return jax.tree.unflatten(treedef, [
            _gather_leaf(leaf, spec, "fsdp", quant=quant, key_base=16 + i)
            for i, (leaf, spec) in enumerate(zip(leaves, spec_leaves))])

    # -- embedding (outside the block schedule) -------------------------
    embed_spec = shd.logical_to_spec(("vocab", "embed_fsdp"), None, mesh)
    embed_full_d = (_gather_leaf(params["embed"], embed_spec, "fsdp",
                                 quant=quant, key_base=1)
                    if fsdp_on else params["embed"])
    pos_table = None
    if cfg.pos == "learned":
        pos_spec = shd.logical_to_spec((None, "embed_fsdp"), None, mesh)
        pos_table = (_gather_leaf(params["pos_embed"], pos_spec, "fsdp",
                                  quant=quant, key_base=2)
                     if fsdp_on else params["pos_embed"])
    with jax.named_scope("overlap/embed"):
        x = _embed_local(tokens, cfg,
                         d_full_table=embed_full_d, pos_table=pos_table,
                         tp_axis=tp_axis, tp_rank=tp_rank,
                         seq_offset=seq_offset, s_local=S_local)

    # -- per-block double-buffered scan --------------------------------
    shards = params["layers"]
    per_layer_specs = jax.tree.map(_drop_lead, layer_specs,
                                   is_leaf=lambda s: isinstance(s, P))
    L = cfg.n_layers

    def take(i):
        return jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            shards)

    def gather_block(i):
        # named scope: the prefetched per-block weight all-gather — the
        # hop the schedule claims to hide behind the previous block's
        # matmuls (visible in xplane captures / r9_telemetry timelines)
        with jax.named_scope("overlap/gather_block"):
            return gather_fsdp(
                jax.tree.map(lambda a: a.astype(cfg.dtype), take(i)),
                per_layer_specs)

    def block(gathered, x):
        with jax.named_scope("overlap/block"):
            return _block_apply(gathered, x, cfg, positions=positions,
                                tp_axis=tp_axis)

    # Two schedules, because jax.checkpoint pins the choice: a
    # checkpointed function's *inputs* are always backward residuals,
    # so a cross-block prefetched gather (which must ride the scan
    # carry) cannot also be remat-transient.
    #
    # - remat=False: double-buffer prefetch.  Block i+1's gather is
    #   issued before block i's compute, hiding each ICI transfer
    #   behind a block of matmuls.  The gathered bf16 weights become
    #   backward residuals — but in no-remat mode every activation is
    #   resident anyway, so nothing is lost vs GSPMD no-remat.
    # - remat=True (ZeRO-3 memory): the gather moves *inside* the
    #   rematted region with only the local shard as its input, so
    #   gathered weights are transient in both passes and the backward
    #   re-gathers per block (the re-gather overlaps the neighbouring
    #   block's backward the same way the forward hop overlaps
    #   compute; collective_bytes_per_step charges it).
    if cfg.remat:
        def remat_block(x, i):
            return block(gather_block(i), x)
        remat_block = jax.checkpoint(remat_block)

        if cfg.unroll_layers:
            for i in range(L):
                x = remat_block(x, i)
        else:
            x, _ = lax.scan(lambda c, i: (remat_block(c, i), None),
                            x, jnp.arange(L))
    elif cfg.unroll_layers:
        gathered = gather_block(0)
        for i in range(L):
            # issue the next block's gather before this block's compute
            nxt = gather_block(i + 1) if i + 1 < L else None
            x = block(gathered, x)
            gathered = nxt
    else:
        def body(carry, i):
            x, gathered = carry
            # prefetch block i+1 (wraps at L-1; the wrapped gather is
            # the discarded final carry — zero cotangent, uniform shapes)
            nxt = gather_block(lax.rem(i + 1, L))
            x = block(gathered, x)
            return (x, nxt), None

        (x, _), _ = lax.scan(body, (x, gather_block(0)), jnp.arange(L))

    x = gpt_mod._norm(x, params["ln_f"], cfg.norm,
                      bias=params.get("ln_f_b"),
                      eps=gpt_mod.norm_eps(cfg))

    # -- vocab-parallel loss head --------------------------------------
    if cfg.tie_embeddings:
        head_local = jnp.swapaxes(embed_full_d, 0, 1)      # [d, V/tp]
    else:
        head_spec = shd.logical_to_spec(("embed_fsdp", "vocab"), None,
                                        mesh)
        head_local = (_gather_leaf(params["lm_head"], head_spec, "fsdp",
                                   quant=quant, key_base=3)
                      if fsdp_on else params["lm_head"])
    head_local = head_local.astype(cfg.dtype)
    with jax.named_scope("overlap/head_ring"):
        logits = ring_allgather_matmul(x, head_local, tp_axis,
                                       out_dtype=jnp.float32)
    logits = logits.reshape(B * S, -1)
    with jax.named_scope("overlap/vocab_ce"):
        nll = _vocab_parallel_ce(logits, targets.reshape(B * S),
                                 tp_axis=tp_axis, tp_rank=tp_rank)
    maskf = (targets.reshape(B * S) >= 0).astype(jnp.float32)
    nll = (nll * maskf).reshape(B, S)
    if tp_axis is not None:
        # this rank's distinct share: its own sequence chunk (every tp
        # rank computed all tokens' nll, but each token is owned once)
        nll = lax.dynamic_slice_in_dim(nll, seq_offset, S_local, 1)
        maskf = lax.dynamic_slice_in_dim(maskf.reshape(B, S), seq_offset,
                                         S_local, 1)
    reduce_axes = tuple(a for a in ("dcn", "dp", "fsdp", "tp")
                        if a in mesh.shape)
    n_local = jnp.sum(maskf)
    n_total = (lax.psum(lax.stop_gradient(n_local), reduce_axes)
               if reduce_axes else n_local)
    return jnp.sum(nll) / jnp.maximum(n_total, 1.0)


def _missing_axes(spec, mesh) -> Tuple[str, ...]:
    """Mesh axes a grad of a leaf with this spec still needs psum over:
    every manual batch/model axis the leaf is *not* sharded on (the
    sharded axes were reduced by the all_gather transposes).

    On a ``dcn`` mesh this is where the hierarchy closes: params carry
    no ``dcn`` in their specs (replicated per pod), so every leaf's
    grad needs the ``dcn`` psum — and for fsdp-sharded leaves that psum
    acts on the 1/fsdp shard the ICI ``psum_scatter`` (the all_gather
    transpose) already produced.  ICI reduce-scatter → DCN all-reduce
    of the shard → no re-scatter, exactly the 2510.20171 schedule."""
    present = _spec_axes(spec)
    return tuple(a for a in ("dcn", "dp", "fsdp", "tp")
                 if a in mesh.shape and a not in present)


def build_overlap_step_fns(cfg, mesh, *,
                           quant: Optional[str] = None) -> Dict[str, Any]:
    """The overlap-scheduled loss/grad entry points for ``(cfg, mesh)``.

    Returns ``dict(value_and_grad, loss)`` — both take
    ``(params, tokens, targets)`` as *global* (NamedSharding'd) arrays
    and run the whole computation inside one full-manual shard_map, so
    the collective schedule is exactly what this module wrote, not what
    GSPMD inferred.  Grads come back in the params' shardings (masters
    never leave their shards).

    ``quant`` ("none" / "int8" / "dcn"; default: :func:`comm_config`)
    sets the wire dtype per fabric tier — "int8" moves block-scaled
    int8 payloads (:func:`quant_all_gather`) on the ICI FSDP gathers /
    grad reduce-scatters AND the cross-pod legs; "dcn" quantizes only
    the DCN grad all-reduce (:func:`quant_psum`), leaving ICI at
    ``cfg.dtype``.

    On a ``dcn`` mesh the schedule is hierarchical by construction:
    the batch shards over ``dcn`` (pure data parallelism across pods),
    params are pod-replicated, so weight gathers never leave the pod;
    grads reduce-scatter over ``fsdp`` inside the pod (the all_gather
    transposes) and only the 1/fsdp-sized shard all-reduces over the
    slow tier, staged ICI-first in :func:`_tier_psum` — no re-scatter.
    """
    if quant is None:
        quant = comm_config().quant
    if quant not in ("none", "int8", "dcn"):
        raise ValueError(f"unknown comm quant {quant!r}; "
                         "expected 'none', 'int8' or 'dcn' "
                         "(check RAY_TPU_COMM_QUANT)")
    specs = _param_specs(cfg, mesh)
    layer_specs = specs["layers"]
    data_spec = P(shd.data_axes(mesh), None)
    grad_psum = jax.tree.map(lambda s: _missing_axes(s, mesh), specs,
                             is_leaf=lambda s: isinstance(s, P))
    reduce_axes = tuple(a for a in ("dcn", "dp", "fsdp", "tp")
                        if a in mesh.shape)
    # per-tier wire: "dcn" keeps the ICI gathers/RS at cfg.dtype
    ici_quant = "int8" if quant == "int8" else "none"
    dcn_quant = quant in ("int8", "dcn") \
        and mesh.shape.get("dcn", 1) > 1

    def _tier_psum(g, axes, *, key_base):
        """Grad psum staged per tier: pod-local ICI axes first, then
        the cross-pod leg carries only the already-reduced payload —
        quantized when the dcn wire is on."""
        ici = tuple(a for a in axes if a != "dcn")
        if ici:
            g = lax.psum(g, ici)
        if "dcn" in axes:
            with jax.named_scope("overlap/dcn_allreduce"):
                g = (quant_psum(g, "dcn", key_base=key_base)
                     if dcn_quant else lax.psum(g, "dcn"))
        return g

    def obj(params, tokens, targets):
        return _overlap_local_obj(params, tokens, targets, cfg,
                                  mesh=mesh, layer_specs=layer_specs,
                                  quant=ici_quant)

    def local_value_and_grad(params, tokens, targets):
        local, grads = jax.value_and_grad(
            lambda p: obj(p, tokens, targets))(params)
        # sharded leaves received their cross-rank grad sums through the
        # collective transposes; replicated ones get the explicit psum
        # (on a dcn mesh every leaf at least carries the dcn leg)
        with jax.named_scope("overlap/grad_psum"):
            g_leaves, treedef = jax.tree.flatten(grads)
            ax_leaves = jax.tree.leaves(
                grad_psum, is_leaf=lambda a: isinstance(a, tuple))
            if len(ax_leaves) != len(g_leaves):
                raise ValueError(
                    f"grad/psum tree mismatch: {len(g_leaves)} leaves "
                    f"vs {len(ax_leaves)} axis tuples")
            grads = jax.tree.unflatten(treedef, [
                _tier_psum(g, axes, key_base=4096 + i) if axes else g
                for i, (g, axes) in enumerate(zip(g_leaves, ax_leaves))])
            loss = (lax.psum(local, reduce_axes) if reduce_axes
                    else local)
        return loss, grads

    vg = shard_map(local_value_and_grad, mesh=mesh,
                   in_specs=(specs, data_spec, data_spec),
                   out_specs=(P(), specs))

    def local_loss(params, tokens, targets):
        local = obj(params, tokens, targets)
        return lax.psum(local, reduce_axes) if reduce_axes else local

    loss = shard_map(local_loss, mesh=mesh,
                     in_specs=(specs, data_spec, data_spec),
                     out_specs=P())
    return {"value_and_grad": vg, "loss": loss}


# ---------------------------------------------------------------------------
# collective-traffic accounting (bytes per step, per fabric tier)
# ---------------------------------------------------------------------------

#: Analytic bandwidth price per fabric tier, GB/s — rough sustained
#: figures (ICI: per-device all-gather bandwidth of a v4-generation 3D
#: torus; DCN: effective per-pod-boundary link, ~25 Gbit/s).  These are
#: *prices*, not measurements: ``collective_bytes_per_step`` divides
#: each tier's bytes by them so bench/telemetry rows show where a step's
#: communication seconds would go — the ~30x tier gap is the whole
#: argument for the hierarchical schedule and the dcn-only int8 wire.
TIER_BANDWIDTH_GBPS: Dict[str, float] = {"ici": 90.0, "dcn": 3.1}


def tier_seconds(nbytes: int, tier: str) -> float:
    """Analytic seconds to move ``nbytes`` on ``tier`` at the
    :data:`TIER_BANDWIDTH_GBPS` price."""
    return nbytes / (TIER_BANDWIDTH_GBPS[tier] * 1e9)


def collective_bytes_per_step(cfg, mesh, *, batch: int, seq: int,
                              comm_mode: str = "overlap",
                              quant: str = "none") -> Dict[str, Any]:
    """Logical collective bytes moved per train step, **per tier**:
    ``{"ici": {...}, "dcn": {...}, "total": int}``.

    Analytical accounting of the collectives each schedule emits
    (ring cost model: a W-way all-gather / reduce-scatter of an
    N-byte global payload moves ``N*(W-1)/W`` bytes per device; an
    all-reduce twice that).  Both schedules' figures use the same
    model so ``MULTICHIP_r*.json`` rows are comparable across rounds;
    XLA may fuse or elide some GSPMD traffic, so treat these as the
    schedule's *intent*, not a wire trace.

    Units per tier: the ``ici`` entries are bytes per **device** (the
    usual ring accounting — every device moves them concurrently); the
    ``dcn`` entries are bytes crossing each **pod-boundary link** — the
    bottleneck resource on the slow tier, and the number the
    hierarchical schedule exists to shrink.  ``dcn`` additionally
    reports ``flat_equivalent_bytes`` (what a *flat* schedule, its
    fsdp ring spanning ``dcn*fsdp`` devices, would push across the
    same pod-boundary link: full weight gathers + grad reduce-scatters
    instead of one shard-sized all-reduce) and ``reduction_vs_flat``
    (their ratio, ~pod size — asserted in tests).  Each tier carries an analytic ``seconds``
    at the :data:`TIER_BANDWIDTH_GBPS` price; the top-level ``total``
    stays a plain int (Prometheus emission reads it).

    Each collective reports ``{"bytes", "wire_dtype"}``.  ``quant``
    (overlap only — GSPMD owns its collectives, so those arms are
    charged unquantized): ``"int8"`` prices the FSDP weight-AG /
    grad-RS *and* the DCN all-reduce at ``ray_tpu.quant.wire_bytes``
    (int8 codes + one f32 scale per :data:`WIRE_BLOCK` elements,
    ~1.03 B/elem); ``"dcn"`` prices only the DCN leg that way.
    """
    from ray_tpu.models import gpt as gpt_mod
    from ray_tpu.quant import wire_bytes as quant_wire_bytes
    dcn = mesh.shape.get("dcn", 1)
    dp = mesh.shape.get("dp", 1)
    fsdp = mesh.shape.get("fsdp", 1)
    tp = mesh.shape.get("tp", 1)
    act_bytes = jnp.dtype(cfg.dtype).itemsize
    wire_name = jnp.dtype(cfg.dtype).name
    b_local = max(batch // max(dcn * dp * fsdp, 1), 1)
    d = cfg.d_model
    ici_quant_on = quant == "int8" and comm_mode == "overlap"
    dcn_quant_on = quant in ("int8", "dcn") and comm_mode == "overlap"
    q_name = f"int8+f32/{WIRE_BLOCK}"    # codes + per-block scales

    specs = _param_specs(cfg, mesh)
    shapes = jax.eval_shape(
        functools.partial(gpt_mod.init_params, cfg),
        jax.random.PRNGKey(0))

    fsdp_param_bytes = 0     # global cfg.dtype bytes of fsdp leaves
    fsdp_wire_payload = 0    # same leaves priced at the int8 wire
    all_param_bytes = 0
    shard_elems = 0          # per-device grad elements (post ICI
    #                          reduction) — the dcn all-reduce payload
    for leaf, spec in zip(jax.tree.leaves(shapes),
                          jax.tree.leaves(specs,
                                          is_leaf=lambda s:
                                          isinstance(s, P))):
        all_param_bytes += leaf.size * act_bytes
        div = 1
        for a in _spec_axes(spec):
            div *= mesh.shape.get(a, 1)
        shard_elems += leaf.size // max(div, 1)
        if "fsdp" in _spec_axes(spec):
            fsdp_param_bytes += leaf.size * act_bytes
            if len(leaf.shape) < 2:
                # _gather_leaf's 1-D guard: moves at cfg.dtype
                fsdp_wire_payload += leaf.size * act_bytes
            else:
                # mirror quant_all_gather's _to2d geometry exactly:
                # scale blocks run along the flattened non-gather axes,
                # one row per gather-dim index — so non-128-multiple
                # rows are charged their real padded-block scales
                dim = _gather_axis_dims(spec, "fsdp")[0]
                rows = leaf.shape[dim]
                fsdp_wire_payload += rows * quant_wire_bytes(
                    leaf.size // rows, block=WIRE_BLOCK)

    def entry(nbytes, dtype_name):
        return {"bytes": int(nbytes), "wire_dtype": dtype_name}

    def tier_total(tier):
        return int(sum(v["bytes"] for v in tier.values()
                       if isinstance(v, dict) and "bytes" in v))

    # ---------------------------------------------------- ICI tier ----
    ici: Dict[str, Any] = {}
    # FSDP: per-block weight AG forward (+ the remat re-gather if any)
    # and the mirrored per-block grad RS backward.  Quantized payload:
    # every fsdp-sharded GPT leaf is >=2-D, so the int8 wire covers the
    # whole stream (1-D leaves would stay at cfg.dtype).  On a dcn
    # mesh this entire stream stays inside the pod: params are
    # pod-replicated, so no weight byte ever crosses DCN.
    ring_frac_num, ring_frac_den = fsdp - 1, max(fsdp, 1)
    payload = fsdp_wire_payload if ici_quant_on else fsdp_param_bytes
    ag = payload * ring_frac_num // ring_frac_den
    regather = ag if cfg.remat else 0
    fsdp_wire = q_name if ici_quant_on else wire_name
    ici["weight_allgather"] = entry(ag + regather, fsdp_wire)
    ici["grad_reduce_scatter"] = entry(ag, fsdp_wire)
    # DP: grads of every leaf all-reduce over dp (leaves sharded on
    # fsdp move only their shard); stays at cfg.dtype — the dp ring is
    # XLA's in both schedules
    dp_bytes = (all_param_bytes - fsdp_param_bytes
                + fsdp_param_bytes // max(fsdp, 1))
    ici["grad_allreduce_dp"] = entry(
        2 * dp_bytes * (dp - 1) // max(dp, 1), wire_name)

    # TP activations — unquantized in both schedules (the per-hop
    # activation chunks sit on the critical path; quantizing them is
    # the natural next step once the fsdp arm has chip numbers)
    tp_frac = (tp - 1) / max(tp, 1)
    act_x = b_local * seq * d * act_bytes       # one full-seq hidden
    if tp > 1:
        if comm_mode == "overlap":
            # per block: ring-AG into qkv + ring-AG into MLP up(+gate:
            # same ring, chunks move once) + 2 psum_scatter out; the
            # backward mirrors each (transpose ring / all_gather)
            per_block = 4 * act_x * tp_frac * 2
            # embed psum (fwd) + loss-head ring-AG fwd/bwd + lse psums
            head_io = 2 * act_x * tp_frac * 2 + 2 * act_x * tp_frac
            ici["tp_ring"] = entry(cfg.n_layers * per_block + head_io,
                                   wire_name)
        else:
            # GSPMD: 2 all-reduce per block fwd + 2 bwd, plus the
            # involuntary full-table embed all-gather each step
            per_block = 2 * (2 * act_x * tp_frac) * 2
            table = cfg.vocab_size * d * act_bytes * tp_frac
            ici["tp_ring"] = entry(cfg.n_layers * per_block + 2 * table,
                                   wire_name)
    else:
        ici["tp_ring"] = entry(0, wire_name)
    ici["total"] = tier_total(ici)
    ici["seconds"] = tier_seconds(ici["total"], "ici")

    # ---------------------------------------------------- DCN tier ----
    # Hierarchical schedule: the only cross-pod traffic is one grad
    # all-reduce of each device's post-ICI-reduction shard (1/fsdp of
    # the fsdp leaves, 1/tp of the tp leaves), ring over the dcn pods.
    # Per-pod-boundary-link bytes: RS + AG legs each carry
    # payload*(K-1)/K across every link of the K-pod ring.
    dcn_tier: Dict[str, Any] = {}
    if dcn_quant_on:
        dcn_payload = quant_wire_bytes(shard_elems, block=WIRE_BLOCK)
        dcn_wire = q_name
    else:
        dcn_payload = shard_elems * act_bytes
        dcn_wire = wire_name
    dcn_frac_num, dcn_frac_den = dcn - 1, max(dcn, 1)
    dcn_tier["grad_allreduce_dcn"] = entry(
        2 * dcn_payload * dcn_frac_num // dcn_frac_den, dcn_wire)
    dcn_tier["total"] = tier_total(dcn_tier)
    dcn_tier["seconds"] = tier_seconds(dcn_tier["total"], "dcn")
    if dcn > 1:
        # the comparator: a flat schedule whose fsdp ring spans all
        # dcn*fsdp devices pushes the FULL weight-gather + grad-RS
        # streams across every pod-boundary link it crosses (each of a
        # ring's links carries payload*(W-1)/W), where the hierarchy
        # sends one shard-sized all-reduce.  Priced at the same wire
        # the hierarchical dcn leg uses, so the ratio isolates the
        # schedule, not the quantizer.
        W = dcn * fsdp
        flat_payload = (fsdp_wire_payload if dcn_quant_on
                        else fsdp_param_bytes)
        flat_ag = flat_payload * (W - 1) // W
        flat = flat_ag * (3 if cfg.remat else 2)   # AG (+regather) + RS
        # non-fsdp leaves' dp-style all-reduce also spans pods flat
        non_fsdp = all_param_bytes - fsdp_param_bytes
        flat += 2 * non_fsdp * (W - 1) // W
        dcn_tier["flat_equivalent_bytes"] = int(flat)
        total = dcn_tier["total"]
        dcn_tier["reduction_vs_flat"] = (
            round(flat / total, 2) if total else None)

    return {"ici": ici, "dcn": dcn_tier,
            "total": ici["total"] + dcn_tier["total"]}
