"""Mixture-of-Experts with expert parallelism over an ``ep`` mesh axis.

Absent natively in the reference (SURVEY.md §2.4).  TPU-native design:
top-k token routing with a static capacity (XLA needs static shapes — no
ragged dispatch), expressed as one-hot einsums the compiler turns into
MXU-friendly matmuls; under an ``ep`` axis the dispatched tokens move to
their experts with ``lax.all_to_all`` and return the same way.

:func:`dropless_moe` is the serve path's expert layer: no capacity, so no
token is dropped.  It is told which experts of a deployment it holds,
routes over all of them, and computes the held experts' part by a
grouped matrix product over the rows that picked them, and the identity
experts' part; an expert held elsewhere adds nothing (the exchange that
would bring its part is not run here).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel.compat import shard_map


class MoEParams(NamedTuple):
    wg: jnp.ndarray   # [d, E] router
    w1: jnp.ndarray   # [E, d, h]
    w2: jnp.ndarray   # [E, h, d]


def init_moe_params(key, d_model: int, hidden: int, n_experts: int,
                    dtype=jnp.float32) -> MoEParams:
    k1, k2, k3 = jax.random.split(key, 3)
    scale = d_model ** -0.5
    return MoEParams(
        wg=(jax.random.normal(k1, (d_model, n_experts)) * scale
            ).astype(dtype),
        w1=(jax.random.normal(k2, (n_experts, d_model, hidden)) * scale
            ).astype(dtype),
        w2=(jax.random.normal(k3, (n_experts, hidden, d_model))
            * hidden ** -0.5).astype(dtype),
    )


def _route(x, wg, top_k: int, capacity: int):
    """Compute dispatch/combine tensors.

    x: [T, d] tokens.  Returns dispatch [T, E, C] (0/1), combine [T, E, C]
    (gate weights), aux_loss (load-balance).
    """
    T = x.shape[0]
    E = wg.shape[1]
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        wg.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = lax.top_k(probs, top_k)          # [T, k]
    # normalize the selected gates
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    # position of each token within its expert's buffer, per k-slot
    dispatch = jnp.zeros((T, E, capacity), jnp.float32)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    # fill slot by slot so capacity is consumed in priority order
    used = jnp.zeros((E,), jnp.int32)
    for slot in range(top_k):
        e = expert_idx[:, slot]                               # [T]
        onehot = jax.nn.one_hot(e, E, dtype=jnp.int32)        # [T, E]
        pos_in_e = (jnp.cumsum(onehot, axis=0) - onehot) + used[None, :]
        pos = jnp.sum(pos_in_e * onehot, axis=1)              # [T]
        keep = pos < capacity
        pos_oh = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)
        sel = (onehot.astype(jnp.float32) * keep[:, None].astype(
            jnp.float32))
        dispatch = dispatch + sel[:, :, None] * pos_oh[:, None, :]
        combine = combine + (sel * gate_vals[:, slot:slot + 1]
                             )[:, :, None] * pos_oh[:, None, :]
        used = used + jnp.sum(sel, axis=0).astype(jnp.int32)
    # load-balance auxiliary loss (Switch-style)
    me = probs.mean(axis=0)
    ce = (dispatch.sum(axis=2) > 0).astype(jnp.float32).mean(axis=0)
    aux = E * jnp.sum(me * ce)
    return dispatch, combine, aux


def _expert_ffn(w1, w2, tokens):
    """tokens: [E, C, d] -> [E, C, d] through each expert's FFN."""
    h = jnp.einsum("ecd,edh->ech", tokens, w1)
    h = jax.nn.gelu(h)
    return jnp.einsum("ech,ehd->ecd", h, w2)


def moe_layer(params: MoEParams, x, *, top_k: int = 2,
              capacity_factor: float = 1.5,
              axis_name: Optional[str] = None,
              expert_ffn=None):
    """Apply an MoE FFN to ``x`` ``[T, d]`` (flatten batch*seq first).

    With ``axis_name`` set, runs the expert-parallel path: tokens are local
    to each device, experts sharded over the axis; dispatched tokens
    all_to_all to their expert's device and back.
    """
    if expert_ffn is None:
        expert_ffn = _expert_ffn
    T, d = x.shape
    E = params.wg.shape[1]
    if axis_name is None:
        capacity = max(top_k, int(capacity_factor * T * top_k / E))
        dispatch, combine, aux = _route(x, params.wg, top_k, capacity)
        expert_in = jnp.einsum("tec,td->ecd", dispatch, x)
        expert_out = expert_ffn(params.w1, params.w2, expert_in)
        out = jnp.einsum("tec,ecd->td", combine, expert_out)
        return out.astype(x.dtype), aux

    # ---- expert-parallel: params.w1/w2 are the LOCAL expert shard ----
    n = lax.axis_size(axis_name)
    E_local = params.w1.shape[0]
    E_global = E_local * n
    assert params.wg.shape[1] == E_global, (
        "router must score all global experts")
    capacity = max(top_k, int(capacity_factor * T * top_k / E_global))
    dispatch, combine, aux = _route(x, params.wg, top_k, capacity)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)   # [E_glob, C, d]
    # send each expert's tokens to the device owning it:
    # [E_glob, C, d] -> [E_local, n*C, d]
    expert_in = lax.all_to_all(
        expert_in.reshape(n, E_local, capacity, d), axis_name,
        split_axis=0, concat_axis=1).reshape(E_local, n * capacity, d)
    expert_out = expert_ffn(params.w1, params.w2, expert_in)
    # route back: [E_local, n*C, d] -> [E_glob, C, d]
    expert_out = lax.all_to_all(
        expert_out.reshape(E_local, n, capacity, d), axis_name,
        split_axis=1, concat_axis=0).reshape(E_global, capacity, d)
    out = jnp.einsum("tec,ecd->td", combine, expert_out)
    return out.astype(x.dtype), lax.pmean(aux, axis_name)


def make_moe_fn(mesh, *, top_k: int = 2, capacity_factor: float = 1.5):
    """shard_map-wrapped expert-parallel MoE for a mesh with an ep axis.

    Token batch sharded over (dp, fsdp, ep is folded over tokens too);
    experts sharded over ep.
    """
    ep = mesh.shape.get("ep", 1)
    if ep <= 1:
        def dense(params, x):
            return moe_layer(params, x, top_k=top_k,
                             capacity_factor=capacity_factor)
        return dense

    pspec = MoEParams(wg=P(None, None), w1=P("ep", None, None),
                      w2=P("ep", None, None))

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(pspec, P("ep", None)),
                       out_specs=(P("ep", None), P()))
    def fn(params, x):
        out, aux = moe_layer(params, x, top_k=top_k,
                             capacity_factor=capacity_factor,
                             axis_name="ep")
        return out, aux

    return fn


# ---------------------------------------------------------------------------
# the dropless expert layer of the serve path
# ---------------------------------------------------------------------------

# what :func:`dropless_moe` counts, in the order of its counts vector
MOE_COUNTS = ("rows", "held_picks", "identity_picks", "picks",
              "experts_hit", "calls")
_TILE = 128       # rows of one expert a step of the grouped product takes


def _grouped_experts(x, local, weight, e_gate, e_up, e_down, lead):
    """sum over a row's held picks of ``weight * expert(x)``, float32.

    x [T, d]; local [T, K] int32: the pick's index among the experts
    held, or ``held`` (one past the last) where the pick is not this
    chip's; weight [T, K] float32; e_gate, e_up [*lead, held, d, f];
    e_down [*lead, held, f, d], read at the indices ``lead`` (a layer
    of stacked weights: an expert's matrices are sliced where they
    stand, one expert a step, and never a layer's).  The picks are sorted by expert, and a loop takes one
    tile of one expert's rows a step: gather the rows, the expert's
    swiglu, scatter-add the weighted outputs.  Its trip count is the
    tiles the picks fill (``sum_e ceil(n_e / tile)``), so a step costs
    what was picked: an expert nobody picked is never read, and nothing
    is padded to a worst case."""
    T, K = local.shape
    held = e_gate.shape[len(lead)]

    def expert(w, e):
        at = len(lead) + 1
        return lax.dynamic_slice(
            w, (*lead, e) + (0,) * (w.ndim - at),
            (1,) * at + w.shape[at:]).reshape(w.shape[at:])

    tile = min(_TILE, -(-T // 16) * 16)    # an expert has at most T rows
    flat = local.reshape(T * K)
    order = jnp.argsort(flat, stable=True)            # held picks first
    pad = jnp.zeros((tile,), jnp.int32)
    token = jnp.concatenate([(order // K).astype(jnp.int32), pad])
    wsort = jnp.concatenate([weight.reshape(T * K)[order],
                             pad.astype(jnp.float32)])
    n = jnp.sum(flat[:, None] == jnp.arange(held)[None, :], axis=0,
                dtype=jnp.int32)                          # rows an expert
    start = jnp.cumsum(n) - n
    tiles = (n + tile - 1) // tile
    tile_end = jnp.cumsum(tiles)

    def step(t, out):
        e = jnp.minimum(jnp.sum(t >= tile_end), held - 1)
        k = t - (tile_end[e] - tiles[e])       # the expert's k-th tile
        first = start[e] + k * tile
        live = jnp.arange(tile) < n[e] - k * tile
        rows = jnp.where(live, lax.dynamic_slice(token, (first,), (tile,)),
                         0)
        w = jnp.where(live, lax.dynamic_slice(wsort, (first,), (tile,)),
                      0.0)
        xs = x[rows]
        h = jax.nn.silu(xs @ expert(e_gate, e)) * (xs @ expert(e_up, e))
        y = (h @ expert(e_down, e)).astype(jnp.float32) * w[:, None]
        return out.at[rows].add(y)

    out = lax.fori_loop(0, tile_end[-1], step,
                        jnp.zeros(x.shape, jnp.float32))
    return out, jnp.sum(n > 0)


def dropless_moe(x, router, router_bias, e_gate, e_up, e_down, *,
                 held: Sequence[int], n_routed: int, top_k: int,
                 scale: float, valid=None, lead=()):
    """One chip's part of a dropless expert layer on x [T, d] -> (its
    output [T, d], counts [len(MOE_COUNTS)] int32).

    ``router`` [d, E] scores every expert of the deployment in float32
    (softmax); the ``top_k`` of ``score + router_bias`` are a row's
    picks, weighted by their scores as they are (no renormalisation)
    and by ``scale``.  Experts ``0 .. n_routed - 1`` are swiglu experts,
    of which this chip holds ``held`` (their ids, in the order of
    ``e_gate, e_up`` [*lead, held, d, f] and ``e_down`` [*lead, held,
    f, d], read at the indices ``lead``: stacked layers); experts
    from ``n_routed`` up are identity experts (``E_e(x) = x``), computed
    where the row lives.  A pick on a routed expert held elsewhere adds
    nothing.  ``valid`` [T] bool marks the rows that are tokens of a
    sequence (absent: all): the others pick nothing and count nothing.
    Device scopes: ``route``, ``experts``, ``identity`` (the caller
    names the layer)."""
    T, d = x.shape
    E = router.shape[1]
    if valid is None:
        valid = jnp.ones((T,), bool)
    with jax.named_scope("route"):
        logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                            router.astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        score = jax.nn.softmax(logits, axis=-1)
        _, pick = lax.top_k(score + router_bias.astype(jnp.float32), top_k)
        weight = jnp.take_along_axis(score, pick, axis=-1)       # [T, K]
        local_of = np.full((E,), len(held), np.int32)
        local_of[list(held)] = np.arange(len(held))
        local = jnp.where(valid[:, None], jnp.asarray(local_of)[pick],
                          len(held))
        identity = valid[:, None] & (pick >= n_routed)
        ours = local < len(held)
    with jax.named_scope("experts"):
        out, hit = _grouped_experts(x, local, jnp.where(ours, weight, 0.0),
                                    e_gate, e_up, e_down, tuple(lead))
    with jax.named_scope("identity"):
        out = out + x.astype(jnp.float32) * jnp.sum(
            jnp.where(identity, weight, 0.0), -1, keepdims=True)
    rows = jnp.sum(valid)
    counts = jnp.stack([rows, jnp.sum(ours), jnp.sum(identity),
                        rows * top_k, hit, jnp.int32(1)]).astype(jnp.int32)
    return (scale * out).astype(x.dtype), counts
