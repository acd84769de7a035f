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
would bring its part is not run here).  A call nobody differentiates
takes that loop; a differentiated one (a train step) takes the same
layer as grouped products over the sorted picks, with a backward of its
own, because a loop with a traced trip count has no reverse mode.  The
products and the combine of their rows into tokens are
``ops/grouped_matmul.py``'s Pallas kernels wherever their tiles divide
the layer's shapes (``grouped_matmul.uses_kernel`` and
``combine_uses_kernel``; :func:`product_path` names the form) and
``jax.lax.ragged_dot`` and a gather elsewhere.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import grouped_matmul
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


# ---- the dropless expert layer of the serve path and the routed train step

# what :func:`dropless_moe` counts, in the order of its counts vector
MOE_COUNTS = ("rows", "held_picks", "identity_picks", "picks",
              "experts_hit", "calls", "loop_trips")
_TILE = 128       # rows of one expert a step of the grouped product takes
# how a router's logits become scores (:func:`dropless_moe`'s ``scoring``)
SCORINGS = {"softmax": lambda logits: jax.nn.softmax(logits, axis=-1),
            "sigmoid": jax.nn.sigmoid}


def _tile_rows(T: int) -> int:
    """Rows of one step of :func:`_grouped_experts` over ``T`` tokens."""
    return min(_TILE, -(-T // 16) * 16)


def _sort_picks(local, weight, held: int):
    """The picks ``[T, K]`` sorted by expert, stably (the held first, an
    expert's rows ascending by token): each sorted row's pick, an index
    into the flat ``T * K``, its weight, and the rows each held expert
    has.  The weight rides the sort: what belongs to a pick is never
    fetched by index (0.94-1.34 ms for a sort's 0.05 in the routed 8k
    cell, and a gather's gradient is a scatter: PR 62)."""
    flat = local.reshape(local.size)
    _, order, ws = lax.sort(
        (flat, jnp.arange(flat.size, dtype=jnp.int32),
         weight.reshape(flat.size)), num_keys=1, is_stable=True)
    return order, ws, jnp.sum(flat[:, None] == jnp.arange(held)[None, :],
                              axis=0, dtype=jnp.int32)


def _to_picks(order, rows):
    """``out[order[r]] = rows[r]``: the sorted rows' values back at their
    picks, the permutation's inverse as a sort keyed on the picks."""
    return lax.sort((order, rows), num_keys=1, is_stable=False)[1]


def _at_picks(table, pick):
    """``table[t, pick[t, k]]`` (``table [T, E]`` or ``[1, E]``, ``pick [T,
    K]``): the columns' indices compared with the pick and the row summed,
    exact (one term is not 0; a ``where``: nothing else in a row can leak)."""
    hit = pick[:, :, None] == jnp.arange(table.shape[-1])
    return jnp.sum(jnp.where(hit, table[:, None, :], 0), axis=-1)


def _grouped_experts(x, local, weight, e_gate, e_up, e_down, lead,
                     piece=None):
    """sum over a row's held picks of ``weight * expert(x)``, float32.

    x [T, d]; local [T, K] int32: the pick's index among the experts
    held, or ``held`` (one past the last) where the pick is not this
    chip's; weight [T, K] float32; e_gate, e_up [*lead, held, d, f];
    e_down [*lead, held, f, d], read at the indices ``lead`` (a layer of
    stacked weights: an expert's matrices are sliced where they stand,
    one expert a step, never a layer's).  The picks are sorted by expert
    (:func:`_sort_picks`), and a loop takes one tile of one expert's
    rows a step: gather the rows, the expert's swiglu, scatter-add the
    weighted outputs.  Its trip count is the tiles the picks fill
    (``sum_e ceil(n_e / tile)``): an expert nobody picked is never read,
    and nothing is padded to a worst case (``piece`` is the rule's)."""
    T, K = local.shape
    held = e_gate.shape[len(lead)]

    def expert(w, e):
        at = len(lead) + 1
        return lax.dynamic_slice(
            w, (*lead, e) + (0,) * (w.ndim - at),
            (1,) * at + w.shape[at:]).reshape(w.shape[at:])

    tile = _tile_rows(T)
    order, wsort, n = _sort_picks(local, weight, held)  # held picks first
    pad = jnp.zeros((tile,), jnp.int32)
    token = jnp.concatenate([order // K, pad])
    wsort = jnp.concatenate([wsort, pad.astype(jnp.float32)])
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


def _at(w, lead):
    return w[tuple(lead)] if lead else w


def _ragged(lhs, rhs, sizes, walk, transposed: bool = False):
    """``lhs [M, k]`` against each group's ``rhs [G, k, n]`` (or, with
    ``transposed``, ``rhs [G, n, k]``): ``grouped_matmul.gmm`` over the
    rows' schedule ``walk``, which reads a transposed matrix through its
    index map; where ``walk`` is None, the canonical form of
    ``jax.lax.ragged_dot``, the one the TPU compiler has a kernel for (a
    product that contracts another dimension of ``rhs`` it expands into
    a dense one over every group, sixteen times the work in the routed
    8k cell, AOT, PR 56: the transpose is spelled out there)."""
    if walk is not None:
        return grouped_matmul.gmm(lhs, rhs, transpose_rhs=transposed,
                                  walk=walk)
    return lax.ragged_dot(
        lhs, jnp.swapaxes(rhs, 1, 2) if transposed else rhs, sizes)


def _ragged_outer(a, b, sizes, walk):
    """``sum over a group's rows of a[m]^T b[m]``: [G, ka, kb], the
    gradient of a grouped product in its matrices
    (``grouped_matmul.tgmm``, or ``jax.lax.ragged_dot_general`` where
    ``walk`` is None)."""
    if walk is not None:
        return grouped_matmul.tgmm(a, b, walk=walk)
    return lax.ragged_dot_general(
        a, b, sizes, lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(((0,), (0,)), ((), ())),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[]))


def _pick_sum(rows, pos, ours):
    """``out[t] = sum over t's picks k with ours[t, k] of rows[pos[t,
    k]]``, float32: a gather of ``T x K`` rows, so nothing is scattered.
    Rows read in no order take 43 ns each on a v5e (5.6 ms for 16384 x 8
    rows of 2304) where rows in ascending runs, ``x[token]``, take 7.6;
    a scatter-add of the held rows alone read 5.1-6.5 ms (PR 56).  So a
    layer whose shapes the kernels take sums the held picks' rows where
    the sort left them (:func:`_combine`: 1.5 ms for this form's 5.96,
    PR 59); this is the form of the others and the kernel's reference."""
    T, K = pos.shape
    picked = rows[pos.reshape(T * K)].reshape(T, K, -1)
    return jnp.sum(jnp.where(ours[:, :, None], picked.astype(jnp.float32),
                             0.0), axis=1)


# A piece of the sorted picks, over what uniform routing fills, rounded
# up to _PIECE_ROWS.  A piece that barely overflows pays a second
# piece's fixed costs (~25 ms a layer in the routed 8k cell, PR 56):
# half again is room for a draw's skew and some of a router's drift.
# Against one buffer for every pick the pieces read 42.4 k tokens/s for
# 37.8 k there and 1.3 GB less of the step's temporaries (AOT)
_PIECE_HEADROOM = 1.5
_PIECE_ROWS = 512


def piece_rows(T: int, top_k: int, held: int, experts: int) -> int:
    """Rows of one piece of the sorted picks (:func:`_sorted_experts_fwd`):
    what the held experts take under uniform routing, ``T * top_k * held /
    experts``, and half again; at most every pick's row (``T * top_k``)."""
    want = _PIECE_HEADROOM * T * top_k * held / experts
    return min(T * top_k, -(-int(want) // _PIECE_ROWS) * _PIECE_ROWS)


def _kernels_take(T: int, rows: int, d: int, f: int) -> bool:
    """Whether a layer's grouped products over ``rows`` sorted rows of
    ``T`` tokens, and the combines of their rows, are
    ``ops/grouped_matmul.py``'s: one decision for a layer, the gate's on
    each contraction and width of its products (gate|up and down, and
    the two read transposed for the rows' gradients; the matrices'
    gradients have the same widths) and the combine's on its shapes."""
    return bool(grouped_matmul.combine_uses_kernel(T, rows, d)) and all(
        grouped_matmul.uses_kernel(rows, k, n) for k, n in (
            (d, 2 * f), (f, d), (d, f), (2 * f, d)))


def product_path(T: int, top_k: int, held: int, experts: int, d: int,
                 f: int) -> str:
    """The form a differentiated layer's grouped products take at these
    shapes, ``pallas`` or ``ragged_dot`` (the step telemetry's
    ``moe_product``)."""
    return "pallas" if _kernels_take(
        T, piece_rows(T, top_k, held, experts), d, f) else "ragged_dot"


def combine_path(*shapes: int) -> str:
    """The form that layer sums its rows into tokens in, ``pallas`` or
    ``xla`` (a gather), with its products (telemetry's ``moe_combine``)."""
    return "pallas" if product_path(*shapes) == "pallas" else "xla"


def _sorted_picks(local, weight, held: int, piece: int):
    """The picks sorted by expert, the held ones first: for each sorted
    row its pick (:func:`_sort_picks`; its token is ``pick // K``) and
    weight (0 behind the last held pick), for each pick its sorted row
    (``pos [T, K]``), and the first sorted row of each held expert and
    the one past its last.  The rows' vectors are padded to whole pieces
    with rows no pick has: every piece's products have one shape."""
    T, K = local.shape
    order, ws, n = _sort_picks(local, weight, held)
    pos = _to_picks(order, jnp.arange(T * K, dtype=jnp.int32)).reshape(T, K)
    ends = jnp.cumsum(n)
    ws = jnp.where(jnp.arange(T * K) < ends[-1], ws, 0.0)
    pad = (0, -(T * K) % piece)
    return jnp.pad(order, pad), pos, jnp.pad(ws, pad), ends - n, ends


def _piece(a, rows: int, order, pos, ws, starts, ends, ours):
    """Sorted rows ``a .. a + rows - 1`` (``a`` an int32 operand): their
    tokens and weights, which of them are held picks, each expert's rows
    among them, and the picks ``[T, K]`` that lie in the piece with their
    row in it."""
    b = a + rows
    n = jnp.clip(jnp.minimum(ends, b) - jnp.maximum(starts, a), 0, None)
    live = a + jnp.arange(rows) < ends[-1]
    mine = ours & (pos >= a) & (pos < b)
    # a pick outside the piece reads some row of it (masked where it is
    # summed): rows spread over the piece, not one row for all of them
    spread = jnp.arange(pos.size, dtype=jnp.int32).reshape(pos.shape) % rows
    return (lax.dynamic_slice_in_dim(order, a, rows) // pos.shape[1],
            lax.dynamic_slice_in_dim(ws, a, rows), live, n, mine,
            jnp.where(mine, pos - a, spread))


def _pieces(M: int, piece: int):
    """The sorted rows in pieces of ``piece``; the last may end in the pad."""
    return [(a, a + piece) for a in range(0, M, piece)]


def _later_pieces(M: int, piece: int, ends, run, first):
    """``first`` and, added to it leaf by leaf, ``run(a)`` of each piece
    behind the first that a held pick lies in (``a`` its first row, an
    int32): a loop of as many passes as there are such pieces, so the
    step holds their code once, under a conditional, so that a step
    whose held picks fit the first piece carries nothing through a loop
    (9 ms a step in the routed 8k cell, ``PERF.md`` section 6, PR 58)."""
    if len(_pieces(M, piece)) == 1:
        return first

    def later(first):
        # (the bounds int32 and not a weak 1: ``i * piece`` then has the
        # type the first piece's row 0 has, and one trace serves both)
        return lax.fori_loop(
            jnp.int32(1), lax.div(ends[-1] + (piece - 1), jnp.int32(piece)),
            lambda i, total: jax.tree.map(jnp.add, total, run(i * piece)),
            first)

    return lax.cond(ends[-1] > piece, later, lambda first: first, first)


def _walk(n, T: int, rows: int, d: int, f: int):
    """The schedule of a piece's products (``grouped_matmul.group_tiles``),
    made once a piece, or None where they are ``jax.lax.ragged_dot``'s."""
    if not _kernels_take(T, rows, d, f):
        return None
    return grouped_matmul.group_tiles(n, rows,
                                      grouped_matmul.tile_rows(rows))


def _runs(local, starts, piece: int, d: int, f: int):
    """Where the sort left the rows of each (token tile, held expert)
    (``grouped_matmul.combine_runs``), made once a layer and direction
    for every piece's combine, or None where :func:`_pick_sum` sums."""
    T = local.shape[0]
    if not _kernels_take(T, piece, d, f):
        return None
    return grouped_matmul.combine_runs(
        local, starts, tile_t=grouped_matmul.combine_tiling(T, d)[0])


def _combine(rows, a, token, runs, at, mine):
    """``out[t] = sum of the piece's rows of t's held picks``, float32:
    ``grouped_matmul.combine`` over the runs' parts in the piece from
    ``a`` where the layer takes the kernels, else :func:`_pick_sum`."""
    if runs is None:
        return _pick_sum(rows, at, mine)
    return grouped_matmul.combine(rows, token, runs, a, T=at.shape[0])


def _combine_windows(local, load, experts: int, d: int, f: int):
    """The windows of sorted rows that the combines of one direction of
    a layer bring for the picks ``local [T, K]`` (``load [held]``: the
    rows each held expert took), over every piece, an int32; 0 where the
    layer keeps the gather (telemetry's ``moe.combine_windows``)."""
    T, K = local.shape
    piece = piece_rows(T, K, load.shape[0], experts)
    runs = _runs(local, jnp.cumsum(load) - load, piece, d, f)
    if runs is None:
        return jnp.int32(0)
    return grouped_matmul.combine_windows(
        runs, [a for a, _ in _pieces(T * K, piece)], piece,
        grouped_matmul.combine_tiling(T, d)[1])


@functools.partial(jax.jit, static_argnames=("piece",))
def _piece_fwd(a, x, sort, ours, runs, w_gu, w_down, *, piece: int):
    """The forward of the ``piece`` sorted rows from ``a`` (int32): its
    part of the layer's sum, and its gate|up product.  One jitted
    function for every piece of every layer: a step traces it once, not
    a closure a piece and layer (0.8 s of a warm worker's set-up in the
    routed 8k cell, PR 58), and the compiler, which inlines it, sees
    ``a == 0`` where that is what the caller passed."""
    token, ws, live, n, mine, at = _piece(a, piece, *sort, ours)
    walk = _walk(n, x.shape[0], piece, x.shape[1], w_down.shape[1])
    gu = _ragged(x[token], w_gu, n, walk)                     # [piece, 2f]
    y = _ragged(_hidden(gu, ws, live), w_down, n, walk)
    return _combine(y, a, token, runs, at, mine), gu


def _sorted_experts_fwd(x, local, weight, e_gate, e_up, e_down, lead,
                        piece):
    """:func:`_grouped_experts` for a call that is differentiated: the
    same sum, as grouped products over the picks sorted by expert (gate
    and up as one product against the two matrices side by side).

    Every pick has a row in the sorted order (``T * K``: none can be
    dropped), the held picks first.  The rows are taken in equal pieces
    of ``piece`` rows: the first always, a later one only if a held pick
    lies in it (:func:`_later_pieces`), so under any routing the work
    and the buffers touched follow the picks to within a piece, and
    nothing is sized by the worst case but the index vectors.  Inside a
    piece the products run over the groups' own sizes; rows behind the
    last held pick are never computed and masked wherever read.  The
    weight meets the hidden product before the down projection, so the
    combine is a sum of rows.  Kept for the backward: the first piece's
    gate|up product; a later piece computes its own again."""
    T, K = local.shape
    held = e_gate.shape[len(lead)]
    ours = local < held
    sort = _sorted_picks(local, weight, held, piece)
    w_gu, w_down = _gate_up(e_gate, e_up, lead), _at(e_down, lead)
    runs = _runs(local, sort[3], piece, x.shape[1], w_down.shape[1])

    def run(a):
        with grouped_matmul.one_trace():
            return _piece_fwd(a, x, sort, ours, runs, w_gu, w_down,
                              piece=piece)

    out, gu = run(jnp.int32(0))
    out = _later_pieces(T * K, piece, sort[-1], lambda a: run(a)[0], out)
    hit = jnp.sum(sort[-1] > sort[-2])
    return (out, hit), (x, local, weight, gu, e_gate, e_up, e_down, lead)


def _gate_up(e_gate, e_up, lead):
    return jnp.concatenate([_at(e_gate, lead), _at(e_up, lead)], axis=-1)


def _hidden(gu, ws, live):
    """``silu(gate) * up * weight`` of the live sorted rows, 0 behind
    them; gu [M, 2f] -> [M, f]."""
    g, u = jnp.split(gu.astype(jnp.float32), 2, axis=-1)
    h = jax.nn.silu(g) * u * ws[:, None]
    return jnp.where(live[:, None], h, 0.0).astype(gu.dtype)


@functools.partial(jax.jit, static_argnames=("piece",))
def _piece_bwd(a, gu, x, dout, sort, ours, runs, w_gu, w_down, *,
               piece: int):
    """The backward of the ``piece`` sorted rows from ``a``, given the
    piece's gate|up product or None (it is computed again): its part of
    the gradients in ``x``, the weights ``[T, K]`` and the two matrices."""
    token, ws, live, n, mine, at = _piece(a, piece, *sort, ours)
    dt = x.dtype
    walk = _walk(n, x.shape[0], piece, x.shape[1], w_down.shape[1])
    rows = live[:, None]
    xs = x[token]
    if gu is None:
        gu = _ragged(xs, w_gu, n, walk)
    dy = jnp.where(rows, dout[token], 0)                       # [piece, d]
    dh = jnp.where(rows, _ragged(dy, w_down, n, walk, True), 0
                   ).astype(jnp.float32)                       # [piece, f]
    g, u = jnp.split(gu.astype(jnp.float32), 2, axis=-1)
    sg = jax.nn.sigmoid(g)
    act = g * sg
    dws = jnp.sum(dh * act * u, axis=-1)
    dh = dh * ws[:, None]
    dgu = jnp.concatenate([dh * u * sg * (1.0 + g * (1.0 - sg)),
                           dh * act], axis=-1).astype(dt)
    dxs = _ragged(dgu, w_gu, n, walk, True)
    if runs is None:
        # (the kernel's combine reads no row behind the live ones, and
        # takes the product's rows as the forward's does: one trace)
        dxs = jnp.where(rows, dxs, 0)
    # (back to their picks through a sort: zeros where the piece has no row)
    dws = _to_picks(sort[0][:mine.size], lax.dynamic_update_slice_in_dim(
        jnp.zeros(sort[0].shape, dws.dtype), dws, a, 0)[:mine.size])
    return (_combine(dxs, a, token, runs, at, mine),
            jnp.where(mine, dws.reshape(mine.shape), 0.0),
            _ragged_outer(xs, dgu, n, walk),                   # [G, d, 2f]
            _ragged_outer(_hidden(gu, ws, live), dy, n, walk))


def _sorted_experts_bwd(piece, res, cts):
    x, local, weight, gu_first, e_gate, e_up, e_down, lead = res
    # what the backward computes again from the residuals (the sort, the
    # fetched rows, the float32 view of gate|up, the hidden product) the
    # compiler would otherwise share with the forward's and keep alive
    # in between, a sorted buffer a layer: the barrier keeps them apart
    x, local, weight, gu_first = lax.optimization_barrier(
        (x, local, weight, gu_first))
    T, K = local.shape
    dt = x.dtype
    held = e_gate.shape[len(lead)]
    f = gu_first.shape[1] // 2
    ours = local < held
    sort = _sorted_picks(local, weight, held, piece)
    w_gu, w_down = _gate_up(e_gate, e_up, lead), _at(e_down, lead)
    runs = _runs(local, sort[3], piece, x.shape[1], f)
    dout = cts[0].astype(dt)

    def run(a, gu=None):
        with grouped_matmul.one_trace():
            return _piece_bwd(a, gu, x, dout, sort, ours, runs, w_gu,
                              w_down, piece=piece)

    grads = _later_pieces(T * K, piece, sort[-1], run,
                          run(jnp.int32(0), gu_first))
    dx, dweight, dw_gu, dw_down = grads
    # (a scheduler free to put the matrices' gradients off keeps the sorted
    # operands alive: they leave with dx)
    dx, dw_gu, dw_down = lax.optimization_barrier((dx, dw_gu, dw_down))

    def stacked(dw, w):
        dw = dw.astype(w.dtype)
        return jnp.zeros_like(w).at[tuple(lead)].set(dw) if lead else dw

    return (dx.astype(dt), None, dweight, stacked(dw_gu[..., :f], e_gate),
            stacked(dw_gu[..., f:], e_up), stacked(dw_down, e_down), None)


# one expert layer, two lowerings: JAX runs the primal (the loop over the
# tiles the picks fill) where no gradient is taken, the rule elsewhere
_experts = jax.custom_vjp(_grouped_experts, nondiff_argnums=(7,))
_experts.defvjp(_sorted_experts_fwd, _sorted_experts_bwd)


def dropless_moe(x, router, router_bias, e_gate, e_up, e_down, *,
                 held: Sequence[int], n_routed: int, top_k: int,
                 scale: float, valid=None, lead=(),
                 renormalise: bool = False, scoring: str = "softmax",
                 with_load: bool = False):
    """One chip's part of a dropless expert layer on x [T, d] -> (its
    output [T, d], counts [len(MOE_COUNTS)] int32).

    ``router`` [d, E] scores every expert of the deployment in float32
    (``scoring``: a ``softmax`` over the row's logits, or each logit's
    ``sigmoid``); the ``top_k`` of ``score + router_bias`` are a row's
    picks, weighted by their scores as they are, or with ``renormalise``
    by their scores over the sum of the row's ``top_k`` picked scores
    wherever those experts live (so the shares of a deployment add up to
    the uncut layer), and by ``scale``.  Experts ``0 .. n_routed - 1``
    are swiglu experts, of which this chip holds ``held`` (their ids, in
    the order of ``e_gate, e_up`` [*lead, held, d, f] and ``e_down``
    [*lead, held, f, d], read at the indices ``lead``: stacked layers);
    experts from ``n_routed`` up are identity experts (``E_e(x) = x``),
    computed where the row lives.  A pick on a routed expert held
    elsewhere adds nothing.  ``valid`` [T] bool marks the rows that are
    tokens of a sequence (absent: all): the others pick nothing and
    count nothing.  ``with_load`` appends to what is returned the rows
    each held expert took ([held] int32) and the windows a
    differentiated call's combines bring in one direction
    (:func:`_combine_windows`).  Device scopes: ``route``, ``experts``,
    ``identity`` (the caller names the layer).  A differentiated call
    computes the held experts' part by grouped products over the sorted
    picks and has gradients in ``x``, ``router`` (through the weights)
    and the three expert matrices; a call nobody differentiates takes
    the loop (:func:`_grouped_experts`)."""
    T, d = x.shape
    E = router.shape[1]
    if valid is None:
        valid = jnp.ones((T,), bool)
    with jax.named_scope("route"):
        logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                            router.astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        score = SCORINGS[scoring](logits)
        _, pick = lax.top_k(score + router_bias.astype(jnp.float32), top_k)
        weight = _at_picks(score, pick)                          # [T, K]
        if renormalise:
            # (apart from the selection's sum, or the compiler merges the
            # two and adds a row's K scores in another order: an ulp)
            weight = lax.optimization_barrier(weight)
            weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
        local_of = np.full((E,), len(held), np.int32)
        local_of[list(held)] = np.arange(len(held))
        local = jnp.where(valid[:, None],
                          _at_picks(jnp.asarray(local_of)[None], pick),
                          len(held))
        identity = valid[:, None] & (pick >= n_routed)
        ours = local < len(held)
    with jax.named_scope("experts"):
        out, hit = _experts(x, local, jnp.where(ours, weight, 0.0),
                            e_gate, e_up, e_down, tuple(lead),
                            piece_rows(T, top_k, len(held), n_routed))
    with jax.named_scope("identity"):
        out = out + x.astype(jnp.float32) * jnp.sum(
            jnp.where(identity, weight, 0.0), -1, keepdims=True)
    rows = jnp.sum(valid)
    load = jnp.sum(local[:, :, None] == jnp.arange(len(held)),
                   axis=(0, 1), dtype=jnp.int32)
    # the tiles the picks fill: the loop's trips, which its time follows
    tile = _tile_rows(T)
    counts = jnp.stack([rows, jnp.sum(ours), jnp.sum(identity),
                        rows * top_k, hit, jnp.int32(1),
                        jnp.sum((load + tile - 1) // tile)]
                       ).astype(jnp.int32)
    out = (scale * out).astype(x.dtype)
    if with_load:
        return out, counts, load, _combine_windows(
            local, load, n_routed, d, e_gate.shape[-1])
    return out, counts
