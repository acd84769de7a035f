"""Grouped matrix products over rows sorted by group, as Pallas kernels.

The differentiated expert layer (``parallel/moe.py``) sorts a step's
picks by expert and takes three kinds of product over the sorted rows,
``sizes [G]`` rows a group in order, rows behind ``sum(sizes)``
belonging to no group:

- :func:`gmm` — ``lhs [M, k]`` against each group's ``rhs [G, k, n]``
  (or ``[G, n, k]``, read transposed through its index map: no copy of
  the matrices) -> ``[M, n]``; rows of no group come out zero.
- :func:`tgmm` — ``sum over a group's rows of lhs[m]^T rhs[m]`` ->
  ``[G, k, n]``, the products' gradients in their matrices; zeros for
  an empty group.  (The rows of no group are masked in ``lhs`` alone:
  ``rhs`` is finite there.)

- :func:`combine` — ``out[t] = sum of the sorted rows whose token is t``
  over the runs the sort left them in (below) -> ``[T, d]`` float32.

The two products walk the same schedule, :func:`group_tiles`: the row tiles of
``tile_m`` rows in order, a tile that straddles a boundary once for
each group it touches with the other groups' rows masked, so the steps
that compute follow the live rows and an expert nobody picked is never
read.  The schedule is computed from ``sizes`` outside the kernels and
reaches them by scalar prefetch; the grid is static (``M / tile_m + G -
1`` steps, the most a schedule can take), and the steps behind the
last computing one write the zeros: of the row tiles no group has rows
in (``gmm``), of the empty groups' matrices (``tgmm``).  Every product
accumulates in float32.

**What the kernels cost a process's set-up**, warm or cold (``PERF.md``
section 6, PR 58), in the order it was found.  (1) Before jax can look
a step's executable up it traces the step and lowers it to StableHLO,
and a ``pallas_call`` is traced and lowered to a Mosaic module in Python
there, once a call site unless something dedupes it (~0.05 s a site at
the routed 8k cell's shapes).  So the two entry points are reached
through one module-level ``jax.jit`` each, static in the tiling, the
transposition, the output's dtype and the interpret flag: call sites
with equal shapes share one traced jaxpr and the lowered module holds
one kernel body a distinct product.  (2) Loading the executable costs
~0.23 ms an HLO instruction of the compiled module, whatever its bytes:
the schedule is inlined once a piece of every layer, forward and
backward, so :func:`group_tiles` is written as a few broadcast
comparisons and sums (239 instructions a copy; with ``cumsum``, ``//``
and gathers it was 665, a third of that step's module, and 1.9 s of
every warm process's load).  (3) The executable holds a kernel's *code*
once a call site: a block's product unrolled whole (8,064 MXU pushes at
256 x 2304 x 1792) made that step's executable 74 MB larger, so a kernel
walks its block's columns in chunks of ``_CHUNK`` with a loop the
compiler keeps, and its code is one chunk's product.  Tilings are
functions of the shapes alone (:func:`tiling`); nothing is tuned at run
time.

**The combine** (PR 59).  A stable sort by group leaves a group's rows
ascending by token, so the rows of group ``g`` that belong to a tile of
``tile_t`` consecutive tokens are one contiguous run of the sorted rows
(:func:`combine_runs`).  :func:`combine` takes a token tile's sum as one
grid step, run by run: a run is brought in windows of ``window`` rows by
DMA from the rows where they lie in HBM (a window starts on a multiple
of 128 rows: Mosaic slices the ``(16, 128)``-tiled bfloat16 buffer in
whole tiles only — a one-row copy is refused, "Slice shape along
dimension 0 must be aligned to tiling (8), but is 1" — and the tokens'
``[1, M]`` lane vector on multiples of 128), met with a 0/1 matrix
``S[t, r] = (token[r] == t)`` on the MXU and added in float32: products
by 0 or 1, the sum a gather of the rows makes, in another order.  Two
windows are in flight while one is summed.

:func:`uses_kernel` and :func:`combine_uses_kernel` are the one place
that decides from shapes whether a product or a combine takes these
kernels or the compiler's form.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import substrate

_LANES = 128
# rows of a tile.  A tile that holds a boundary is computed once a group
# it touches, whole, so deep tiles pay for the boundaries (G - 1 of them)
# and shallow ones feed the MXU worse: at the routed 8k cell's shapes,
# 16 groups of ~2,048 rows, 256 read 4-7 % under 512 and 1024 15-19 %
# over it in every product, 128 0-3 % over 256 (``PERF.md`` section 6,
# PR 58)
_TILE_M = (256, 128)
# gmm holds [tile_m, k] of lhs and [k, tile_n] of a group's matrix: the
# whole contraction, at most _GMM_TILE_K wide (walked in two grid steps
# through an accumulator it read 30-40 % slower there; the gate declines
# a wider one), and the whole width up to _GMM_TILE_N, so the rows are
# read once and the matrix block changes only where the group does.
# tgmm accumulates a group's [tile_k, tile_n] in float32 over its row
# tiles
_GMM_TILE_K = 2304
_GMM_TILE_N = 2304
_TGMM_TILE_K = 1152
_TGMM_TILE_N = 2304
# columns of a block one pass of a kernel's loop takes: the kernel's code
# is one chunk's product, and the executable holds it once a call site
# (with whole blocks unrolled at 80 sites the routed 8k cell's serialized
# executable grew from 200.8 to 274.7 MB, ``PERF.md`` section 6, PR 58)
_CHUNK = 256
# the widest blocks above, double-buffered and in float32, need 54 MiB
# (gmm: 4.7 lhs + 42.5 rhs + 4.7 out + 2.4 product; in bfloat16 28),
# over Mosaic's default 16; a v5e has 128
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# the combine: tokens of a tile and rows of a window, and the columns one
# product takes.  At the routed 8k cell's shapes (16,384 tokens, 16
# groups, ~32.7 k of 49,152 rows live, 2304 wide; the gather 5.96 ms):
# tiles of 512 tokens read 1.52 ms a call, 256 1.61, 128 2.20, windows
# of 256 rows 2.37-2.47; and the window's product over the whole width
# at once, where walked 256 columns at a time it read 2.63 for 1.61 (a
# product's fixed latency nine times a window), 768 1.86, 1152 1.73
# (``PERF.md`` section 6, PR 59)
_TILE_T = (512, 256, 128)
_WINDOW = 128
_COMBINE_CHUNK = 2304


def _largest_tile(n: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``n`` and is at most
    ``cap`` (``n`` is a multiple of 128)."""
    return max(t for t in range(_LANES, min(n, cap) + 1, _LANES)
               if n % t == 0)


def uses_kernel(M: int, k: int, n: int) -> substrate.Support:
    """Whether the product of ``M`` sorted rows ``[M, k]`` with
    ``[G, k, n]`` (:func:`gmm`; the one with ``[G, n, k]`` read
    transposed is asked as what it computes, ``k`` the contraction) takes
    the Pallas kernel — the single source of the decision
    (``parallel/moe.py`` asks for each of a layer's products, the step
    telemetry reports it): wherever the tiles divide the shapes and a
    block holds the whole contraction.  A shape they do not keeps
    ``jax.lax.ragged_dot``.  :func:`tgmm` of ``[M, k]`` with ``[M, n]``
    takes whatever shapes two such products take, ``[M, k]`` and
    ``[M, n]`` against a group's ``[k, n]``."""
    for name, size in (("M", M), ("k", k), ("n", n)):
        if size <= 0 or size % _LANES:
            return substrate.unsupported(
                f"{name}={size} is not a multiple of {_LANES}")
    if k > _GMM_TILE_K:
        return substrate.unsupported(
            f"k={k}: a block holds the whole contraction, at most "
            f"{_GMM_TILE_K} wide")
    return substrate.supported("pallas")


def tile_rows(M: int) -> int:
    """Rows of one tile of ``M`` sorted rows."""
    return next(t for t in _TILE_M if M % t == 0)


def tiling(M: int, k: int, n: int, *, transposed_lhs: bool = False
           ) -> Tuple[int, ...]:
    """``(tile_m, tile_n, chunk)`` of :func:`gmm`, whose block holds the
    whole contraction, or, with ``transposed_lhs``, ``(tile_m, tile_k,
    tile_n, chunk)`` of :func:`tgmm` at these shapes; ``chunk``: the
    columns of a block one pass of the kernel's loop takes."""
    if transposed_lhs:
        tile_n = _largest_tile(n, _TGMM_TILE_N)
        return (tile_rows(M), _largest_tile(k, _TGMM_TILE_K), tile_n,
                _largest_tile(tile_n, _CHUNK))
    tile_n = _largest_tile(n, _GMM_TILE_N)
    return tile_rows(M), tile_n, _largest_tile(tile_n, _CHUNK)


def combine_uses_kernel(T: int, M: int, d: int) -> substrate.Support:
    """Whether the combine of ``M`` sorted rows ``[M, d]`` into ``T``
    tokens takes :func:`combine`: wherever the token tiles divide ``T``,
    the windows ``M`` and the lanes ``d``.  Other shapes keep the
    compiler's gather (``parallel/moe.py:_pick_sum``)."""
    for name, size, tile in (("T", T, _TILE_T[-1]), ("M", M, _WINDOW),
                             ("d", d, _LANES)):
        if size <= 0 or size % tile:
            return substrate.unsupported(
                f"{name}={size} is not a multiple of {tile}")
    return substrate.supported("pallas")


def combine_tiling(T: int, d: int) -> Tuple[int, int, int]:
    """``(tile_t, window, chunk)`` of :func:`combine` at these shapes:
    tokens a tile, rows a window, columns one pass of the kernel's loop
    takes."""
    return (next(t for t in _TILE_T if T % t == 0), _WINDOW,
            _largest_tile(d, _COMBINE_CHUNK))


def one_trace():
    """jax traces a ``custom_vjp``'s rule under the abstract mesh by
    name, an empty one where there is none, and the function itself
    under no name at all, and its trace cache tells the two apart: a
    product a backward computes again would be a second kernel body.
    Under the mesh there is, by name, both are one (observed on jax
    0.9.0; ``tests/test_tpu_aot.py`` counts the step's kernel bodies, and
    a count over six after an upgrade points here)."""
    return jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh())


class GroupTiles(NamedTuple):
    """The schedule of one set of sorted rows (:func:`group_tiles`),
    ``S = M / tile_m + G - 1`` steps of which the first ``active``
    compute."""
    offsets: jax.Array    # [G + 1] first row of a group; [G]: live rows
    group: jax.Array      # [S] a step's group; past active: the last one
    tile: jax.Array       # [S] a step's row tile; past active: the tiles
    #                       no group has rows in, then the last tile
    zeroed: jax.Array     # [S] group, and past active the empty groups
    active: jax.Array     # [1] steps that compute


def group_tiles(sizes, M: int, tile_m: int) -> GroupTiles:
    """The walk of ``M`` sorted rows in tiles of ``tile_m``: the tiles
    a group has rows in, group by group in order (an empty group has
    none), so a tile that holds a boundary is a step of each group it
    touches and its visits are consecutive.  Computed once for all the
    products over one set of sorted rows (and traced once for all the
    sets of one shape)."""
    with one_trace():
        return _group_tiles(sizes, M=M, tile_m=tile_m)


@functools.partial(jax.jit, static_argnames=("M", "tile_m"))
def _group_tiles(sizes, *, M: int, tile_m: int) -> GroupTiles:
    # (broadcast comparisons and sums throughout, no running sum, gather
    # or signed floor: a step's module holds this once a piece of every
    # layer, forward and backward, and what the compiler made of those
    # was a third of the routed 8k step's instructions, ``PERF.md``
    # section 6, PR 58)
    G = sizes.shape[0]
    tiles_m = M // tile_m
    S = tiles_m + G - 1
    i32 = jnp.int32
    sizes = sizes.astype(i32)
    ids = lax.iota(i32, G)
    upto = ids[None, :] <= ids[:, None]              # [g, h]: h <= g

    def running(v):
        return jnp.sum(jnp.where(upto, v[None, :], 0), axis=1, dtype=i32)

    held = sizes > 0
    ends = running(sizes)
    first = lax.div(ends - sizes, i32(tile_m))
    tiles = jnp.where(held, lax.div(ends - 1, i32(tile_m)) - first + 1, 0)
    tile_end = running(tiles)
    empties = running((~held).astype(i32))
    active, n_empty = tile_end[G - 1], empties[G - 1]
    last_held = jnp.max(jnp.where(held, ids, 0))
    last_empty = jnp.max(jnp.where(held, 0, ids))
    s = lax.iota(i32, S)
    group = jnp.minimum(
        jnp.sum(s[:, None] >= tile_end[None, :], axis=1, dtype=i32),
        last_held)
    # a computing step's tile: its group's first and as many on as the
    # step is behind the group's first step
    of = group[:, None] == ids[None, :]
    base = jnp.sum(jnp.where(of, (first - tile_end + tiles)[None, :], 0),
                   axis=1, dtype=i32)
    tail = s - active                    # >= 0 behind the computing steps
    live_tiles = lax.div(ends[G - 1] + (tile_m - 1), i32(tile_m))
    tile = jnp.where(tail < 0, base + s,
                     jnp.minimum(live_tiles + tail, tiles_m - 1))
    # the tail-th empty group: the groups before it are those whose
    # count of empty groups so far is at most tail
    empty = jnp.sum(empties[None, :] <= tail[:, None], axis=1, dtype=i32)
    zeroed = jnp.where(
        tail < 0, group,
        jnp.where(empty < G, empty,
                  jnp.where(n_empty > 0, last_empty, last_held)))
    return GroupTiles(jnp.concatenate([jnp.zeros((1,), i32), ends]), group,
                      tile, zeroed, active.reshape(1))


class Runs(NamedTuple):
    """Where a stable sort by group left the rows of each (token tile,
    group) (:func:`combine_runs`): one run of the sorted rows each."""
    first: jax.Array      # [T / tile_t, G] the run's first sorted row
    count: jax.Array      # [T / tile_t, G] its rows


def combine_runs(local, starts, *, tile_t: int) -> Runs:
    """The runs of picks sorted by group with a stable sort: ``local [T,
    K]`` a pick's group (``G`` and up: none), ``starts [G]`` the first
    sorted row of each group.  A group's rows ascend by token, so those
    of a tile of ``tile_t`` consecutive tokens lie together.  Computed
    once for the combines over every piece of the sorted rows."""
    with one_trace():
        return _combine_runs(local, starts, tile_t=tile_t)


@functools.partial(jax.jit, static_argnames=("tile_t",))
def _combine_runs(local, starts, *, tile_t: int) -> Runs:
    # (broadcast comparisons and sums, as ``_group_tiles`` and for its
    # reason; the picks along the lanes: [G, tiles])
    T, K = local.shape
    G = starts.shape[0]
    tiles = T // tile_t
    i32 = jnp.int32
    count = jnp.sum(
        local.reshape(1, tiles, tile_t * K) == lax.iota(i32, G)[:, None, None],
        axis=2, dtype=i32)
    ids = lax.iota(i32, tiles)
    before = jnp.sum(jnp.where(ids[None, :] < ids[:, None], count[:, None, :],
                               0), axis=2, dtype=i32)
    return Runs((starts.astype(i32)[:, None] + before).T, count.T)


def _windows(lo, hi, window: int):
    """The first row of the first window of the run ``lo .. hi`` of a
    piece's rows, and the windows of ``window`` rows it takes from
    there (none for an empty run): scalars in the kernel, arrays in the
    count."""
    first = lax.div(lo, jnp.int32(_WINDOW)) * _WINDOW
    return first, jnp.where(
        hi > lo, lax.div(hi - first + (window - 1), jnp.int32(window)), 0)


def _in_piece(runs: Runs, a, M: int):
    """The runs' parts among the ``M`` sorted rows from row ``a``, as
    rows of that piece: ``lo, hi``, equal where a run has no row
    there."""
    lo = runs.first - a
    return jnp.clip(lo, 0, M), jnp.clip(lo + runs.count, 0, M)


def combine_windows(runs: Runs, pieces, M: int, window: int):
    """The windows :func:`combine` brings for ``runs`` over the pieces of
    ``M`` sorted rows that start at the rows ``pieces [P]``, an int32
    (the step telemetry's ``moe.combine_windows``)."""
    lo, hi = _in_piece(runs, jnp.asarray(pieces, jnp.int32)[:, None, None],
                       M)
    return jnp.sum(_windows(lo, hi, window)[1], dtype=jnp.int32)


def _last_computing(s, active_ref):
    """The step whose blocks a step behind the computing ones keeps (so
    nothing is fetched for it)."""
    return jnp.minimum(s, jnp.maximum(active_ref[0] - 1, 0))


def _rows_of(row0, shape, lo, hi):
    rows = row0 + lax.broadcasted_iota(jnp.int32, shape, 0)
    return (rows >= lo) & (rows < hi)


def _columns(tile_n: int, chunk: int, body):
    """``body(cols)`` for each ``chunk`` of a block's ``tile_n`` columns
    in turn, as a loop the compiler keeps: the kernel's code is one
    chunk's product, not the block's (a step's module holds a copy of
    the kernel's code a call site)."""
    def step(j, carry):
        body(pl.ds(pl.multiple_of(j * chunk, chunk), chunk))
        return carry
    lax.fori_loop(0, tile_n // chunk, step, 0)


def _gmm_kernel(offsets, group, tile, active, lhs_ref, rhs_ref, out_ref, *,
                tile_m: int, chunk: int, transpose_rhs: bool):
    s = pl.program_id(1)
    G = offsets.shape[0] - 1
    g = group[s]
    row0 = tile[s] * tile_m
    computing = s < active[0]

    @pl.when(computing)
    def _():
        # the group's rows of the tile over what the tile's earlier
        # visits left; its first visit finds nothing of its own in the
        # block.  (One masked store for whole tiles and boundary tiles
        # alike: a second, unmasked form would be as much code again in
        # every call site of the executable.)
        ours = _rows_of(row0, (tile_m, chunk), offsets[g], offsets[g + 1])
        revisit = (s > 0) & (tile[jnp.maximum(s - 1, 0)] == tile[s])

        def store(cols):
            if transpose_rhs:
                y = lax.dot_general(lhs_ref[...], rhs_ref[cols, :],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            else:
                y = jnp.dot(lhs_ref[...], rhs_ref[:, cols],
                            preferred_element_type=jnp.float32)
            before = jnp.where(
                revisit, out_ref[:, cols].astype(jnp.float32), 0.0)
            out_ref[:, cols] = jnp.where(ours, y, before).astype(
                out_ref.dtype)
        _columns(out_ref.shape[1], chunk, store)

    @pl.when(~computing & (row0 >= offsets[G]))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def _tgmm_kernel(offsets, zeroed, tile, active, lhs_ref, rhs_ref, out_ref,
                 acc_ref, *, tile_m: int, chunk: int, steps: int):
    s = pl.program_id(2)
    g = zeroed[s]
    row0 = tile[s] * tile_m

    @pl.when((s == 0) | (zeroed[jnp.maximum(s - 1, 0)] != g))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s < active[0])
    def _():
        # the other groups' rows of the tile zeroed in lhs: what they
        # hold in rhs then adds nothing
        lhs = lhs_ref[...]
        keep = _rows_of(row0, lhs.shape, offsets[g], offsets[g + 1])
        lhs = jnp.where(keep, lhs.astype(jnp.float32), 0.0).astype(lhs.dtype)

        def add(cols):
            acc_ref[:, cols] += lax.dot_general(
                lhs, rhs_ref[:, cols], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        _columns(out_ref.shape[1], chunk, add)

    @pl.when((s == steps - 1) | (zeroed[jnp.minimum(s + 1, steps - 1)] != g))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _combine_kernel(lo_ref, hi_ref, live_ref, token_ref, rows_ref, out_ref,
                    win_ref, sem_ref, *, G: int, tile_t: int, window: int,
                    chunk: int):
    i = pl.program_id(0)
    M = rows_ref.shape[0]
    slots = win_ref.shape[0]

    def run(g):
        # group g's run in this tile, the first row of its first window
        # and its windows (none behind the last group)
        at = i * G + jnp.minimum(g, G - 1)
        lo, hi = lo_ref[at], hi_ref[at]
        first, n = _windows(lo, hi, window)
        return lo, hi, first, jnp.where(g < G, n, 0)

    def seek(g):
        # the first group from g on that has a run (G and up: none)
        return lax.while_loop(lambda h: (h < G) & (run(h)[3] == 0),
                              lambda h: h + 1, g)

    def following(g, j):
        # the window behind window j of group g: the run's next, or the
        # first of the next group that has one
        more = j + 1 < run(g)[3]
        return jnp.where(more, g, seek(g + 1)), jnp.where(more, j + 1, 0)

    def copy(g, j, slot):
        # (the buffer's last window where one would end behind it: the
        # rows an earlier window brought are masked below)
        row0 = pl.multiple_of(
            jnp.minimum(run(g)[2] + j * window, M - window), _WINDOW)
        return row0, pltpu.make_async_copy(
            rows_ref.at[pl.ds(row0, window)], win_ref.at[slot],
            sem_ref.at[slot])

    def start(g, j, slot):
        @pl.when(g < G)
        def _():
            copy(g, j, slot)[1].start()

    def add(state):
        # two windows are in flight while one is summed
        g, j, g1, j1, slot = state
        g2, j2 = following(g1, j1)
        start(g2, j2, lax.rem(slot + 2, slots))
        row0, brought = copy(g, j, slot)
        brought.wait()
        # the run's rows of the window that no earlier window brought:
        # the others are 0 in the 0/1 matrix
        lo, hi, first, _ = run(g)
        lo = jnp.maximum(lo, first + j * window)
        token = token_ref[:, pl.ds(row0, window)]              # [1, window]
        lane = row0 + lax.broadcasted_iota(jnp.int32, (1, window), 1)
        tokens = i * tile_t + lax.broadcasted_iota(
            jnp.int32, (tile_t, window), 0)
        pick = ((token == tokens) & (lane >= lo) & (lane < hi)).astype(
            win_ref.dtype)                                 # [tile_t, window]

        def adding(live):
            def store(cols):
                rows = win_ref[slot, :, cols]
                if live is not None:
                    rows = jnp.where(live, rows.astype(jnp.float32),
                                     0.0).astype(rows.dtype)
                out_ref[:, cols] += jnp.dot(
                    pick, rows, preferred_element_type=jnp.float32)
            return store

        # a row behind the last live one may hold anything, and 0 x NaN
        # is NaN: a window that reaches behind them is masked itself (a
        # pass over the window that the others are spared: it was more
        # than their product, ``PERF.md`` section 6, PR 59)
        behind = row0 + window > live_ref[0]

        @pl.when(behind)
        def _():
            narrow = _largest_tile(chunk, _CHUNK)
            _columns(out_ref.shape[1], narrow, adding(
                _rows_of(row0, (window, 1), 0, live_ref[0])))

        @pl.when(~behind)
        def _():
            _columns(out_ref.shape[1], chunk, adding(None))
        return g1, j1, g2, j2, lax.rem(slot + 1, slots)

    out_ref[...] = jnp.zeros_like(out_ref)
    g, j = seek(jnp.int32(0)), jnp.int32(0)
    g1, j1 = following(g, j)
    start(g, j, 0)
    start(g1, j1, 1)
    lax.while_loop(lambda state: state[0] < G, add,
                   (g, j, g1, j1, jnp.int32(0)))


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _combine(rows, token, runs, a, *, tiles, interpret):
    tile_t, window, chunk = tiles
    M, d = rows.shape
    lo, hi = _in_piece(runs, a, M)
    # (the runs lie end to end: the rows before the last run's end are
    # the live ones)
    live = jnp.max(hi).reshape(1)
    n_tiles, G = lo.shape
    return pl.pallas_call(
        functools.partial(_combine_kernel, G=G, tile_t=tile_t,
                          window=window, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec((1, M), lambda i, *_: (0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile_t, d), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((3, window, d), rows.dtype),
                            pltpu.SemaphoreType.DMA((3,))],
        ),
        compiler_params=substrate.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tile_t, d), jnp.float32),
        interpret=interpret,
        name="combine",
    )(lo.reshape(-1), hi.reshape(-1), live, token.reshape(1, M), rows)


def _check(steps, M: int, tile_m: int, G: int):
    if M % tile_m or steps.shape[0] != M // tile_m + G - 1:
        raise ValueError(
            f"a schedule of {steps.shape[0]} steps is not that of {M} "
            f"rows in tiles of {tile_m} over {G} groups")


@functools.partial(jax.jit, static_argnames=(
    "tiles", "transpose_rhs", "out_dtype", "interpret"))
def _gmm(lhs, rhs, offsets, group, tile, active, *, tiles, transpose_rhs,
         out_dtype, interpret):
    # (the schedule's vectors one by one, those the kernel reads: the
    # traced function is the same wherever it is called from)
    tile_m, tile_n, chunk = tiles
    M, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    _check(group, M, tile_m, rhs.shape[0])

    def lhs_map(j, s, offsets, group, tile, active):
        return tile[_last_computing(s, active)], 0

    def rhs_map(j, s, offsets, group, tile, active):
        return (group[s], j, 0) if transpose_rhs else (group[s], 0, j)

    def out_map(j, s, offsets, group, tile, active):
        return tile[s], j

    return pl.pallas_call(
        functools.partial(_gmm_kernel, tile_m=tile_m, chunk=chunk,
                          transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tile_n, group.shape[0]),
            in_specs=[
                pl.BlockSpec((tile_m, k), lhs_map),
                pl.BlockSpec((None, tile_n, k) if transpose_rhs
                             else (None, k, tile_n), rhs_map)],
            out_specs=pl.BlockSpec((tile_m, tile_n), out_map),
        ),
        compiler_params=substrate.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        out_shape=jax.ShapeDtypeStruct((M, n), out_dtype),
        interpret=interpret,
        name="gmm_t" if transpose_rhs else "gmm",
    )(offsets, group, tile, active, lhs, rhs)


@functools.partial(jax.jit, static_argnames=(
    "tiles", "out_dtype", "interpret"))
def _tgmm(lhs, rhs, offsets, zeroed, tile, active, *, tiles, out_dtype,
          interpret):
    tile_m, tile_k, tile_n, chunk = tiles
    (M, k), n = lhs.shape, rhs.shape[1]
    G = offsets.shape[0] - 1
    _check(zeroed, M, tile_m, G)
    S = zeroed.shape[0]

    def lhs_map(i, j, s, offsets, zeroed, tile, active):
        return tile[_last_computing(s, active)], i

    def rhs_map(i, j, s, offsets, zeroed, tile, active):
        return tile[_last_computing(s, active)], j

    def out_map(i, j, s, offsets, zeroed, tile, active):
        return zeroed[s], i, j

    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tile_m=tile_m, chunk=chunk,
                          steps=S),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(k // tile_k, n // tile_n, S),
            in_specs=[pl.BlockSpec((tile_m, tile_k), lhs_map),
                      pl.BlockSpec((tile_m, tile_n), rhs_map)],
            out_specs=pl.BlockSpec((None, tile_k, tile_n), out_map),
            scratch_shapes=[pltpu.VMEM((tile_k, tile_n), jnp.float32)],
        ),
        compiler_params=substrate.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        out_shape=jax.ShapeDtypeStruct((G, k, n), out_dtype),
        interpret=interpret,
        name="tgmm",
    )(offsets, zeroed, tile, active, lhs, rhs)


def gmm(lhs, rhs, sizes=None, *, transpose_rhs: bool = False,
        walk: GroupTiles = None):
    """``out[m] = lhs[m] @ rhs[g]`` for the rows ``m`` of group ``g``
    (``lhs[m] @ rhs[g].T`` with ``transpose_rhs``: ``rhs [G, n, k]``),
    zeros for the rows behind ``sum(sizes)``; lhs ``[M, k]``, sizes
    ``[G]`` -> ``[M, n]`` in the operands' dtype.  ``walk`` is the rows'
    schedule where the caller has it already (:func:`group_tiles` at
    ``tile_rows(M)``), else it is made from ``sizes``."""
    M, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tiles = tiling(M, k, n)
    if walk is None:
        walk = group_tiles(sizes, M, tiles[0])
    with one_trace():
        return _gmm(lhs, rhs, walk.offsets, walk.group, walk.tile,
                    walk.active, tiles=tiles, transpose_rhs=transpose_rhs,
                    out_dtype=jnp.result_type(lhs, rhs),
                    interpret=substrate.use_interpret())


def tgmm(lhs, rhs, sizes=None, *, walk: GroupTiles = None):
    """``out[g] = sum over the rows m of group g of lhs[m]^T rhs[m]``,
    zeros for an empty group; lhs ``[M, k]``, rhs ``[M, n]``, sizes
    ``[G]`` -> ``[G, k, n]`` in the operands' dtype."""
    (M, k), n = lhs.shape, rhs.shape[1]
    tiles = tiling(M, k, n, transposed_lhs=True)
    if walk is None:
        walk = group_tiles(sizes, M, tiles[0])
    with one_trace():
        return _tgmm(lhs, rhs, walk.offsets, walk.zeroed, walk.tile,
                     walk.active, tiles=tiles,
                     out_dtype=jnp.result_type(lhs, rhs),
                     interpret=substrate.use_interpret())


def combine(rows, token, runs: Runs, a=0, *, T: int):
    """``out[t] = sum of rows[r] over the sorted rows r of token t that
    lie in a run``, float32: ``rows [M, d]`` the piece of the sorted rows
    from row ``a`` (an int32 operand), ``token [M]`` their tokens
    (int32), ``runs`` those of the picks (:func:`combine_runs` at
    ``combine_tiling``'s tile) -> ``[T, d]``.  A row outside every run
    is never added, and one behind the last run's end may hold anything
    (a sort's runs lie end to end: those are the rows no pick has)."""
    tiles = combine_tiling(T, rows.shape[1])
    if runs.first.shape[0] * tiles[0] != T:
        raise ValueError(
            f"runs of {runs.first.shape[0]} token tiles are not those of "
            f"{T} tokens in tiles of {tiles[0]}")
    with one_trace():
        return _combine(rows, token, runs, jnp.asarray(a, jnp.int32),
                        tiles=tiles, interpret=substrate.use_interpret())
