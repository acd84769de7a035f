"""The grouped products of ``ops/grouped_matmul.py`` (interpreted here,
through ``substrate.use_interpret``) against ``jax.lax.ragged_dot`` and a
dense loop over the groups: empty groups, boundaries inside a tile,
groups smaller than a tile, rows no group has; the schedule they walk;
the gate; that call sites of one shape share one traced kernel; and the
combine of sorted rows into tokens against the layer's gather and a loop
over the picks."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.ops import grouped_matmul as gm
from ray_tpu.ops import substrate
from ray_tpu.parallel import moe

M, K, N = 384, 256, 128                 # three row tiles of 128

# rows a group, in order; the rows behind their sum belong to no group
SIZES = {
    "an_empty_group": [100, 0, 156, 64],
    "first_and_last_group_empty": [0, 200, 120, 0],
    "boundaries_inside_tiles": [100, 156, 64, 64],        # sum == M
    "groups_smaller_than_a_tile": [3, 5, 40, 20],
    "rows_behind_the_last_pick": [128, 128, 0, 1],
    "every_row_held_on_tile_edges": [128, 0, 128, 128],   # sum == M
    "one_group_holds_every_row": [0, 0, M, 0],
    "nothing_held": [0, 0, 0, 0],
}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _operands(dtype, m=M, k=K, n=N, groups=4):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return (jax.random.normal(ks[0], (m, k), dtype),
            jax.random.normal(ks[1], (groups, k, n), dtype) * k ** -0.5,
            jax.random.normal(ks[2], (m, n), dtype))


def _loop(a, b, sizes):
    """sum over a group's rows of a[m]^T b[m], group by group."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    out, lo = [], 0
    for size in sizes:
        out.append(a[lo:lo + size].T @ b[lo:lo + size])
        lo += size
    return np.stack(out)


def _tol(dtype):
    return dict(atol=1e-4, rtol=1e-5) if dtype == jnp.float32 else dict(
        atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("product", ["gmm", "gmm_transposed", "tgmm"])
@pytest.mark.parametrize("case", list(SIZES))
def test_products_match_ragged_dot_and_the_loop(case, product, dtype):
    assert substrate.use_interpret()
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    lhs, rhs, other = _operands(dtype)
    if product == "tgmm":
        got = gm.tgmm(lhs, other, sizes)
        assert got.shape == (4, K, N) and got.dtype == dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   _loop(lhs, other, SIZES[case]),
                                   **_tol(dtype))
        for g, size in enumerate(SIZES[case]):
            if size == 0:                 # an empty group: zeros, written
                assert not np.any(np.asarray(got[g], np.float32))
        return
    if product == "gmm":
        got = gm.gmm(lhs, rhs, sizes)
    else:
        got = gm.gmm(lhs, jnp.swapaxes(rhs, 1, 2), sizes, transpose_rhs=True)
    assert got.shape == (M, N) and got.dtype == dtype
    held = sum(SIZES[case])
    want = np.asarray(lax.ragged_dot(
        lhs.astype(jnp.float32), rhs.astype(jnp.float32), sizes))
    np.testing.assert_allclose(np.asarray(got[:held], np.float32),
                               want[:held], **_tol(dtype))
    # rows behind the last held pick are never computed and come out zero
    assert not np.any(np.asarray(got[held:], np.float32))


@pytest.mark.parametrize("product", ["gmm", "gmm_transposed", "tgmm"])
def test_a_block_wider_than_a_chunk_is_walked_in_chunks(product):
    """2,560 columns: two blocks of 1,280, each walked in five chunks of
    256 by the kernel's own loop; boundary tiles are masked chunk by
    chunk."""
    n = 2560
    assert gm.tiling(256, 128, n) == (256, 1280, 256)
    assert gm.tiling(256, 128, n, transposed_lhs=True) == (
        256, 128, 1280, 256)
    sizes = [70, 0, 100, 50]
    lhs, rhs, other = _operands(jnp.float32, m=256, k=128, n=n)
    if product == "tgmm":
        np.testing.assert_allclose(
            gm.tgmm(lhs, other, jnp.asarray(sizes, jnp.int32)),
            _loop(lhs, other, sizes), atol=2e-4, rtol=1e-5)
        return
    if product == "gmm":
        got = gm.gmm(lhs, rhs, jnp.asarray(sizes, jnp.int32))
    else:
        got = gm.gmm(lhs, jnp.swapaxes(rhs, 1, 2),
                     jnp.asarray(sizes, jnp.int32), transpose_rhs=True)
    want = np.asarray(lax.ragged_dot(lhs, rhs, jnp.asarray(sizes, jnp.int32)))
    np.testing.assert_allclose(got[:220], want[:220], atol=2e-4, rtol=1e-5)
    assert not np.any(np.asarray(got[220:]))


@pytest.mark.parametrize("case", list(SIZES))
def test_the_walk_follows_the_live_rows_and_visits_a_tile_in_a_row(case):
    sizes = np.asarray(SIZES[case])
    tile = 128
    walk = jax.tree.map(np.asarray, gm.group_tiles(
        jnp.asarray(sizes, jnp.int32), M, tile))
    steps = M // tile + len(sizes) - 1
    assert walk.group.shape == walk.tile.shape == walk.zeroed.shape == (
        steps,)
    active = int(walk.active[0])
    ends = np.cumsum(sizes)
    starts = ends - sizes
    np.testing.assert_array_equal(walk.offsets, [0, *ends])
    # the computing steps: each held group's tiles, in order
    want = [(g, t) for g in range(len(sizes)) if sizes[g]
            for t in range(starts[g] // tile, (ends[g] - 1) // tile + 1)]
    assert active == len(want) <= steps
    got = list(zip(walk.group[:active], walk.tile[:active]))
    assert got == want
    # an expert nobody picked is never read; the steps behind keep the
    # last held group's matrices
    assert all(sizes[g] or not sizes.any() for g in walk.group)
    # gmm: the steps behind write the tiles no group has rows in, each
    # tile's visits in a row
    visited = list(walk.tile)
    assert visited == sorted(visited)
    dead = set(range(-(-int(ends[-1]) // tile), M // tile))
    assert dead <= set(walk.tile[active:])
    # tgmm: the steps behind visit every empty group, each group's
    # visits in a row
    groups = list(walk.zeroed)
    assert set(groups[active:]) >= {g for g in range(len(sizes))
                                    if not sizes[g]}
    seen = [g for i, g in enumerate(groups) if i == 0 or groups[i - 1] != g]
    assert len(seen) == len(set(seen))
    assert set(seen) == set(range(len(sizes))) or active == 0


def test_the_gate_declines_what_the_tiles_do_not_divide_and_says_why():
    assert gm.uses_kernel(49152, 2304, 1792)
    assert gm.uses_kernel(49152, 896, 2304).reason == "pallas"
    for shape, name in (((48, 128, 128), "M=48"), ((128, 64, 128), "k=64"),
                        ((128, 128, 96), "n=96"),
                        ((128, 2560, 128), "whole contraction")):
        gate = gm.uses_kernel(*shape)
        assert not gate and name in gate.reason
    # the routed 8k cell's six products: rows in tiles of 256, the whole
    # contraction and the whole width in one block (a group's accumulator
    # at most 1152 x 2304), walked 256 columns at a time
    assert gm.tiling(49152, 2304, 1792) == (256, 1792, 256)
    assert gm.tiling(49152, 896, 2304) == (256, 2304, 256)
    assert gm.tiling(49152, 2304, 896) == (256, 896, 128)
    assert gm.tiling(49152, 2304, 1792, transposed_lhs=True) == (
        256, 1152, 1792, 256)
    assert gm.tiling(49152, 896, 2304, transposed_lhs=True) == (
        256, 896, 2304, 256)
    assert gm.tile_rows(384) == 128 and gm.tile_rows(768) == 256


def test_call_sites_of_one_shape_share_one_kernel_body():
    """What keeps a step's set-up time: twelve call sites, under a
    conditional and inside a ``custom_vjp`` rule among them, lower to
    one function a distinct product."""
    sizes = jnp.asarray(SIZES["an_empty_group"], jnp.int32)
    lhs, rhs, other = _operands(jnp.float32)

    @jax.custom_vjp
    def product(lhs, rhs):
        return gm.gmm(lhs, rhs, sizes)

    def fwd(lhs, rhs):
        return gm.gmm(lhs, rhs, sizes), (lhs, rhs)

    def bwd(res, ct):
        lhs, rhs = res
        again = gm.gmm(lhs, rhs, sizes)       # a backward's recompute
        return (gm.gmm(ct + again, rhs, sizes, transpose_rhs=True),
                gm.tgmm(lhs, ct, sizes))

    product.defvjp(fwd, bwd)

    def loss(lhs, rhs):
        out = 0.0
        for _ in range(3):
            y = product(lhs, rhs)
            out = out + jnp.sum(lax.cond(
                sizes[0] > 0, lambda: y + product(lhs, rhs), lambda: y))
        return out

    text = jax.jit(jax.grad(loss, (0, 1))).lower(lhs, rhs).as_text()
    bodies = re.findall(r"func\.func private @(_t?gmm)\w*\(", text)
    assert sorted(bodies) == ["_gmm", "_gmm", "_tgmm"], bodies
    assert len(re.findall(r"call @_t?gmm", text)) >= 12


# ------------------------------------------------------------ the combine

T_C, K_C, E_C, G_C = 1024, 2, 8, 4       # two token tiles of 512


def _picks(case):
    """``local [T, K]``: a pick's index among the ``G_C`` experts held,
    ``G_C`` where it is not this chip's."""
    rng = np.random.default_rng(3)
    pick = np.stack([rng.permutation(E_C)[:K_C] for _ in range(T_C)])
    local = np.where(pick < G_C, pick, G_C)
    if case == "no_held_pick":
        local[:] = G_C
    if case == "every_pick_held":
        local = np.stack([rng.permutation(G_C)[:K_C] for _ in range(T_C)])
    if case == "one_token_all_picks_one_none":
        local[5], local[6] = (0, 3), (G_C, G_C)
    if case == "an_expert_nobody_picked":
        local[local == 2] = G_C
    if case == "a_run_longer_than_a_window":
        local[:, 0] = 1                      # 512 rows a token tile
        local[:, 1] = np.where(local[:, 1] == 1, G_C, local[:, 1])
    if case == "runs_off_the_tiling":        # 3, 5, 40 and 21 rows
        local[:] = G_C
        for e, (first, n) in enumerate(((7, 3), (250, 5), (300, 40),
                                        (100, 21))):
            local[first:first + 2 * n:2, e % K_C] = e
    return local.astype(np.int32)


# case -> (first row, rows) of the piece of the T_C x K_C sorted rows
COMBINES = {
    "uniform": (0, 2048), "no_held_pick": (0, 2048),
    "every_pick_held": (0, 2048), "one_token_all_picks_one_none": (0, 2048),
    "an_expert_nobody_picked": (0, 2048),
    "a_run_longer_than_a_window": (0, 2048),
    "runs_off_the_tiling": (0, 128),
    # (uniform routing holds ~1024 rows: picks before, in and behind)
    "a_later_piece": (384, 512), "the_last_piece": (896, 256),
    "nan_behind_the_last_live_row": (0, 1152),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(COMBINES))
def test_combine_matches_the_gather_and_a_loop_over_the_picks(case, dtype):
    a, rows_piece = COMBINES[case]
    local = jnp.asarray(_picks(case))
    d = 256
    sort = moe._sorted_picks(local, jnp.ones(local.shape), G_C, rows_piece)
    token, _, live, n, mine, at = moe._piece(
        jnp.int32(a), rows_piece, *sort, local < G_C)
    # whatever lies behind the last live row must not reach a sum
    rows = jnp.where(live[:, None], jax.random.normal(
        jax.random.PRNGKey(1), (rows_piece, d)), jnp.nan).astype(dtype)
    tile_t, window, _ = gm.combine_tiling(T_C, d)
    runs = gm.combine_runs(local, sort[3], tile_t=tile_t)
    lo, hi = gm._in_piece(runs, a, rows_piece)
    # the runs are each (token tile, expert)'s rows, here the piece's part
    count = np.stack([(np.asarray(local).reshape(-1, tile_t * K_C) == e
                       ).sum(1) for e in range(G_C)], 1)
    first = np.asarray(sort[3])[None] + np.cumsum(count, 0) - count
    np.testing.assert_array_equal(lo, np.clip(first - a, 0, rows_piece))
    np.testing.assert_array_equal(
        hi, np.clip(first + count - a, 0, rows_piece))
    np.testing.assert_array_equal(runs.first, first)
    np.testing.assert_array_equal(runs.count, count)
    got = np.asarray(gm.combine(rows, token, runs, jnp.int32(a), T=T_C))
    want = np.zeros((T_C, d), np.float32)
    held = np.zeros((T_C,), np.int32)
    for r in range(int(jnp.sum(live))):
        want[token[r]] += np.asarray(rows[r], np.float32)
        held[token[r]] += 1
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-6)
    np.testing.assert_allclose(got, moe._pick_sum(rows, at, mine),
                               rtol=2e-6, atol=1e-6)
    np.testing.assert_array_equal(got[held <= 1], want[held <= 1])
    assert held.sum() == int(jnp.sum(mine)) == int(jnp.sum(hi - lo))
    if case == "a_run_longer_than_a_window":
        assert int(jnp.max(hi - lo)) == tile_t > window
    if case == "no_held_pick":
        assert not got.any()
    # the windows a run takes: from the 128 rows its first row lies in
    windows = sum(-(-(h - l // 128 * 128) // window)
                  for l, h in zip(np.asarray(lo).ravel(),
                                  np.asarray(hi).ravel()) if h > l)
    assert int(gm.combine_windows(runs, [a], rows_piece, window)) == windows
    assert windows > 0 or case == "no_held_pick"


def test_combine_gate_declines_what_its_tiles_do_not_divide():
    assert gm.combine_uses_kernel(16384, 49152, 2304).reason == "pallas"
    assert gm.combine_tiling(16384, 2304) == (512, 128, 2304)
    assert gm.combine_tiling(384, 128)[0] == 128
    for shape, name in (((192, 512, 128), "T=192"), ((256, 80, 128), "M=80"),
                        ((256, 512, 64), "d=64")):
        gate = gm.combine_uses_kernel(*shape)
        assert not gate and name in gate.reason
    with pytest.raises(ValueError, match="token tiles"):
        gm.combine(jnp.zeros((128, 128)), jnp.zeros((128,), jnp.int32),
                   gm.Runs(jnp.zeros((3, 4), jnp.int32),
                           jnp.zeros((3, 4), jnp.int32)), T=256)


def test_combines_of_one_shape_share_one_kernel_body():
    local = jnp.asarray(_picks("uniform"))
    sort = moe._sorted_picks(local, jnp.ones(local.shape), G_C, 2048)
    rows = jax.random.normal(jax.random.PRNGKey(1), (2048, 128))

    def loss(rows):
        out = 0.0
        for a in (0, 0, 0):
            runs = gm.combine_runs(local, sort[3], tile_t=512)
            y = gm.combine(rows, sort[0] // local.shape[1], runs,
                           jnp.int32(a), T=T_C)
            out = out + jnp.sum(lax.cond(
                runs.first[0, 0] == 0, lambda: y + gm.combine(
                    rows, sort[0], runs, jnp.int32(a), T=T_C), lambda: y))
        return out

    text = jax.jit(loss).lower(rows).as_text()
    bodies = re.findall(r"func\.func private @(_combine(?:_runs)?)\w*\(", text)
    assert sorted(bodies) == ["_combine", "_combine_runs"], bodies
    assert len(re.findall(r"call @_combine\b", text)) >= 6
