"""The grouped products of ``ops/grouped_matmul.py`` (interpreted here,
through ``substrate.use_interpret``) against ``jax.lax.ragged_dot`` and a
dense loop over the groups: empty groups, boundaries inside a tile,
groups smaller than a tile, rows no group has; the schedule they walk;
the gate; and that call sites of one shape share one traced kernel."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.ops import grouped_matmul as gm
from ray_tpu.ops import substrate

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
