"""Grouped-query attention, window layers mixed with full ones, and the
dropless expert layer on the train path: the kernels against the einsum
formulation, the ropes against their closed forms, the differentiated
grouped products against the serve path's loop and a dense formulation,
the tiny preset against ``benchmark/reference/mellum.py`` leaf by leaf,
the shares of an expert-parallel layer against the uncut one, what the
telemetry counts, and the engine's refusal."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import common
from benchmark.reference import mellum as reference
from ray_tpu.models import gpt, training
from ray_tpu.ops import attention as A
from ray_tpu.parallel import moe
from ray_tpu.parallel.mesh import make_mesh

TINY = common.load_json(common.BENCH_DIR + "/configs/mellum.rehearsal.json")
YARN = A.Rope(theta=500000.0, factor=16.0, original_max=8192,
              beta_fast=32.0, beta_slow=1.0,
              attention_factor=1.2772588722239782)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


# ---------------------------------------------------------------- kernels

def _qkv(S, H, Hkv, D):
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    return (jax.random.normal(ks[0], (1, S, H, D)),
            jax.random.normal(ks[1], (1, S, Hkv, D)),
            jax.random.normal(ks[2], (1, S, Hkv, D)),
            jax.random.normal(ks[3], (1, S, H, D)))


def _kernel_against_einsum(window, group, fused, S=512, D=128, block=128):
    H, Hkv = (2, 2) if group == 1 else (group, 1)
    q, k, v, t = _qkv(S, H, Hkv, D)
    pos = jnp.arange(S)

    def kernel(q, k, v):
        o = A.flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block,
            bwd_block_q=block, bwd_block_k=block, window=window,
            positions=pos if fused else None, rope_theta=YARN)
        return jnp.sum(o * t), o

    def einsum(q, k, v):
        if fused:
            q, k = A.rope_rotate(q, pos, YARN), A.rope_rotate(k, pos, YARN)
        o = A.xla_attention(q, k, v, causal=True, window=window)
        return jnp.sum(o * t), o

    (_, o1), g1 = jax.value_and_grad(kernel, (0, 1, 2), has_aux=True)(q, k, v)
    (_, o2), g2 = jax.value_and_grad(einsum, (0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(o1, o2, atol=2e-5)
    for got, want, name in zip(g1, g2, ("dq", "dk", "dv")):
        np.testing.assert_allclose(got, want, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("fused", [False, True], ids=["rope_xla", "rope_fused"])
@pytest.mark.parametrize("group", [1, 8])
@pytest.mark.parametrize("window", [64, 200, 1000],
                         ids=["in_block", "across_blocks", "over_seq"])
def test_window_and_kv_group_kernels_match_einsum(window, group, fused):
    _kernel_against_einsum(window, group, fused)


@pytest.mark.parametrize("window,group", [(None, 8), (200, 8), (200, 1)])
def test_two_kernel_backward_sums_a_kv_heads_group(monkeypatch, window,
                                                   group):
    # the dq / dkv pair that long sequences take, forced at a small one
    monkeypatch.setattr(A, "_FUSED_BWD_SCRATCH_BYTES", 0)
    _kernel_against_einsum(window, group, fused=False, S=384)


def test_window_or_group_where_no_path_honours_it_raises():
    q, k, v, _ = _qkv(128, 4, 2, 16)
    seg = jnp.ones((1, 128), jnp.int32)
    with pytest.raises(NotImplementedError, match="segment_ids"):
        A.flash_attention(q, k, v, segment_ids=seg)
    with pytest.raises(ValueError, match="window"):
        A.flash_attention(q, q, q, causal=False, window=8)
    cfg = gpt.GPTConfig.mellum_tiny(dtype=jnp.float32)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    plain = A.make_flash_attention_fn(None, rope_theta=cfg.rope_theta)
    with pytest.raises(ValueError, match="attention hook made for it"):
        gpt.forward_hidden(params, jnp.zeros((1, 64), jnp.int32), cfg,
                           attn_fn=plain)


def test_coverage_counts_the_blocks_the_window_leaves():
    # forward blocks of 1024 at 8192: a full layer's q blocks meet 36 of
    # 64 kv blocks, a window layer's 15
    assert A.causal_coverage(8192, 8192, 1024, 1024, None) == 36 / 64
    assert A.causal_coverage(8192, 8192, 1024, 1024, None, 1024) == 15 / 64
    assert A.needed_coverage(8192, 1024) == pytest.approx(0.1172, abs=1e-4)
    assert A.needed_coverage(8192) == pytest.approx(0.50006, abs=1e-5)
    fn = A.make_flash_attention_fn(None, window=1024, kv_heads=4, rope=YARN)
    full = A.make_flash_attention_fn(None, kv_heads=4, rope=YARN)
    assert (fn.window, fn.kv_heads, fn.fused_rope) == (1024, 4, True)
    cover = fn.coverage(8192, 32, 128)
    assert cover["needed"] < cover["executed"] < full.coverage(
        8192, 32, 128)["executed"] / 2


# ------------------------------------------------------------------ ropes

def test_yarn_tables_are_the_closed_form_and_window_layers_plain():
    D, half = 128, 64
    base = 500000.0 ** (-np.arange(half) / half)

    def dim(rot):
        return D * math.log(8192 / (rot * 2 * math.pi)) / (
            2 * math.log(500000.0))

    low, high = math.floor(dim(32)), math.ceil(dim(1))
    ramp = np.clip((np.arange(half) - low) / (high - low), 0, 1)
    want = (1 - ramp) * base + ramp * base / 16
    np.testing.assert_allclose(YARN.inv_freq(D), want, rtol=1e-6)
    np.testing.assert_allclose(reference.inv_freq(
        {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
         "original_max_position_embeddings": 8192, "beta_fast": 32,
         "beta_slow": 1}, D), want, rtol=1e-12)
    assert 0 < low < high < half          # both ends of the ramp are used
    pos = jnp.arange(0, 8192, 97)
    cos2, sinm = A.rope_tables(pos, D, YARN, jnp.float32)
    ang = np.asarray(pos)[:, None] * want[None]
    f = 1.2772588722239782
    assert f == pytest.approx(0.1 * math.log(16) + 1)
    np.testing.assert_allclose(cos2[:, :half], f * np.cos(ang), atol=2e-3)
    np.testing.assert_allclose(sinm[:, half:], f * np.sin(ang), atol=2e-3)
    np.testing.assert_allclose(sinm[:, :half], -f * np.sin(ang), atol=2e-3)
    cfg = gpt.GPTConfig.mellum2_12b_a2_5b(n_layers=4)
    assert cfg.rope("window") == 500000.0 and cfg.rope("full") == YARN
    plain = A.rope_tables(pos, D, cfg.rope("window"), jnp.float32)
    again = A.rope_tables(pos, D, A.Rope(theta=500000.0), jnp.float32)
    np.testing.assert_array_equal(plain[0], again[0])
    np.testing.assert_allclose(
        plain[0][:, :half], np.cos(np.asarray(pos)[:, None] * base),
        atol=2e-3)


# ------------------------------------------------------- the expert layer

def _layer(T=96, d=32, f=48, E=8, held=(1, 2, 5, 6), seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(ks[0], (T, d)),
        router=jax.random.normal(ks[1], (d, E)) * 3 * d ** -0.5,
        gate=jax.random.normal(ks[2], (len(held), d, f)) * d ** -0.5,
        up=jax.random.normal(ks[3], (len(held), d, f)) * d ** -0.5,
        down=jax.random.normal(ks[4], (len(held), f, d)) * f ** -0.5,
        target=jax.random.normal(ks[5], (T, d)), held=held, E=E)


def _dropless(x, router, gate, up, down, *, held, E, K):
    return moe.dropless_moe(x, router, jnp.zeros((E,)), gate, up, down,
                            held=held, n_routed=E, top_k=K, scale=1.0,
                            renormalise=True)


def _dense(x, router, gate, up, down, *, held, K):
    """Every held expert over every row, weighted by who picked it."""
    p = jax.nn.softmax(x @ router, -1)
    top, pick = jax.lax.top_k(p, K)
    w = top / top.sum(-1, keepdims=True)
    out = 0.0
    for j, e in enumerate(held):
        mine = jnp.sum(jnp.where(pick == e, w, 0.0), -1)
        out = out + mine[:, None] * (
            (jax.nn.silu(x @ gate[j]) * (x @ up[j])) @ down[j])
    return out


# the layer at widths that keep the compiler's product and its gather;
# at widths the Pallas kernels' tiles divide (interpreted here: the
# grouped products and the combine of their rows); at those widths with
# tokens the combine's tiles do not divide, which keeps the compiler's
# forms for the whole layer; and for each how the sorted picks (T x 2
# rows) are cut: (headroom, rounding) -> rows a piece
_LAYERS = {"ragged_dot": dict(T=96, d=32, f=48),
           "pallas": dict(T=256, d=128, f=128),
           "tokens_off_the_tiles": dict(T=192, d=128, f=128)}
_CUTS = {("ragged_dot", "four_pieces"): (0.5, 8, 48),            # 4 x 48
         ("ragged_dot", "padded_last_piece"): (0.5, 40, 80),     # 192 of 240
         ("pallas", "four_pieces"): (0.5, 128, 128),             # 4 x 128
         ("pallas", "padded_last_piece"): (0.5, 384, 384),       # 512 of 768
         ("tokens_off_the_tiles", "four_pieces"): (0.5, 128, 128),  # 3
         ("tokens_off_the_tiles", "padded_last_piece"): (0.5, 256, 256)}


@pytest.mark.parametrize("products", list(_LAYERS))
@pytest.mark.parametrize("pieces", ["one_piece", "four_pieces",
                                    "padded_last_piece"])
@pytest.mark.parametrize("routing", ["uniform", "one_expert", "idle_expert",
                                     "all_held"])
def test_differentiated_grouped_products_match_loop_and_dense(
        monkeypatch, routing, pieces, products):
    shape = _LAYERS[products]
    if pieces != "one_piece":
        # the sorted picks in equal pieces, the later ones run only where
        # a held pick lies in them; the last may end behind the last row
        headroom, rounding, rows = _CUTS[products, pieces]
        monkeypatch.setattr(moe, "_PIECE_HEADROOM", headroom)
        monkeypatch.setattr(moe, "_PIECE_ROWS", rounding)
        assert moe.piece_rows(shape["T"], 2, 4, 8) == rows
    assert moe.product_path(shape["T"], 2, 4, 8, shape["d"], shape["f"]) == (
        "pallas" if products == "pallas" else "ragged_dot")
    L = _layer(**shape)
    # the cases the parent had keep its tolerances; RTOL_WIDER
    rtol = 2e-6 if (products != "ragged_dot" or routing == "all_held"
                    or pieces == "padded_last_piece") else 1e-7
    held, E, K = L["held"], L["E"], 2
    router = L["router"]
    if routing == "all_held":          # every pick on a held expert: the
        # sorted rows are full to the last, every piece runs
        L["x"] = L["x"].at[:, 0].set(6.0)
        router = router.at[0, jnp.array([0, 3, 4, 7])].set(-50.0)
    if routing == "one_expert":        # every row's first pick is expert 5
        router = router.at[:, 5].set(0.0)
        L["x"] = L["x"].at[:, 0].set(6.0)
        router = router.at[0, 5].set(5.0)
    if routing == "idle_expert":       # nobody picks expert 2
        L["x"] = L["x"].at[:, 0].set(6.0)
        router = router.at[0, 2].set(-50.0)
    args = (L["x"], router, L["gate"], L["up"], L["down"])
    out, counts = _dropless(*args, held=held, E=E, K=K)      # the loop
    want = _dense(*args, held=held, K=K)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=rtol)
    named = dict(zip(moe.MOE_COUNTS, np.asarray(counts)))
    if routing == "one_expert":
        assert named["held_picks"] >= L["x"].shape[0]
    if routing == "idle_expert":
        assert named["experts_hit"] == len(held) - 1
    if routing == "all_held":
        assert named["held_picks"] == named["picks"]

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * L["target"])

    got_v, got = jax.value_and_grad(
        loss(lambda *a: _dropless(*a, held=held, E=E, K=K)[0]),
        (0, 1, 2, 3, 4))(*args)
    want_v, want_g = jax.value_and_grad(
        loss(lambda *a: _dense(*a, held=held, K=K)), (0, 1, 2, 3, 4))(*args)
    # the rule's forward (sorted, grouped) is the loop's sum
    assert float(got_v) == pytest.approx(float(want_v), rel=1e-5)
    for g, w, name in zip(got, want_g, ("x", "router", "gate", "up", "down")):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=rtol, err_msg=name)
    if routing == "idle_expert":
        assert not np.any(np.asarray(got[2][1]))   # expert 2: no gradient


# the three forms PR 62 took out of ``parallel/moe.py``, kept here alone:
# the weights through the sort's order, values back through its inverse,
# and a table's entries at the picks, each by a gather of T x K scalars
def _gathered_sort_picks(local, weight, held):
    flat = local.reshape(local.size)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    return order, weight.reshape(flat.size)[order], jnp.sum(
        flat[:, None] == jnp.arange(held)[None, :], axis=0, dtype=jnp.int32)


def _gathered_to_picks(order, rows):
    return rows[jnp.argsort(order)]


def _gathered_at_picks(table, pick):
    return jnp.take_along_axis(jnp.broadcast_to(
        table, (pick.shape[0], table.shape[-1])), pick, axis=-1)


# how each routed family calls the layer: mellum (train), sarvam and
# longcat (serve; longcat's identity experts are columns behind the
# routed ones, and some of its rows are no tokens)
_ROUTINGS = {
    "softmax_renormalised": dict(scoring="softmax", renormalise=True,
                                 bias=0.0, identity=0, scale=1.0),
    "sigmoid_bias_renormalised": dict(scoring="sigmoid", renormalise=True,
                                      bias=0.1, identity=0, scale=2.5),
    "softmax_identity_experts": dict(scoring="softmax", renormalise=False,
                                     bias=0.02, identity=4, scale=6.0)}


@pytest.mark.parametrize("routing", list(_ROUTINGS))
def test_values_that_ride_the_sort_or_a_compare_equal_the_gathers_bit_for_bit(
        monkeypatch, routing):
    R = _ROUTINGS[routing]
    # four pieces of 48 sorted rows, and a draw skewed to expert 5, whose
    # rows alone overflow the first: the later pieces' loop runs
    monkeypatch.setattr(moe, "_PIECE_HEADROOM", 0.5)
    monkeypatch.setattr(moe, "_PIECE_ROWS", 8)
    L = _layer()
    held, n_routed, K = L["held"], L["E"], 2
    assert moe.piece_rows(96, K, len(held), n_routed) == 48
    ks = jax.random.split(jax.random.PRNGKey(62), 3)
    router = jnp.concatenate([L["router"], jax.random.normal(
        ks[0], (32, R["identity"])) * 0.5], axis=1)
    # (the skewed dimension feeds expert 5 alone: a row's second pick is
    # its own, on a held, a foreign or an identity expert)
    router = router.at[0].set(0.0).at[:, 5].set(0.0).at[0, 5].set(1.0)
    x = L["x"].at[:, 0].set(6.0)
    bias = R["bias"] * jax.random.normal(ks[1], (router.shape[1],))
    valid = jnp.arange(96) % 7 != 3 if R["identity"] else None
    args = (x, router, L["gate"], L["up"], L["down"])

    local = jax.random.randint(ks[2], (96, K), 0, len(held) + 1)
    weight = jax.random.uniform(ks[2], (96, K))

    def everything():
        # (functions of this call's own: a trace is cached by function)
        def layer(*a):
            return moe.dropless_moe(
                a[0], a[1], bias, *a[2:], held=held, n_routed=n_routed,
                top_k=K, scale=R["scale"], valid=valid,
                renormalise=R["renormalise"], scoring=R["scoring"])

        def grads(*a):
            return jax.value_and_grad(
                lambda *a: jnp.sum(layer(*a)[0] * L["target"]),
                (0, 1, 2, 3, 4))(*a)

        out, counts = layer(*args)                  # the loop
        value, g = grads(*args)                     # the rule
        # (and compiled: the compiler merges what it may, as in a step)
        value_c, g_c = jax.jit(grads)(*args)
        return (out, counts, value, *g, value_c, *g_c,
                *moe._sorted_picks(local, weight, len(held), 48),
                str(jax.make_jaxpr(grads)(*args)))

    *got, text = everything()
    named = dict(zip(moe.MOE_COUNTS, np.asarray(got[1])))
    assert 48 < named["held_picks"] < named["picks"] and (
        named["identity_picks"] > 0) == bool(R["identity"])
    monkeypatch.setattr(moe, "_sort_picks", _gathered_sort_picks)
    monkeypatch.setattr(moe, "_to_picks", _gathered_to_picks)
    monkeypatch.setattr(moe, "_at_picks", _gathered_at_picks)
    # (a jit of its own, or the piece's backward would not be traced again)
    inner = moe._piece_bwd.__wrapped__
    monkeypatch.setattr(moe, "_piece_bwd", jax.jit(
        lambda *a, piece: inner(*a, piece=piece), static_argnames=("piece",)))
    *want, gathered = everything()
    leaves = ("loss", "dx", "drouter", "dgate", "dup", "ddown")
    names = ("out", "counts", *leaves, *(n + " (jit)" for n in leaves),
             "order", "pos", "ws", "starts", "ends")
    for g, w, name in zip(got, want, names, strict=True):
        assert g.dtype == w.dtype and np.array_equal(g, w), name
    assert np.any(np.asarray(got[4])) and np.any(np.asarray(got[5]))
    # the forms are the ones named: the gathers and the scatter of the
    # router's gradient are in the old text alone
    assert gathered.count("gather[") >= text.count("gather[") + 5
    assert "scatter-add" in gathered and "scatter" not in text


def test_forward_only_call_keeps_the_loop_and_renormalise_defaults_off():
    L = _layer()
    args = (L["x"], L["router"], jnp.zeros((L["E"],)), L["gate"], L["up"],
            L["down"])
    kw = dict(held=L["held"], n_routed=L["E"], top_k=2, scale=1.0)
    text = str(jax.make_jaxpr(lambda *a: moe.dropless_moe(*a, **kw))(*args))
    assert "while" in text and "ragged_dot" not in text
    grad = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
        moe.dropless_moe(*a, **kw)[0])))(*args))
    assert "ragged_dot" in grad and "while" not in grad
    plain, _ = moe.dropless_moe(*args, **kw)
    renorm, _ = moe.dropless_moe(*args, renormalise=True, **kw)
    assert float(jnp.abs(plain).sum()) < float(jnp.abs(renorm).sum())


def test_a_piece_is_what_uniform_routing_fills_and_half_again():
    # the routed cell's layer: 16,384 rows, 8 of 64, 16 held
    assert moe.piece_rows(16384, 8, 16, 64) == 49152
    assert moe.piece_rows(16384, 8, 64, 64) == 16384 * 8   # all held
    # equal pieces, so that their products have one shape: the last ends
    # behind the last row, on rows no pick has
    assert moe._pieces(131072, 49152) == [
        (0, 49152), (49152, 98304), (98304, 147456)]
    sort = moe._sorted_picks(jnp.zeros((8, 2), jnp.int32),
                             jnp.ones((8, 2)), 1, 12)
    assert sort[0].shape == sort[2].shape == (24,)
    assert not np.any(np.asarray(sort[2][16:]))


def test_the_four_shares_of_ep4_add_up_to_the_uncut_layer():
    L = _layer(E=8, held=tuple(range(8)), seed=3)
    x, router = L["x"], L["router"]
    lp = {"moe_wg": router, "moe_w1": L["gate"], "moe_w3": L["up"],
          "moe_w2": L["down"]}

    def uncut(x):
        return reference.experts(x, lp, list(range(8)), 2)

    def shares(x):
        out = 0.0
        for lo in range(0, 8, 2):
            held = (lo, lo + 1)
            out = out + _dropless(x, router, L["gate"][lo:lo + 2],
                                  L["up"][lo:lo + 2], L["down"][lo:lo + 2],
                                  held=held, E=8, K=2)[0]
        return out

    np.testing.assert_allclose(shares(x), uncut(x), atol=2e-5)
    got = jax.grad(lambda x: jnp.sum(shares(x) * L["target"]))(x)
    want = jax.grad(lambda x: jnp.sum(uncut(x) * L["target"]))(x)
    np.testing.assert_allclose(got, want, atol=5e-5)


# ------------------------------------------------- the model, leaf by leaf

def _tiny(**kw):
    cfg = gpt.GPTConfig.mellum_tiny(dtype=jnp.float32, **kw)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0,
                                cfg.vocab_size)
    return cfg, params, {"tokens": tokens,
                         "targets": jnp.roll(tokens, -1, axis=1)}


@pytest.mark.parametrize("unroll, piece", [
    (True, None), (False, None), (False, 264), (False, 136)],
    ids=["unrolled", "scan", "padded_last_piece", "four_pieces"])
def test_tiny_preset_matches_the_reference_in_loss_and_every_leaf(
        monkeypatch, unroll, piece):
    if piece:
        # the layers' held picks are 145 to 286 of 512 sorted rows: in
        # pieces of 264 the first layer's overflow into the second and
        # last piece, which ends 16 rows behind the last row; in pieces of
        # 136 every layer's reach the second and the first layer's the
        # third, and the last is not run
        monkeypatch.setattr(moe, "_PIECE_HEADROOM", 0.5)
        monkeypatch.setattr(moe, "_PIECE_ROWS", piece)
        assert moe.piece_rows(256, 2, 4, 8) == piece
        assert len(moe._pieces(512, piece)) == -(-512 // piece)
    cfg, params, batch = _tiny(unroll_layers=unroll)
    assert cfg.layer_kinds == ("window",) * 3 + ("full",) + (
        "window",) * 3 + ("full",)
    nll, count, want = reference.loss_and_grad_sums(
        params, batch["tokens"], batch["targets"], 2, TINY)
    (loss, counts), got = jax.value_and_grad(
        lambda p: gpt.loss_fn(p, batch, cfg, with_counts=True,
                              attn_fn=gpt.attention_fns(cfg)),
        has_aux=True)(params)
    assert float(loss) == pytest.approx(float(nll / count), rel=2e-6)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    assert set(flat_got) == set(flat_want) and len(flat_got) == 13
    for path, g in flat_got.items():
        w = np.asarray(flat_want[path]) / float(count)
        np.testing.assert_allclose(
            g, w, atol=2e-6 + 2e-4 * np.abs(w).max(),
            err_msg=jax.tree_util.keystr(path))
    rows = int(counts[moe.MOE_COUNTS.index("rows")])
    assert rows == 8 * 2 * 128                      # 8 layers' rows
    assert int(counts[len(moe.MOE_COUNTS):].sum()) == int(
        counts[moe.MOE_COUNTS.index("held_picks")])


def test_reference_faults_move_it_and_it_imports_nothing_of_the_program():
    _, params, batch = _tiny()
    clean = reference.loss_and_grad_norm(
        params, batch["tokens"][:1], batch["targets"][:1], 1, TINY)
    for fault in reference.FAULTS:
        moved = reference.loss_and_grad_norm(
            params, batch["tokens"][:1], batch["targets"][:1], 1,
            dict(TINY, _fault=fault))
        assert moved != clean, fault
    source = open(reference.__file__).read()
    assert "import ray_tpu" not in source and "from ray_tpu" not in source
    logits = reference.logits_last(params, batch["tokens"], 4, TINY)
    assert logits.shape == (2, 4, 512)


# -------------------------------------------------- the step's telemetry

def test_step_returns_counts_and_telemetry_reports_them_with_coverage():
    cfg, _, batch = _tiny(unroll_layers=True, ce_chunk=-1)
    mesh = make_mesh(devices=jax.devices()[:1], dp=-1)
    fns = training.build_gpt_train(cfg, mesh, telemetry=True)
    state = fns["init_fn"](jax.random.PRNGKey(0))
    batch = jax.device_put(batch, fns["batch_sharding"])
    for _ in range(3):
        state, metrics = fns["step_fn"](state, batch)
    assert metrics["grad_norm"].dtype == jnp.float32
    # MOE_COUNTS, a row count a held expert, the combines' windows
    assert metrics["moe_counts"].shape == (len(moe.MOE_COUNTS) + 4 + 1,)
    tel = fns["telemetry"]
    first = tel.records[0]
    assert set(first["attn_coverage"]) == {"window", "full"}
    assert first["moe"]["rows"] == 8 * 256 and first["moe"]["calls"] == 8
    assert first["moe"]["imbalance"] >= 1.0
    # the form of the grouped products, from the step's shapes: experts
    # 32 wide keep the compiler's; the routed 8k cell's take the kernels
    assert first["moe_product"] == "ragged_dot"
    # ... and their combines the gather, which brings no window
    assert first["moe_combine"] == "xla"
    assert first["moe"]["combine_windows"] == 0
    assert "moe_product" not in tel.records[1]
    assert "moe_combine" not in tel.records[1]
    assert moe.product_path(2 * 8192, 8, 16, 64, 2304, 896) == "pallas"
    summary = tel.summary()
    assert summary["moe_product"] == "ragged_dot"
    assert summary["moe_combine"] == "xla"
    assert summary["moe"]["combine_windows"] == 0
    assert summary["moe"]["experts_hit_per_layer"] <= 4
    picks = summary["moe"]["held_picks_per_token"]
    assert 0 < picks <= 2
    from ray_tpu.telemetry import flops
    assert summary["flops_per_token"] == flops.gpt_train_flops_per_token(
        cfg, 128, ce_recompute=False, held_picks_per_token=picks)
    with pytest.raises(NotImplementedError, match="accum_steps"):
        training.build_gpt_train(cfg, mesh, accum_steps=2)


def _windows_by_hand(local, held, piece, tile_t, window):
    """The windows the combines of one direction bring: for each piece
    of the sorted rows and each (token tile, held expert) with a row in
    it, from the 128 rows its run's first row there lies in to the run's
    end, in windows of ``window``."""
    local = np.asarray(local)
    T, K = local.shape
    n = np.array([(local == e).sum() for e in range(held)])
    starts, total = np.cumsum(n) - n, 0
    for a in range(0, T * K, piece):
        for e in range(held):
            at = starts[e]
            for tile in local.reshape(T // tile_t, tile_t * K):
                rows = int((tile == e).sum())
                lo, hi = (int(np.clip(v - a, 0, piece))
                          for v in (at, at + rows))
                at += rows
                if hi > lo:
                    total += -(-(hi - lo // 128 * 128) // window)
    return total


@pytest.mark.parametrize("pieces", ["one_piece", "four_pieces"])
def test_layer_counts_its_combines_windows_as_the_routing_gives_them(
        monkeypatch, pieces):
    """``dropless_moe(with_load=True)`` appends the windows its combines
    bring in one direction; 0 for a layer that keeps the gather."""
    from ray_tpu.ops import grouped_matmul
    shape = _LAYERS["pallas"]
    if pieces != "one_piece":
        headroom, rounding, _ = _CUTS["pallas", pieces]
        monkeypatch.setattr(moe, "_PIECE_HEADROOM", headroom)
        monkeypatch.setattr(moe, "_PIECE_ROWS", rounding)
    L = _layer(**shape)
    held, E, K = L["held"], L["E"], 2
    kw = dict(held=held, n_routed=E, top_k=K, scale=1.0, renormalise=True,
              with_load=True)
    args = (L["x"], L["router"], jnp.zeros((E,)), L["gate"], L["up"],
            L["down"])
    _, counts, load, windows = moe.dropless_moe(*args, **kw)
    pick = jax.lax.top_k(jax.nn.softmax(L["x"] @ L["router"], -1), K)[1]
    local_of = np.full((E,), len(held))
    local_of[list(held)] = np.arange(len(held))
    tile_t, window, _ = grouped_matmul.combine_tiling(shape["T"], shape["d"])
    want = _windows_by_hand(
        local_of[np.asarray(pick)], len(held),
        moe.piece_rows(shape["T"], K, len(held), E), tile_t, window)
    assert int(windows) == want > 0
    assert int(load.sum()) == int(counts[moe.MOE_COUNTS.index("held_picks")])
    # every held row lies in a window: the windows cover the held picks
    assert int(windows) * window >= int(load.sum())
    small = _layer()                    # 32 wide: the compiler's forms
    _, _, _, none = moe.dropless_moe(
        small["x"], small["router"], jnp.zeros((E,)), small["gate"],
        small["up"], small["down"], **kw)
    assert int(none) == 0


def test_telemetry_names_the_routed_cells_combine_and_reads_its_windows():
    """At the routed 8k cell's shapes the layers' combines are the
    kernel's (``moe_combine`` with ``moe_product``: one decision a
    layer), and the step's counts carry their windows last."""
    from ray_tpu.telemetry.step import StepTelemetry
    cfg = gpt.GPTConfig.mellum2_12b_a2_5b(
        n_layers=4, vocab_size=24576, held_experts=tuple(range(16)),
        max_seq=8192)
    tel = StepTelemetry(cfg)
    held = len(cfg.held_experts)
    vec = np.zeros((gpt.moe_counts_len(cfg),), np.int32)
    vec[:len(moe.MOE_COUNTS)] = (4 * 16384, 130_000, 0, 4 * 131_072, 64, 4,
                                 1_050)
    vec[len(moe.MOE_COUNTS):-1] = 130_000 // held
    vec[-1] = 3_052
    tokens = jnp.zeros((2, 8192), jnp.int32)

    def step(state, batch):
        return state, {"loss": jnp.float32(1.0), "moe_counts": vec}

    wrapped = tel.wrap(step)
    for _ in range(3):
        wrapped(None, {"tokens": tokens, "targets": tokens})
    first = tel.records[0]
    assert first["moe_product"] == first["moe_combine"] == "pallas"
    assert first["moe"]["combine_windows"] == 3_052
    assert first["moe"]["imbalance"] == 1.0
    summary = tel.summary()
    assert summary["moe_combine"] == "pallas"
    assert summary["moe"]["combine_windows"] == 3_052
    # how much the windows over-read: held picks over the rows brought
    assert 0.2 < summary["moe"]["held_picks"] / (3_052 * 128) < 0.4


@pytest.mark.parametrize("warmup", [10, 1000])
def test_warmup_steps_is_the_default_optimizers_warm_up(warmup):
    """The recipe key reaches ``default_optimizer``: AdamW's first steps
    move a weight by the learning rate, which the schedule has at
    ``step / warmup`` of 3e-4, so after two steps (the first at rate 0)
    the largest movement is one step at ``3e-4 / warmup`` (read on the
    embedding, whose weights are small beside float32's resolution)."""
    cfg, _, batch = _tiny(unroll_layers=True, ce_chunk=-1,
                          warmup_steps=warmup)
    mesh = make_mesh(devices=jax.devices()[:1], dp=-1)
    fns = training.build_gpt_train(cfg, mesh, telemetry=False)
    state = fns["init_fn"](jax.random.PRNGKey(0))
    before = np.asarray(state.params["embed"])
    batch = jax.device_put(batch, fns["batch_sharding"])
    for _ in range(2):
        state, _ = fns["step_fn"](state, batch)
    moved = float(np.max(np.abs(np.asarray(state.params["embed"]) - before)))
    assert moved == pytest.approx(3e-4 / warmup, rel=0.2)
    assert gpt.GPTConfig.gpt2().warmup_steps == 100


def test_routed_queries_are_drawn_sharper_by_layer_kind_and_dense_as_before():
    """A routed config's queries are drawn 4 (window layers) and 3 (full
    layers) times the fan-in deviation; a dense config's draw is the
    plain one, value for value."""
    cfg = gpt.GPTConfig.mellum_tiny(dtype=jnp.float32)
    wq = gpt.init_params(cfg, jax.random.PRNGKey(0))["layers"]["wq"]
    got = np.asarray(wq.std(axis=(1, 2, 3))) * cfg.d_model ** 0.5
    want = [4.0 if kind == "window" else 3.0 for kind in cfg.layer_kinds]
    np.testing.assert_allclose(got, want, rtol=0.05)
    dense = gpt.GPTConfig.tiny(dtype=jnp.float32)
    assert gpt._query_scales(dense) == 1.0
    keys = jax.random.split(jax.random.PRNGKey(0), 24)
    plain = jax.random.normal(keys[1], (2, 64, 4, 16)) * 64 ** -0.5
    np.testing.assert_array_equal(
        gpt.init_params(dense, jax.random.PRNGKey(0))["layers"]["wq"], plain)


def test_program_flops_agree_with_the_benchmarks_costs():
    from benchmark.reduce import costs_mellum
    from ray_tpu.telemetry import flops
    conf = common.load_json(
        common.BENCH_DIR + "/configs/mellum2-12b-a2.5b-ep4.json")
    kwargs = dict(conf["model"]["kwargs"], dtype=jnp.bfloat16, ce_chunk=-1)
    cfg = gpt.GPTConfig.mellum2_12b_a2_5b(**kwargs)
    assert gpt.num_params(jax.eval_shape(
        lambda: gpt.init_params(cfg, jax.random.PRNGKey(0)))) == 595_153_152
    for seq in (1024, 8192):
        assert flops.gpt_train_flops_per_token(cfg, seq) == pytest.approx(
            costs_mellum.train_flops_per_token(conf, seq), rel=1e-12)
    assert costs_mellum.train_flops_per_token(conf, 8192) == pytest.approx(
        1.493e9, rel=1e-3)
    tiny = gpt.GPTConfig.mellum_tiny()
    assert flops.gpt_train_flops_per_token(tiny, 128, ce_recompute=False) \
        == pytest.approx(costs_mellum.train_flops_per_token(TINY, 128))


# ---------------------------------------------------------------- serving

@pytest.mark.parametrize("kw,names", [
    ({"n_kv_heads": 2}, "grouped K/V heads"),
    ({"layer_types": ("window", "full"), "window": 16}, "window layers"),
    ({"held_experts": (0, 1), "n_routed_experts": 4}, "held_experts"),
])
def test_engine_refuses_by_name_what_only_the_train_path_has(kw, names):
    from ray_tpu.inference.engine import InferenceEngine
    cfg = gpt.GPTConfig.tiny(**kw)
    with pytest.raises(NotImplementedError, match=names):
        InferenceEngine(cfg, None)


def test_deployment_refuses_the_preset_before_drawing_weights():
    from ray_tpu.inference import serve_gpt
    with pytest.raises(NotImplementedError, match="is not served"):
        serve_gpt._build_engine("mellum2_12b_a2_5b", None, None, 0)
