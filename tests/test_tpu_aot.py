"""What can be known about a TPU run without a TPU.

libtpu can describe a v5e topology and compile for it from a CPU-only
process, so Mosaic's verdict on every Pallas family is available at zero
chip cost: each test below lowers a kernel family, forward and backward,
at the shapes GPT-2 124M runs it (batch 24 x 1024, the serving engine's
8 slots x 1024 context) and compiles it for ``v5e:2x2``.  A block spec
Mosaic refuses fails here, not on the first chip run.

The rest pins the policies that keep a CPU from passing for a chip: the
interpret-mode rule, the peak-FLOPs table, and where the compile cache
lives.
"""

import dataclasses
import os
import re
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops import attention, flash_ce, fused_norm, substrate

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BF16 = jnp.bfloat16
B, S, H, D = 24, 1024, 12, 64            # GPT-2 124M, the bench batch
N, DM, V = B * S, 768, 50304
SLOTS, CTX, PAGE = 8, 1024, 128          # the engine's decode geometry
PAGES = SLOTS * (CTX // PAGE) + 1


@pytest.fixture(scope="module")
def v5e():
    """Sharding onto one device of a described (not attached) v5e."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"libtpu cannot describe a v5e:2x2 topology here: {e}")
    return NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())


def _compile_for_v5e(fn, sharding, *shapes):
    specs = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
             for s, d in shapes]
    with substrate.compile_for_tpu():
        compiled = jax.jit(fn, out_shardings=sharding).lower(*specs) \
            .compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _instructions(hlo):
    """The HLO instructions in a compiled module's text: a warm worker's
    load of an executable follows them (PERF.md section 6, PR 58)."""
    return sum(1 for line in hlo.splitlines()
               if re.match(r"\s+(ROOT )?%?[\w.\-]+ = ", line))


def _stats_operands(hlo, scope):
    """For each backward Mosaic call under ``scope`` in a compiled
    module's text, how many lane-padded f32 row-stats slabs
    (``[.., STATS_LANES]``) it takes as operands: its ``lse`` alone
    since PR 64 (two for a packed pair of heads), which makes ``delta =
    sum(do * o)`` in the kernel; one more a sub-head before."""
    counts = []
    for line in hlo.splitlines():
        name = re.search(r'op_name="([^"]+)"', line)
        if ("tpu_custom_call" not in line or name is None
                or scope not in name.group(1)
                or "transpose(jvp(" not in name.group(1)):
            continue
        operands = re.search(
            r"operand_layout_constraints=\{(.*?)\}, frontend", line).group(1)
        counts.append(len(re.findall(
            rf"f32\[[\d,]*,{substrate.STATS_LANES}\]", operands)))
    return counts


@pytest.mark.parametrize("batch, seq, pack2", [
    (B, S, True), (B, S, False),
    # the train cells' exact call: the fn build_gpt_train makes, fused
    # RoPE, default blocks, nothing pinned (the walked pack2 schedule,
    # forward and backward, VMEM included)
    (B, S, None),
    # two blocks of 1024 a side: an interior block, walked unmasked
    (B // 2, 2 * S, None),
    # the packed gate's edge: the longest kv sequence whose [Sk, 128]
    # f32 dk / dv scratch uses_pack2 admits, eight blocks a side
    (1, 8 * S, None),
], ids=["pack2", "single_head", "train_cell", "seq2048", "pack2_gate_edge"])
def test_flash_attention_fwd_bwd_compiles_for_v5e(v5e, batch, seq, pack2):
    attn = attention.make_flash_attention_fn(rope_theta=10000.0,
                                             pack2=pack2)

    def step(q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: attn(q, k, v, positions=jnp.arange(seq))
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    hlo = _compile_for_v5e(step, v5e,
                           *[((batch, seq, H, D), BF16)] * 3).as_text()
    if pack2 is not False:
        assert attention.uses_pack2(seq, seq, H, D, pack2=pack2)
        if seq == 8 * S:
            assert not attention.uses_pack2(2 * seq, 2 * seq, H, D)
        assert "attn/pack2" in hlo and "attn/flash" not in hlo
        # the backward is handed o and its two lse slabs: no delta slab,
        # and nothing under the dispatcher's scope broadcasts one
        assert _stats_operands(hlo, "attn/pack2") == [2]
        assert "attn/pack2))/broadcast_in_dim" not in hlo
    else:
        assert _stats_operands(hlo, "attn/flash") == [1]


def test_flash_ce_with_norm_fwd_bwd_compiles_for_v5e(v5e):
    def step(x, head, targets, scale):
        return jax.value_and_grad(
            lambda x, head, scale: flash_ce.flash_ce_norm_sum(
                x, head, targets, scale)[0], argnums=(0, 1, 2))(
                    x, head, scale)

    _compile_for_v5e(step, v5e, ((N, DM), BF16), ((DM, V), BF16),
                     ((N,), jnp.int32), ((DM,), BF16))


# What a v5e's allocator has to give (``memory_stats()["bytes_limit"]``
# on the chip, PR 49: 15.75 GiB of the 16), and what the one-chip cell's
# step may take of it: its arguments and temporaries read 0.69 + 12.36
# GiB with the logits kept (PR 49), which leaves 2.7 GiB; the limit
# below leaves 1.75 GiB, room for the loss head's f32 logits to grow by
# a third before this fails ahead of the chip.
V5E_HBM_BYTES = 16_909_336_064
TRAIN_STEP_LIMIT_BYTES = 14 * 2**30


@pytest.mark.parametrize("ce_chunk, n_layers, flash_calls", [
    # the one-chip train cell's recipe (benchmark/cells/
    # train-gpt2-124m-b24x1024.json), whole: it keeps its logits
    (-1, 12, 0),
    # a recipe that recomputes them, two layers deep (the loss head is
    # what is looked for): flash-CE, forward and backward
    (4096, 2, 2),
], ids=["cell_recipe_keeps_logits", "default_recomputes"])
def test_train_step_loss_head_and_memory_on_v5e(v5e, ce_chunk, n_layers,
                                                flash_calls):
    """The step ``build_gpt_train`` compiles at 24 x 1024 for one v5e:
    which loss head is in the executable follows the recipe's
    ``ce_chunk`` (no ``ce/flash`` Mosaic call where the logits are
    kept, the forward and the backward kernel where they are
    recomputed), and the cell's step, logits resident, fits the chip
    with the margin stated above."""
    from ray_tpu.models import training
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.parallel.mesh import make_mesh

    cfg = dataclasses.replace(
        GPTConfig.gpt2(vocab_size=V, max_seq=S, dtype=BF16, remat=False,
                       unroll_layers=True, ce_chunk=ce_chunk),
        n_layers=n_layers)
    mesh = make_mesh(devices=list(v5e.mesh.devices.flat), dp=-1)
    fns = training.build_gpt_train(cfg, mesh, telemetry=False)
    state = jax.eval_shape(fns["init_fn"], jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((B, S), jnp.int32,
                                     sharding=fns["batch_sharding"])
             for k in ("tokens", "targets")}
    with substrate.compile_for_tpu():
        compiled = fns["step_fn"].lower(state, batch).compile()
    hlo = compiled.as_text()
    kernels = [line for line in hlo.splitlines()
               if "tpu_custom_call" in line and "op_name=" in line]
    assert any("attn/pack2" in line for line in kernels)
    # PR 64: a layer's backward kernel makes delta from o; the step holds
    # no slab of it and none of the instructions that made one
    assert _stats_operands(hlo, "attn/pack2") == [2] * n_layers
    assert "attn/pack2/broadcast_in_dim" not in hlo
    # PR 53: a differentiated step takes XLA's out-proj epilogue
    assert not [line for line in kernels if "norm/fused_epilogue" in line]
    assert sum("ce/flash" in line for line in kernels) == flash_calls, \
        [line for line in kernels if "/ce" in line]
    if n_layers == 12:
        # 12,100 with XLA's delta (PR 63's tree), 11,440 without
        assert _instructions(hlo) <= 12_100, _instructions(hlo)
        mem = compiled.memory_analysis()
        taken = mem.argument_size_in_bytes + mem.temp_size_in_bytes
        assert taken < TRAIN_STEP_LIMIT_BYTES < V5E_HBM_BYTES, (
            f"arguments {mem.argument_size_in_bytes / 2**30:.2f} GiB + "
            f"temporaries {mem.temp_size_in_bytes / 2**30:.2f} GiB")


# one chip's share of the routed 8k model (benchmark/configs/
# mellum2-12b-a2.5b-ep4.json): 32 query heads on 4 K/V heads of 128
MELLUM = dict(n_layers=4, vocab_size=24576, held_experts=tuple(range(16)),
              max_seq=8192, dtype=BF16)
MELLUM_STEP_LIMIT_BYTES = 15.75 * 1e9


@pytest.mark.parametrize("window", [None, 1024], ids=["full", "window"])
def test_window_and_kv_group_kernels_compile_for_v5e(v5e, window):
    """The single-head kernels as the routed model's layers call them, 2
    x 8192 x 32/4 x 128, forward and backward, fused YaRN rope: the
    strip-mined backward holds a K/V head's whole sequence and its
    dk / dv accumulators (34 MiB of VMEM) over the head's 8 query
    heads."""
    from ray_tpu.models.gpt import GPTConfig
    cfg = GPTConfig.mellum2_12b_a2_5b(**MELLUM)
    kind = "full" if window is None else "window"
    attn = attention.make_flash_attention_fn(
        window=window, kv_heads=cfg.kv_heads, rope=cfg.rope(kind))
    b, s, h, kv, d = 2, 8192, cfg.n_heads, cfg.kv_heads, cfg.head_dim

    def step(q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: attn(q, k, v, positions=jnp.arange(s))
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    hlo = _compile_for_v5e(step, v5e, ((b, s, h, d), BF16),
                           ((b, s, kv, d), BF16),
                           ((b, s, kv, d), BF16)).as_text()
    # one forward and one fused backward kernel, and dk / dv at K/V's size
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 2
    # ... which is handed o and lse: no delta slab (PR 64)
    assert _stats_operands(hlo, "attn/flash") == [1]
    assert f"bf16[{b},{kv},{s},{d}]" in hlo
    cover = attn.coverage(s, h, d)
    assert cover["needed"] < cover["executed"] < (0.2 if window else 0.55)


def _kernel_bodies(functions):
    """The distinct serialized Mosaic kernels in the text of some lowered
    functions."""
    return {body for f in functions for body in re.findall(
        r"body\\22: \\22([A-Za-z0-9+/=]+)", f)}


def test_routed_train_step_fits_a_v5e_with_its_cells_recipe(v5e):
    """The 4-layer step of ``train-mellum2-12b-a2.5b-ep4-b2x8192`` with
    the cell file's recipe, compiled for one v5e: window and full
    attention kernels, the grouped products, their gradients and the
    combines of their rows as Mosaic calls of seven distinct kernels, no
    gather of a pick's row from a piece's buffer, no gather or scatter
    of a scalar a pick, no more HLO instructions than the step had
    before the combine was a kernel and 2 %, and arguments + temporaries under the 15.75 GB the issue allows
    (13.5 GB when the recipe was settled, PR 56)."""
    import json

    from ray_tpu.models import training
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.parallel import moe
    from ray_tpu.parallel.mesh import make_mesh

    with open(os.path.join(REPO, "benchmark", "cells",
                           "train-mellum2-12b-a2.5b-ep4-b2x8192.json")) as f:
        recipe = json.load(f)["train"]["kwargs"]
    cfg = GPTConfig.mellum2_12b_a2_5b(**MELLUM, **recipe)
    mesh = make_mesh(devices=list(v5e.mesh.devices.flat), dp=-1)
    fns = training.build_gpt_train(cfg, mesh, telemetry=False)
    state = jax.eval_shape(fns["init_fn"], jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((2, 8192), jnp.int32,
                                     sharding=fns["batch_sharding"])
             for k in ("tokens", "targets")}
    with substrate.compile_for_tpu():
        lowered = fns["step_fn"].lower(state, batch)
        compiled = lowered.compile()
    kernels = [line for line in compiled.as_text().splitlines()
               if "tpu_custom_call" in line and "op_name=" in line]
    window = [line for line in kernels if "window/attn/flash" in line]
    flash = [line for line in kernels if "attn/flash" in line]
    assert len(window) == 6 and len(flash) == 8, (len(window), len(flash))
    # the grouped products are ops/grouped_matmul.py's kernels, under the
    # layer's scope, and none is left to the compiler's ragged-dot
    # rewrite.  A layer's first piece of sorted picks: gate|up and down
    # forward, two in the rows and two in the matrices backward; the
    # pieces behind it, one loop of as many passes as there are such
    # pieces with a held pick: two forward, five backward.  And with
    # each piece's products the combine of its rows into their tokens,
    # one forward (the down projection's rows) and one backward (the
    # gradients in the sorted rows)
    hlo = compiled.as_text()
    # each attention layer's backward makes delta from o: lse is its one slab
    assert _stats_operands(hlo, "attn/flash") == [1] * 4
    assert "ragged-dot-none" not in hlo
    products = [line for line in kernels if "moe/experts" in line]
    assert len(products) == 4 * (8 + 9) == len(kernels) - len(flash)
    assert sum("while/body" in line for line in products) == 4 * 9
    combines = [line for line in products if "jit(_combine)/combine" in line]
    assert len(combines) == 4 * 4
    assert sum("while/body" in line for line in combines) == 4 * 2
    # ... so no gather is left that fetches a row a pick (T x K =
    # 131,072 rows of 2304, sixteen in the step before) from a piece's
    # [49152, 2304] buffer; those that fetch a piece's rows of x and
    # dout by token are: 49,152 rows of [16384, 2304]
    piece = moe.piece_rows(2 * 8192, cfg.moe_top_k, len(cfg.held_experts),
                           cfg.n_routed_experts)
    assert piece == 49152
    fetched = re.findall(r"= \w+\[([\d,]+)\]\S* gather\(", hlo)
    assert f"{piece},{cfg.d_model}" in fetched
    assert f"{2 * 8192 * cfg.moe_top_k},{cfg.d_model}" not in fetched, (
        sorted(set(fetched)))
    # ... and none that fetches a scalar a pick (T x K = 131,072: the
    # weights through the sort's order, the router's scores at its
    # picks, the weights' gradients back: twenty in the step before
    # PR 62), nor a scatter that puts one back (the scores' gradient,
    # 131,072 updates of a [16384 x 64] table, four): those ride the
    # sorts or a compare, and the only scatter left is the embedding's
    picks = 2 * 8192 * cfg.moe_top_k
    assert not {str(picks), f"{2 * 8192},{cfg.moe_top_k}"} & set(fetched), (
        sorted(set(fetched)))
    scattered = re.findall(r"= \w+\[([\d,]+)\]\S* scatter\(", hlo)
    assert scattered == [f"{cfg.vocab_size},{cfg.d_model}"], scattered
    # a warm worker's load costs ~0.23 ms an instruction of the compiled
    # module (PERF.md section 6, PR 58): the schedule of the combines'
    # runs is inlined once a layer and direction
    instructions = _instructions(hlo)
    assert instructions <= 16_530 * 1.02, (
        f"{instructions} HLO instructions; the parent's step (PR 58, "
        "XLA's gather for the combine) held 16,530")
    # ... and the module every process traces and lowers before it can
    # look the executable up holds seven distinct kernels for them,
    # however many layers and pieces call them: every piece has the
    # first's rows, and every call goes through the three module-level
    # jits (a call inside a loop's body and one outside it lower to two
    # functions of one body: fourteen functions, the same serialized
    # kernel in each two)
    functions = [f for f in lowered.as_text().split("func.func ")
                 if re.match(r"private @(_t?gmm|_combine\b|_combine_\d)", f)]
    assert len(functions) <= 14 and len(_kernel_bodies(functions)) == 7, (
        len(functions), len(_kernel_bodies(functions)))
    assert moe.product_path(2 * 8192, cfg.moe_top_k, len(cfg.held_experts),
                            cfg.n_routed_experts, cfg.d_model,
                            cfg.ff_dim) == "pallas"
    mem = compiled.memory_analysis()
    # the state: 6 bytes a parameter (bfloat16 and two bfloat16 moments)
    assert 0 <= mem.argument_size_in_bytes - 595_153_152 * 6 < 1e6
    taken = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert taken < MELLUM_STEP_LIMIT_BYTES < V5E_HBM_BYTES, (
        f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB + "
        f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB")


@pytest.mark.parametrize("width, path", [(256, "pallas"),
                                         (192, "ragged_dot")])
def test_expert_width_the_tiles_do_not_divide_keeps_ragged_dot(v5e, width,
                                                               path):
    """``grouped_matmul.uses_kernel`` decides a differentiated layer's
    products from its shapes: experts 192 wide, which tiles of 128 do
    not divide, keep the compiler's grouped product (``ragged-dot-none``
    in the executable), its gather of a row a pick for the combine, and
    hold no kernel of ours; 256 wide the products and the combine are
    ours and neither is left: one decision a layer."""
    from ray_tpu.ops import grouped_matmul
    from ray_tpu.parallel import moe
    T, d, E, held, top_k = 1024, 256, 8, (0, 1, 2, 3), 2
    rows = moe.piece_rows(T, top_k, len(held), E)
    assert bool(grouped_matmul.uses_kernel(rows, width, d)) == (
        path == "pallas")
    assert moe.product_path(T, top_k, len(held), E, d, width) == path

    def step(x, router, gate, up, down):
        def loss(*a):
            return moe.dropless_moe(
                a[0], a[1], jnp.zeros((E,)), *a[2:], held=held, n_routed=E,
                top_k=top_k, scale=1.0, renormalise=True
            )[0].astype(jnp.float32).sum()
        return jax.grad(loss, (0, 1, 2, 3, 4))(x, router, gate, up, down)

    n = len(held)
    specs = [jax.ShapeDtypeStruct(shape, BF16, sharding=v5e) for shape in (
        (T, d), (d, E), (n, d, width), (n, d, width), (n, width, d))]
    with substrate.compile_for_tpu():
        lowered = jax.jit(step, out_shardings=v5e).lower(*specs)
        hlo = lowered.compile().as_text()
    ours = [f for f in lowered.as_text().split("func.func ")
            if re.match(r"private @_t?gmm", f)]
    combines = [f for f in lowered.as_text().split("func.func ")
                if re.match(r"private @_combine(_\d+)?\(", f)]
    a_row_a_pick = f"{T * top_k},{d}" in re.findall(
        r"= \w+\[([\d,]+)\]\S* gather\(", hlo)
    if path == "pallas":
        assert "ragged-dot-none" not in hlo
        assert 0 < len(_kernel_bodies(ours)) <= 6
        assert len(_kernel_bodies(combines)) == 1 and not a_row_a_pick
    else:
        assert "ragged-dot-none" in hlo and not ours
        assert not combines and a_row_a_pick
        assert "tpu_custom_call" in hlo      # the compiler's own kernel


def test_fused_norm_epilogue_fwd_bwd_compiles_for_v5e(v5e):
    """The out-proj epilogue at a prefill's rows (1 x 1024) and at the
    train step's (24 x 1024): the forward compiles as Mosaic's kernel,
    a value-and-grad of the same call holds no Mosaic call at all (PR
    53: its forward and backward are XLA's)."""
    def fwd(a, w, resid, scale):
        return fused_norm.matmul_residual_norm(a, w, resid, scale)

    def step(a, w, resid, scale):
        def loss(a, w, resid, scale):
            r, y = fwd(a, w, resid, scale)
            return (r.astype(jnp.float32).sum()
                    + (y.astype(jnp.float32) ** 2).sum())
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(
            a, w, resid, scale)

    def shapes(b):
        return (((b, S, H, D), BF16), ((H, D, DM), BF16),
                ((b, S, DM), BF16), ((DM,), BF16))

    for b in (1, B):
        hlo = _compile_for_v5e(fwd, v5e, *shapes(b)).as_text()
        assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 1
    specs = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in shapes(B)]
    with substrate.compile_for_tpu():
        compiled = jax.jit(step, out_shardings=v5e).lower(*specs).compile()
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("kv_dtype", [BF16, jnp.int8])
def test_decode_attention_compiles_for_v5e(v5e, kv_dtype):
    """The kernel over the cache's own pool, ``[L, P, H, D, page]``: a
    page of one layer is the block, picked by the table from SMEM."""
    L = 12
    kv = ((L, PAGES, H, D, PAGE), kv_dtype)
    shapes = [((SLOTS, H, D), BF16), kv, kv, ((SLOTS,), jnp.int32),
              ((SLOTS, CTX // PAGE), jnp.int32), ((), jnp.int32)]
    if kv_dtype == jnp.int8:
        shapes += [((L, PAGES, H, PAGE), jnp.float32)] * 2

    def step(q, k, v, lengths, page_table, layer, *scales):
        kw = dict(zip(("k_scale", "v_scale"), scales))
        # "auto" must pick the kernel wherever kernels are compiled
        return attention.decode_attention(q, k, v, lengths, page_table,
                                          layer, impl="auto", **kw)

    hlo = _compile_for_v5e(step, v5e, *shapes).as_text()
    # the K and V pools go to the kernel as they came: no copy of them
    pool = "[" + ",".join(map(str, kv[0])) + "]"
    assert not [ln for ln in hlo.splitlines()
                if pool in ln.split(" copy(")[0] and " copy(" in ln]


# the two serve cells' engines (benchmark/cells/serve-*.json): preset,
# slots, pages
_SERVE_CELLS = {"124m": ("gpt2", 128, 1025), "large": ("gpt2_large", 64, 513)}


@pytest.mark.parametrize("kv_dtype", [BF16, jnp.int8])
@pytest.mark.parametrize("cell", sorted(_SERVE_CELLS))
def test_decode_write_compiles_for_v5e(v5e, cell, kv_dtype):
    """The write kernel alone, at the two serve cells' geometry: Mosaic
    takes it, "the pool blocks" is true of both pools a cache can hold,
    and the pools go in and come out as they are, with no copy and
    nothing of a pool's size beside them."""
    from ray_tpu.models.gpt import GPTConfig

    preset, slots, pages = _SERVE_CELLS[cell]
    cfg = getattr(GPTConfig, preset)(vocab_size=V, max_seq=CTX, dtype=BF16)
    pool = ((cfg.n_layers, pages, cfg.n_heads, D, PAGE), kv_dtype)
    rows = ((slots, cfg.n_heads, D), BF16)
    with substrate.compile_for_tpu():
        assert attention.decode_write_uses_pallas(D, PAGE, kv_dtype)

    def step(k, v, k_new, v_new, lengths, page_table, layer):
        return attention.decode_write(k, v, k_new, v_new, lengths,
                                      page_table, layer, skip_page=0)

    specs = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in (
        pool, pool, rows, rows, ((slots,), jnp.int32),
        ((slots, CTX // PAGE), jnp.int32), ((), jnp.int32))]
    with substrate.compile_for_tpu():
        compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*specs) \
            .compile()
    hlo = compiled.as_text()
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 1
    dims = "[" + ",".join(map(str, pool[0])) + "]"
    assert not [ln for ln in hlo.splitlines()
                if dims in ln.split(" copy(")[0] and " copy(" in ln]
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("kind", ["decode", "prefill", "prefill_cached"])
@pytest.mark.parametrize("cell", sorted(_SERVE_CELLS))
def test_serve_step_keeps_the_cache_in_place_on_v5e(v5e, cell, kind):
    """The TPU compiler's verdict on the engine's cache handling, at the
    two serve cells' own geometry (GPT-2 124M: 12 heads, 128 slots, 1025
    pages; GPT-2 large: 20 heads, 64 slots, 513 pages) and for all three
    serve executables: the stacked K and V come in, are written and
    read, and go out in ONE layout — no ``copy`` of them, nothing that
    produces a layer's ``[pages, H, D, page]`` pool.  (A row-granular
    scatter compiles to a relayout of the whole cache, head_dim minor
    and padded 2.7x, before and after the layer loop; a per-layer
    dynamic-slice copies a layer's pool out and back in every layer.
    Neither shows on the CPU.)  The decode attends over the pool where
    it lies and lays its new rows into the live slots' tail pages in
    place: its executable holds two kernels, the write and the
    attention, no operation whose result is the slots' padded context,
    gathered or turned, or every slot's tail page (the blend's
    ``[slots, H, D, page]``), and its temporaries are the logits, not a
    context."""
    import re

    from ray_tpu.inference.engine import InferenceEngine
    from ray_tpu.models.gpt import GPTConfig, init_params

    preset, slots, pages = _SERVE_CELLS[cell]
    cfg = getattr(GPTConfig, preset)(vocab_size=V, max_seq=CTX, dtype=BF16)
    heads, page = cfg.n_heads, PAGE
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=v5e)
    params = jax.tree.map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    stacked = (cfg.n_layers, pages, heads, D, page)
    state = (spec(stacked, BF16),) * 2
    # the step builder reads only these: no arrays, no allocation
    eng = object.__new__(InferenceEngine)
    eng.cfg, eng.lora_cfg = cfg, None
    eng.cache = types.SimpleNamespace(state=state)
    fn = eng._build_step(kind)
    i32, mp = jnp.int32, CTX // page
    if kind == "decode":
        tail = (spec((slots,), i32), spec((slots,), i32),
                spec((slots, mp), i32))
    elif kind == "prefill":
        tail = (spec((1, 256), i32), spec((), i32), spec((mp,), i32))
    else:       # a cached suffix's bucket
        tail = (spec((1, 64), i32), spec((), i32), spec((), i32),
                spec((mp,), i32))
    with substrate.compile_for_tpu():
        compiled = fn.lower(params, *state, *tail).compile()
    hlo = compiled.as_text()

    def dims(shape):
        return "[" + ",".join(map(str, shape)) + "]"

    layouts = set()
    for ln in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = bf16(\[[0-9,]+\])"
                     r"(\{[^ ]*\}) ([\w\-]+)\(", ln)
        if not m:
            continue
        shape, layout, op = m.groups()
        assert shape not in (dims(stacked[1:]),
                             dims((1,) + stacked[1:])), ln
        if kind == "decode":
            assert shape not in (dims((slots, CTX, heads, D)),
                                 dims((slots, mp, page, heads, D)),
                                 dims((slots, mp, heads, D, page)),
                                 dims((slots, heads, CTX, D)),
                                 dims((slots, heads, D, page))), ln
        if shape == dims(stacked):
            assert not op.startswith("copy"), ln
            layouts.add(re.sub(r"S\(\d+\)", "", layout))
    assert len(layouts) == 1, layouts
    if kind == "decode":
        assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 2
        assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


# the latent cell's engine (benchmark/cells/serve-longcat-flash-omni-
# agent.json): slots, pages, max_seq; the model as its configuration
# file builds it
_LATENT_CELL = (16, 2561, 12288)


def _latent_cfg():
    from ray_tpu.models import longcat
    return longcat.LongcatConfig.longcat_flash_omni(
        n_layers=4, held_experts=tuple(range(16)), vocab_size=16384,
        max_seq=_LATENT_CELL[2], dtype=BF16)


def _pool_copies(hlo, shape):
    dims = "[" + ",".join(map(str, shape)) + "]"
    return [ln for ln in hlo.splitlines()
            if dims in ln.split(" copy(")[0] and " copy(" in ln]


def test_latent_decode_kernels_compile_for_v5e(v5e):
    """The two kernels over a latent pool alone, at the cell's geometry
    (a 576-value row, 64 heads, 16 slots of 96 pages): Mosaic takes
    both, and the pool goes in and comes out as it is."""
    slots, pages, max_seq = _LATENT_CELL
    cfg = _latent_cfg()
    rank, rope = cfg.latent_row
    pool = (cfg.cache_layers, pages, rank + rope, PAGE)
    with substrate.compile_for_tpu():
        assert attention.latent_decode_uses_pallas(rank + rope, PAGE, BF16)

    def step(rows, q, new, lengths, page_table, layer):
        rows = attention.latent_decode_write(rows, new, lengths, page_table,
                                             layer, skip_page=0)
        return rows, attention.latent_decode_attention(
            q, rows, lengths + 1, page_table, layer,
            scale=cfg.qk_head_dim ** -0.5, value_dim=rank)

    specs = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in (
        (pool, BF16), ((slots, cfg.n_heads, rank + rope), BF16),
        ((slots, rank + rope), BF16), ((slots,), jnp.int32),
        ((slots, max_seq // PAGE), jnp.int32), ((), jnp.int32))]
    with substrate.compile_for_tpu():
        compiled = jax.jit(step, donate_argnums=(0,)).lower(*specs) \
            .compile()
    hlo = compiled.as_text()
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == 2
    assert not _pool_copies(hlo, pool)
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


@pytest.mark.parametrize("kind, bucket", [("decode", 0),
                                          ("prefill_cached", 384)])
def test_latent_serve_step_keeps_the_pool_in_place_on_v5e(v5e, kind,
                                                          bucket):
    """The engine's steps over the latent pool at the cell's own shapes
    (published widths, 4 blocks, 16 held experts): the pool comes in, is
    written and read, and goes out with no copy of it; no layer's
    weights are copied out of their stack (a matrix is sliced where it
    stands, an expert only when a row picked it); a decode's executable
    holds the write and the attention of both sublayers (the scan's
    body: four kernels) and its temporaries are megabytes."""
    from ray_tpu.inference.engine import InferenceEngine
    from ray_tpu.models import longcat

    slots, pages, max_seq = _LATENT_CELL
    cfg = _latent_cfg()
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=v5e)
    params = jax.tree.map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda: longcat.init_params(cfg,
                                                   jax.random.PRNGKey(0))))
    pool = (cfg.cache_layers, pages, sum(cfg.latent_row), PAGE)
    eng = object.__new__(InferenceEngine)
    eng.cfg, eng.lora_cfg = cfg, None
    eng.cache = types.SimpleNamespace(state=(spec(pool, BF16),))
    i32, mp = jnp.int32, max_seq // PAGE
    if kind == "decode":
        tail = (spec((slots,), i32), spec((slots,), i32),
                spec((slots, mp), i32))
    else:
        tail = (spec((1, bucket), i32), spec((), i32), spec((), i32),
                spec((mp,), i32))
    with substrate.compile_for_tpu():
        compiled = eng._build_step(kind).lower(
            params, spec(pool, BF16), *tail).compile()
    hlo = compiled.as_text()
    assert not _pool_copies(hlo, pool)
    # no stacked weight, and no layer's slice of one, is copied (a
    # prefill turns the two halves of W_kvb, 67 MB each, once a step:
    # 0.2 ms of its tens)
    for name, a in params["layers"].items():
        if a.ndim >= 3 and (kind == "decode"
                            or name not in ("wk_b", "wv_b")):
            assert not _pool_copies(hlo, a.shape), name
            assert not _pool_copies(hlo, a.shape[1:]), name
    temp = compiled.memory_analysis().temp_size_in_bytes
    kernels = hlo.count("custom_call_target=\"tpu_custom_call\"")
    if kind == "decode":
        assert kernels == 4 and temp < 64 << 20
    else:
        # the flash forward over the gathered context, both sublayers:
        # no [heads, queries, 12288] scores in HBM
        assert kernels == 2 and temp < 1 << 30
        assert "f32[64,128,12288]" not in hlo


# the latent, routed cell's engine (benchmark/cells/serve-sarvam-105b-
# ep4-docs.json): slots, pages, max_seq; the model as its configuration
# file builds it
_SARVAM_CELL = (16, 1025, 8192)


@pytest.mark.parametrize("kind, bucket", [("decode", 0), ("prefill", 1152),
                                          ("prefill", 6144)])
def test_sarvam_serve_step_keeps_pool_and_weights_in_place_on_v5e(
        v5e, kind, bucket):
    """The engine's steps over sarvam-105b's share (published widths,
    the dense layer and 5 routed layers, 32 held experts): the pool goes
    in and out with no copy of it, no stacked weight or layer's slice of
    one is copied, a decode holds the write and the attention of the
    dense layer and of the scan's body (four kernels) in megabytes of
    temporaries, and the widest bucket's arguments and temporaries stay
    under what the chip gives a program (12.5 GB of 15.75: the cell
    file's arithmetic)."""
    from ray_tpu.inference.engine import InferenceEngine
    from ray_tpu.models import sarvam

    slots, pages, max_seq = _SARVAM_CELL
    cfg = sarvam.SarvamConfig.sarvam_105b(
        n_layers=6, held_experts=tuple(range(32)), vocab_size=65536,
        max_seq=max_seq, dtype=BF16)
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=v5e)
    params = jax.tree.map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda: sarvam.init_params(cfg,
                                                  jax.random.PRNGKey(0))))
    pool = (cfg.cache_layers, pages, sum(cfg.latent_row), PAGE)
    eng = object.__new__(InferenceEngine)
    eng.cfg, eng.lora_cfg = cfg, None
    eng.cache = types.SimpleNamespace(state=(spec(pool, BF16),))
    i32, mp = jnp.int32, max_seq // PAGE
    if kind == "decode":
        tail = (spec((slots,), i32), spec((slots,), i32),
                spec((slots, mp), i32))
    else:
        tail = (spec((1, bucket), i32), spec((), i32), spec((mp,), i32))
    with substrate.compile_for_tpu():
        compiled = eng._build_step(kind).lower(
            params, spec(pool, BF16), *tail).compile()
    hlo = compiled.as_text()
    assert not _pool_copies(hlo, pool)
    for stack in ("dense", "layers"):
        for name, a in params[stack].items():
            if a.ndim >= 3 and (kind == "decode"
                                or name not in ("wk_b", "wv_b")):
                assert not _pool_copies(hlo, a.shape), name
                assert not _pool_copies(hlo, a.shape[1:]), name
    mem = compiled.memory_analysis()
    kernels = hlo.count("custom_call_target=\"tpu_custom_call\"")
    if kind == "decode":
        assert kernels == 4 and mem.temp_size_in_bytes < 64 << 20
    else:
        assert kernels == 2 and mem.temp_size_in_bytes < 1 << 30
        assert f"f32[64,{bucket},{max_seq}]" not in hlo
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.0e9


def _sampler_specs(rows, vocab, sharding=None):
    return [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in (
        ((rows, vocab), jnp.float32), ((rows,), jnp.int32),
        ((rows,), jnp.int32), ((rows,), jnp.float32),
        ((rows,), jnp.int32), ((rows,), jnp.float32))]


def _sorts(jaxpr, under_cond=False):
    """``under_cond`` of every ``sort`` in a jaxpr, sub-jaxprs included:
    whether it sits inside a branch of a ``cond``."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            found.append(under_cond)
        inside = under_cond or eqn.primitive.name == "cond"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _sorts(sub, inside)
    return found


def test_sampler_sorts_only_under_its_cond():
    """The sampler's full-vocabulary sorts sit inside a branch of its
    one batch-level ``cond`` (a ``cond`` under ``vmap`` would be a
    ``select`` that runs both sides) and nowhere a greedy call runs;
    two of the three branches hold none."""
    from ray_tpu.inference.sampling import sample_tokens_logprobs
    jaxpr = jax.make_jaxpr(sample_tokens_logprobs)(
        *_sampler_specs(4, 64)).jaxpr
    sorts = _sorts(jaxpr)
    assert sorts and all(sorts), sorts
    assert _sorts(jax.make_jaxpr(
        jax.vmap(lambda x, p: jax.lax.cond(p, jnp.sort, jnp.negative, x)))(
        jnp.ones((4, 64)), jnp.ones((4,), bool)).jaxpr) == [False]

    def conds(jaxpr):
        return [e for eqn in jaxpr.eqns for e in (
            [eqn] if eqn.primitive.name == "cond" else
            [c for sub in jax.core.jaxprs_in_params(eqn.params)
             for c in conds(sub)])]
    (cond,) = conds(jaxpr)
    assert [bool(_sorts(br.jaxpr)) for br in cond.params["branches"]] \
        == [False, False, True]


def test_sampler_keeps_its_sorts_in_the_branch_on_v5e(v5e):
    """What the jaxpr cannot show: compiled for the chip at the batch
    cell's ``[128, 50304]``, the sorts stay in the conditional's branch
    (not hoisted into what every call runs) and the branches take the
    logits as given, with no copy."""
    from ray_tpu.inference.sampling import sample_tokens_logprobs
    text = sample_tokens_logprobs.lower(
        *_sampler_specs(128, V, v5e)).compile().as_text()
    entry = text[text.index("\nENTRY "):]
    assert " sort(" in text and " sort(" not in entry
    assert entry.count(" conditional(") == 1
    assert " copy(" not in entry


def test_interpret_mode_only_where_the_cpu_was_asked_for(monkeypatch):
    # the suite asks for the CPU by name (conftest): interpret mode
    assert substrate.cpu_requested()
    assert substrate.use_interpret() is True
    assert not attention.decode_uses_pallas(D, PAGE, impl="auto")
    assert not attention.decode_write_uses_pallas(D, PAGE, BF16)
    # the same backend when nobody asked for it is a chip that failed to
    # initialise, or a worker started without one: an error, not a
    # quiet interpret-mode run
    monkeypatch.setattr(substrate, "cpu_requested", lambda: False)
    with pytest.raises(RuntimeError, match="neither a TPU was found"):
        substrate.use_interpret()
    with pytest.raises(RuntimeError, match="neither a TPU was found"):
        attention.decode_uses_pallas(D, PAGE, impl="auto")
    with pytest.raises(RuntimeError, match="neither a TPU was found"):
        attention.decode_write_uses_pallas(D, PAGE, BF16)
    # compiling for a described TPU needs no backend at all
    with substrate.compile_for_tpu():
        assert substrate.use_interpret() is False


def test_chip_peak_is_an_error_for_a_device_not_in_the_table():
    from ray_tpu.telemetry.flops import chip_peak_tflops

    v5e_chip = types.SimpleNamespace(device_kind="TPU v5 lite")
    assert chip_peak_tflops(v5e_chip) == 197.0
    with pytest.raises(ValueError, match="no bf16 peak on record"):
        chip_peak_tflops(types.SimpleNamespace(device_kind="cpu"))
    with pytest.raises(ValueError, match="no bf16 peak on record"):
        chip_peak_tflops(jax.devices()[0])


def _cache_dir_in_fresh_process(env_overrides):
    """(returned dir, jax's configured dir) from a process that did not
    ask for the CPU — the cache stays off where it did."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    env.update(env_overrides, PYTHONPATH=REPO)
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax\n"
         "from ray_tpu._private.compile_cache import enable_compile_cache\n"
         "print(enable_compile_cache())\n"
         "print(jax.config.jax_compilation_cache_dir)\n"
         "from jax._src import xla_bridge\n"
         "assert not xla_bridge._backends  # placing it starts nothing\n"],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_compile_cache_dir_comes_from_outside_or_is_fixed(tmp_path):
    # unset: a fixed path inside the checkout (git-ignored), set in code
    fixed = os.path.join(REPO, ".jax_cache")
    assert _cache_dir_in_fresh_process({}) == [fixed, fixed]
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    # set: jax reads the variable itself and the code sets nothing else
    outside = str(tmp_path / "cache")
    assert _cache_dir_in_fresh_process(
        {"JAX_COMPILATION_CACHE_DIR": outside}) == [outside, outside]
    # the CPU suite itself keeps the checkout clean
    from ray_tpu._private.compile_cache import enable_compile_cache
    assert enable_compile_cache() == ""
    assert jax.config.jax_compilation_cache_dir is None
