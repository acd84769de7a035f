"""The quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user would call,
at the full width of GPT-2 124M with seeded random weights:

1. **train** — ``ray_tpu.init()`` finds the host's chips without jax;
   ``JaxTrainer`` starts one worker that owns all of them, builds
   ``build_gpt_train(cfg, make_mesh(dp=-1))`` with no kernel pins and
   takes a few steps on one seeded batch (24 x 1024 per chip).
2. **kernels** — a one-chip task checks every Pallas kernel the
   dispatch gates selected against the XLA formulation in the tree, at
   the shapes the model runs them, outputs and gradients.
3. **serve** — after those workers have exited and released the chips,
   ``serve.run(GPTDeployment.bind(model="gpt2", ...))`` puts a replica
   on one chip and answers streaming requests that span prefill buckets,
   share a prefix, and arrive while others decode; a second, identical
   wave must compile nothing.

This process is the parent: it never initialises a jax backend (a
process that has touched jax holds the chip its workers need).  Each
phase prints one JSON line; a phase that fails raises and the script
exits non-zero.  The last line of a passing run is
``{"ok": true, "device": {...}}`` with the device as jax reported it in
the train worker.  Times in the phase lines are observations of one run,
not benchmark results.

It exits non-zero, printing no result, when the host has no TPU chip.
``--rehearse-on-cpu[=N]`` walks the same code at toy shapes on N virtual
CPU devices (Pallas in interpret mode) to debug the script without a
chip; a rehearsal never prints the result line and always exits 3.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
TRAIN_STEPS = 16

# Parity tolerances, as max|kernel - reference| / max|reference| per
# tensor.  The references run in f32 on the kernel's own bf16 inputs
# (or, for the two XLA mirrors the tree names, in the same mixed
# precision); the kernels round probabilities, rotated q/k, score
# gradients and outputs to bf16 (2^-8 relative) at points the
# references do not, so a few bf16 ulps of the tensor's largest element
# is what a correct kernel shows.  A wrong lane roll, mask or block index
# moves whole rows and shows as O(1).
TOL_OUT = 2e-2
TOL_GRAD = 5e-2
TOL_LOSS = 1e-3      # f32-accumulated scalar sums

REAL = {
    "model": {"preset": "gpt2",
              "kwargs": {"vocab_size": 50304, "max_seq": 1024,
                         "dtype": "bfloat16"}},
    # the r05 recipe for the single-chip step
    "train_kwargs": {"remat": False, "unroll_layers": True,
                     "ce_chunk": -1},
    "batch_per_chip": 24, "seq": 1024,
    # the routed 8k train cell's kernels (train-mellum2-12b-a2.5b-ep4-
    # b2x8192): one K/V head with its 8 query heads of one sequence (a
    # K/V head's work is independent of the others'), and one expert
    # layer at the cell's rows
    "routed": {"seq": 8192, "group": 8, "head_dim": 128, "window": 1024,
               "rows": 16384, "d_model": 2304, "expert_ff": 896,
               "held": 16, "experts": 64, "top_k": 8},
    # the latent, routed serve cell's expert layer as its model calls it
    # (serve-sarvam-105b-ep4-docs: sigmoid scores, a bias, renormalised,
    # 32 of 128 experts held, beside a shared expert) at a prefill's
    # rows, and the geometry its latent kernels' gates are asked at (the
    # row, the page, a bucket over the gathered context)
    "latent_routed": {"rows": 1152, "d_model": 4096, "expert_ff": 2048,
                      "held": 32, "experts": 128, "top_k": 8,
                      "scale": 2.5, "row": 576, "page": 128,
                      "bucket": 2304, "context": 8192},
    # (prompt length, new tokens); page 128, buckets 32..1024
    "shared_prefix": 288,
    "requests": {"r0": (20, 48), "r1": (100, 64), "r2": (300, 32),
                 "r3": (600, 40), "r4": (340, 32), "r5": (50, 64),
                 "r6": (200, 32)},
}
TOY = {
    # wide enough (d 128, head_dim 64) for every kernel gate to pass, so
    # a rehearsal interprets the kernels the chip compiles; the serving
    # deployment only takes presets, whose toy one is narrower
    "model": {"preset": None,
              "kwargs": {"vocab_size": 512, "d_model": 128, "n_layers": 2,
                         "n_heads": 2, "max_seq": 512,
                         "dtype": "bfloat16"}},
    "serve_model": {"preset": "tiny",
                    "kwargs": {"vocab_size": 512, "max_seq": 512,
                               "dtype": "bfloat16"}},
    "train_kwargs": {"remat": False, "unroll_layers": True,
                     "ce_chunk": -1},
    "batch_per_chip": 2, "seq": 256,
    "routed": {"seq": 256, "group": 2, "head_dim": 128, "window": 96,
               "rows": 128, "d_model": 128, "expert_ff": 128,
               "held": 2, "experts": 8, "top_k": 2},
    "latent_routed": {"rows": 48, "d_model": 128, "expert_ff": 128,
                      "held": 4, "experts": 16, "top_k": 4,
                      "scale": 2.5, "row": 48, "page": 128,
                      "bucket": 256, "context": 512},
    "shared_prefix": 288,
    "requests": {"r0": (20, 8), "r1": (100, 8), "r2": (300, 6),
                 "r3": (40, 8), "r4": (340, 6), "r5": (50, 8),
                 "r6": (200, 6)},
}


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# code that runs in the workers (pickled by value from __main__)
# ---------------------------------------------------------------------------

def _model_cfg(spec: dict, **extra):
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig
    kwargs = dict(spec["kwargs"], **extra)
    kwargs["dtype"] = getattr(jnp, kwargs["dtype"])
    if spec["preset"] is None:
        return GPTConfig(**kwargs)
    return getattr(GPTConfig, spec["preset"])(**kwargs)


def _device_block(rehearsal: bool) -> dict:
    """This process's device as jax reports it; a non-TPU platform in a
    run that is not a rehearsal fails the run."""
    import jax
    devices = jax.devices()
    block = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    if block["platform"] != "tpu" and not rehearsal:
        raise RuntimeError(f"worker is on {block}, not on a TPU")
    return block


def _peak_hbm(devices) -> dict:
    out = {}
    for d in devices:
        stats = d.memory_stats() or {}
        out[str(d.id)] = {k: stats.get(k) for k in
                          ("peak_bytes_in_use", "bytes_in_use",
                           "bytes_limit")}
    return out


def _hlo_kernels(hlo: str) -> dict:
    import re
    scopes = ("attn/pack2", "attn/flash", "attn/xla", "ce/flash_norm",
              "ce/flash", "ce/xla", "norm/fused_epilogue")
    return {"custom_calls": hlo.count("tpu_custom_call"),
            "all_reduce_ops": len(re.findall(r"= [^\n]*? all-reduce", hlo)),
            "scopes": [s for s in scopes
                       if re.search(rf'op_name="[^"]*{s}[/"]', hlo)]}


def train_loop(config: dict) -> None:
    import jax

    from ray_tpu import train
    from ray_tpu._private.compile_cache import (compile_stats,
                                                enable_compile_cache)
    from ray_tpu.models import gpt, training
    from ray_tpu.ops import flash_ce
    from ray_tpu.ops.attention import (latent_decode_uses_pallas,
                                       latent_prefill_uses_pallas,
                                       train_causal_coverage, uses_pack2)
    from ray_tpu.parallel import moe
    from ray_tpu.parallel.mesh import make_mesh

    cache_dir = enable_compile_cache()
    device = _device_block(config["rehearsal"])
    devices = jax.devices()
    mesh = make_mesh(dp=-1)
    cfg = _model_cfg(config["model"], **config["train_kwargs"])
    fns = training.build_gpt_train(cfg, mesh)       # no kernel pins
    n = len(devices)
    B, S = config["batch_per_chip"] * n, config["seq"]
    batch = jax.device_put(
        training.synthetic_lm_batch(jax.random.PRNGKey(1), B, S,
                                    cfg.vocab_size),
        fns["batch_sharding"])
    state = fns["init_fn"](jax.random.PRNGKey(0))
    first_step_s = None
    t0 = time.monotonic()
    for i in range(config["steps"]):
        state, metrics = fns["step_fn"](state, batch)
        loss = float(metrics["loss"])                # waits for the step
        if first_step_s is None:
            first_step_s = time.monotonic() - t0
        train.report({"step": i, "loss": loss})

    # what the gates chose, from the shapes the step ran ...
    N, d, V = B * S, cfg.d_model, cfg.vocab_size
    gate = dict(n_devices=n, norm=cfg.norm, has_bias=cfg.use_bias)
    routed_shapes = [config["routed"][k] for k in (
        "rows", "top_k", "held", "experts", "d_model", "expert_ff")]
    latent = config["latent_routed"]
    ce = gpt.ce_path(N, d, V, ce_chunk=cfg.ce_chunk, n_devices=n)
    if flash_ce.uses_flash_ce_norm(N, d, V, ce_chunk=cfg.ce_chunk, **gate):
        ce = "flash_norm"
    gates = {
        "attn_pack2": uses_pack2(S, S, cfg.n_heads, cfg.head_dim),
        # the share of the causal score square that schedule executes
        # (0.5005 needed at 1024; whole blocks of 512 masked ran 0.75)
        "causal_coverage": train_causal_coverage(S, cfg.n_heads,
                                                 cfg.head_dim),
        "ce": ce,
        # off whatever the shapes: the step is differentiated, and a
        # differentiated out-proj epilogue is XLA's einsum + add + norm
        # (ops/fused_norm.py; its kernel is the forward-only call's,
        # which the kernels phase checks at the prefill buckets)
        "fuse_norm": False,
        # the form the routed 8k cell's differentiated expert layers
        # take their grouped products in (this step has no such layer;
        # the kernels phase checks them at these shapes)
        "moe_product": moe.product_path(*routed_shapes),
        # ... and the combines of their rows with them
        "moe_combine": moe.combine_path(*routed_shapes),
        # the latent models' serve kernels at the latent, routed serve
        # cell's geometry: the decode's read and write over the pool's
        # rows, and a prefill's attention over the gathered context
        "latent_decode_pallas": latent_decode_uses_pallas(
            latent["row"], latent["page"], cfg.dtype),
        "latent_prefill_pallas": latent_prefill_uses_pallas(
            latent["bucket"], latent["context"], cfg.dtype),
    }
    # ... and what the compiled step holds (the jitted call's own
    # executable comes back out of the cache)
    raw_step = fns.get("raw_step_fn", fns["step_fn"])
    hlo = _hlo_kernels(raw_step.lower(state, batch).compile().as_text())
    summary = {
        "device": device, "mesh": dict(mesh.shape), "gates": gates,
        "hlo": hlo, "batch": [B, S],
        "batch_shards": {str(s.device.id): list(s.data.shape)
                         for s in batch["tokens"].addressable_shards},
        "param_bytes": sum(p.nbytes for p in jax.tree.leaves(state.params)),
        "peak_hbm": _peak_hbm(devices),
        "first_step_s": round(first_step_s, 2),
        "compile": compile_stats(), "compile_cache_dir": cache_dir,
    }
    tel = fns.get("telemetry")
    if tel is not None:
        s = tel.summary()
        summary["observed"] = {k: s.get(k) for k in
                               ("steady_step_s",
                                "tokens_per_sec_per_device", "mfu")}
    train.report({"summary": summary})


def _rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-30))


def kernel_parity(config: dict) -> dict:
    """Every Pallas kernel the gates select on this host, against the XLA
    formulation in the tree, at the shapes the model runs it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu._private.compile_cache import (compile_stats,
                                                enable_compile_cache)
    from ray_tpu.inference.config import default_buckets, infer_config
    from ray_tpu.ops import attention as A
    from ray_tpu.ops import flash_ce, fused_norm
    from ray_tpu.ops.substrate import use_interpret
    from ray_tpu.parallel.ring_attention import local_attention

    enable_compile_cache()
    device = _device_block(config["rehearsal"])
    cfg = _model_cfg(config["model"])
    n_train = config["train_devices"]
    B, S = config["batch_per_chip"], config["seq"]
    H, D, d, V = cfg.n_heads, cfg.head_dim, cfg.d_model, cfg.vocab_size
    N, K = B * S, H * D
    f32, dt = jnp.float32, cfg.dtype
    # (the first 64 draws as they have been; the rows PR 61 added ran past
    # them on the chip, where every kernel's row is drawn)
    keys = (k for seed in (7, 8) for k in jax.random.split(
        jax.random.PRNGKey(seed), 64))
    rows = []

    def rand(shape, scale=1.0, dtype=dt):
        return (jax.random.normal(next(keys), shape) * scale).astype(dtype)

    def up(x):
        return x.astype(f32)

    def row(kernel, shape, errs, tols, calls=None, want_calls=None,
            **facts):
        """``calls``: the Mosaic kernels counted in the executable (None
        where none was counted), which have to be ``want_calls``;
        ``facts``: what else the row says of the kernel it checked."""
        r = {"kernel": kernel, "shape": shape, **facts,
             "err": {k: float(f"{v:.3g}") for k, v in errs.items()},
             "ok": all(errs[k] <= tols[k] for k in errs)}
        if calls is not None:
            r["mosaic_calls"] = calls
            r["ok"] = r["ok"] and calls == want_calls
        rows.append(r)

    def compiled(fn, *args):
        """``fn``'s executable for ``args`` and the Mosaic kernels in it
        (None where the CPU was asked for: interpret mode compiles
        none)."""
        exe = jax.jit(fn).lower(*args).compile()
        return exe, None if use_interpret() else exe.as_text().count(
            'custom_call_target="tpu_custom_call"')

    def with_vjp(fn):
        def both(args, cts):
            out, pull = jax.vjp(fn, *args)
            return out, pull(cts)
        return both

    def vjp_np(fn, args, cts):
        """(outputs, grads) as host arrays, so nothing stays on the
        device between the kernel and its reference; jitted, so the
        reference's [N, V]-sized intermediates are fused, not each
        materialised."""
        return jax.tree.map(np.asarray, jax.jit(with_vjp(fn))(args, cts))

    # -- training attention: fused-RoPE flash (pack2 at head_dim 64) ------
    if A.supports(S, S, D):
        pos = jnp.arange(S)
        q, k, v = (rand((B, S, H, D)) for _ in range(3))
        w = rand((B, S, H, D))
        o, g = vjp_np(lambda q, k, v: A.flash_attention(
            q, k, v, positions=pos, rope_theta=cfg.rope_theta), (q, k, v), w)
        o_ref, g_ref = vjp_np(lambda q, k, v: local_attention(
            A.rope_rotate(q, pos, cfg.rope_theta),
            A.rope_rotate(k, pos, cfg.rope_theta), v, causal=True),
            (up(q), up(k), up(v)), up(w))
        name = "attn/pack2" if A.uses_pack2(S, S, H, D) else "attn/flash"
        row(name + "+rope fwd+bwd", [B, S, H, D],
            {"o": _rel_err(o, o_ref), "dq": _rel_err(g[0], g_ref[0]),
             "dk": _rel_err(g[1], g_ref[1]), "dv": _rel_err(g[2], g_ref[2])},
            {"o": TOL_OUT, "dq": TOL_GRAD, "dk": TOL_GRAD, "dv": TOL_GRAD},
            causal_coverage=A.train_causal_coverage(S, H, D))
        del q, k, v, w

    # -- the routed 8k cell's attention: window and full layers, 8 query
    # heads on a K/V head, fused YaRN rope, the single-head schedule ------
    r = config["routed"]
    Sr, G, Dr = r["seq"], r["group"], r["head_dim"]
    yarn = A.Rope(theta=500000.0, factor=16.0, original_max=8192,
                  attention_factor=1.2772588722239782)
    pos = jnp.arange(Sr)
    for window in (r["window"], None):
        rope = yarn if window is None else 500000.0
        q, w = rand((1, Sr, G, Dr)), rand((1, Sr, G, Dr))
        k, v = rand((1, Sr, 1, Dr)), rand((1, Sr, 1, Dr))
        hook = A.make_flash_attention_fn(window=window, kv_heads=1,
                                         rope=rope)
        o, g = vjp_np(lambda q, k, v: hook(q, k, v, positions=pos),
                      (q, k, v), w)
        o_ref, g_ref = vjp_np(lambda q, k, v: A.xla_attention(
            A.rope_rotate(q, pos, rope), A.rope_rotate(k, pos, rope), v,
            causal=True, window=window), (up(q), up(k), up(v)), up(w))
        row(f"attn/flash {'window' if window else 'full'}+group+rope "
            "fwd+bwd", [1, Sr, G, 1, Dr],
            {"o": _rel_err(o, o_ref), "dq": _rel_err(g[0], g_ref[0]),
             "dk": _rel_err(g[1], g_ref[1]), "dv": _rel_err(g[2], g_ref[2])},
            {"o": TOL_OUT, "dq": TOL_GRAD, "dk": TOL_GRAD, "dv": TOL_GRAD},
            coverage=hook.coverage(Sr, G, Dr))
        del q, k, v, w, o_ref, g_ref

    # -- its expert layer, differentiated: grouped products over the
    # sorted held picks against every held expert over every row ---------
    from ray_tpu.parallel import moe
    T, dm, fe = r["rows"], r["d_model"], r["expert_ff"]
    held, E, topk = tuple(range(r["held"])), r["experts"], r["top_k"]
    x, ct = rand((T, dm)), rand((T, dm))
    router = rand((dm, E), 3 * dm ** -0.5)
    gate, upm = (rand((len(held), dm, fe), dm ** -0.5) for _ in range(2))
    down = rand((len(held), fe, dm), fe ** -0.5)

    def routed(x, router, gate, upm, down):
        return moe.dropless_moe(
            x, router, jnp.zeros((E,), f32), gate, upm, down, held=held,
            n_routed=E, top_k=topk, scale=1.0, renormalise=True)

    def dense(x, router, gate, upm, down):
        p = jax.nn.softmax(jnp.einsum(
            "td,de->te", x, router, precision=jax.lax.Precision.HIGHEST), -1)
        top, pick = jax.lax.top_k(p, topk)
        wgt = top / top.sum(-1, keepdims=True)

        def one(out, e):
            j, g1, u1, d1 = e
            mine = jnp.sum(jnp.where(pick == j, wgt, 0.0), -1)
            return out + mine[:, None] * (
                (jax.nn.silu(x @ g1) * (x @ u1)) @ d1), None
        return jax.lax.scan(one, jnp.zeros_like(x),
                            (jnp.asarray(held), gate, upm, down))[0]

    args = (x, router, gate, upm, down)
    counts = np.asarray(jax.jit(routed)(*args)[1])
    o, g = vjp_np(lambda *a: routed(*a)[0], args, ct)
    with jax.default_matmul_precision("highest"):
        o_ref, g_ref = vjp_np(dense, tuple(up(a) for a in args), up(ct))
    names = ("dx", "drouter", "dgate", "dup", "ddown")
    errs = {"o": _rel_err(o, o_ref)}
    errs.update({n: _rel_err(a, b) for n, a, b in zip(names, g, g_ref)})
    product = moe.product_path(T, topk, len(held), E, dm, fe)
    row("moe/grouped fwd+bwd", [T, dm, fe, len(held), E, topk], errs,
        {"o": TOL_OUT, **dict.fromkeys(names, TOL_GRAD)},
        counts=dict(zip(moe.MOE_COUNTS, (int(c) for c in counts))),
        moe_product=product)
    del x, ct, args, o_ref, g_ref

    # -- the serve path's expert layer as the latent, routed model calls
    # it (sigmoid scores, selection by a bias, the picks renormalised,
    # a share of the experts held, beside a shared expert; nobody
    # differentiates it: the loop over the tiles the picks fill) against
    # the benchmark's plain reference of that layer at the cell's widths
    from benchmark.reference import sarvam as plain
    from ray_tpu.models import latent
    lr = config["latent_routed"]
    Tl, dl, fl, El = (lr[k] for k in ("rows", "d_model", "expert_ff",
                                       "experts"))
    mine = tuple(range(lr["held"]))
    w = {"router": rand((1, dl, El), 3 * dl ** -0.5),
         "router_bias": rand((1, El), 0.05, f32),
         **{"e_" + n: rand((1, len(mine)) + shape, sc)
            for n, shape, sc in (("gate", (dl, fl), dl ** -0.5),
                                 ("up", (dl, fl), dl ** -0.5),
                                 ("down", (fl, dl), fl ** -0.5))},
         **{"s_" + n: rand((1,) + shape, sc)
            for n, shape, sc in (("gate", (dl, fl), dl ** -0.5),
                                 ("up", (dl, fl), dl ** -0.5),
                                 ("down", (fl, dl), fl ** -0.5))}}
    x = rand((Tl, dl))

    def served(x, w):
        out, counts = moe.dropless_moe(
            x, w["router"][0], w["router_bias"][0], w["e_gate"],
            w["e_up"], w["e_down"], held=mine, n_routed=El,
            top_k=lr["top_k"], scale=lr["scale"], lead=(0,),
            renormalise=True, scoring="sigmoid")
        return out + latent.swiglu(x[None], w["s_gate"][0], w["s_up"][0],
                                   w["s_down"][0])[0], counts

    def reference(x, w):
        with jax.default_matmul_precision("highest"):
            return plain.moe(up(x), plain._reader(w, 0),
                             (lr["top_k"], lr["scale"], mine),
                             lambda a: a, jnp.asarray(plain.FAULTS[""], f32))

    o, counts = jax.jit(served)(x, w)
    o_ref, margin = jax.jit(reference)(x, w)
    # a row a held expert stands at the top-k's edge of differs rightly
    # (bfloat16 rows against the reference's float32 ones)
    decided = np.asarray(margin) >= plain.CHOICE_MARGIN
    row("moe/serve sigmoid+bias+renorm+shared",
        [Tl, dl, fl, len(mine), El, lr["top_k"]],
        {"o": _rel_err(np.asarray(up(o))[decided],
                       np.asarray(o_ref)[decided])}, {"o": TOL_OUT},
        counts=dict(zip(moe.MOE_COUNTS, (int(c) for c in counts))),
        decided_rows=int(decided.sum()))
    del x, w, o, o_ref

    # -- its grouped products, kernel by kernel, over one piece of sorted
    # rows with uneven groups and rows no group has: the forward's
    # product, the gradient in the rows (the matrices read transposed)
    # and the gradient in the matrices, against jax.lax.ragged_dot ------
    if product == "pallas":
        from ray_tpu.ops import grouped_matmul
        rows_p = moe.piece_rows(T, topk, len(held), E)
        n = np.random.default_rng(7).multinomial(
            2 * rows_p // 3, np.ones(len(held)) / len(held))
        n[len(held) // 2] = 0                   # an expert nobody picked
        n = jnp.asarray(n, jnp.int32)
        xs, dgu = rand((rows_p, dm)), rand((rows_p, 2 * fe))
        w_gu = rand((len(held), dm, 2 * fe), dm ** -0.5)
        live = (jnp.arange(rows_p) < jnp.sum(n))[:, None]

        def ours(xs, dgu, w_gu):
            return (grouped_matmul.gmm(xs, w_gu, n),
                    grouped_matmul.gmm(dgu, w_gu, n, transpose_rhs=True),
                    grouped_matmul.tgmm(xs, dgu, n))

        def xla(xs, dgu, w_gu):
            return (jnp.where(live, moe._ragged(xs, w_gu, n, None), 0),
                    jnp.where(live, moe._ragged(dgu, w_gu, n, None, True), 0),
                    moe._ragged_outer(xs, dgu, n, None))

        exe, calls = compiled(ours, xs, dgu, w_gu)
        got = jax.tree.map(np.asarray, exe(xs, dgu, w_gu))
        with jax.default_matmul_precision("highest"):
            want = jax.tree.map(np.asarray, jax.jit(xla)(
                up(xs), up(dgu), up(w_gu)))
        kinds = ("gmm", "gmm_transposed", "tgmm")
        row("moe/grouped products", [rows_p, dm, 2 * fe, len(held)],
            {k: _rel_err(a, b) for k, a, b in zip(kinds, got, want)},
            {"gmm": TOL_OUT, "gmm_transposed": TOL_GRAD, "tgmm": TOL_GRAD},
            calls, 3, moe_product=product,
            tiles={"gmm": grouped_matmul.tiling(rows_p, dm, 2 * fe),
                   "gmm_transposed": grouped_matmul.tiling(
                       rows_p, 2 * fe, dm),
                   "tgmm": grouped_matmul.tiling(
                       rows_p, dm, 2 * fe, transposed_lhs=True)})
        del xs, dgu, w_gu, got, want

        # -- its combine: a router's picks sorted by expert, the held
        # picks' rows of the first piece summed into their tokens where
        # the sort left them, against the gather of every pick's row ----
        pick = jax.lax.top_k(rand((T, E), dtype=f32), topk)[1]
        local = jnp.where(pick < len(held), pick, len(held)).astype(
            jnp.int32)
        sort = moe._sorted_picks(local, jnp.ones(local.shape, f32),
                                 len(held), rows_p)
        token, _, live, _, mine, at = jax.jit(lambda: moe._piece(
            jnp.int32(0), rows_p, *sort, local < len(held)))()
        # (whatever lies behind the last live row must not reach a sum)
        y = jnp.where(live[:, None], rand((rows_p, dm)), jnp.nan)
        tiles = grouped_matmul.combine_tiling(T, dm)

        def summed(y):
            runs = grouped_matmul.combine_runs(local, sort[3],
                                               tile_t=tiles[0])
            return (grouped_matmul.combine(y, token, runs, T=T),
                    grouped_matmul.combine_windows(runs, [0], rows_p,
                                                   tiles[1]))

        exe, calls = compiled(summed, y)
        got, windows = exe(y)
        want = jax.jit(moe._pick_sum)(y, at, mine)
        row("moe/combine", [T, rows_p, dm, len(held)],
            {"sum": _rel_err(got, want)}, {"sum": 2e-6}, calls, 1,
            moe_combine="pallas", tiles=tiles, windows=int(windows),
            held_rows=int(jnp.sum(mine)))
        del y, got, want

    # -- out-proj + residual + rmsnorm epilogue, differentiated ------------
    # (PR 53: the rule is XLA's, so this row guards its wiring: no Mosaic
    # call in the executable, and XLA's own outputs and gradients)
    norm_gate = dict(norm=cfg.norm, has_bias=cfg.use_bias)
    if fused_norm.out_proj_norm_plan(N, K, d, seq=S, n_devices=n_train,
                                     **norm_gate):
        a, wo = rand((B, S, H, D)), rand((H, D, d), K ** -0.5)
        resid = rand((B, S, d))
        scale = (1 + 0.1 * rand((d,), dtype=f32)).astype(dt)
        cts = (rand((B, S, d)), rand((B, S, d)))
        args, eps = (a, wo, resid, scale), 1e-6

        def epilogue(*x):
            return fused_norm.matmul_residual_norm(*x, eps=eps)
        exe, calls = compiled(with_vjp(epilogue), args, cts)
        o, g = jax.tree.map(np.asarray, exe(args, cts))
        o_ref, g_ref = vjp_np(
            lambda *x: fused_norm.xla_matmul_residual_norm(*x, eps=eps),
            args, cts)
        names = ("da", "dw", "dresid", "dscale")
        errs = {"r": _rel_err(o[0], o_ref[0]), "y": _rel_err(o[1], o_ref[1])}
        errs.update({nm: _rel_err(x, y)
                     for nm, x, y in zip(names, g, g_ref)})
        row("norm/fused_epilogue fwd+bwd", [B, S, H, D, d], errs,
            {"r": TOL_OUT, "y": TOL_OUT, **dict.fromkeys(names, TOL_GRAD)},
            calls=calls, want_calls=0)
        del a, wo, resid, args, cts

    # -- flash-CE with the final norm in its prologue ----------------------
    # (at the default recipe, cfg.ce_chunk >= 0: the train phase's keeps
    # its logits and runs XLA's head, which needs no parity row)
    if flash_ce.uses_flash_ce_norm(N, d, V, ce_chunk=cfg.ce_chunk,
                                   n_devices=n_train, **norm_gate):
        x, head = rand((N, d)), rand((d, V), 0.02)
        scale = (1 + 0.1 * rand((d,), dtype=f32)).astype(dt)
        tgt = jax.random.randint(next(keys), (N,), 0, V)
        tgt = jnp.where(jax.random.uniform(next(keys), (N,)) < 0.05, -1, tgt)
        eps = 1e-6

        def fused(x, head, scale):
            s, n = flash_ce.flash_ce_norm_sum(x, head, tgt, scale, eps=eps)
            return s / n

        def unfused(x, head, scale):
            x32 = x.astype(f32)
            x32 = x32 * jax.lax.rsqrt(
                jnp.mean(x32 * x32, -1, keepdims=True) + eps)
            y = (x32 * scale.astype(f32)).astype(x.dtype)
            s, n = flash_ce._xla_ce_sum(y, head, tgt)
            return s / n

        loss, g = jax.tree.map(np.asarray, jax.jit(jax.value_and_grad(
            fused, (0, 1, 2)))(x, head, scale))
        ref, g_ref = jax.tree.map(np.asarray, jax.jit(jax.value_and_grad(
            unfused, (0, 1, 2)))(x, head, scale))
        loss, ref = float(loss), float(ref)
        names = ("dx", "dhead", "dscale")
        errs = {"loss": abs(loss - ref) / abs(ref)}
        errs.update({nm: _rel_err(a_, b_)
                     for nm, a_, b_ in zip(names, g, g_ref)})
        row("ce/flash_norm fwd+bwd", [N, d, V], errs,
            {"loss": TOL_LOSS, **dict.fromkeys(names, TOL_GRAD)})
        del x, head, g, g_ref

    # -- serving: prefill attention and epilogue per bucket, decode -------
    icfg = infer_config()
    buckets = [b for b in (icfg.buckets or default_buckets(cfg.max_seq))
               if b <= cfg.max_seq]
    attn_buckets = [b for b in buckets if A.supports(b, b, D)]
    for b in sorted({buckets[0], buckets[-1], *attn_buckets[:1]}):
        if not use_interpret() and b in attn_buckets:
            q, k, v = (rand((1, b, H, D)) for _ in range(3))
            o = jax.jit(lambda q, k, v: A.flash_attention(
                q, k, v, causal=True))(q, k, v)
            o_ref = jax.jit(lambda q, k, v: local_attention(
                q, k, v, causal=True))(up(q), up(k), up(v))
            name = "attn/pack2" if A.uses_pack2(b, b, H, D) else "attn/flash"
            row(name + " prefill fwd", [1, b, H, D],
                {"o": _rel_err(o, o_ref)}, {"o": TOL_OUT})
        if fused_norm.out_proj_norm_plan(b, K, d, seq=b, **norm_gate):
            a, wo = rand((1, b, H, D)), rand((H, D, d), K ** -0.5)
            resid, scale = rand((1, b, d)), jnp.ones((d,), dt)
            exe, calls = compiled(fused_norm.matmul_residual_norm,
                                  a, wo, resid, scale)
            r, y = exe(a, wo, resid, scale)
            r_ref, y_ref = jax.jit(fused_norm.xla_matmul_residual_norm)(
                a, wo, resid, scale)
            row("norm/fused_epilogue prefill fwd", [1, b, H, D, d],
                {"r": _rel_err(r, r_ref), "y": _rel_err(y, y_ref)},
                {"r": TOL_OUT, "y": TOL_OUT},
                calls=calls, want_calls=1)   # forward only: the kernel
    # decode attention over the paged pool at the two serve cells' own
    # geometry (benchmark/cells/serve-*.json: slots, pages, heads), two
    # layers of it: every slot's pages through a shuffled table, ragged
    # lengths, the second layer
    page, mp = icfg.page_size, -(-cfg.max_seq // icfg.page_size)
    cells = ((64, 513, 20), (128, 1025, 12))
    for slots, pages, heads in cells if A.decode_uses_pallas(D, page) else ():
        q = rand((slots, heads, D))
        k, v = (rand((2, pages, heads, D, page)) for _ in range(2))
        table = 1 + jax.random.permutation(
            next(keys), pages - 1)[:slots * mp].reshape(slots, mp)
        lengths = jax.random.randint(next(keys), (slots,), 1, mp * page + 1)
        o = jax.jit(lambda *x: A.decode_attention(*x, 1, impl="pallas"))(
            q, k, v, lengths, table)
        o_ref = jax.jit(lambda *x: A.decode_attention(*x, 1, impl="xla"))(
            up(q), up(k), up(v), lengths, table)
        row("attn/decode_pallas fwd", [slots, pages, heads, D, page],
            {"o": _rel_err(o, o_ref)}, {"o": TOL_OUT})
        # the decode's write, in place, against the cache's whole-page
        # blend: every third slot sits the decode out, and the pools are
        # equal to the bit in every page but the garbage page
        if A.decode_write_uses_pallas(D, page, k.dtype):
            from ray_tpu.inference import kv_cache as kvc
            k_new, v_new = rand((slots, heads, D)), rand((slots, heads, D))
            live = table.at[::3].set(kvc.GARBAGE_PAGE)
            at = (lengths - 1, live, jnp.int32(1))
            got = jax.jit(lambda *x: A.decode_write(
                *x, skip_page=kvc.GARBAGE_PAGE))(k, v, k_new, v_new, *at)
            want = [jax.jit(kvc.write_decode)(pool, new, at[2], live, at[0])
                    for pool, new in ((k, k_new), (v, v_new))]
            row("attn/write_pallas", [slots, pages, heads, D, page],
                {n: float(not jnp.array_equal(a[:, 1:], b[:, 1:]))
                 for n, a, b in zip("kv", got, want)}, {"k": 0.0, "v": 0.0})
            del got, want
        del q, k, v

    return {"device": device, "kernels": rows,
            "tolerance": {"out": TOL_OUT, "grad": TOL_GRAD,
                          "loss": TOL_LOSS},
            "peak_hbm": _peak_hbm(jax.devices()),
            "compile": compile_stats()}


# ---------------------------------------------------------------------------
# the parent's phases
# ---------------------------------------------------------------------------

def phase_train(sizes: dict, chips: int, rehearsal: bool) -> dict:
    from ray_tpu.train import RunConfig, ScalingConfig
    from ray_tpu.train.jax import JaxTrainer

    storage = tempfile.mkdtemp(prefix="chip_smoke_train_")
    try:
        result = JaxTrainer(
            train_loop,
            train_loop_config={**sizes, "steps": TRAIN_STEPS,
                               "rehearsal": rehearsal},
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True,
                resources_per_worker={"TPU": chips}),
            run_config=RunConfig(name="chip_smoke",
                                 storage_path=storage)).fit()
    finally:
        shutil.rmtree(storage, ignore_errors=True)
    if result.error is not None:
        raise result.error
    losses = [m["loss"] for m in result.metrics_history if "loss" in m]
    summary = result.metrics["summary"]
    emit("train", losses=[round(x, 4) for x in losses], **summary)

    vocab = sizes["model"]["kwargs"]["vocab_size"]
    check(len(losses) >= 8, f"{len(losses)} train steps reported")
    check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    check(abs(losses[0] - math.log(vocab)) < 0.5,
          f"first loss {losses[0]} is not ~ln({vocab})={math.log(vocab):.3f}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(summary["device"]["count"] == chips,
          f"worker saw {summary['device']['count']} devices of {chips}")
    per_chip = [sizes["batch_per_chip"], sizes["seq"]]
    check(len(summary["batch_shards"]) == chips
          and all(s == per_chip for s in summary["batch_shards"].values()),
          f"batch shards {summary['batch_shards']} != {per_chip} x {chips}")
    gates, scopes = summary["gates"], summary["hlo"]["scopes"]
    for gate_on, scope in ((gates["attn_pack2"], "attn/pack2"),
                           (gates["ce"] == "flash_norm", "ce/flash_norm"),
                           (gates["fuse_norm"], "norm/fused_epilogue")):
        check(gate_on == (scope in scopes),
              f"gate says {scope}={gate_on}, compiled step has {scopes}")
    if chips > 1:
        check(summary["hlo"]["all_reduce_ops"] > 0,
              "no gradient all-reduce in the dp step's HLO")
    if not rehearsal:
        for dev, mem in summary["peak_hbm"].items():
            check((mem["peak_bytes_in_use"] or 0) > summary["param_bytes"],
                  f"device {dev} peak HBM {mem} below the parameter bytes")
    return summary


def phase_kernels(sizes: dict, chips: int, rehearsal: bool) -> None:
    import ray_tpu
    task = ray_tpu.remote(num_tpus=1)(kernel_parity)
    report = ray_tpu.get(task.remote({**sizes, "train_devices": chips,
                                      "rehearsal": rehearsal}),
                         timeout=900)
    emit("kernels", **report)
    check(report["device"]["count"] == 1 or rehearsal,
          f"task owns {report['device']['count']} chips, asked for one")
    check(report["kernels"], "no Pallas kernel was selected")
    bad = [r for r in report["kernels"] if not r["ok"]]
    check(not bad, f"kernel parity outside tolerance: {bad}")


def _prompts(sizes: dict, seed: int) -> dict:
    """Seeded prompts of the planned lengths; r2 and r4 share a prefix
    of more than two pages."""
    import numpy as np
    rng = np.random.RandomState(seed)
    vocab = sizes.get("serve_model",
                      sizes["model"])["kwargs"]["vocab_size"]
    shared = rng.randint(0, vocab, size=sizes["shared_prefix"]).tolist()
    out = {}
    for name, (plen, _new) in sizes["requests"].items():
        body = rng.randint(0, vocab, size=plen).tolist()
        if name in ("r2", "r4"):
            body[:len(shared)] = shared
        out[name] = body
    return out


def _wave(handle, sizes: dict, seed: int) -> dict:
    """One wave of streaming requests: r0-r2 at once; r3 and r4 (the
    prefix sharer) once r2's prompt is in the cache; r5 and r6 while r0
    is mid-decode."""
    prompts = _prompts(sizes, seed)
    got = {name: [] for name in prompts}
    errors, threads = [], {}
    r2_started, r0_midway = threading.Event(), threading.Event()

    def stream(name):
        try:
            payload = {"tokens": prompts[name],
                       "max_new_tokens": sizes["requests"][name][1]}
            if name in ("r1", "r5"):       # the sampler's other branch
                payload.update(temperature=0.8, top_p=0.95, seed=seed)
            for tok in handle.options(stream=True).remote(payload):
                got[name].append(tok)
                if name == "r2":
                    r2_started.set()
                if name == "r0" and len(got[name]) >= 4:
                    r0_midway.set()
        except BaseException as e:  # noqa: BLE001 — re-raised by _wave
            errors.append((name, e))
            r2_started.set()
            r0_midway.set()

    def start(*names):
        for name in names:
            threads[name] = threading.Thread(target=stream, args=(name,),
                                             name=f"stream-{name}")
            threads[name].start()

    start("r0", "r1", "r2")
    check(r2_started.wait(600), "r2 produced no token in 600 s")
    start("r3", "r4")
    check(r0_midway.wait(600), "r0 produced no token in 600 s")
    start("r5", "r6")
    for name, t in threads.items():
        t.join(600)
        check(not t.is_alive(), f"stream {name} did not finish in 600 s")
    if errors:
        raise errors[0][1]
    return got


def phase_serve(sizes: dict, rehearsal: bool) -> None:
    import jax.numpy as jnp

    import ray_tpu.serve as serve
    from ray_tpu.inference.serve_gpt import GPTDeployment

    model = sizes.get("serve_model", sizes["model"])
    model_config = dict(model["kwargs"],
                        dtype=getattr(jnp, model["kwargs"]["dtype"]))
    t0 = time.monotonic()
    handle = serve.run(GPTDeployment.bind(model=model["preset"],
                                          model_config=model_config),
                       name="gpt")
    deploy_s = time.monotonic() - t0

    def summary():
        return handle.telemetry_summary.remote().result(timeout_s=120)

    t0 = time.monotonic()
    _wave(handle, sizes, seed=1)                     # warms every shape
    warm_s, warm = time.monotonic() - t0, summary()
    t0 = time.monotonic()
    got = _wave(handle, sizes, seed=2)
    wave_s, after = time.monotonic() - t0, summary()

    device, stats = after["device"], after["stats"]
    recompiles = (after["jax_compiles"]["compiles"]
                  - warm["jax_compiles"]["compiles"])
    decode_steps = after.get("decode_steps", 0) - warm.get("decode_steps", 0)
    decode_tokens = (after.get("decode_tokens", 0)
                     - warm.get("decode_tokens", 0))
    emit("serve", device={k: device[k] for k in ("platform", "kind",
                                                 "count")},
         decode_impl=stats["decode_impl"],
         decode_write_impl=stats["decode_write_impl"],
         kv_dtype=stats["kv_dtype"],
         requests=len(got),
         tokens_returned={k: len(v) for k, v in got.items()},
         engine_compiles=stats["compiles"],
         engine_compiles_after_warmup={
             k: v - warm["stats"]["compiles"][k]
             for k, v in stats["compiles"].items()},
         recompiles_after_warmup=recompiles,
         compile=after["jax_compiles"],
         prefix={k: stats["prefix"][k] - warm["stats"]["prefix"][k]
                 for k in ("hit_pages", "requests_hit")},
         decode_tokens_per_step=round(decode_tokens / max(decode_steps, 1),
                                      2),
         peak_hbm=device["memory"] and {
             k: device["memory"].get(k) for k in
             ("peak_bytes_in_use", "bytes_in_use", "bytes_limit")},
         observed={"deploy_s": round(deploy_s, 2),
                   "warm_wave_s": round(warm_s, 2),
                   "second_wave_s": round(wave_s, 2)})

    check(device["platform"] == "tpu" or rehearsal,
          f"replica is on {device['platform']}, not on a TPU")
    # (virtual CPU devices are not chips: nothing confines them)
    check(device["count"] == 1 or rehearsal,
          f"replica owns {device['count']} chips, asked for one")
    vocab = model["kwargs"]["vocab_size"]
    for name, (_plen, new) in sizes["requests"].items():
        check(len(got[name]) == new,
              f"{name}: {len(got[name])} of {new} tokens returned")
        check(all(isinstance(t, int) and 0 <= t < vocab for t in got[name]),
              f"{name}: token outside the vocabulary: {got[name]}")
    check(len(got) >= 6, f"{len(got)} requests served")
    check(recompiles == 0 and not any(
        v - warm["stats"]["compiles"][k]
        for k, v in stats["compiles"].items()),
        f"{recompiles} compiles after warm-up")
    buckets_hit = stats["compiles"]["prefill"]
    check(buckets_hit >= 3, f"{buckets_hit} prefill buckets compiled")
    check(stats["prefix"]["hit_pages"]
          - warm["stats"]["prefix"]["hit_pages"] >= 2,
          f"no >=2-page prefix hit: {stats['prefix']}")
    check(decode_tokens > decode_steps,
          "no decode step carried more than one sequence")
    check(stats["decode_impl"] == "pallas" or rehearsal,
          f"decode dispatched to {stats['decode_impl']}")
    check(stats["decode_write_impl"] == "pallas" or rehearsal,
          f"decode's write dispatched to {stats['decode_write_impl']}")


def main() -> int:
    flag = next((a for a in sys.argv[1:]
                 if a.startswith("--rehearse-on-cpu")), None)
    rehearsal = flag is not None
    if rehearsal:
        virtual = int(flag.partition("=")[2] or 1)
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={virtual}")

    from ray_tpu.accelerators.tpu import detect_num_tpus
    chips = virtual if rehearsal else detect_num_tpus()
    if chips < 1:
        print("chip_smoke: no TPU chip on this host (no /dev/accel* or "
              "/dev/vfio/<n> device node, or JAX_PLATFORMS asks for "
              f"{os.environ.get('JAX_PLATFORMS')!r})", file=sys.stderr)
        return 2

    # built from what git would commit: the native object store is
    # git-ignored, so start without it and let the runtime rebuild it
    # from src/shmstore/shmstore.cc.  That takes g++ and a checkout that
    # can be written to and loaded from; a host without them runs every
    # phase below on the Python file store, the runtime's other complete
    # backend, and the init line says which one it was and why.
    native = os.path.join(HERE, "ray_tpu", "_native", "libshmstore.so")
    if os.path.exists(native):
        os.remove(native)

    import ray_tpu
    from ray_tpu._private.worker import global_worker
    sizes = TOY if rehearsal else REAL
    # no num_tpus: autodetection finds the chips (a rehearsal has none
    # to find and names its virtual ones)
    ray_tpu.init(num_tpus=chips if rehearsal else None)
    try:
        store = global_worker().store
        emit("init", chips=chips, rehearsal=rehearsal,
             cluster_tpus=ray_tpu.cluster_resources().get("TPU", 0),
             object_store=store.backend,
             native_store_rebuilt=os.path.exists(native),
             native_store_error=store.native_error)
        check(ray_tpu.cluster_resources().get("TPU", 0) == chips,
              "init() did not register the host's chips")

        train = phase_train(sizes, chips, rehearsal)
        phase_kernels(sizes, chips, rehearsal)
        phase_serve(sizes, rehearsal)
    finally:
        import ray_tpu.serve as serve
        if ray_tpu.is_initialized():
            serve.shutdown()
            ray_tpu.shutdown()

    # the parent imported jax (GPTDeployment's module does) but must
    # never have initialised a backend: that would have taken the chip
    from jax._src import xla_bridge
    check(not xla_bridge._backends,
          f"the parent initialised jax backends {list(xla_bridge._backends)}")
    if rehearsal:
        print("chip_smoke: rehearsal on the CPU complete — not a chip run, "
              "no result", file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": train["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
