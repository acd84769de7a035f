"""Flagship decoder-only transformer (GPT family), TPU-first.

Capability parity target: the models the reference fine-tunes through HF
Transformers (GPT-2 in ``release/release_tests.yaml`` gptj/gpt2 suites) —
but built natively for XLA: stacked layer params swept by ``lax.scan``
(O(1) compile in depth), bf16 matmuls with f32 stats, RoPE, optional
ring attention over an ``sp`` axis, optional MoE FFNs sharded over ``ep``,
and logical-axis annotations so one model runs under any
dp/fsdp/tp/sp/ep mesh (see ``ray_tpu.parallel.sharding``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.parallel import sharding as shd
from ray_tpu.parallel.ring_attention import local_attention, ring_attention


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304          # GPT-2 vocab padded to 128 multiple
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_head: Optional[int] = None
    d_ff: Optional[int] = None       # default 4*d_model (8/3 for swiglu)
    max_seq: int = 1024
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    act: str = "swiglu"              # swiglu | gelu
    pos: str = "rope"                # rope | learned
    rope_theta: float = 10000.0
    n_experts: int = 0               # >0: every FFN is MoE
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.5
    dtype: Any = jnp.bfloat16
    remat: bool = False
    tie_embeddings: bool = True
    # biases on every projection + norm (GPT-2 exact-architecture mode,
    # used by the HF weight-porting path in ``train.huggingface``)
    use_bias: bool = False
    # unroll the layer loop instead of lax.scan: scan's per-iteration
    # residual stashing (dynamic-update-slice into [L, ...] buffers)
    # costs ~20% of a training step on TPU; unrolling trades compile
    # time (O(L)) for free scheduling.  scan stays the default for deep
    # models / fast iteration.
    unroll_layers: bool = False
    # cross-entropy chunk rows (0 = one chunk over the whole batch;
    # -1 = one chunk *without* rematerialization: backward reuses the
    # saved [N, V] f32 logits instead of recomputing them — one fewer
    # full vocab matmul per step, at the cost of keeping the logits
    # resident between forward and backward).  Smaller positive chunks
    # bound the [chunk, V] f32 logits transient.
    ce_chunk: int = 4096

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def ff_dim(self) -> int:
        if self.d_ff:
            return self.d_ff
        return (int(8 * self.d_model / 3 / 128) * 128 or 128) \
            if self.act == "swiglu" else 4 * self.d_model

    # canonical size presets, parity with HF gpt2 family
    @classmethod
    def gpt2(cls, **kw):
        return cls(d_model=768, n_layers=12, n_heads=12, **kw)

    @classmethod
    def gpt2_medium(cls, **kw):
        return cls(d_model=1024, n_layers=24, n_heads=16, **kw)

    @classmethod
    def gpt2_large(cls, **kw):
        return cls(d_model=1280, n_layers=36, n_heads=20, **kw)

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq", 128)
        return cls(d_model=64, n_layers=2, n_heads=4, **kw)


def init_params(cfg: GPTConfig, key) -> Dict[str, Any]:
    keys = iter(jax.random.split(key, 24))
    d, H, hd, f, L = (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.ff_dim,
                      cfg.n_layers)
    dt = cfg.dtype

    def norm_init(k, shape, scale):
        return (jax.random.normal(k, shape) * scale).astype(dt)

    params: Dict[str, Any] = {
        "embed": norm_init(next(keys), (cfg.vocab_size, d), 0.02),
    }
    if cfg.pos == "learned":
        params["pos_embed"] = norm_init(next(keys), (cfg.max_seq, d), 0.02)
    layer = {
        "ln1": jnp.ones((L, d), dt),
        "wq": norm_init(next(keys), (L, d, H, hd), d ** -0.5),
        "wk": norm_init(next(keys), (L, d, H, hd), d ** -0.5),
        "wv": norm_init(next(keys), (L, d, H, hd), d ** -0.5),
        "wo": norm_init(next(keys), (L, H, hd, d),
                        (H * hd) ** -0.5 / (2 * L) ** 0.5),
        "ln2": jnp.ones((L, d), dt),
    }
    if cfg.n_experts > 0:
        E = cfg.n_experts
        layer["moe_wg"] = norm_init(next(keys), (L, d, E), d ** -0.5)
        layer["moe_w1"] = norm_init(next(keys), (L, E, d, f), d ** -0.5)
        if cfg.act == "swiglu":
            layer["moe_w3"] = norm_init(next(keys), (L, E, d, f), d ** -0.5)
        layer["moe_w2"] = norm_init(next(keys), (L, E, f, d),
                                    f ** -0.5 / (2 * L) ** 0.5)
    else:
        layer["w1"] = norm_init(next(keys), (L, d, f), d ** -0.5)
        if cfg.act == "swiglu":
            layer["w3"] = norm_init(next(keys), (L, d, f), d ** -0.5)
        layer["w2"] = norm_init(next(keys), (L, f, d),
                                f ** -0.5 / (2 * L) ** 0.5)
    if cfg.use_bias:
        layer["ln1_b"] = jnp.zeros((L, d), dt)
        layer["ln2_b"] = jnp.zeros((L, d), dt)
        layer["bq"] = jnp.zeros((L, H, hd), dt)
        layer["bk"] = jnp.zeros((L, H, hd), dt)
        layer["bv"] = jnp.zeros((L, H, hd), dt)
        layer["bo"] = jnp.zeros((L, d), dt)
        if cfg.n_experts == 0:
            layer["b1"] = jnp.zeros((L, f), dt)
            if cfg.act == "swiglu":
                layer["b3"] = jnp.zeros((L, f), dt)
            layer["b2"] = jnp.zeros((L, d), dt)
    params["layers"] = layer
    params["ln_f"] = jnp.ones((d,), dt)
    if cfg.use_bias:
        params["ln_f_b"] = jnp.zeros((d,), dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = norm_init(next(keys), (d, cfg.vocab_size), 0.02)
    return params


def param_logical_axes(cfg: GPTConfig) -> Dict[str, Any]:
    """Logical-axis tree matching ``init_params`` output (leading L = None)."""
    axes: Dict[str, Any] = {
        "embed": ("vocab", "embed_fsdp"),
    }
    if cfg.pos == "learned":
        axes["pos_embed"] = (None, "embed_fsdp")
    layer = {
        "ln1": (None, None),
        "wq": (None, "embed_fsdp", "heads", None),
        "wk": (None, "embed_fsdp", "heads", None),
        "wv": (None, "embed_fsdp", "heads", None),
        "wo": (None, "heads", None, "embed_fsdp"),
        "ln2": (None, None),
    }
    if cfg.n_experts > 0:
        layer["moe_wg"] = (None, None, None)
        layer["moe_w1"] = (None, "experts", "embed_fsdp", "expert_mlp")
        if cfg.act == "swiglu":
            layer["moe_w3"] = (None, "experts", "embed_fsdp", "expert_mlp")
        layer["moe_w2"] = (None, "experts", "expert_mlp", "embed_fsdp")
    else:
        layer["w1"] = (None, "embed_fsdp", "mlp")
        if cfg.act == "swiglu":
            layer["w3"] = (None, "embed_fsdp", "mlp")
        layer["w2"] = (None, "mlp", "embed_fsdp")
    if cfg.use_bias:
        layer["ln1_b"] = (None, None)
        layer["ln2_b"] = (None, None)
        layer["bq"] = (None, "heads", None)
        layer["bk"] = (None, "heads", None)
        layer["bv"] = (None, "heads", None)
        layer["bo"] = (None, None)
        if cfg.n_experts == 0:
            layer["b1"] = (None, "mlp")
            if cfg.act == "swiglu":
                layer["b3"] = (None, "mlp")
            layer["b2"] = (None, None)
    axes["layers"] = layer
    axes["ln_f"] = (None,)
    if cfg.use_bias:
        axes["ln_f_b"] = (None,)
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed_fsdp", "vocab")
    return axes


# Which loss head runs follows ``GPTConfig.ce_chunk``: :func:`ce_path`
# names it, over the one gate ray_tpu.ops.flash_ce.uses_flash_ce.


def norm_eps(cfg: "GPTConfig") -> float:
    """Norm epsilon: HF GPT-2 (exact-architecture mode) uses 1e-5."""
    return 1e-5 if cfg.use_bias else 1e-6


def _norm(x, scale, kind: str, bias=None, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    if kind == "rmsnorm":
        x32 = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    else:
        mu = jnp.mean(x32, -1, keepdims=True)
        var = jnp.mean((x32 - mu) ** 2, -1, keepdims=True)
        x32 = (x32 - mu) * lax.rsqrt(var + eps)
    x32 = x32 * scale.astype(jnp.float32)
    if bias is not None:
        x32 = x32 + bias.astype(jnp.float32)
    return x32.astype(x.dtype)


def _rope(x, positions, theta: float):
    """x: [B, S, H, D]; rotate pairs along D.

    Angles/cos/sin in f32 (position precision), the rotation itself in
    the activation dtype — the f32 q/k intermediates otherwise double
    HBM traffic for every layer.  Delegates to
    ``ray_tpu.ops.attention.rope_rotate`` so the XLA-side rotation and
    the in-kernel fused one (``make_flash_attention_fn(rope_theta=...)``)
    share one formulation."""
    from ray_tpu.ops.attention import rope_rotate
    return rope_rotate(x, positions, theta)


def lora_delta(lora, name: str, x):
    """Low-rank delta ``scale * (x @ A) @ B`` for one target matmul,
    or None when the adapter tree carries no factors for ``name``.

    Two modes, dispatched on the presence of ``ids``:

    - **single adapter** (training): ``<name>_a`` [in, r] /
      ``<name>_b`` [r, out] shared across the batch, scalar ``scale``
      — the trainable-adapter path in ``models/training.py``.
    - **banked** (serving): factors carry a leading bank axis
      ([N, in, r] / [N, r, out], ``scale`` [N]) and ``ids`` [B] picks
      one bank slot per batch row — the grouped matmul that lets
      co-batched tenants share a single decode tick.  Slot 0 is
      all-zeros, so base traffic pays two skinny einsums against zero
      factors and lands on the exact base output.

    Rank-space accumulation runs in the activation dtype (matching the
    base matmuls); the f32 per-slot scale is applied last."""
    a = lora.get(name + "_a")
    if a is None:
        return None
    b = lora[name + "_b"]
    scale = jnp.asarray(lora["scale"], jnp.float32)
    ids = lora.get("ids")
    if ids is None:
        t = jnp.einsum("bsi,ir->bsr", x, a.astype(x.dtype))
        d = jnp.einsum("bsr,ro->bso", t, b.astype(x.dtype))
        return (d.astype(jnp.float32) * scale).astype(x.dtype)
    av = jnp.take(a, ids, axis=0)
    bv = jnp.take(b, ids, axis=0)
    s = jnp.take(scale, ids, axis=0)
    t = jnp.einsum("bsi,bir->bsr", x, av.astype(x.dtype))
    d = jnp.einsum("bsr,bro->bso", t, bv.astype(x.dtype))
    return (d.astype(jnp.float32) * s[:, None, None]).astype(x.dtype)


def _dense_ffn(lp, x, cfg: GPTConfig, lora=None):
    h = jnp.einsum("bsd,df->bsf", x, lp["w1"])
    if lora is not None:
        d1 = lora_delta(lora, "w1", x)
        if d1 is not None:
            h = h + d1
    if "b1" in lp:
        h = h + lp["b1"]
    if cfg.act == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, lp["w3"])
        if lora is not None:
            d3 = lora_delta(lora, "w3", x)
            if d3 is not None:
                g = g + d3
        if "b3" in lp:
            g = g + lp["b3"]
        h = jax.nn.silu(h) * g
    else:
        h = jax.nn.gelu(h)
    h = shd.constrain(h, ("batch", "seq", "mlp"))
    out = jnp.einsum("bsf,fd->bsd", h, lp["w2"])
    if lora is not None:
        d2 = lora_delta(lora, "w2", h)
        if d2 is not None:
            out = out + d2
    if "b2" in lp:
        out = out + lp["b2"]
    return out


def _moe_ffn(lp, x, cfg: GPTConfig):
    from ray_tpu.parallel.moe import MoEParams, moe_layer
    B, S, d = x.shape
    flat = x.reshape(B * S, d)
    if cfg.act == "swiglu":
        # fold w3 into a silu-gated expert FFN by concatenation
        w1 = jnp.concatenate([lp["moe_w1"], lp["moe_w3"]], axis=-1)

        def ffn(w1w3, w2, tokens):
            h = jnp.einsum("ecd,edh->ech", tokens, w1w3)
            a, b = jnp.split(h, 2, axis=-1)
            return jnp.einsum("ech,ehd->ecd", jax.nn.silu(a) * b, w2)
        out, aux = moe_layer(
            MoEParams(lp["moe_wg"], w1, lp["moe_w2"]), flat,
            top_k=cfg.moe_top_k,
            capacity_factor=cfg.moe_capacity_factor, expert_ffn=ffn)
    else:
        out, aux = moe_layer(
            MoEParams(lp["moe_wg"], lp["moe_w1"], lp["moe_w2"]), flat,
            top_k=cfg.moe_top_k, capacity_factor=cfg.moe_capacity_factor)
    return out.reshape(B, S, d), aux


def layer_apply(lp, x, cfg: GPTConfig, *, positions, attn_fn, mesh=None,
                cache=None, fuse_norm=None, lora=None):
    """One transformer block: ``(layer params, hidden [B,S,d]) -> (hidden,
    moe aux)``.  Shared by the stacked ``lax.scan`` in ``forward_hidden``,
    the per-stage scan in the pipeline-parallel trainer
    (``models/training.py`` build_gpt_train_pp) and the inference
    engine's prefill/decode steps (``ray_tpu.inference.engine``).

    ``positions`` is [S] (shared across the batch) or [B, S]
    (per-sequence absolute positions — the decode path, see
    ``rope_rotate``).  ``cache`` threads per-layer KV-cache state to the
    attention hook: when not None, ``attn_fn`` is called as
    ``attn_fn(q, k, v, cache=cache)`` with the *rotated* k (cache
    entries store post-RoPE keys, so decode never re-rotates history)
    and must return ``(attn_out, new_cache)``; the block then returns
    ``(hidden, aux, new_cache)`` instead of the 2-tuple.

    The out-proj epilogue (out-proj matmul + residual add + pre-FFN
    rmsnorm) goes through ``ray_tpu.ops.fused_norm`` where its dispatch
    gate (``fused_norm.out_proj_norm_plan``) engages: one Pallas kernel
    in a call nobody differentiates (a prefill), XLA's einsum + add +
    norm and their gradients in a differentiated one (a train step),
    which is what the branch below the gate writes.  The gate declines
    layernorm, biases, sharded meshes and the S=1 decode step;
    ``fuse_norm=False`` declines it too (the tests' pin; ``None`` is
    on).

    ``lora``: per-layer low-rank adapter factors (``lora_delta``
    layout, single or banked) added to the qkv/out-proj/MLP matmul
    outputs before biases and RoPE — so the result equals running the
    merged weights ``W + scale * A @ B`` through the base block.  An
    active ``lora`` declines the fused out-proj epilogue (the kernel
    folds the wo matmul, which would skip the wo delta)."""
    from ray_tpu.ops import fused_norm as fnorm
    constrain = functools.partial(shd.constrain, mesh=mesh)
    eps = norm_eps(cfg)
    h2 = None
    with jax.named_scope("gpt/attn"):
        h = _norm(x, lp["ln1"], cfg.norm, bias=lp.get("ln1_b"), eps=eps)
        # (a fused [d, 3Hk] qkv projection was A/B'd on the v5e bench
        # and lost ~5%: the runtime weight concat serializes against
        # the matmul and XLA already pipelines the three projections)
        q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
        if lora is not None:
            dq = lora_delta(lora, "wq", h)
            dk = lora_delta(lora, "wk", h)
            dv = lora_delta(lora, "wv", h)
            if dq is not None:
                q = q + dq.reshape(q.shape)
            if dk is not None:
                k = k + dk.reshape(k.shape)
            if dv is not None:
                v = v + dv.reshape(v.shape)
        if "bq" in lp:
            q = q + lp["bq"]
            k = k + lp["bk"]
            v = v + lp["bv"]
        fused_rope = (cfg.pos == "rope"
                      and getattr(attn_fn, "fused_rope", False))
        if cfg.pos == "rope" and not fused_rope:
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
        q = constrain(q, ("batch", "seq", "heads", None))
        k = constrain(k, ("batch", "seq", "heads", None))
        v = constrain(v, ("batch", "seq", "heads", None))
        if cache is not None:
            if fused_rope:
                raise ValueError(
                    "cache= requires an attn_fn without fused RoPE: "
                    "cache entries must store post-RoPE keys, but a "
                    "fused_rope attn_fn receives them un-rotated")
            attn, cache = attn_fn(q, k, v, cache=cache)
        elif fused_rope:
            attn = attn_fn(q, k, v, positions=positions)
        else:
            attn = attn_fn(q, k, v)
        attn = constrain(attn, ("batch", "seq", "heads", None))
        B, S, Hn, hd = attn.shape
        d = x.shape[-1]
        plan = None if lora is not None else fnorm.out_proj_norm_plan(
            B * S, Hn * hd, d, norm=cfg.norm,
            has_bias=("bo" in lp) or ("ln2_b" in lp),
            n_devices=getattr(mesh, "size", 1) if mesh is not None else 1,
            seq=S, enabled=fuse_norm)
        if plan:
            # out-proj + residual add + pre-FFN norm as one op: a
            # kernel that writes the residual stream once where no
            # gradient is taken, the branch below where one is
            x, h2 = fnorm.matmul_residual_norm(attn, lp["wo"], x,
                                               lp["ln2"], eps=eps)
        else:
            proj = jnp.einsum("bshk,hkd->bsd", attn, lp["wo"])
            if lora is not None:
                do = lora_delta(lora, "wo", attn.reshape(B, S, Hn * hd))
                if do is not None:
                    proj = proj + do
            if "bo" in lp:
                proj = proj + lp["bo"]
            x = x + proj
    with jax.named_scope("gpt/ffn"):
        if h2 is None:
            h2 = _norm(x, lp["ln2"], cfg.norm, bias=lp.get("ln2_b"),
                       eps=eps)
        if cfg.n_experts > 0:
            if lora is not None:
                raise ValueError("LoRA adapters are dense-FFN only "
                                 "(see adapters.lora.effective_targets)")
            ffn_out, aux = _moe_ffn(lp, h2, cfg)
        else:
            ffn_out, aux = _dense_ffn(lp, h2, cfg, lora=lora), jnp.float32(0)
        x = x + ffn_out
        x = constrain(x, ("batch", "seq", None))
    if cache is not None:
        return x, aux, cache
    return x, aux


def _with_segments(attn_fn, segment_ids):
    """Close ``segment_ids`` over an attention hook, preserving the
    ``fused_rope`` marker ``layer_apply`` dispatches on.  Every
    in-tree hook (``local_attention``, ``flash_attention`` and the
    ``make_flash_attention_fn`` wrappers) accepts the kwarg; the
    Pallas schedules decline it with the XLA segment formulation."""
    fused = getattr(attn_fn, "fused_rope", False)
    fn = functools.partial(attn_fn, segment_ids=segment_ids)
    fn.fused_rope = fused
    return fn


def embed_tokens(params: Dict[str, Any], tokens, cfg: GPTConfig, *,
                 mesh=None, positions=None):
    """tokens [B, S] -> hidden [B, S, d], sharded (batch, seq).

    The table is (vocab:tp, d:fsdp)-sharded for the tied head matmul; a
    gather across sharded dims makes SPMD replicate it *involuntarily*
    ("full rematerialization" warning), and any surviving shard on d
    clashes with the batch/seq sharding of the output.  ZeRO-3 semantics:
    all-gather the table once, gather, let the output land directly on
    its (batch, seq) sharding; the table grad reduce-scatters back.
    """
    constrain = functools.partial(shd.constrain, mesh=mesh)
    S = tokens.shape[1]
    with jax.named_scope("gpt/embed"):
        table = constrain(params["embed"].astype(cfg.dtype),
                          (None, None))
        x = constrain(table[tokens], ("batch", "seq", None))
        if cfg.pos == "learned":
            pos_table = params["pos_embed"].astype(cfg.dtype)
            if positions is not None and getattr(positions, "ndim", 1) == 2:
                # packed batches: positions restart per document, so
                # the learned table is gathered per row, not sliced
                x = x + pos_table[positions]
            else:
                x = x + pos_table[None, :S]
        return constrain(x, ("batch", "seq", None))


def loss_from_hidden(params, x, targets, cfg: GPTConfig, *, mesh=None,
                     ce_mode: Optional[str] = None, norm_scale=None):
    """(final *normed* hidden [B,S,d], targets [B,S]) -> mean NLL
    (CE glue shared by the dense and pipeline-parallel trainers).

    ``ce_mode`` pins the loss head for tests and A/B drivers (default:
    what ``cfg.ce_chunk`` says, ``flash_ce.uses_flash_ce``); ``mesh``
    gates the Pallas paths to single-device meshes (a ``pallas_call``
    has no SPMD rule, so on a sharded mesh the XLA formulations run
    instead — lifting that with a shard_map wrapper is an open item).

    ``norm_scale``: when given, ``x`` is the RAW residual stream (the
    final hidden *before* ``ln_f``) and the norm fuses into the
    flash-CE vocab-matmul prologue (``flash_ce.flash_ce_norm_sum``) —
    the normed tensor never materializes and the norm-scale grad comes
    back through per-row-block partials.  If the fused gate declines,
    the norm runs here in XLA and the regular CE dispatch follows (the
    loud end of the fallback chain — ``ce/norm_xla`` in timelines)."""
    B, S, d = x.shape
    recipe = _ce_recipe(cfg, mesh, ce_mode)
    with jax.named_scope("gpt/ce"):
        if norm_scale is not None:
            from ray_tpu.ops import flash_ce
            # enabled=True: passing norm_scale IS the caller's knob
            # decision — only the kernel-capability half re-gates here
            if flash_ce.uses_flash_ce_norm(
                    B * S, d, cfg.vocab_size, norm=cfg.norm,
                    has_bias=cfg.use_bias, enabled=True, **recipe):
                s, n = flash_ce.flash_ce_norm_sum(
                    x.reshape(B * S, d), lm_head(params, cfg),
                    targets.reshape(B * S), norm_scale,
                    eps=norm_eps(cfg))
                return s / jnp.maximum(n, 1.0)
            x = _norm(x, norm_scale, cfg.norm,
                      bias=params.get("ln_f_b"), eps=norm_eps(cfg))
        s, n = _chunked_ce(x.reshape(B * S, d), lm_head(params, cfg),
                           targets.reshape(B * S), **recipe)
        return s / jnp.maximum(n, 1.0)


def _ce_recipe(cfg, mesh, ce_mode) -> Dict[str, Any]:
    """What ``flash_ce.uses_flash_ce`` is asked beside the shapes."""
    return dict(ce_chunk=getattr(cfg, "ce_chunk", _CE_CHUNK),
                n_devices=getattr(mesh, "size", 1) if mesh is not None
                else 1,
                mode=ce_mode)


def forward_hidden(params: Dict[str, Any], tokens, cfg: GPTConfig, *,
                   attn_fn: Optional[Callable] = None, mesh=None,
                   fuse_norm: Optional[bool] = None,
                   final_norm: bool = True,
                   segment_ids=None, positions=None, lora=None):
    """tokens [B, S] int32 -> (final hidden [B, S, d], moe aux loss).

    ``attn_fn(q, k, v) -> out`` defaults to causal local attention; pass a
    ring-attention fn (``make_ring_attention_fn``) for sp>1 meshes.

    ``fuse_norm`` pins the fused norm epilogues (see ``layer_apply``);
    ``final_norm=False`` skips the closing ``ln_f`` and returns the raw
    residual stream — for ``loss_fn``'s fused-CE path, which computes
    that norm inside the vocab-matmul kernel instead.

    ``segment_ids``/``positions`` [B, S] carry a sample-packed batch
    (``ray_tpu.data.SamplePacker``): attention masks block-diagonally
    per segment and RoPE/learned positions restart at every document
    start, so the packed forward equals the per-document unpacked one.

    ``lora``: a single adapter's stacked factors ([L, in, r]/[L, r, out]
    per target, + scalar ``scale``) applied to every adapted matmul —
    the trainable-adapter forward used by
    ``models/training.py`` when the base params are frozen.
    """
    B, S = tokens.shape
    if attn_fn is None:
        attn_fn = functools.partial(local_attention, causal=True)
    if segment_ids is not None:
        if positions is None:
            # global arange positions across packed documents would
            # silently break the packed==per-doc parity (RoPE/learned
            # positions must restart at every document start)
            raise ValueError(
                "segment_ids without positions: a packed batch needs "
                "its per-document positions (SamplePacker emits both)")
        attn_fn = _with_segments(attn_fn, segment_ids)
    constrain = functools.partial(shd.constrain, mesh=mesh)
    x = embed_tokens(params, tokens, cfg, mesh=mesh,
                     positions=positions)
    if positions is None:
        positions = jnp.arange(S)

    # the adapter's stacked factors scan alongside params["layers"]
    # (both carry leading L); the scalar scale broadcasts unscanned
    lora_scan = None
    if lora is not None:
        lora_scan = {k: v for k, v in lora.items() if k != "scale"}

    def layer_body(x, lp_la):
        lp, la = lp_la
        layer_lora = None if la is None else {**la, "scale": lora["scale"]}
        return layer_apply(lp, x, cfg, positions=positions,
                           attn_fn=attn_fn, mesh=mesh,
                           fuse_norm=fuse_norm, lora=layer_lora)

    if cfg.remat:
        layer_body = jax.checkpoint(layer_body)
    if cfg.unroll_layers:
        aux_total = jnp.float32(0)
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            la = None if lora_scan is None else \
                jax.tree.map(lambda a: a[i], lora_scan)
            x, aux = layer_body(x, (lp, la))
            aux_total = aux_total + aux
    else:
        x, auxes = lax.scan(layer_body, x,
                            (params["layers"], lora_scan))
        aux_total = jnp.sum(auxes)
    if final_norm:
        x = _norm(x, params["ln_f"], cfg.norm,
                  bias=params.get("ln_f_b"), eps=norm_eps(cfg))
    return x, aux_total


def lm_head(params, cfg: GPTConfig):
    return (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(cfg.dtype)


def forward(params: Dict[str, Any], tokens, cfg: GPTConfig, *,
            attn_fn: Optional[Callable] = None, mesh=None,
            fuse_norm: Optional[bool] = None,
            segment_ids=None, positions=None, lora=None):
    """tokens [B, S] int32 -> logits [B, S, V] (f32)."""
    constrain = functools.partial(shd.constrain, mesh=mesh)
    x, aux = forward_hidden(params, tokens, cfg, attn_fn=attn_fn,
                            mesh=mesh, fuse_norm=fuse_norm,
                            segment_ids=segment_ids,
                            positions=positions, lora=lora)
    logits = jnp.einsum("bsd,dv->bsv", x, lm_head(params, cfg))
    logits = constrain(logits, ("batch", "seq", "vocab"))
    return logits.astype(jnp.float32), aux


# Cross-entropy over a 50k vocab dominates activation memory if the
# [B, S, V] logits (and log-softmax residuals) are materialized and saved.
# Chunk tokens and rematerialize: backward recomputes each chunk's logits
# from (x, head) — one extra matmul per chunk for O(chunk * V) transient
# memory instead of O(B * S * V) resident.
_CE_CHUNK = 4096


def ce_path(N: int, d: int, V: int, *, ce_chunk: int = _CE_CHUNK,
            n_devices: int = 1, mode: Optional[str] = None) -> str:
    """The loss head a step of this shape, recipe and mesh runs, by
    name — what :func:`_chunked_ce` switches on and the step telemetry
    reports and prices its FLOPs from:

    - ``flash``: flash-CE (``ops/flash_ce.py``), where its gate
      ``flash_ce.uses_flash_ce`` passes: the recipe recomputes anyway
      (``ce_chunk >= 0``) on one device; the [N, V] logits exist only
      as VMEM tiles in both passes (``mode`` pins the gate, for tests
      and A/B drivers).
    - ``xla_saved`` (``ce_chunk < 0``): the f32 logits are kept for
      the backward — three vocabulary matmuls, the fastest head where
      they fit (``PERF.md`` section 6, PR 49).
    - ``xla_chunked`` (``ce_chunk >= 0`` and the gate declined): row
      chunks under ``jax.checkpoint``, four (``0``: one chunk).
    """
    from ray_tpu.ops import flash_ce
    if flash_ce.uses_flash_ce(N, d, V, ce_chunk=ce_chunk,
                              n_devices=n_devices, mode=mode):
        return "flash"
    return "xla_saved" if ce_chunk < 0 else "xla_chunked"


def _chunked_ce(x, head, targets, *, ce_chunk: int = _CE_CHUNK,
                n_devices: int = 1, mode: Optional[str] = None):
    """x [N, d] (bf16 ok), head [d, V], targets [N] -> (sum_nll, n_valid)
    through the loss head :func:`ce_path` names.

    The XLA heads' chunks are a *python* loop (static N): a lax.scan
    here stashes its residuals with dynamic-update-slice, which
    profiles slower than the unrolled chunks whose remat boundaries
    XLA schedules freely.
    """
    N, d = x.shape
    path = ce_path(N, d, head.shape[1], ce_chunk=ce_chunk,
                   n_devices=n_devices, mode=mode)
    if path == "flash":
        from ray_tpu.ops import flash_ce
        return flash_ce.flash_ce_sum(x, head.astype(x.dtype), targets)
    remat = path == "xla_chunked"
    chunk = ce_chunk if ce_chunk > 0 else N

    def chunk_loss(xc, tc):
        logits = jnp.einsum("nd,dv->nv", xc, head,
                            preferred_element_type=jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        true = jnp.take_along_axis(
            logits, jnp.maximum(tc, 0)[:, None], axis=-1)[:, 0]
        mask = (tc >= 0).astype(jnp.float32)
        return jnp.sum((lse - true) * mask), jnp.sum(mask)

    if remat:
        chunk_loss = jax.checkpoint(chunk_loss)

    if N <= chunk:
        return chunk_loss(x, targets)
    s, n = jnp.float32(0), jnp.float32(0)
    for i in range(0, N, chunk):
        cs, cn = chunk_loss(x[i:i + chunk], targets[i:i + chunk])
        s, n = s + cs, n + cn
    return s, n


def loss_fn(params, batch, cfg: GPTConfig, *, attn_fn=None, mesh=None,
            aux_weight: float = 0.01, ce_mode: Optional[str] = None,
            fuse_norm: Optional[bool] = None, lora=None):
    """batch: dict(tokens [B,S], targets [B,S]); returns scalar loss.

    ``fuse_norm=False`` pins the fused norm epilogues off (``None`` is
    on): the per-layer out-proj epilogue in ``layer_apply`` (under a
    gradient XLA's formulation either way), plus — when the
    flash-CE-with-norm gate passes — skipping the XLA ``ln_f`` entirely
    and folding it into the vocab-matmul kernel's prologue.  That gate
    declines where the recipe keeps its logits (``cfg.ce_chunk < 0``):
    the loss head is then XLA's, and so is ``ln_f``.

    Sample-packed batches additionally carry ``segment_ids`` and
    ``positions`` [B, S] (``ray_tpu.data``): attention masks
    block-diagonally and positions restart per document; the packer's
    ``targets`` already mask document boundaries with ``-1``."""
    from ray_tpu.ops import flash_ce
    B, S = batch["tokens"].shape
    ce_norm = flash_ce.uses_flash_ce_norm(
        B * S, cfg.d_model, cfg.vocab_size, norm=cfg.norm,
        has_bias=cfg.use_bias, enabled=fuse_norm,
        **_ce_recipe(cfg, mesh, ce_mode))
    x, aux = forward_hidden(params, batch["tokens"], cfg, attn_fn=attn_fn,
                            mesh=mesh, fuse_norm=fuse_norm,
                            final_norm=not ce_norm,
                            segment_ids=batch.get("segment_ids"),
                            positions=batch.get("positions"), lora=lora)
    loss = loss_from_hidden(
        params, x, batch["targets"], cfg, mesh=mesh, ce_mode=ce_mode,
        norm_scale=params["ln_f"] if ce_norm else None)
    return loss + aux_weight * aux


def num_params(params) -> int:
    return sum(p.size for p in jax.tree.leaves(params))
