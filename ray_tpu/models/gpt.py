"""Flagship decoder-only transformer (GPT family), TPU-first.

Capability parity target: the models the reference fine-tunes through HF
Transformers (GPT-2 in ``release/release_tests.yaml`` gptj/gpt2 suites) —
but built natively for XLA: stacked layer params swept by ``lax.scan``
(O(1) compile in depth), bf16 matmuls with f32 stats, RoPE, optional
ring attention over an ``sp`` axis, optional MoE FFNs sharded over ``ep``,
and logical-axis annotations so one model runs under any
dp/fsdp/tp/sp/ep mesh (see ``ray_tpu.parallel.sharding``).

The same block also runs, on the train path, with fewer K/V heads than
query heads (``n_kv_heads``), with layers of two kinds in one stack
(``layer_types``: ``window`` layers, whose rows see ``window`` keys, and
``full`` ones, each kind with its own rope and its own attention hook:
:func:`attention_fns`), and with a dropless expert FFN that is told
which experts of a deployment it holds (``held_experts``:
``parallel/moe.py:dropless_moe``, one chip's share of an
expert-parallel layer, the exchange between chips not run).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import Rope
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
    # K/V heads (None: as many as query heads); query head i reads K/V
    # head i // (n_heads // n_kv_heads)
    n_kv_heads: Optional[int] = None
    # one period of the layer pattern, "window" and "full", repeated
    # over the depth (None: every layer full).  A window layer's rows
    # see ``window`` keys, their own included, and keep the plain
    # ``rope_theta``; the full layers take ``rope_full`` where it is set
    # (a context-extended model scales the rope of the layers that see
    # the whole context)
    layer_types: Optional[Tuple[str, ...]] = None
    window: Optional[int] = None
    rope_full: Optional[Rope] = None
    # the dropless expert FFN (parallel/moe.py:dropless_moe): the ids,
    # among the deployment's ``n_routed_experts``, of the experts this
    # chip holds; the router scores all of them and picks ``moe_top_k``,
    # weighted by their scores over the picked scores' sum where
    # ``moe_renormalise``.  No capacity, no drop, no auxiliary loss.
    # (``n_experts`` is the capacity path's and stays 0.)
    held_experts: Optional[Tuple[int, ...]] = None
    n_routed_experts: int = 0
    moe_renormalise: bool = False
    # a recipe key, as ``remat`` and ``ce_chunk`` are: the steps over
    # which the builders' default optimizer (``models/training.py:
    # default_optimizer``) warms its learning rate up.  A dropless
    # layer's work follows its router, so a run whose steps are to cost
    # the same takes a warm-up under which the router stays where it
    # was drawn (PERF.md section 6, PR 56)
    warmup_steps: int = 100

    def __post_init__(self):
        if self.layer_types is not None:
            kinds = tuple(self.layer_types)
            if (not kinds or set(kinds) - {"window", "full"}
                    or self.n_layers % len(kinds)):
                raise ValueError(
                    f"layer_types {kinds} must be a period of 'window' "
                    f"and 'full' that divides n_layers={self.n_layers}")
            if "window" in kinds and not self.window:
                raise ValueError("window layers need window=")
            object.__setattr__(self, "layer_types", kinds)
        if self.n_heads % self.kv_heads:
            raise ValueError(f"n_heads={self.n_heads} is no multiple of "
                             f"n_kv_heads={self.kv_heads}")
        if self.held_experts is not None:
            held = tuple(int(e) for e in self.held_experts)
            if self.n_experts:
                raise ValueError("held_experts is the dropless layer's; "
                                 "n_experts is the capacity path's")
            if len(set(held)) != len(held) or any(
                    not 0 <= e < self.n_routed_experts for e in held):
                raise ValueError(
                    f"held_experts must be distinct ids below "
                    f"n_routed_experts={self.n_routed_experts}, got {held}")
            object.__setattr__(self, "held_experts", held)

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def dropless(self) -> bool:
        return self.held_experts is not None

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Every layer's kind, in order."""
        period = self.layer_types or ("full",)
        return period * (self.n_layers // len(period))

    @property
    def plain_attention(self) -> bool:
        """One kind of layer with a K/V head a query head: what every
        attention hook of the repo computes."""
        return self.layer_types is None and self.kv_heads == self.n_heads

    def rope(self, kind: str = "full"):
        """The rope of a layer kind: a plain theta or a ``Rope``."""
        if kind == "full" and self.rope_full is not None:
            return self.rope_full
        return self.rope_theta

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

    @classmethod
    def mellum2_12b_a2_5b(cls, **kw):
        """Mellum2-12B-A2.5B at its published widths
        (huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct
        config.json): 32 query heads on 4 K/V heads of 128, three window
        layers (1024 keys, plain rope) then one full layer (static YaRN,
        factor 16 over 8192) a period, every FFN 8 of 64 experts of
        width 896 with renormalised weights, untied head.  A
        deployment's share narrows ``n_layers``, ``held_experts`` and
        ``vocab_size``."""
        kw.setdefault("vocab_size", 98304)
        kw.setdefault("n_layers", 28)
        kw.setdefault("max_seq", 8192)
        kw.setdefault("held_experts", tuple(range(64)))
        return cls(d_model=2304, n_heads=32, n_kv_heads=4, d_head=128,
                   d_ff=896, rope_theta=500000.0,
                   layer_types=("window", "window", "window", "full"),
                   window=1024,
                   rope_full=Rope(theta=500000.0, factor=16.0,
                                  original_max=8192, beta_fast=32.0,
                                  beta_slow=1.0,
                                  attention_factor=1.2772588722239782),
                   n_routed_experts=64, moe_top_k=8,
                   moe_renormalise=True, tie_embeddings=False, **kw)

    @classmethod
    def mellum_tiny(cls, **kw):
        """The same shape of block at test size: two periods of (window,
        window, window, full), 2 K/V heads under 4 query heads, 8
        experts top-2 of which 4 are held, a window shorter than the
        tests' sequences, YaRN on the full layers."""
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq", 256)
        kw.setdefault("n_heads", 4)
        kw.setdefault("held_experts", (0, 1, 2, 3))
        return cls(d_model=64, n_layers=8, n_kv_heads=2, d_head=16,
                   d_ff=32, rope_theta=500000.0,
                   layer_types=("window", "window", "window", "full"),
                   window=48,
                   rope_full=Rope(theta=500000.0, factor=4.0,
                                  original_max=64, beta_fast=32.0,
                                  beta_slow=1.0,
                                  attention_factor=1.1386294361119891),
                   n_routed_experts=8, moe_top_k=2,
                   moe_renormalise=True, tie_embeddings=False, **kw)


# the presets a deployment may name (``inference/serve_gpt.py``); the
# last two are named so that they are refused with the reason
# (``inference/engine.py:refuse_unserved``), not as unknown names
PRESETS = ("tiny", "gpt2", "gpt2_medium", "gpt2_large",
           "mellum2_12b_a2_5b", "mellum_tiny")
CONFIG = GPTConfig

# a routed config's factor on its drawn queries, by layer kind (the
# attention scores' standard deviation before a rope's own factor)
_ROUTED_QUERY_SCALE = {"window": 4.0, "full": 3.0}


def _query_scales(cfg: GPTConfig):
    """Every layer's factor on its drawn queries, ``[L, 1, 1, 1]``: 1 but
    for a routed config, whose work follows its draw.  With scores of
    standard deviation 1 a row's softmax over a thousand keys is flat,
    every token's attention output is the same average, and the router
    sends every token to the same few experts (one held expert took 17
    rows of 16,384 and another 5,360 in the fourth layer, my chip run,
    PR 56).  The sharper the window layers' softmax, the nearer a
    layer's experts come to equal loads, and with them the picks this
    chip holds to their expectation whatever the seed: over eight seeds
    the held picks a step spread 2,754 (standard deviation, of 131 k)
    with every layer's queries at 3, 1,319 at 4, 969 with the window
    layers' at 5, 665 at 7.5, and the full layer's own sharpness moved
    nothing (its scores stand at 4.9 already, through the 1.63 that
    YaRN's factor puts on them).  Against that, a sharper softmax shows
    more of bfloat16's rounding beside the float32 reference: with the
    window layers at 5 the clean loss gap reached 2.8e-4 of the 3.4e-4
    allowed, at 4 it stays under 1.3e-4 over thirteen seeds, and ten
    seeds' tokens a second spread 0.25 % (PERF.md section 6, PR 56)."""
    if not cfg.dropless:
        return 1.0
    return jnp.asarray([_ROUTED_QUERY_SCALE[kind]
                        for kind in cfg.layer_kinds],
                       jnp.float32)[:, None, None, None]


def init_params(cfg: GPTConfig, key) -> Dict[str, Any]:
    keys = iter(jax.random.split(key, 24))
    d, H, hd, f, L = (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.ff_dim,
                      cfg.n_layers)
    Hkv = cfg.kv_heads
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
        # a routed model's queries are drawn for sharper attention
        # scores, by layer kind (``_query_scales``)
        "wq": norm_init(next(keys), (L, d, H, hd),
                        _query_scales(cfg) * d ** -0.5),
        "wk": norm_init(next(keys), (L, d, Hkv, hd), d ** -0.5),
        "wv": norm_init(next(keys), (L, d, Hkv, hd), d ** -0.5),
        "wo": norm_init(next(keys), (L, H, hd, d),
                        (H * hd) ** -0.5 / (2 * L) ** 0.5),
        "ln2": jnp.ones((L, d), dt),
    }
    if cfg.dropless:
        # the router scores the deployment's experts, the matrices are
        # the held ones'.  Router logits of standard deviation 3: a
        # row's picks then differ in weight as a trained router's do
        # (at 1 they are near equal and a check against the reference
        # cannot see which experts ran; PERF.md section 6, PR 48)
        E = len(cfg.held_experts)
        layer["moe_wg"] = norm_init(next(keys), (L, d, cfg.n_routed_experts),
                                    3.0 * d ** -0.5)
        layer["moe_w1"] = norm_init(next(keys), (L, E, d, f), d ** -0.5)
        layer["moe_w3"] = norm_init(next(keys), (L, E, d, f), d ** -0.5)
        layer["moe_w2"] = norm_init(next(keys), (L, E, f, d),
                                    f ** -0.5 / (2 * L) ** 0.5)
    elif cfg.n_experts > 0:
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
        layer["bk"] = jnp.zeros((L, Hkv, hd), dt)
        layer["bv"] = jnp.zeros((L, Hkv, hd), dt)
        layer["bo"] = jnp.zeros((L, d), dt)
        if cfg.n_experts == 0 and not cfg.dropless:
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
    if cfg.n_experts > 0 or cfg.dropless:
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
        if cfg.n_experts == 0 and not cfg.dropless:
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


def _rope(x, positions, theta):
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


def _dropless_ffn(lp, x, cfg: GPTConfig):
    """The dropless expert FFN on x [B, S, d] -> (its output, the
    layer's counts: ``parallel/moe.py:MOE_COUNTS``, then the rows each
    held expert took, then the windows its combines bring)."""
    from ray_tpu.parallel.moe import dropless_moe
    B, S, d = x.shape
    with jax.named_scope("moe"):
        out, counts, load, windows = dropless_moe(
            x.reshape(B * S, d), lp["moe_wg"],
            jnp.zeros((cfg.n_routed_experts,), jnp.float32),
            lp["moe_w1"], lp["moe_w3"], lp["moe_w2"],
            held=cfg.held_experts, n_routed=cfg.n_routed_experts,
            top_k=cfg.moe_top_k, scale=1.0,
            renormalise=cfg.moe_renormalise, with_load=True)
    return out.reshape(B, S, d), jnp.concatenate([counts, load, windows[None]])


def moe_counts_len(cfg: GPTConfig) -> int:
    """Length of a layer's counts vector: ``MOE_COUNTS``, then a row
    count a held expert and its combines' windows."""
    from ray_tpu.parallel.moe import MOE_COUNTS
    return len(MOE_COUNTS) + len(cfg.held_experts or ()) + 1


def xla_attention_fn(cfg: GPTConfig, kind: str = "full"):
    """The einsum attention hook of one layer kind (no kernel): what a
    config with window layers or grouped K/V heads runs where no hook
    is given."""
    from ray_tpu.ops.attention import xla_attention
    window = cfg.window if kind == "window" else None
    fn = functools.partial(xla_attention, causal=True, window=window)
    fn.window, fn.kv_heads = window, cfg.kv_heads
    return fn


def attention_fns(cfg: GPTConfig, mesh=None, **kw) -> Dict[str, Callable]:
    """One flash-attention hook a layer kind of ``cfg``
    (``ops.attention.make_flash_attention_fn`` with the kind's window,
    K/V heads and rope): what ``forward_hidden`` takes as ``attn_fn``
    for a config whose layers differ."""
    from ray_tpu.ops.attention import make_flash_attention_fn
    return {
        kind: make_flash_attention_fn(
            mesh, causal=True,
            window=cfg.window if kind == "window" else None,
            kv_heads=cfg.kv_heads,
            rope=cfg.rope(kind) if cfg.pos == "rope" else None, **kw)
        for kind in sorted(set(cfg.layer_kinds))}


def _kind_attn_fn(attn_fn, cfg: GPTConfig, kind: str):
    """The hook layer kind ``kind`` runs, checked: a window layer never
    runs a hook that does not say it honours that window."""
    if isinstance(attn_fn, dict):
        attn_fn = attn_fn[kind]
    want = cfg.window if kind == "window" else None
    if cfg.plain_attention:
        return attn_fn
    if (getattr(attn_fn, "window", None) != want
            or getattr(attn_fn, "kv_heads", cfg.n_heads) != cfg.kv_heads):
        raise ValueError(
            f"a {kind!r} layer of a config with window layers or "
            f"grouped K/V heads (window={want}, "
            f"n_kv_heads={cfg.kv_heads}) needs an attention hook made "
            "for it (models.gpt.attention_fns); the hook given says "
            f"window={getattr(attn_fn, 'window', None)}, "
            f"kv_heads={getattr(attn_fn, 'kv_heads', None)}")
    return attn_fn


def layer_apply(lp, x, cfg: GPTConfig, *, positions, attn_fn, mesh=None,
                cache=None, fuse_norm=None, lora=None, kind: str = "full",
                with_counts: bool = False):
    """One transformer block: ``(layer params, hidden [B,S,d]) -> (hidden,
    moe aux)``.  ``kind`` is the layer's kind (``cfg.layer_kinds``): it
    picks the rope, and the hook (``attn_fn``: one callable, or one a
    kind) is checked to be that kind's.  A window layer's attention
    runs under the scope ``window`` (``gpt/attn/window/attn/...``).
    With ``with_counts`` the block also returns its expert layer's
    counts (zeros for a dense FFN) last.
    Shared by the stacked ``lax.scan`` in ``forward_hidden``,
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
    attn_fn = _kind_attn_fn(attn_fn, cfg, kind)
    counts = None
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
            q = _rope(q, positions, cfg.rope(kind))
            k = _rope(k, positions, cfg.rope(kind))
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
        elif kind == "window":
            with jax.named_scope("window"):
                attn = (attn_fn(q, k, v, positions=positions)
                        if fused_rope else attn_fn(q, k, v))
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
        if (cfg.n_experts > 0 or cfg.dropless) and lora is not None:
            raise ValueError("LoRA adapters are dense-FFN only "
                             "(see adapters.lora.effective_targets)")
        if cfg.dropless:
            # no capacity, so no auxiliary term: aux is 0
            ffn_out, counts = _dropless_ffn(lp, h2, cfg)
            aux = jnp.float32(0)
        elif cfg.n_experts > 0:
            ffn_out, aux = _moe_ffn(lp, h2, cfg)
        else:
            ffn_out, aux = _dense_ffn(lp, h2, cfg, lora=lora), jnp.float32(0)
        x = x + ffn_out
        x = constrain(x, ("batch", "seq", None))
    out = (x, aux) if cache is None else (x, aux, cache)
    if with_counts:
        out += (jnp.zeros((moe_counts_len(cfg),), jnp.int32)
                if counts is None else counts,)
    return out


def _with_segments(attn_fn, segment_ids):
    """Close ``segment_ids`` over an attention hook, preserving the
    ``fused_rope`` marker ``layer_apply`` dispatches on.  Every
    in-tree hook (``local_attention``, ``flash_attention`` and the
    ``make_flash_attention_fn`` wrappers) accepts the kwarg; the
    Pallas schedules decline it with the XLA segment formulation."""
    if isinstance(attn_fn, dict):
        return {kind: _with_segments(fn, segment_ids)
                for kind, fn in attn_fn.items()}
    fn = functools.partial(attn_fn, segment_ids=segment_ids)
    for mark in ("window", "kv_heads"):
        if hasattr(attn_fn, mark):
            setattr(fn, mark, getattr(attn_fn, mark))
    fn.fused_rope = getattr(attn_fn, "fused_rope", False)
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
                   segment_ids=None, positions=None, lora=None,
                   with_counts: bool = False):
    """tokens [B, S] int32 -> (final hidden [B, S, d], moe aux loss),
    and with ``with_counts`` the expert layers' summed counts
    (``parallel/moe.py:MOE_COUNTS``) last.

    ``attn_fn(q, k, v) -> out`` defaults to causal local attention; pass a
    ring-attention fn (``make_ring_attention_fn``) for sp>1 meshes.  A
    config whose layers differ in kind (``cfg.layer_types``) or whose
    K/V heads are grouped takes one hook a kind
    (:func:`attention_fns`; default: the einsum formulation).  Unrolled
    layers pick their kind's hook statically; the scan sweeps whole
    periods of the pattern, so a window layer is never run as a full
    one.

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
        attn_fn = (functools.partial(local_attention, causal=True)
                   if cfg.plain_attention else
                   {kind: xla_attention_fn(cfg, kind)
                    for kind in set(cfg.layer_kinds)})
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

    def layer_body(x, lp_la, kind="full"):
        lp, la = lp_la
        layer_lora = None if la is None else {**la, "scale": lora["scale"]}
        out = layer_apply(lp, x, cfg, positions=positions,
                          attn_fn=attn_fn, mesh=mesh,
                          fuse_norm=fuse_norm, lora=layer_lora,
                          kind=kind, with_counts=with_counts)
        return out[0], out[1:]

    kinds = cfg.layer_kinds
    bodies = {kind: functools.partial(layer_body, kind=kind)
              for kind in set(kinds)}
    if cfg.remat:
        bodies = {kind: jax.checkpoint(body)
                  for kind, body in bodies.items()}

    def sweep(x, stacked, kinds):
        """The first ``len(kinds)`` layers of the stacked trees, in
        order -> (x, what they return beside it, summed)."""
        sums = None
        for i, kind in enumerate(kinds):
            x, extra = bodies[kind](x, jax.tree.map(lambda a: a[i], stacked))
            sums = extra if sums is None else jax.tree.map(
                jnp.add, sums, extra)
        return x, sums

    stacked = (params["layers"], lora_scan)
    if cfg.unroll_layers:
        x, sums = sweep(x, stacked, kinds)
    elif cfg.layer_types is None:
        x, extras = lax.scan(bodies["full"], x, stacked)
        sums = jax.tree.map(lambda a: jnp.sum(a, axis=0), extras)
    else:
        # one scan step is one period of the layer pattern: [L, ...] is
        # swept as [L / p, p, ...]
        period = cfg.layer_types
        folded = jax.tree.map(
            lambda a: a.reshape(a.shape[0] // len(period), len(period),
                                *a.shape[1:]), stacked)
        x, extras = lax.scan(lambda x, one: sweep(x, one, period), x, folded)
        sums = jax.tree.map(lambda a: jnp.sum(a, axis=0), extras)
    if final_norm:
        x = _norm(x, params["ln_f"], cfg.norm,
                  bias=params.get("ln_f_b"), eps=norm_eps(cfg))
    return (x,) + tuple(sums)


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
            fuse_norm: Optional[bool] = None, lora=None,
            with_counts: bool = False):
    """batch: dict(tokens [B,S], targets [B,S]); returns scalar loss:
    the mean next-token NLL plus ``aux_weight`` times the expert layers'
    auxiliary term.  Only the capacity path (``n_experts``) has one: a
    dense FFN's and the dropless layer's (``held_experts``: nothing is
    dropped, so there is no load to balance a capacity against) are 0,
    and their loss is the NLL alone.  With ``with_counts``: ``(loss,
    the expert layers' summed counts)``, for ``jax.value_and_grad(...,
    has_aux=True)``.

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
    x, aux, *counts = forward_hidden(
        params, batch["tokens"], cfg, attn_fn=attn_fn, mesh=mesh,
        fuse_norm=fuse_norm, final_norm=not ce_norm,
        segment_ids=batch.get("segment_ids"),
        positions=batch.get("positions"), lora=lora,
        with_counts=with_counts)
    loss = loss_from_hidden(
        params, x, batch["targets"], cfg, mesh=mesh, ce_mode=ce_mode,
        norm_scale=params["ln_f"] if ce_norm else None)
    loss = loss + aux_weight * aux
    return (loss, counts[0]) if with_counts else loss


def num_params(params) -> int:
    return sum(p.size for p in jax.tree.leaves(params))
