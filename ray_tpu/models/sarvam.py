"""sarvam-105b (``model_type: sarvam_mla``) on the serve path: latent
attention in every layer, a dense FFN in the leading layer and, in the
layers behind it, a shared expert beside a sigmoid-scored expert layer,
as one chip's share of an expert-parallel deployment.

With ``N`` = RMSNorm and ``A`` the latent-attention sublayer
(``models/latent.py:mla``: the query projected directly, ``q = N_q(h
W_q)`` normed over each head's channels, the latent normed, the rotation
``deepseek_yarn``'s)::

    layer 0:     y = x + A(N(x));  z = y + swiglu_dense(N(y))
    layers 1..:  y = x + A(N(x));  u = N(y)
                 s = sigmoid(u W_r) in float32;  picks = top-k of (s + b)
                 w = scale * s[picks] / sum(s[picks])
                 z = y + swiglu_shared(u) + sum_k w_k E_pick_k(u)

The expert layer is ``parallel/moe.py:dropless_moe(scoring="sigmoid",
renormalise=True)``: the router scores every expert of the deployment,
this chip computes the experts it holds (``cfg.held_experts``) and an
expert held elsewhere adds nothing here (the exchange between chips is
not run).  The shared expert is a dense swiglu every chip of the
deployment computes alike: adding the shares up counts it once.

``deepseek_yarn`` scales the rotation's cos and sin by ``mscale``'s
factor over ``mscale_all_dim``'s: the published two are both 1, so cos
and sin are unscaled and no field keeps them apart.  The softmax's scale
is multiplied by ``(0.1 * mscale_all_dim * ln(factor) + 1) ** 2``
(:attr:`SarvamConfig.softmax_scale`, which the engine's hook reads).

This file is the serve path only, through the seam
``models/longcat.py`` describes (``serve_hidden``, ``lm_head``,
``cache_layers``, ``latent_row``, ``softmax_scale``, ``step_counts``).
The dense layers and the routed layers are two parameter stacks; the
cache's layer is the model's.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import latent
from ray_tpu.models.gpt import _norm
from ray_tpu.ops.attention import Rope
from ray_tpu.parallel.moe import MOE_COUNTS, dropless_moe


@dataclasses.dataclass(frozen=True)
class SarvamConfig:
    vocab_size: int = 262144
    d_model: int = 4096
    n_layers: int = 32
    n_dense_layers: int = 1             # first_k_dense_replace
    n_heads: int = 64
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 16384                   # the dense layers' FFN
    expert_ff: int = 2048               # and the one shared expert's
    n_routed_experts: int = 128
    moe_top_k: int = 8
    routed_scale: float = 2.5
    # the routed experts this chip holds, by their ids in the deployment
    held_experts: Tuple[int, ...] = tuple(range(128))
    max_seq: int = 131072
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0            # mscale = mscale_all_dim
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        held = tuple(int(e) for e in self.held_experts)
        if len(set(held)) != len(held) or any(
                not 0 <= e < self.n_routed_experts for e in held):
            raise ValueError(f"held_experts must be distinct ids below "
                             f"{self.n_routed_experts}, got {held}")
        object.__setattr__(self, "held_experts", held)
        if not 0 <= self.n_dense_layers < self.n_layers:
            raise ValueError(
                f"n_dense_layers {self.n_dense_layers} of {self.n_layers} "
                "layers: the leading dense layers, with a routed layer "
                "behind them")

    # the layers the cache keeps: one latent row a token a layer
    @property
    def cache_layers(self) -> int:
        return self.n_layers

    @property
    def latent_row(self) -> Tuple[int, int]:
        return (self.kv_lora_rank, self.qk_rope_head_dim)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def rope(self) -> Rope:
        """``deepseek_yarn``'s frequencies, cos and sin unscaled."""
        return Rope(
            theta=self.rope_theta, factor=self.rope_factor,
            original_max=self.rope_original_max,
            beta_fast=self.rope_beta_fast, beta_slow=self.rope_beta_slow,
            attention_factor=1.0)

    # what the attention hook multiplies the scores by
    @property
    def softmax_scale(self) -> float:
        yarn = 1.0 if self.rope_factor <= 1 else \
            0.1 * self.rope_mscale * math.log(self.rope_factor) + 1.0
        return self.qk_head_dim ** -0.5 * yarn ** 2

    # what a serve step of this model returns between its logits and
    # the cache: one int32 vector, the expert layers' counts by name
    step_counts = MOE_COUNTS

    def serve_hidden(self, params, tokens, positions, arrays, attn_fn,
                     valid):
        return serve_hidden(params, self, tokens, positions, arrays,
                            attn_fn, valid)

    def lm_head(self, params):
        return params["lm_head"].astype(self.dtype)

    @classmethod
    def sarvam_105b(cls, **kw):
        """The published model (huggingface.co/sarvamai/sarvam-105b
        config.json); a deployment's share narrows ``n_layers``,
        ``held_experts`` and ``vocab_size``."""
        return cls(**kw)

    @classmethod
    def sarvam_tiny(cls, **kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq", 128)
        kw.setdefault("held_experts", tuple(range(8)))
        return cls(d_model=64, n_layers=3, n_heads=4, kv_lora_rank=32,
                   qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
                   d_ff=128, expert_ff=32,
                   n_routed_experts=32, moe_top_k=4, rope_original_max=32,
                   **kw)


PRESETS = ("sarvam_105b", "sarvam_tiny")
CONFIG = SarvamConfig

# the draw's scales (``init_params`` says why)
_Q_STD = 2.0            # of a query channel before its head's norm
_Q_NORM = 0.5           # that norm's weight: a normed query channel's size
_EMBED_STD = 2.5        # of an embedding row's channels
_ATTN_GAIN = 3.0        # on W_o: attention's output averages many values
_FFN_GAIN = 2.5         # on the down projections: silu(g) * u is ~0.4
_EXPERT_GAIN = 0.6      # ... and on the routed experts'
_ROUTER_STD = 3.0       # of the router's logits


def init_params(cfg: SarvamConfig, key) -> Dict[str, Any]:
    """Random weights from ``key``: ``dense`` stacked over the leading
    dense layers, ``layers`` over the routed ones behind them.  Each
    tensor is drawn one layer at a time, so the float32 draw of the
    widest (a layer's held experts) never stands whole beside the
    weights.

    The draw decides two things beside the weights' sizes, and both
    were set on the chip (PR 61; ``benchmark/reference/sarvam.py`` has
    the check's readings): how evenly the router spreads its picks,
    which is the cell's work, and what bfloat16 reads against the
    float32 reference.

    - **The residual stream is the tokens' own**: embedding rows at
      ``_EMBED_STD``, W_o at ``_ATTN_GAIN``.  Attention averages values,
      so what it adds is mostly common to a sequence's tokens; drawn as
      the sibling family is (rows of 1, W_o at 8) that common part was
      amplified layer by layer, every token of a sequence sent its picks
      to the same few experts, and the picks this chip holds ran from
      1.04 to 2.86 a token between sequences (standard deviation 0.44
      over 32 prompts of 2,048): ``serve_out_tok_s`` followed a seed's
      held picks (correlation -0.95) and six seeds spread 2.3-3.3 %.  As
      drawn the sequences read 1.90-2.10 (0.053).
    - the query is drawn at ``_Q_STD`` and its head's norm has the
      weight ``_Q_NORM``: the softmax's logits have standard deviation
      0.5 x 1.87 = 0.94 and a forward without that norm reads four times
      as sharp.  At 1.87 bfloat16 read twice as much on every row and
      flipped a held pick at the top-k's edge in a quarter of the rows,
      at 0.94 in a tenth (0.35 and 0.25 of 1.87 read no lower);
    - the latent's matrices at ``rank ** -0.5`` behind its norm, so k
      and v start at unit variance; the dense branches' down projections
      at ``_FFN_GAIN``; the router's logits at ``_ROUTER_STD``, under
      which a row's eight sigmoid scores are 0.98-1 (a softmax in their
      place weighs the first ~15 x the eighth) and the renormalised
      weights each ~2.5 / 8;
    - so a flipped eighth pick moves a row as leaving any expert out
      does, and the routed experts' down projections are drawn at
      ``_EXPERT_GAIN``: a flip and the routed part's planted faults
      scale with it, the float8 reference's reading does not, and at 0.6
      the largest flip of 16 seeds reads 0.055 where float8, the yarn
      factor left out and a held pick sent to another expert read
      0.12-0.14 at their weakest."""
    d, H = cfg.d_model, cfg.n_heads
    rkv, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    E, held = cfg.n_routed_experts, len(cfg.held_experts)
    dt = cfg.dtype
    keys = iter(jax.random.split(key, 40))

    def stack(L):
        def draw(shape, scale):
            """[L, *shape], one layer a step."""
            def one(k):
                return (jax.random.normal(k, shape) * scale).astype(dt)
            return jax.jit(lambda ks: lax.map(one, ks))(
                jax.random.split(next(keys), L))
        return draw

    def flat(shape, scale):
        return (jax.random.normal(next(keys), shape) * scale).astype(dt)

    def attention(L, draw):
        return {
            "ln_attn": jnp.ones((L, d), dt),
            "ln_ffn": jnp.ones((L, d), dt),
            "wq": draw((d, H, cfg.qk_head_dim), _Q_STD * d ** -0.5),
            "q_head_norm": jnp.full((L, cfg.qk_head_dim), _Q_NORM, dt),
            "wkv_a": draw((d, rkv + rope), d ** -0.5),
            "kv_norm": jnp.ones((L, rkv), dt),
            # W_kvb's two halves, head-major (``models/longcat.py``)
            "wk_b": draw((H, rkv, cfg.qk_nope_head_dim), rkv ** -0.5),
            "wv_b": draw((H, rkv, cfg.v_head_dim), rkv ** -0.5),
            "wo": draw((H * cfg.v_head_dim, d),
                       _ATTN_GAIN * (H * cfg.v_head_dim) ** -0.5)}

    def ffn(draw, prefix, *lead, f):
        gain = _EXPERT_GAIN if prefix == "e_" else _FFN_GAIN
        return {prefix + "gate": draw(lead + (d, f), d ** -0.5),
                prefix + "up": draw(lead + (d, f), d ** -0.5),
                prefix + "down": draw(lead + (f, d), gain * f ** -0.5)}

    n_dense, n_routed = cfg.n_dense_layers, cfg.n_layers - cfg.n_dense_layers
    dense = stack(n_dense)
    routed = stack(n_routed)
    return {
        "embed": flat((cfg.vocab_size, d), _EMBED_STD),
        "dense": {**attention(n_dense, dense),
                  **ffn(dense, "w_", f=cfg.d_ff)},
        "layers": {
            **attention(n_routed, routed),
            "router": routed((d, E), _ROUTER_STD * d ** -0.5),
            # moves which experts are chosen, not their weights; zero
            # at init
            "router_bias": jnp.zeros((n_routed, E), jnp.float32),
            **ffn(routed, "e_", held, f=cfg.expert_ff),
            **ffn(routed, "s_", f=cfg.expert_ff)},
        "ln_f": jnp.ones((d,), dt),
        "lm_head": flat((d, cfg.vocab_size), 0.02)}


def _attend(lp, x, cfg: SarvamConfig, positions, attn_fn, cache):
    """``x + A(N(x))`` with one layer's weights (``lp(name)``)."""
    h = _norm(x, lp("ln_attn"), "rmsnorm", eps=cfg.norm_eps)
    with jax.named_scope("sarvam/attn"):
        a, arrays = latent.mla(
            lp, h, positions=positions, attn_fn=attn_fn, cache=cache,
            rope=cfg.rope, rope_dim=cfg.qk_rope_head_dim, eps=cfg.norm_eps,
            q_lora=False, head_norm=True)
    return x + a, arrays


def dense_layer(layers, i: int, x, cfg: SarvamConfig, *, positions,
                attn_fn, arrays):
    """Leading dense layer ``i`` (the cache's layer ``i``) on x [B, S,
    d] -> (x, arrays)."""
    lp = lambda name: latent.at(layers, name, i)          # noqa: E731
    y, arrays = _attend(lp, x, cfg, positions, attn_fn, (i, arrays))
    u = _norm(y, lp("ln_ffn"), "rmsnorm", eps=cfg.norm_eps)
    with jax.named_scope("sarvam/dense_ffn"):
        z = y + latent.swiglu(u, lp("w_gate"), lp("w_up"), lp("w_down"))
    return z, arrays


def routed_layer(layers, b, x, cfg: SarvamConfig, *, positions, attn_fn,
                 arrays, valid):
    """Routed layer ``b`` of the stacked ``layers`` (the cache's layer
    ``n_dense_layers + b``) on x [B, S, d].  ``valid`` [B, S] marks the
    rows that are tokens of a sequence: the expert layer computes and
    counts those alone.  -> (x, arrays, the expert layer's counts)."""
    lp = lambda name: latent.at(layers, name, b)          # noqa: E731
    B, S, d = x.shape
    y, arrays = _attend(lp, x, cfg, positions, attn_fn,
                        (cfg.n_dense_layers + b, arrays))
    u = _norm(y, lp("ln_ffn"), "rmsnorm", eps=cfg.norm_eps)
    with jax.named_scope("moe"):
        s, counts = dropless_moe(
            u.reshape(B * S, d), lp("router"), lp("router_bias"),
            layers["e_gate"], layers["e_up"], layers["e_down"],
            held=cfg.held_experts, n_routed=cfg.n_routed_experts,
            top_k=cfg.moe_top_k, scale=cfg.routed_scale,
            valid=valid.reshape(B * S), lead=(b,), renormalise=True,
            scoring="sigmoid")
        with jax.named_scope("shared"):
            shared = latent.swiglu(u, lp("s_gate"), lp("s_up"),
                                   lp("s_down"))
    return y + shared + s.reshape(B, S, d), arrays, counts


def serve_hidden(params, cfg: SarvamConfig, tokens, positions, arrays,
                 attn_fn, valid):
    """tokens [B, S] at ``positions`` through the dense layers and then
    every routed layer, the cache's stacked ``arrays`` in the scan's
    carry -> (final normed hidden [B, S, d], arrays, the expert layers'
    summed counts (``parallel/moe.py:MOE_COUNTS``))."""
    x = params["embed"].astype(cfg.dtype)[tokens]
    for i in range(cfg.n_dense_layers):
        x, arrays = dense_layer(params["dense"], i, x, cfg,
                                positions=positions, attn_fn=attn_fn,
                                arrays=arrays)

    def body(carry, b):
        x, arrays = carry
        x, arrays, counts = routed_layer(
            params["layers"], b, x, cfg, positions=positions,
            attn_fn=attn_fn, arrays=arrays, valid=valid)
        return (x, arrays), counts

    (x, arrays), counts = lax.scan(
        body, (x, arrays), jnp.arange(cfg.n_layers - cfg.n_dense_layers))
    x = _norm(x, params["ln_f"], "rmsnorm", eps=cfg.norm_eps)
    return x, arrays, counts.sum(0)
