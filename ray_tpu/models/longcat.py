"""LongCat-Flash's language model on the serve path: a block of two
latent-attention (MLA) sublayers, two dense FFNs and one expert layer
whose output joins at the block's end (the shortcut), as one chip's
share of an expert-parallel deployment.

With ``N`` = RMSNorm, for one block and its sublayers ``i`` = 0, 1::

    x1 = x + MLA_0(N(x));  u = N(x1);  s = MoE(u);  x2 = x1 + FFN_0(u)
    x3 = x2 + MLA_1(N(x2));  y = x3 + FFN_1(N(x3)) + s

``MLA`` keeps one row a token in the cache: the normed, scaled latent
``c`` (``kv_lora_rank`` values) and the rotated ``k_rot``
(``qk_rope_head_dim`` values, shared by every head).  A prefill
materialises K and V from the latent; a decode absorbs ``W_kvb`` into
the query and the output and attends over the latent rows where they
lie (``ops/attention.py:latent_kv``, ``absorb_query``,
``expand_output``).  What a row is, and how it is written and read, is
``inference/kv_cache.py``'s; the block hands the attention hook the
parts and the sublayer's ``W_kvb`` (its K half and V half,
head-major).  ``MoE`` is
``parallel/moe.py:dropless_moe``: the router scores every expert of the
deployment, this chip computes the experts it holds
(``cfg.held_experts``) and the identity experts' part, and an expert
held elsewhere adds nothing here (the exchange between chips is not
run).

This file is the serve path only: parameters, the block, and the
forward the inference engine's steps run.  The engine knows no model by
name: a config that runs its own stack offers ``serve_hidden`` and
``lm_head`` (``inference/engine.py:_build_step``), says what its cache
keeps (``cache_layers``, ``latent_row``) and names what its step
returns beside the logits (``step_counts``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import latent
from ray_tpu.models.gpt import _norm
from ray_tpu.ops.attention import Rope
from ray_tpu.parallel.moe import MOE_COUNTS, dropless_moe


@dataclasses.dataclass(frozen=True)
class LongcatConfig:
    vocab_size: int = 131072
    d_model: int = 6144
    n_layers: int = 28
    n_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 12288
    expert_ff: int = 2048
    n_routed_experts: int = 512
    n_identity_experts: int = 256
    moe_top_k: int = 12
    routed_scale: float = 6.0
    # the routed experts this chip holds, by their ids in the deployment
    held_experts: Tuple[int, ...] = tuple(range(512))
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    max_seq: int = 131072
    rope_theta: float = 1e7
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        held = tuple(int(e) for e in self.held_experts)
        if len(set(held)) != len(held) or any(
                not 0 <= e < self.n_routed_experts for e in held):
            raise ValueError(f"held_experts must be distinct ids below "
                             f"{self.n_routed_experts}, got {held}")
        object.__setattr__(self, "held_experts", held)

    # the layers the cache keeps: one latent row a token a sublayer
    @property
    def cache_layers(self) -> int:
        return 2 * self.n_layers

    @property
    def latent_row(self) -> Tuple[int, int]:
        return (self.kv_lora_rank, self.qk_rope_head_dim)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    # what the attention hook multiplies the scores by
    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5

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
    def longcat_flash_omni(cls, **kw):
        """The published language model of LongCat-Flash-Omni
        (huggingface.co/meituan-longcat/LongCat-Flash-Omni config.json);
        a deployment's share narrows ``n_layers``, ``held_experts`` and
        ``vocab_size``."""
        return cls(**kw)

    @classmethod
    def longcat_tiny(cls, **kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq", 128)
        kw.setdefault("held_experts", (0, 1, 2, 3))
        return cls(d_model=64, n_layers=2, n_heads=4, q_lora_rank=32,
                   kv_lora_rank=32, qk_nope_head_dim=16,
                   qk_rope_head_dim=16, v_head_dim=16, d_ff=128,
                   expert_ff=32, n_routed_experts=16,
                   n_identity_experts=8, moe_top_k=3, **kw)


PRESETS = ("longcat_flash_omni", "longcat_tiny")
CONFIG = LongcatConfig

# the draw's scales (``init_params`` says why)
_LOGIT_STD = 2.0        # of the softmax's logits
_ATTN_GAIN = 8.0        # on W_o: attention's output averages many values
_FFN_GAIN = 2.5         # on the down projections: silu(g) * u is ~0.4
_ROUTER_STD = 3.0       # of the router's logits


def init_params(cfg: LongcatConfig, key) -> Dict[str, Any]:
    """Random weights from ``key``, stacked over depth and, for what a
    block has twice, over the sublayer.  Each tensor is drawn one layer
    at a time, so the float32 draw of the widest (a layer's held
    experts) never stands whole beside the weights."""
    d, L, H = cfg.d_model, cfg.n_layers, cfg.n_heads
    rq, rkv, rope = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    E = cfg.n_routed_experts + cfg.n_identity_experts
    held, f, fe = len(cfg.held_experts), cfg.d_ff, cfg.expert_ff
    dt = cfg.dtype
    keys = iter(jax.random.split(key, 24))

    def draw(shape, scale):
        """[L, *shape], one layer a step."""
        def one(k):
            return (jax.random.normal(k, shape) * scale).astype(dt)
        return jax.jit(lambda ks: lax.map(one, ks))(
            jax.random.split(next(keys), L))

    def flat(shape, scale):
        return (jax.random.normal(next(keys), shape) * scale).astype(dt)

    # Random weights have to leave the block well conditioned *and* the
    # expert layer in sight, or the comparison with a float32 reference
    # measures the draw and not the engine
    # (``benchmark/reference/longcat.py`` has the readings):
    # - fan-in scaling counts what the forward multiplies a latent by
    #   (``mla_scale_*``: sqrt(d / rank)): a matrix that reads a scaled
    #   latent of ``rank`` values is drawn at d ** -0.5, so that k and v
    #   start at unit variance; q at ``_LOGIT_STD``, which is then the
    #   standard deviation of the softmax's logits (at 1 attention over
    #   thousands of keys is an average that says nothing of which keys
    #   were read; drawn at rank ** -0.5 it was 5.8 and bfloat16 read
    #   tenths against float32);
    # - the embedding and every dense branch's output are of order one,
    #   so that the residual stream is several times the one thing
    #   rounding can flip, the top-k's last pick (``scale * p_12 *
    #   E(u)``, 0.05 of a unit-norm ``u``);
    # - the router's logits have standard deviation ``_ROUTER_STD``, so
    #   that a row's picks differ in weight as a trained router's do: at
    #   1 the twelve weights are within 2.3 x of each other, each 0.05 of
    #   a unit after the scale, and neither the held experts' part nor
    #   most of the identity experts' can be told from rounding in the
    #   logits; at 3 the first pick weighs ~13 x the twelfth (0.6 of a
    #   unit), the layer's output is of the dense branches' order, and
    #   leaving a part of it out reads 0.06-0.6, while the pick a tie
    #   flips is still the twelfth (0.017 at most).  At 4 and above the
    #   softmax amplifies rounding in its input and a flip read 0.032.
    q_in = d if cfg.mla_scale_q_lora else rq
    kv_in = d if cfg.mla_scale_kv_lora else rkv
    layers = {
        "ln_attn": jnp.ones((L, 2, d), dt),
        "ln_ffn": jnp.ones((L, 2, d), dt),
        "wq_a": draw((2, d, rq), d ** -0.5),
        "q_norm": jnp.ones((L, 2, rq), dt),
        "wq_b": draw((2, rq, H, cfg.qk_head_dim),
                     _LOGIT_STD * q_in ** -0.5),
        "wkv_a": draw((2, d, rkv + rope), d ** -0.5),
        "kv_norm": jnp.ones((L, 2, rkv), dt),
        # W_kvb's two halves, head-major: a decode multiplies by them a
        # head at a time (the absorption), and the compiler then takes
        # them as they are stored, as it takes the plain matrices
        "wk_b": draw((2, H, rkv, cfg.qk_nope_head_dim), kv_in ** -0.5),
        "wv_b": draw((2, H, rkv, cfg.v_head_dim), kv_in ** -0.5),
        "wo": draw((2, H * cfg.v_head_dim, d),
                   _ATTN_GAIN * (H * cfg.v_head_dim) ** -0.5),
        "w_gate": draw((2, d, f), d ** -0.5),
        "w_up": draw((2, d, f), d ** -0.5),
        "w_down": draw((2, f, d), _FFN_GAIN * f ** -0.5),
        "router": draw((d, E), _ROUTER_STD * d ** -0.5),
        # e_score_correction_bias: moves which experts are chosen, not
        # their weights; zero at init
        "router_bias": jnp.zeros((L, E), jnp.float32),
        "e_gate": draw((held, d, fe), d ** -0.5),
        "e_up": draw((held, d, fe), d ** -0.5),
        "e_down": draw((held, fe, d), _FFN_GAIN * fe ** -0.5),
    }
    return {"embed": flat((cfg.vocab_size, d), 1.0),
            "layers": layers,
            "ln_f": jnp.ones((d,), dt),
            "lm_head": flat((d, cfg.vocab_size), 0.02)}


def _mla(lp, i: int, h, cfg: LongcatConfig, positions, attn_fn, cache):
    """Sublayer ``i``'s attention on the normed h [B, S, d] -> (out
    [B, S, d], the cache's updated arrays): ``models/latent.py:mla``
    with the query's low-rank path and the ``mla_scale_*`` multipliers.
    ``lp(name, *index)`` reads the block's weights."""
    d = cfg.d_model
    return latent.mla(
        lambda name: lp(name, i), h, positions=positions, attn_fn=attn_fn,
        cache=cache, rope=Rope(theta=cfg.rope_theta),
        rope_dim=cfg.qk_rope_head_dim, eps=cfg.norm_eps,
        q_gain=(d / cfg.q_lora_rank) ** 0.5 if cfg.mla_scale_q_lora
        else 1.0,
        c_gain=(d / cfg.kv_lora_rank) ** 0.5 if cfg.mla_scale_kv_lora
        else 1.0)


def _ffn(lp, i: int, h):
    return latent.swiglu(h, lp("w_gate", i), lp("w_up", i),
                         lp("w_down", i))


def block_apply(layers, x, cfg: LongcatConfig, *, positions, attn_fn, cache,
                valid):
    """Block ``cache[0]`` of the stacked ``layers`` on x [B, S, d].
    ``cache`` is
    ``(block index, arrays)``: sublayer ``i`` writes and reads cache
    layer ``2 * block + i`` through ``attn_fn(q_nope, q_rot, c, k_rot,
    w_kvb, cache=(layer, arrays)) -> (o [B, S, H, v], arrays)``.
    ``valid`` [B, S] marks the rows that are tokens of a sequence: the
    expert layer computes and counts those alone.  -> (x, arrays,
    the expert layer's counts)."""
    b, arrays = cache
    lp = lambda name, *index: latent.at(layers, name, b, *index)  # noqa: E731
    n = lambda a, name, i: _norm(a, lp(name, i), "rmsnorm",  # noqa: E731
                                 eps=cfg.norm_eps)
    B, S, d = x.shape
    with jax.named_scope("longcat/attn0"):
        a0, arrays = _mla(lp, 0, n(x, "ln_attn", 0), cfg, positions,
                          attn_fn, (2 * b, arrays))
        x1 = x + a0
    u = n(x1, "ln_ffn", 0)
    with jax.named_scope("moe"):
        s, counts = dropless_moe(
            u.reshape(B * S, d), lp("router"), lp("router_bias"),
            layers["e_gate"], layers["e_up"], layers["e_down"],
            held=cfg.held_experts, n_routed=cfg.n_routed_experts,
            top_k=cfg.moe_top_k, scale=cfg.routed_scale,
            valid=valid.reshape(B * S), lead=(b,))
    with jax.named_scope("longcat/ffn0"):
        x2 = x1 + _ffn(lp, 0, u)
    with jax.named_scope("longcat/attn1"):
        a1, arrays = _mla(lp, 1, n(x2, "ln_attn", 1), cfg, positions,
                          attn_fn, (2 * b + 1, arrays))
        x3 = x2 + a1
    with jax.named_scope("longcat/ffn1"):
        y = x3 + _ffn(lp, 1, n(x3, "ln_ffn", 1)) + s.reshape(B, S, d)
    return y, arrays, counts


def serve_hidden(params, cfg: LongcatConfig, tokens, positions, arrays,
                 attn_fn, valid):
    """tokens [B, S] at ``positions`` through every block with the
    cache's stacked ``arrays`` in the scan's carry -> (final normed
    hidden [B, S, d], arrays, the expert layers' summed counts
    (``parallel/moe.py:MOE_COUNTS``))."""
    x = params["embed"].astype(cfg.dtype)[tokens]

    def body(carry, b):
        x, arrays = carry
        x, arrays, counts = block_apply(
            params["layers"], x, cfg, positions=positions,
            attn_fn=attn_fn, cache=(b, arrays), valid=valid)
        return (x, arrays), counts

    (x, arrays), counts = lax.scan(body, (x, arrays),
                                   jnp.arange(cfg.n_layers))
    x = _norm(x, params["ln_f"], "rmsnorm", eps=cfg.norm_eps)
    return x, arrays, counts.sum(0)
