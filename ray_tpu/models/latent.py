"""The latent-attention (MLA) sublayer of the serve path, once, for
every model that keeps one latent row a token in the cache
(``models/longcat.py``, ``models/sarvam.py``).

A row is the normed latent ``c`` (``kv_lora_rank`` values) and the
rotated ``k_rot`` (``qk_rope_head_dim`` values, shared by every head).
A prefill materialises K and V from the latent; a decode absorbs
``W_kvb`` into the query and the output and attends over the latent
rows where they lie (``ops/attention.py:latent_kv``, ``absorb_query``,
``expand_output``).  What a row is, and how it is written and read, is
``inference/kv_cache.py``'s and the engine's hook's
(``engine._latent_hooks``); the sublayer hands the hook the parts and
its ``W_kvb`` (the K half and the V half, head-major).  The softmax's
scale is the hook's too: the model's config states it
(``softmax_scale``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.gpt import _norm
from ray_tpu.ops.attention import Rope, rope_rotate


def mla(w, h, *, positions, attn_fn, cache, rope: Rope, rope_dim: int,
        eps: float, q_lora: bool = True, head_norm: bool = False,
        q_gain: float = 1.0, c_gain: float = 1.0):
    """One sublayer's attention on the normed h [B, S, d] -> (out
    [B, S, d], the cache's updated arrays).  ``w(name)`` reads the
    sublayer's weights:

    - the query: with ``q_lora`` through a normed latent of its own
      (``wq_a`` [d, rq], ``q_norm`` [rq], ``wq_b`` [rq, H, nope +
      rope]), else directly (``wq`` [d, H, nope + rope]); with
      ``head_norm`` each head's channels are normed (``q_head_norm``
      [nope + rope]); times ``q_gain``;
    - the row: ``wkv_a`` [d, rank + rope], the latent normed by
      ``kv_norm`` [rank], times ``c_gain``;
    - ``wk_b`` [H, rank, nope], ``wv_b`` [H, rank, v], ``wo`` [H * v, d].

    ``rope`` rotates the last ``rope_dim`` channels of the query's heads
    and of the row; ``cache`` is ``(cache layer, arrays)``, written and
    read through ``attn_fn(q_nope, q_rot, c, k_rot, w_kvb, cache=) ->
    (o [B, S, H, v], arrays)``."""
    if q_lora:
        c_q = _norm(jnp.einsum("bsd,dr->bsr", h, w("wq_a")), w("q_norm"),
                    "rmsnorm", eps=eps)
        q = jnp.einsum("bsr,rhk->bshk", c_q, w("wq_b"))
    else:
        q = jnp.einsum("bsd,dhk->bshk", h, w("wq"))
    if head_norm:
        q = _norm(q, w("q_head_norm"), "rmsnorm", eps=eps)
    if q_gain != 1.0:
        q = q * q_gain
    kv = jnp.einsum("bsd,dr->bsr", h, w("wkv_a"))
    c = _norm(kv[..., :-rope_dim], w("kv_norm"), "rmsnorm", eps=eps)
    if c_gain != 1.0:
        c = c * c_gain
    q_nope = q[..., :-rope_dim]
    q_rot = rope_rotate(q[..., -rope_dim:], positions, rope)
    k_rot = rope_rotate(kv[..., None, -rope_dim:], positions, rope)[:, :, 0]
    o, arrays = attn_fn(q_nope, q_rot, c, k_rot, (w("wk_b"), w("wv_b")),
                        cache=cache)
    return jnp.einsum("bsk,kd->bsd", o.reshape(o.shape[:2] + (-1,)),
                      w("wo")), arrays


def at(layers, name: str, *index):
    """``layers[name][index]``, sliced where the stacked weight stands
    (one slice a use, so that the compiler reads a matrix from the
    stack and copies no layer out of it)."""
    a = layers[name]
    n = len(index)
    return lax.dynamic_slice(a, index + (0,) * (a.ndim - n),
                             (1,) * n + a.shape[n:]).reshape(a.shape[n:])


def swiglu(h, gate, up, down):
    """A dense swiglu FFN on h [B, S, d]."""
    g = jnp.einsum("bsd,df->bsf", h, gate)
    u = jnp.einsum("bsd,df->bsf", h, up)
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, down)
