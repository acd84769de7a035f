"""The plain reference for both GPT-2 configurations: forward, loss and
gradients in straightforward ``jax.numpy``, float32, no kernels, no
cache, no batching tricks.  It imports nothing from ``ray_tpu``.

It follows the block this repository runs under the GPT-2 names, which
departs from the published one as each configuration file lists under
``assumed``: rmsnorm (eps 1e-6) where GPT-2 has layernorm, rotary
positions (half-split pairs, theta 10000) where GPT-2 has a learned
table, no biases, a swiglu feed-forward ``w2(silu(x w1) * (x w3))``
where GPT-2 has gelu, the output head tied to the embedding, attention
scaled by ``head_dim ** -0.5`` under a causal mask.

Weights arrive in the program's layout (they are data, made from the
seed): ``embed [V, d]``, ``ln_f [d]`` and ``layers`` stacked over depth
with ``ln1, ln2 [L, d]``, ``wq, wk, wv [L, d, H, hd]``, ``wo [L, H, hd,
d]``, ``w1, w3 [L, d, f]``, ``w2 [L, f, d]``.  They are stored in
bfloat16; each layer is widened to float32 as the scan reaches it, so a
774M-parameter model is never held twice.

On a TPU a float32 matmul runs in reduced precision unless told
otherwise, so every entry point runs under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
EPS = 1e-6
ROPE_THETA = 10000.0


def rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) \
        * scale


def rope(x, positions):
    """x [B, S, H, D]: rotate the pairs (i, i + D/2) by position * theta
    ** (-i / (D/2))."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.log(ROPE_THETA) * jnp.arange(half, dtype=F32)
                    / half)
    ang = positions.astype(F32)[:, None] * freqs            # [S, half]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(x, lp, positions):
    """One decoder block on x [B, S, d] with one layer's f32 weights."""
    S, D = x.shape[1], lp["wq"].shape[-1]
    h = rmsnorm(x, lp["ln1"])
    q = rope(jnp.einsum("bsd,dhk->bshk", h, lp["wq"]), positions)
    k = rope(jnp.einsum("bsd,dhk->bshk", h, lp["wk"]), positions)
    v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k) * D ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bhqs,bshk->bqhk", probs, v)
    x = x + jnp.einsum("bshk,hkd->bsd", attn, lp["wo"])
    h = rmsnorm(x, lp["ln2"])
    ff = jax.nn.silu(h @ lp["w1"]) * (h @ lp["w3"])
    return x + ff @ lp["w2"]


def hidden(params: Dict[str, Any], tokens):
    """tokens [B, S] -> final normed hidden [B, S, d], float32."""
    positions = jnp.arange(tokens.shape[1])
    x = params["embed"].astype(F32)[tokens]

    def body(x, lp):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        return block(x, lp, positions), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return rmsnorm(x, params["ln_f"].astype(F32))


def _logits_last(params, tokens, last: int):
    x = hidden(params, tokens)[:, -last:]
    return x @ params["embed"].astype(F32).T


def _loss_sum(params, tokens, targets):
    logits = hidden(params, tokens) @ params["embed"].astype(F32).T
    lse = jax.nn.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None],
                               -1)[..., 0]
    mask = (targets >= 0).astype(F32)
    return jnp.sum((lse - true) * mask), jnp.sum(mask)


@functools.partial(jax.jit, static_argnames=("last",))
def logits_last(params, tokens, last: int):
    """Logits [B, last, V] at the last ``last`` positions of a full
    forward over tokens [B, S]: what prefill-then-decode through a cache
    must reproduce."""
    with jax.default_matmul_precision("highest"):
        return _logits_last(params, tokens, last)


@jax.jit
def loss_sum_and_grads(params, tokens, targets):
    """(sum of next-token NLL, number of targets, d sum / d params as
    float32) for one chunk of a batch; the caller adds chunks up."""
    with jax.default_matmul_precision("highest"):
        (s, n), g = jax.value_and_grad(
            lambda p: _loss_sum(p, tokens, targets), has_aux=True)(
                jax.tree.map(lambda a: a.astype(F32), params))
        return s, n, g


def loss_and_grad_sums(params, tokens, targets, chunk: int):
    """(sum of NLL, number of targets, summed gradients) over a batch,
    accumulated ``chunk`` sequences at a time; nothing is read back, so
    calls on different devices run side by side."""
    total = count = 0.0
    grads = None
    for i in range(0, tokens.shape[0], chunk):
        s, n, g = loss_sum_and_grads(params, tokens[i:i + chunk],
                                     targets[i:i + chunk])
        total, count = total + s, count + n
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return total, count, grads


def loss_and_grad_norm(params, tokens, targets, chunk: int):
    """Mean NLL and the global L2 norm of its gradient over a batch."""
    total, count, grads = loss_and_grad_sums(params, tokens, targets, chunk)
    sq = sum(jnp.sum(jnp.square(g / count)) for g in jax.tree.leaves(grads))
    return float(total / count), float(jnp.sqrt(sq))
