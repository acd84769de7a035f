"""Token sampling for the decode loop: greedy / temperature / top-k / top-p.

Per-sequence PRNG: every request owns a key chain
``fold_in(PRNGKey(seed), n_generated)`` derived *inside* the jitted
sampler from its seed and generation count — a sequence's tokens are a
function of (seed, step) only, never of which slot it landed in or who
it was co-batched with.  That property is what makes continuous
batching transparent to callers (asserted by the solo-vs-batched test
in ``tests/test_inference.py``).

One executable per logits shape serves all four modes, and does only
the work the rows of *this call* ask for.  What a call needs is one
scalar of its own inputs (:func:`sample_path`), computed outside the
``vmap`` — a ``cond`` under ``vmap`` would become a ``select`` that runs
both sides — and a ``lax.switch`` on it picks one of three vmapped
bodies:

- ``plain``: no row has ``temperature > 0``.  Argmax and the chosen
  token's ``log_softmax``, nothing else.
- ``draw``: some row samples, and no sampling row sets ``top_k > 0`` or
  ``top_p < 1`` (the RL actors' setting).  The above plus temperature
  scaling and a Gumbel argmax.
- ``filter``: some sampling row filters.  The above plus the per-row
  top-k threshold and the top-p nucleus mask, computed on the sorted
  distribution and mapped back by probability threshold: the two
  full-vocabulary sorts, which only this body pays.

Each body is a prefix of the next and an unset filter is literally off
(``top_k == 0``, ``top_p >= 1``: ``z`` passes through untouched), so a
row's token and logprob are the same function of its own ``(logits,
seed, count, temperature, top_k, top_p)`` whichever body its co-batch
selected.  Inactive rows carry the null parameters (greedy) and never
force a draw or a sort.  The choice is made on the device from the
arrays the call is given: no second jitted function, no knob, so a
change in the mix of requests compiles nothing and costs no round trip.

The sampler also surfaces the chosen token's **model logprob** —
``log_softmax`` of the *raw* f32 logits at the sampled id, before any
temperature/top-k/top-p shaping.  That is the quantity both consumers
want: serve users get the model's own confidence in the streamed
token, and the RL actors (``ray_tpu.rl``) need ``log pi(a|s)`` under
the distribution the learner differentiates (the policy-gradient step
trains the plain softmax; at ``temperature=1, top_k=0, top_p=1`` the
behavior distribution and the model distribution coincide, so
REINFORCE stays on-policy).  Parity-tested against a teacher-forced
``log_softmax(forward(...))`` recompute in ``tests/test_inference.py``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling configuration.

    ``temperature <= 0`` is greedy (argmax; ``top_k``/``top_p``/``seed``
    are then irrelevant).  ``top_k = 0`` disables the top-k filter;
    ``top_p = 1.0`` disables the nucleus filter.

    ``model_id`` (r25 multi-tenant serving) selects the LoRA adapter
    this request decodes under (``None`` = the base model).  It rides
    the per-request path like every other knob — serve payload ->
    engine — where it resolves to a slot of the engine's adapter bank,
    loaded through the fleet :class:`~ray_tpu.adapters.AdapterStore`
    on miss; an unknown tenant surfaces the typed
    :class:`~ray_tpu.adapters.AdapterUnavailableError`."""
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    model_id: Optional[str] = None


# What a sampler call has to run, cheapest first; see the module
# docstring.  ``sample_path`` and the executable's ``lax.switch`` index
# into this.
SAMPLE_PATHS = ("plain", "draw", "filter")


def _path_index(temps, top_ks, top_ps, xp):
    """0 / 1 / 2 into ``SAMPLE_PATHS``: rows that filter are among the
    rows that sample, so the two ``any`` add up.  ``xp`` is ``numpy``
    on the host and ``jax.numpy`` inside the executable: one rule."""
    samples = temps > 0.0
    filters = samples & ((top_ks > 0) | (top_ps < 1.0))
    return xp.any(samples).astype(xp.int32) + xp.any(filters)


def sample_path(temps, top_ks, top_ps) -> str:
    """Which body the sampler runs for a call with these per-row
    parameters (host arrays): the ``path`` of the ``infer/sample`` span
    and of ``InferTelemetry``'s counter."""
    return SAMPLE_PATHS[int(_path_index(
        np.asarray(temps), np.asarray(top_ks), np.asarray(top_ps), np))]


def _sample_one(level, logits, seed, count, temp, top_k, top_p):
    """One row, doing the first ``level + 1`` of ``SAMPLE_PATHS``'s
    stages (``level`` is static: one traced body each)."""
    V = logits.shape[-1]
    l = logits.astype(jnp.float32)
    tok = jnp.argmax(l, -1).astype(jnp.int32)
    if level >= 1:
        z = l / jnp.maximum(temp, 1e-6)
        if level >= 2:
            # top-k: threshold at the k-th largest logit (0 = off)
            kth = jnp.sort(z)[::-1][jnp.clip(top_k - 1, 0, V - 1)]
            z = jnp.where((top_k > 0) & (z < kth), -jnp.inf, z)
            # top-p: keep the smallest prefix of the sorted
            # distribution whose mass reaches top_p (the first token
            # always survives), mapped back to vocab order by
            # probability threshold (1 = off: an f32 cumulative sum
            # would mask the far tail by rounding)
            probs = jax.nn.softmax(z)
            sp = jnp.sort(probs)[::-1]
            cum = jnp.cumsum(sp)
            keep = (cum - sp) < top_p
            thresh = jnp.min(jnp.where(keep, sp, jnp.inf))
            z = jnp.where((top_p < 1.0) & (probs < thresh), -jnp.inf, z)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), count)
        g = -jnp.log(-jnp.log(
            jax.random.uniform(key, (V,), minval=1e-20, maxval=1.0)))
        sampled = jnp.argmax(z + g, -1).astype(jnp.int32)
        tok = jnp.where(temp <= 0.0, tok, sampled)
    return tok, jax.nn.log_softmax(l)[tok]     # raw-logit distribution


_BODIES = tuple(jax.vmap(functools.partial(_sample_one, level))
                for level in range(len(SAMPLE_PATHS)))


def _sample(logits, seeds, counts, temps, top_ks, top_ps):
    return jax.lax.switch(
        _path_index(temps, top_ks, top_ps, jnp), _BODIES,
        logits, seeds, counts, temps, top_ks, top_ps)


@jax.jit
def sample_tokens_logprobs(logits, seeds, counts, temps, top_ks,
                           top_ps):
    """logits [B, V] f32; seeds/counts [B] i32; temps/top_ps [B] f32;
    top_ks [B] i32 -> (token ids [B] i32, chosen-token model logprobs
    [B] f32), row-independent.  The logprob is ``log_softmax`` of the
    raw logits at the chosen id (see module docstring)."""
    return _sample(logits, seeds, counts, temps, top_ks, top_ps)


@jax.jit
def sample_tokens(logits, seeds, counts, temps, top_ks, top_ps):
    """logits [B, V] f32; seeds/counts [B] i32; temps/top_ps [B] f32;
    top_ks [B] i32 -> sampled token ids [B] i32 (row-independent)."""
    return _sample(logits, seeds, counts, temps, top_ks, top_ps)[0]
