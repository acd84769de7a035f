"""LoRA factor math: init, merged-weights oracle, and the device bank.

Canonical adapter layout (host or device, per adapter):

    {"<t>_a": [L, in_t, r], "<t>_b": [L, r, out_t]}   for t in targets

with every target expressed as a flattened 2-D matmul:

    target   in       out      stacked base weight
    wq/wk/wv d        H*hd     [L, d, H, hd]
    wo       H*hd     d        [L, H, hd, d]
    w1/w3    d        f        [L, d, f]
    w2       f        d        [L, f, d]

The engine-side **bank** stacks ``N = cache_slots + 1`` adapters along
a new leading axis (``[N, L, in, r]`` / ``[N, L, r, out]``) plus a
per-slot f32 ``scale`` vector.  Slot 0 is all-zeros with scale 0 — the
exact identity every adapter-free request rides.  The bank is a plain
pytree of device arrays, so it travels through AOT executables as a
call argument (like params) and is hot-swapped with eager ``.at[].set``
updates: zero recompiles on load, evict, or version republish.
"""

from __future__ import annotations

import hashlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.adapters.config import LoraConfig


def effective_targets(cfg, lcfg: LoraConfig) -> Tuple[str, ...]:
    """``lcfg.targets`` minus targets the architecture doesn't have."""
    drop = set()
    if cfg.act != "swiglu":
        drop.add("w3")
    if cfg.n_experts > 0:
        raise ValueError("LoRA adapters are dense-FFN only (MoE layers "
                         "route tokens per-expert; a per-slot delta on "
                         "the expert matmuls is not yet supported)")
    return tuple(t for t in lcfg.targets if t not in drop)


def target_dims(cfg) -> Dict[str, Tuple[int, int]]:
    """``{target: (in_dim, out_dim)}`` in the flattened 2-D view."""
    d, hk, f = cfg.d_model, cfg.n_heads * cfg.head_dim, cfg.ff_dim
    dims = {"wq": (d, hk), "wk": (d, hk), "wv": (d, hk), "wo": (hk, d),
            "w1": (d, f), "w2": (f, d)}
    if cfg.act == "swiglu":
        dims["w3"] = (d, f)
    return dims


def init_adapter(cfg, lcfg: LoraConfig, key, *, random_b: bool = False,
                 dtype=None) -> Dict[str, Any]:
    """One adapter's host factors.  Standard LoRA init (A gaussian,
    B zeros → the fresh adapter is an exact no-op); ``random_b=True``
    gives a non-identity adapter for parity tests and benchmarks."""
    dt = dtype or cfg.dtype
    L, r = cfg.n_layers, lcfg.rank
    out: Dict[str, Any] = {}
    for t in effective_targets(cfg, lcfg):
        i, o = target_dims(cfg)[t]
        key, ka, kb = jax.random.split(key, 3)
        out[f"{t}_a"] = (jax.random.normal(ka, (L, i, r)) * i ** -0.5) \
            .astype(dt)
        b = jax.random.normal(kb, (L, r, o)) * r ** -0.5 if random_b \
            else jnp.zeros((L, r, o))
        out[f"{t}_b"] = b.astype(dt)
    return out


def merge_adapter(params: Dict[str, Any], adapter: Dict[str, Any],
                  cfg, *, scale: float = 1.0) -> Dict[str, Any]:
    """The parity oracle: new params with ``W += scale * A @ B`` folded
    into every adapted matmul (f32 accumulation, cast back to the
    param dtype).  An engine serving ``adapter`` must match an engine
    serving these merged weights."""
    layers = dict(params["layers"])
    for name in ("wq", "wk", "wv", "wo", "w1", "w3", "w2"):
        a = adapter.get(f"{name}_a")
        if a is None or name not in layers:
            continue
        b = adapter[f"{name}_b"]
        w = layers[name]
        delta = scale * jnp.einsum(
            "lir,lro->lio", a.astype(jnp.float32), b.astype(jnp.float32))
        layers[name] = (w.astype(jnp.float32)
                        + delta.reshape(w.shape)).astype(w.dtype)
    out = dict(params)
    out["layers"] = layers
    return out


def bank_zeros(cfg, lcfg: LoraConfig, *, dtype=None) -> Dict[str, Any]:
    """Fresh all-identity bank: every slot zeroed, scale 0."""
    dt = dtype or cfg.dtype
    N, L, r = lcfg.bank_slots, cfg.n_layers, lcfg.rank
    bank: Dict[str, Any] = {"scale": jnp.zeros((N,), jnp.float32)}
    for t in effective_targets(cfg, lcfg):
        i, o = target_dims(cfg)[t]
        bank[f"{t}_a"] = jnp.zeros((N, L, i, r), dt)
        bank[f"{t}_b"] = jnp.zeros((N, L, r, o), dt)
    return bank


def bank_install(bank: Dict[str, Any], slot: int, adapter: Dict[str, Any],
                 *, scale: float = 1.0) -> Dict[str, Any]:
    """Functionally overwrite one bank slot with an adapter's factors.

    Eager ``.at[].set`` — dispatches a handful of device updates, never
    touches the compile cache.  Targets absent from ``adapter`` are
    zeroed (the slot must not leak a previous tenant's factors)."""
    if slot <= 0:
        raise ValueError(f"bank slot {slot} is not writable (slot 0 is "
                         "the reserved identity)")
    out = dict(bank)
    for k, v in bank.items():
        if k == "scale":
            out[k] = v.at[slot].set(np.float32(scale))
            continue
        src = adapter.get(k)
        if src is None:
            out[k] = v.at[slot].set(0)
        else:
            out[k] = v.at[slot].set(jnp.asarray(src, v.dtype))
    return out


def bank_mismatch(bank: Dict[str, Any],
                  adapter: Any) -> Optional[str]:
    """Reason ``adapter``'s factors cannot install into ``bank``
    (wrong rank / target set / layer dims), else None.

    The serving engine gates every store fetch through this before
    ``bank_install``: a tenant publishing factors of a different
    geometry must surface as a typed per-request
    ``AdapterUnavailableError``, not as a jax shape error escaping the
    replica's step loop.  Targets *absent* from the adapter are fine
    (the install zeroes them); targets the bank does not carry are a
    mismatch — silently dropping them would diverge from the merged
    oracle."""
    if not isinstance(adapter, dict):
        return (f"payload is {type(adapter).__name__}, "
                "expected a factor dict")
    targets = tuple(k for k in bank if k != "scale")
    for k, v in adapter.items():
        if k == "scale":
            continue
        ref = bank.get(k)
        if ref is None:
            return (f"factor {k!r} has no matching bank target "
                    f"(bank carries {targets})")
        shape = tuple(getattr(v, "shape", None)
                      or np.asarray(v).shape)
        row = tuple(ref.shape[1:])
        if shape != row:
            return (f"factor {k!r} shape {shape} != bank row "
                    f"shape {row}")
    return None


def bank_clear(bank: Dict[str, Any], slot: int) -> Dict[str, Any]:
    """Zero a slot back to identity (evict without replacement)."""
    out = dict(bank)
    for k, v in bank.items():
        out[k] = v.at[slot].set(0)
    return out


def adapter_nbytes(adapter: Dict[str, Any]) -> int:
    """Publish payload size — the rank·(in+out)·L·itemsize sum, against
    a full-params publish."""
    total = 0
    for leaf in jax.tree.leaves(adapter):
        arr = np.asarray(jax.device_get(leaf)) if hasattr(leaf, "dtype") \
            else np.asarray(leaf)
        total += arr.nbytes
    return total


def salt_bytes(model_id: Optional[str], version: int) -> bytes:
    """Prefix-cache chain-root salt for an (adapter, version) pair.

    Adapter K/V differs from base K/V for identical token prefixes, so
    salted chains keep the r16 prefix index from ever aliasing
    tenants; a version republish changes the salt,
    so stale entries simply miss and age out of the LRU — no flush."""
    if not model_id:
        return b""
    return hashlib.blake2b(f"{model_id}@{version}".encode(),
                           digest_size=16).digest()
