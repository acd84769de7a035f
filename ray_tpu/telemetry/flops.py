"""Analytic FLOPs + chip-peak accounting for the MFU figure.

The headline bench has always used the ``6·N`` params approximation;
the telemetry layer wants the *analytic* count from ``GPTConfig`` —
per-matmul, attention included, remat recompute charged — so the MFU
in a step record means "fraction of the MXU the schedule actually
earned" rather than "fraction of a rule of thumb".  The chip peak
table lives here too.
"""

from __future__ import annotations

from typing import Optional

# bf16 peak TFLOP/s per chip, keyed by a substring of ``device_kind``
# (Google Cloud TPU documentation, per-generation system pages)
CHIP_PEAK_TFLOPS = {
    "v4": 275.0,
    "v5 lite": 197.0, "v5e": 197.0,
    "v5p": 459.0,
    "v6 lite": 918.0, "v6e": 918.0,
}


def chip_peak_tflops(device=None) -> float:
    """bf16 peak TFLOP/s of ``device`` (default: first visible device).

    A device that is not in the table is an error, not a default: an MFU
    priced against some other chip's peak is a wrong number under a
    device metric's name."""
    if device is None:
        import jax
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "").lower()
    for key, peak in CHIP_PEAK_TFLOPS.items():
        if key in kind:
            return peak
    raise ValueError(
        f"no bf16 peak on record for device_kind={kind!r} (known: "
        f"{sorted(CHIP_PEAK_TFLOPS)}); add it to CHIP_PEAK_TFLOPS with "
        "its source, or pass the peak explicitly")


def gpt_fwd_flops_per_token(cfg, seq: int, *, causal: bool = True,
                            held_picks_per_token: Optional[float] = None
                            ) -> float:
    """Matmul FLOPs per token of ONE forward pass of ``cfg`` at ``seq``.

    Counted per token of a length-``seq`` sequence (2 FLOPs per MAC):

    - qkv projections: ``2·d·hd·(H + 2·Hkv)`` (K and V have
      ``n_kv_heads`` heads where the config groups them)
    - attention score + value matmuls: ``2 · 2·seq·H·hd`` (each is an
      ``S×S×(H·hd)`` matmul per sequence → ``2·seq·H·hd`` per token),
      halved under a causal mask; a window layer's rows see
      ``visible_keys(seq, window)`` keys on average instead of
      ``seq / 2``
    - output projection: ``2·H·hd·d``
    - FFN: ``2·d·f`` per matmul — 3 matmuls for swiglu (w1, w3, w2),
      2 for gelu; MoE charges the gate (``2·d·E``) plus ``top_k``
      experts' FFN; the dropless layer (``held_experts``) the router
      over the deployment's experts plus the *held* picks' FFN:
      ``held_picks_per_token`` where measured, else its expectation
      under uniform routing, ``top_k · held / experts``
    - lm head: ``2·d·V``

    Embedding lookups are gathers (no MXU FLOPs) and norms/activations
    are vector-unit work — both excluded, matching how published MFU
    figures count.
    """
    d, H, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    f, L, V = cfg.ff_dim, cfg.n_layers, cfg.vocab_size
    qkv = 2 * d * hd * (H + 2 * getattr(cfg, "kv_heads", H))
    out = 2 * H * hd * d
    ffn_matmuls = 3 if cfg.act == "swiglu" else 2
    ffn = ffn_matmuls * 2 * d * f
    if getattr(cfg, "dropless", False):
        if held_picks_per_token is None:
            held_picks_per_token = (cfg.moe_top_k * len(cfg.held_experts)
                                    / cfg.n_routed_experts)
        ffn = 2 * d * cfg.n_routed_experts + held_picks_per_token * ffn
    elif cfg.n_experts > 0:
        ffn = 2 * d * cfg.n_experts + cfg.moe_top_k * ffn
    total = 2 * d * V
    for kind in getattr(cfg, "layer_kinds", ("full",) * L):
        keys = seq / 2 if causal else seq
        if kind == "window" and causal:
            keys = visible_keys(seq, cfg.window)
        total += qkv + 2 * 2 * keys * H * hd + out + ffn
    return total


def visible_keys(seq: int, window: int) -> float:
    """The keys a row of a causal window layer sees, its own included,
    on average over a length-``seq`` sequence (``seq / 2``, the full
    layers' convention, where the window does not bind)."""
    if window >= seq:
        return seq / 2
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def gpt_train_flops_per_token(cfg, seq: int, *, causal: bool = True,
                              ce_recompute: Optional[bool] = None,
                              held_picks_per_token: Optional[float] = None
                              ) -> float:
    """Matmul FLOPs per token of ONE training step of ``cfg`` at ``seq``.

    ``3×`` the forward (fwd + 2× backward), plus the recompute the
    configured schedule actually pays: ``cfg.remat`` re-runs every
    block's forward in the backward (+1× the layer stack), and a
    rematerializing CE recomputes the head matmul once (``+2·d·V``).
    ``ce_recompute`` says whether the loss head pays that recompute —
    True for the chunked-remat head and for flash-CE (four vocabulary
    matmuls), False for the saved-logits head (three); ``None`` infers
    it from ``cfg.ce_chunk`` alone, which is what the dispatch follows
    (``ops.flash_ce.uses_flash_ce``) unless a ``ce_mode="flash"`` pin
    overrides it — the telemetry recorder, which knows the pin, passes
    what the gate said.
    """
    fwd = gpt_fwd_flops_per_token(
        cfg, seq, causal=causal, held_picks_per_token=held_picks_per_token)
    head = 2 * cfg.d_model * cfg.vocab_size
    total = 3 * fwd
    if cfg.remat:
        total += fwd - head          # one recompute of the layer stack
    if ce_recompute is None:
        ce_recompute = getattr(cfg, "ce_chunk", 0) >= 0
    if ce_recompute:
        total += head                # one recompute of the head matmul
    return total


def mfu(tokens_per_sec_per_device: float, flops_per_token: float,
        peak_tflops: Optional[float] = None) -> float:
    """Model FLOPs utilization: useful FLOP/s over the chip peak."""
    peak = peak_tflops or chip_peak_tflops()
    return tokens_per_sec_per_device * flops_per_token / (peak * 1e12)
