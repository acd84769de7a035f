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


def gpt_fwd_flops_per_token(cfg, seq: int, *, causal: bool = True) -> float:
    """Matmul FLOPs per token of ONE forward pass of ``cfg`` at ``seq``.

    Counted per token of a length-``seq`` sequence (2 FLOPs per MAC):

    - qkv projections: ``3 · 2·d·H·hd``
    - attention score + value matmuls: ``2 · 2·seq·H·hd`` (each is an
      ``S×S×(H·hd)`` matmul per sequence → ``2·seq·H·hd`` per token),
      halved under a causal mask
    - output projection: ``2·H·hd·d``
    - FFN: ``2·d·f`` per matmul — 3 matmuls for swiglu (w1, w3, w2),
      2 for gelu; MoE charges the gate (``2·d·E``) plus ``top_k``
      experts' FFN
    - lm head: ``2·d·V``

    Embedding lookups are gathers (no MXU FLOPs) and norms/activations
    are vector-unit work — both excluded, matching how published MFU
    figures count.
    """
    d, H, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    f, L, V = cfg.ff_dim, cfg.n_layers, cfg.vocab_size
    qkv = 3 * 2 * d * H * hd
    attn = 2 * 2 * seq * H * hd
    if causal:
        attn /= 2
    out = 2 * H * hd * d
    ffn_matmuls = 3 if cfg.act == "swiglu" else 2
    ffn = ffn_matmuls * 2 * d * f
    if cfg.n_experts > 0:
        ffn = 2 * d * cfg.n_experts + cfg.moe_top_k * ffn
    layer = qkv + attn + out + ffn
    return L * layer + 2 * d * V


def gpt_train_flops_per_token(cfg, seq: int, *, causal: bool = True,
                              ce_recompute: Optional[bool] = None
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
    fwd = gpt_fwd_flops_per_token(cfg, seq, causal=causal)
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
