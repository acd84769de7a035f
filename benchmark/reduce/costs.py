"""The arithmetic the yardstick rests on: operations and bytes a piece of
work needs, computed from shapes, and the chip's peaks.  Kept here, under
the benchmark's paths, so that no PR that claims a gain can change it.
``telemetry/flops.py`` has the program's own copy of the FLOPs count; the
two agree (``benchmark/tests``), and the program's is not read."""

from __future__ import annotations

import json
import os
from typing import Any, Dict

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def chip_peaks(device_kind: str) -> Dict[str, float]:
    kind = device_kind.lower()
    with open(_PEAKS) as f:
        chips = json.load(f)["chips"]
    for key, peaks in chips.items():
        if key in kind:
            return peaks
    raise ValueError(f"no peaks on record for device_kind "
                     f"{device_kind!r}: add it to reduce/peaks.json "
                     "with its source")


def model_dims(config: Dict[str, Any]) -> Dict[str, int]:
    """Shapes of the block as it is run, from a configuration file."""
    d, L, H = config["n_embd"], config["n_layer"], config["n_head"]
    ffn = config["model"]["ffn"]
    return {"d": d, "L": L, "H": H, "hd": d // H,
            "f": ffn["width"], "ffn_matrices": ffn["matrices"],
            "V": config["model"]["kwargs"]["vocab_size"]}


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """Matmul FLOPs one token of a causal length-``seq`` sequence needs
    in forward + backward (3 x forward), 2 per multiply-add.  Recomputed
    operations (flash attention's and flash-CE's second passes) are not
    counted: this prices the model, not the schedule."""
    m = model_dims(config)
    d, H, hd, f = m["d"], m["H"], m["hd"], m["f"]
    qkv = 3 * 2 * d * H * hd
    attn = 2 * 2 * seq * H * hd / 2          # QK^T and PV, causal half
    out = 2 * H * hd * d
    ffn = m["ffn_matrices"] * 2 * d * f      # swiglu: w1, w3, w2
    fwd = m["L"] * (qkv + attn + out + ffn) + 2 * d * m["V"]
    return 3.0 * fwd


def mfu_percent(tokens_per_s_per_chip: float, config: Dict[str, Any],
                seq: int, device_kind: str) -> float:
    peak = chip_peaks(device_kind)["bf16_tflops"] * 1e12
    return 100.0 * tokens_per_s_per_chip * train_flops_per_token(
        config, seq) / peak


def train_attention_cost(config: Dict[str, Any], batch: int, seq: int
                         ) -> Dict[str, float]:
    """One train step's attention kernels (forward + backward, every
    layer) on ``batch`` sequences per chip: the FLOPs the algorithm
    needs (causal: half the S x S square; backward is 2.5 x forward with
    flash attention's recomputation of the scores, which an algorithm
    that keeps O(S) memory must do) and the least bytes (q, k, v, o read
    or written once forward; q, k, v, o, do read and dq, dk, dv written
    backward; bf16)."""
    m = model_dims(config)
    per_layer_fwd = 2 * 2 * batch * m["H"] * seq * seq * m["hd"] / 2
    flops = m["L"] * per_layer_fwd * 3.5
    tensor = batch * seq * m["H"] * m["hd"] * 2
    return {"flops": flops, "bytes": m["L"] * tensor * (4 + 8)}


def decode_attention_cost(config: Dict[str, Any], context_tokens: float,
                          sequences: float) -> Dict[str, float]:
    """One decode tick's attention over all layers: every live context
    token's K and V are read once (bf16), 4 FLOPs per token, head and
    channel."""
    m = model_dims(config)
    kv = context_tokens * m["H"] * m["hd"]
    return {"flops": m["L"] * 4.0 * kv,
            "bytes": m["L"] * (2 * kv * 2
                               + 2 * sequences * m["H"] * m["hd"] * 2)}


def roofline_percent(cost: Dict[str, float], seconds: float,
                     device_kind: str) -> float:
    """The least time the chip could take for ``cost`` over the time it
    took."""
    peaks = chip_peaks(device_kind)
    least = max(cost["flops"] / (peaks["bf16_tflops"] * 1e12),
                cost["bytes"] / (peaks["hbm_gbps"] * 1e9))
    return 100.0 * least / seconds
