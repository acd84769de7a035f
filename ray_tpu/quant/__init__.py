"""``ray_tpu.quant`` — block-scaled int8 quantization utilities.

One shared layer for the two hottest byte streams in the system, both
of which move ``cfg.dtype`` (bf16/f32) today and halve with
block-scaled int8:

- the **int8 KV cache** (``ray_tpu.inference.kv_cache``): paged K/V
  stored as int8 with one scale per (position, head) lane vector,
  dequantized inside ``decode_attention``'s page blocks —
  roughly doubling decode-slot capacity per HBM byte;
- the **quantized overlap collectives**
  (``ray_tpu.parallel.overlap``): EQuARX-style (arXiv:2506.17615)
  quantize→transfer→dequantize weight all-gathers and
  stochastic-rounding grad reduce-scatters, halving
  ``collective_bytes_per_step`` wire totals.

Everything here is pure JAX (traces into compiled steps and into
shard_map collectives); the lane-aligned fast path and the padded
reference produce identical values for aligned shapes
(``tests/test_quant.py``).
"""

from ray_tpu.quant.block_scale import (INT8_MAX,  # noqa: F401
                                       data_salt,
                                       dequantize_block,
                                       quantize_block,
                                       quantize_block_ref,
                                       quant_error_bound,
                                       stochastic_key,
                                       wire_bytes)

__all__ = [
    "INT8_MAX", "quantize_block", "quantize_block_ref",
    "dequantize_block", "quant_error_bound", "wire_bytes",
    "stochastic_key", "data_salt",
]
