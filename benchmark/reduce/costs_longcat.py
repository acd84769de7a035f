"""Shapes and operation counts of the ``longcat`` family (a block of two
latent-attention sublayers, two dense FFNs and one expert layer), from
the configuration file's own keys.  Peaks, ``mfu_percent`` and
``roofline_percent`` stay ``reduce/costs.py``'s."""

from __future__ import annotations

from typing import Any, Dict


def model_dims(config: Dict[str, Any]) -> Dict[str, int]:
    """Shapes of the block as it is run."""
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    return {"d": config["hidden_size"], "L": config["num_layers"],
            "sublayers": 2 * config["num_layers"],
            "H": config["num_attention_heads"],
            "rank": rank, "rope": rope, "row": rank + rope,
            "nope": config["qk_nope_head_dim"], "v": config["v_head_dim"],
            "q_rank": config["q_lora_rank"],
            "f": config["ffn_hidden_size"],
            "fe": config["expert_ffn_hidden_size"],
            "held": len(config["model"]["kwargs"]["held_experts"]),
            "top_k": config["moe_topk"],
            "V": config["model"]["kwargs"]["vocab_size"]}


def decode_attention_cost(config: Dict[str, Any], context_tokens: float,
                          sequences: float) -> Dict[str, float]:
    """One decode's attention path over the latent rows, every sublayer
    (everything under ``attn/decode_pallas/``, which is what
    ``decode_attn_ms_per_tick`` times).  The kernel: a context token's
    row (``rank + rope`` bfloat16 values) is read once for each sequence
    that holds it, and meets every head's absorbed query (``rank + rope``
    multiply-adds a head) and its values' accumulation (``rank`` a
    head); the absorbed queries are read and the latent outputs written
    once a sequence.  The absorption around it: both halves of ``W_kvb``
    (``H x rank x (nope + v)`` values) are read once a decode whatever
    is live, and every sequence's query and output go through them.  The
    projections from and to the hidden state (``q_a``, ``q_b``, ``kv_a``,
    ``o``) are the model step's, not this path's, and are not priced."""
    m = model_dims(config)
    per_token = m["H"] * (m["row"] + m["rank"]) * 2.0
    w_kvb = m["H"] * m["rank"] * (m["nope"] + m["v"])
    return {"flops": m["sublayers"] * (context_tokens * per_token
                                       + sequences * w_kvb * 2.0),
            "bytes": m["sublayers"] * 2.0 * (
                context_tokens * m["row"]
                + sequences * m["H"] * (m["row"] + m["rank"])
                + w_kvb)}


def prefill_attention_cost(config: Dict[str, Any], bucket: float,
                           cached: float) -> Dict[str, float]:
    """One prefill's attention kernels, every sublayer: ``bucket``
    queries (the padded ones too: the kernel computes them) at positions
    ``cached ..`` each meet the keys not past their own, ``nope + rope``
    multiply-adds a head for the score and ``v`` for the value; the
    least bytes read the queries, the materialised K and V of the
    positions seen and the rows' rotary part once, and write the output
    (bfloat16)."""
    m = model_dims(config)
    pairs = bucket * cached + bucket * (bucket + 1) / 2.0
    seen = cached + bucket
    return {"flops": m["sublayers"] * pairs * m["H"] * 2.0 * (
                m["nope"] + m["rope"] + m["v"]),
            "bytes": m["sublayers"] * 2.0 * (
                bucket * m["H"] * (m["nope"] + m["rope"] + m["v"])
                + seen * (m["H"] * (m["nope"] + m["v"]) + m["rope"]))}
