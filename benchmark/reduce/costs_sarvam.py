"""Shapes and operation counts of the ``sarvam`` family (latent
attention in every layer, one leading dense FFN, a shared expert beside
a routed expert layer in the others), from the configuration file's own
keys.  Peaks, ``mfu_percent`` and ``roofline_percent`` stay
``reduce/costs.py``'s.  The attention paths are the ones
``reduce/costs_longcat.py`` prices (the same pool, kernels and
absorption), one sublayer a layer."""

from __future__ import annotations

from typing import Any, Dict


def model_dims(config: Dict[str, Any]) -> Dict[str, int]:
    """Shapes of the stack as it is run."""
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    dense = config["first_k_dense_replace"]
    return {"d": config["hidden_size"], "L": config["num_hidden_layers"],
            "dense_layers": dense,
            "routed_layers": config["num_hidden_layers"] - dense,
            "H": config["num_attention_heads"],
            "rank": rank, "rope": rope, "row": rank + rope,
            "nope": config["qk_nope_head_dim"], "v": config["v_head_dim"],
            "f": config["intermediate_size"],
            "fe": config["moe_intermediate_size"],
            "fs": config["num_shared_experts"]
            * config["moe_intermediate_size"],
            "held": len(config["model"]["kwargs"]["held_experts"]),
            "experts": config["published"]["num_experts"],
            "top_k": config["num_experts_per_tok"],
            "V": config["model"]["kwargs"]["vocab_size"]}


def decode_attention_cost(config: Dict[str, Any], context_tokens: float,
                          sequences: float) -> Dict[str, float]:
    """One decode's attention path over the latent rows, every layer
    (everything under ``attn/decode_pallas/``, which is what
    ``decode_attn_ms_per_tick`` times): a context token's row (``rank +
    rope`` bfloat16 values) read once for each sequence that holds it
    and met with every head's absorbed query (``rank + rope``
    multiply-adds a head) and its values' accumulation (``rank`` a
    head); the absorbed queries read and the latent outputs written once
    a sequence; both halves of ``W_kvb`` (``H x rank x (nope + v)``
    values) read once a decode whatever is live, and every sequence's
    query and output through them.  The projections from and to the
    hidden state (``q``, ``kv_a``, ``o``) are the model step's."""
    m = model_dims(config)
    per_token = m["H"] * (m["row"] + m["rank"]) * 2.0
    w_kvb = m["H"] * m["rank"] * (m["nope"] + m["v"])
    return {"flops": m["L"] * (context_tokens * per_token
                               + sequences * w_kvb * 2.0),
            "bytes": m["L"] * 2.0 * (
                context_tokens * m["row"]
                + sequences * m["H"] * (m["row"] + m["rank"])
                + w_kvb)}


def prefill_attention_cost(config: Dict[str, Any], bucket: float,
                           cached: float) -> Dict[str, float]:
    """One prefill's attention kernels, every layer: ``bucket`` queries
    (the padded ones too: the kernel computes them) at positions
    ``cached ..`` each meet the keys not past their own, ``nope + rope``
    multiply-adds a head for the score and ``v`` for the value; the
    least bytes read the queries, the materialised K and V of the
    positions seen and the rows' rotary part once, and write the output
    (bfloat16)."""
    m = model_dims(config)
    pairs = bucket * cached + bucket * (bucket + 1) / 2.0
    seen = cached + bucket
    return {"flops": m["L"] * pairs * m["H"] * 2.0 * (
                m["nope"] + m["rope"] + m["v"]),
            "bytes": m["L"] * 2.0 * (
                bucket * m["H"] * (m["nope"] + m["rope"] + m["v"])
                + seen * (m["H"] * (m["nope"] + m["v"]) + m["rope"]))}


def decode_moe_cost(config: Dict[str, Any], experts_hit: float,
                    held_picks: float) -> Dict[str, float]:
    """One decode's routed experts (``moe/experts``, with the router
    around it), every routed layer together: ``experts_hit`` is the held
    experts at least one row picked, summed over the step's expert
    layers, and ``held_picks`` the picks on them (what the fetch's span
    carries as ``moe_hit`` and ``moe_held``).  The least bytes read each
    hit expert's three matrices (``3 x d x fe`` bfloat16 values) once;
    the work is a pick's three products.  The router's own matrix, the
    rows and the shared expert are not priced (the shared expert's time
    is taken off what this is set against)."""
    m = model_dims(config)
    matrices = 3.0 * m["d"] * m["fe"]
    return {"flops": held_picks * matrices * 2.0,
            "bytes": experts_hit * matrices * 2.0}
