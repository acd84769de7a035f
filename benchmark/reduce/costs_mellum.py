"""Shapes and operation counts of the ``mellum`` family (grouped K/V
heads, window layers mixed with full ones, every FFN a routed expert
layer of which this chip holds a share), from the configuration file's
own keys.  Peaks, ``mfu_percent`` and ``roofline_percent`` stay
``reduce/costs.py``'s."""

from __future__ import annotations

from typing import Any, Dict


def model_dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """Shapes of the block as it is run.  ``held`` is the file's
    ``num_experts`` (the experts this chip holds); the router's width is
    the published count."""
    L = config["num_hidden_layers"]
    return {"d": config["hidden_size"], "L": L,
            "H": config["num_attention_heads"],
            "KV": config["num_key_value_heads"], "hd": config["head_dim"],
            "fe": config["moe_intermediate_size"],
            "held": config["num_experts"],
            "experts": config["published"]["num_experts"],
            "top_k": config["num_experts_per_tok"],
            "window": config["sliding_window"],
            "kinds": list(config["layer_types"][:L]),
            "V": config["vocab_size"]}


def visible_keys(seq: int, window: int) -> float:
    """The keys a row of a causal window layer sees on average over a
    length-``seq`` sequence, its own included (``seq / 2``, the full
    layers' convention, where the window does not bind)."""
    if window >= seq:
        return seq / 2
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def _keys(m: Dict[str, Any], kind: str, seq: int) -> float:
    return (visible_keys(seq, m["window"]) if kind == "sliding_attention"
            else seq / 2)


def held_picks_per_token(config: Dict[str, Any]) -> float:
    """Picks a token that land on an expert held here, in expectation
    under uniform routing."""
    m = model_dims(config)
    return m["top_k"] * m["held"] / m["experts"]


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """Matmul FLOPs one token of a causal length-``seq`` sequence needs
    in forward + backward (3 x forward), 2 a multiply-add: projections
    with the K/V heads there are, the visible pairs of each layer's
    kind, the router over every expert, the *held* picks' three
    matrices (``top_k x held / experts`` a token), the head over the
    vocabulary slice.  Recomputation is not counted."""
    m = model_dims(config)
    d, H, KV, hd = m["d"], m["H"], m["KV"], m["hd"]
    proj = 2 * d * hd * (H + 2 * KV) + 2 * H * hd * d
    ffn = 2 * d * m["experts"] + held_picks_per_token(config) * 3 * 2 * d \
        * m["fe"]
    fwd = 2 * d * m["V"]
    for kind in m["kinds"]:
        fwd += proj + 2 * 2 * _keys(m, kind, seq) * H * hd + ffn
    return 3.0 * fwd


def train_attention_cost(config: Dict[str, Any], batch: int, seq: int
                         ) -> Dict[str, float]:
    """One train step's attention kernels (forward + backward, every
    layer) on ``batch`` sequences: the FLOPs of the pairs each layer's
    kind needs (backward 2.5 x forward with the recomputed scores, as
    ``costs.py`` counts it) and the least bytes: q and o read or written
    once forward and q, o, do read and dq written backward at the query
    heads' size; k and v read forward and backward and dk, dv written,
    at the K/V heads' size (bfloat16)."""
    m = model_dims(config)
    flops = sum(2 * 2 * batch * m["H"] * seq * _keys(m, kind, seq)
                * m["hd"] * 3.5 for kind in m["kinds"])
    q = batch * seq * m["H"] * m["hd"] * 2
    kv = batch * seq * m["KV"] * m["hd"] * 2
    return {"flops": flops, "bytes": m["L"] * 6.0 * (q + kv)}


def train_moe_cost(config: Dict[str, Any], tokens: int) -> Dict[str, float]:
    """One train step's grouped products over the held picks, every
    layer, forward and backward: three matrices a pick, 2 FLOPs a
    multiply-add, three passes (forward, and the gradients in the rows
    and in the matrices); the least bytes read the held experts'
    matrices forward and backward and write their gradients, and move
    each pick's row in and out of each product (bfloat16).  The picks
    are the expectation under uniform routing, whatever implements the
    product."""
    m = model_dims(config)
    picks = tokens * held_picks_per_token(config)
    matrices = m["held"] * 3 * m["d"] * m["fe"]
    rows = picks * (m["d"] + m["fe"])          # in and out of a product
    return {"flops": m["L"] * picks * 3 * 2 * 3 * m["d"] * m["fe"],
            "bytes": m["L"] * 2.0 * (3 * matrices + 3 * 3 * rows)}
