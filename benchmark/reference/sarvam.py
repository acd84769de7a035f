"""The plain reference for the ``sarvam`` family (sarvam-105b,
``model_type: sarvam_mla``: latent attention in every layer, a dense FFN
in the leading layer, a shared expert beside a sigmoid-scored expert
layer in the others): the forward in straightforward ``jax.numpy``,
float32, no kernels, no cache, no batching, the attention full (K and V
materialised a head, nothing absorbed).  It imports nothing from
``ray_tpu``.

With ``N`` = RMSNorm (``rms_norm_eps``) and ``R`` the ``deepseek_yarn``
rotation (``rope_scaling``)::

    A(h): q = N_q(h W_q) a head (192 channels) = [q_nope | q_rot]
          [c_kv | k_rot] = h W_kva;  c = N_kv(c_kv);  [k_nope | v] = c W_kvb
          q_rot, k_rot <- R;  k_rot shared by all heads
          o = softmax([q_nope|q_rot] . [k_nope|k_rot] * 192^-0.5 * m^2, causal) v
          m = 0.1 * mscale_all_dim * ln(factor) + 1;  out = o W_o
    layer 0:     y = x + A(N(x));  z = y + swiglu_dense(N(y))
    layers 1..:  y = x + A(N(x));  u = N(y)
                 s = sigmoid(u W_r);  T = top-k of (s + b)
                 w_e = scale * s_e / sum_{T} s   (e in T)
                 z = y + swiglu_shared(u) + sum_{e in T} w_e E_e(u)

Departures, each also under ``assumed`` in the configuration file:
``use_qk_norm`` is read as ``N_q`` and ``N_kv`` above; sigmoid scores,
selection by ``s + b``, the top-k renormalised, no groups; RoPE rotates
the pairs (i, i + D/2); ``R`` scales cos and sin by ``mscale``'s factor
over ``mscale_all_dim``'s (1 here); **an expert this chip does not hold
adds nothing** (``held_experts``: the cell is one chip's share of a
deployment, and the exchange that would bring the other chips' parts is
not run, here or in the program), while the sum the weights are
renormalised by runs over all ``k`` picks wherever they live.

Weights arrive in the program's layout (``ray_tpu/models/sarvam.py``):
``dense`` stacked over the leading dense layers and ``layers`` over the
routed ones, each with ``ln_attn, ln_ffn [L, d]``, ``wq [L, d, H, nope +
rope]``, ``q_head_norm [L, nope + rope]``, ``wkv_a [L, d, rank +
rope]``, ``kv_norm [L, rank]``, ``wk_b [L, H, rank, nope]``, ``wv_b [L,
H, rank, v]``, ``wo [L, H * v, d]``; ``dense`` with ``w_gate, w_up [L,
d, f]``, ``w_down [L, f, d]``; ``layers`` with ``router [L, d, E]``,
``router_bias [L, E]``, ``e_gate, e_up [L, held, d, fe]``, ``e_down [L,
held, fe, d]``, ``s_gate, s_up [L, d, fs]``, ``s_down [L, fs, d]``; and
``embed [V, d]``, ``ln_f [d]``, ``lm_head [d, V]``.  They are bfloat16
and each matrix is widened to float32 where it is used: attention a
head at a time, the dense FFN and the head a block of columns at a
time, the experts one at a time, so that the forward fits beside the
served model on one chip.

``config`` is the configuration file's dict and says what no weight's
shape does: ``num_experts_per_tok``, ``routed_scaling_factor``,
``rms_norm_eps``, ``rope_theta``, ``rope_scaling``,
``qk_rope_head_dim`` and, under ``model.kwargs``, ``held_experts``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

# ---------------------------------------------------------- tolerances --
# max|delta| / max|ref| over a request's decided rows, engine (bfloat16
# weights, activations and latent cache) against this file in float32.
# Chip evidence (my chip runs, PR 61: published widths, the dense layer
# and 5 routed layers, 200 and 333 fresh tokens, 32 rows a sample, all
# read by ``benchmark/controls/sarvam_check.py`` through the harness's
# own ``bench_check``, all at the draw's final constants).  The limit
# was chosen after call l: the clean check on 16 seeds, 1,024 rows
# (6100000101, ..203, ..307, ..409, ..613, ..717, ..819, ..923,
# 6100001021, ..1123, ..1229, 4800002115 and the next four), the faults
# and float8 on 6100000511, 3100000007, 6100001327 and 6100001429 (the
# call printed the limit of its day, 0.08, which moves no reading).
# Call m, the first under 0.082, read the faults on two more seeds
# (6100005731, 6100005833: the six below), and call n 18 seeds that
# took no part in any choice (16 clean, two with every fault;
# ``PERF.md`` section 6 names them): out of sample the largest of 1,152
# rows is 0.0499, the largest decided row 0.0378, float8's weaker check
# 0.134, every judged fault seen.  Under the limit (calls l and m):
# - a correct engine's rows read 0.0076 at the median; a tenth of them
#   carry a held pick that rounding flipped at the top-k's edge and read
#   0.015-0.0549, the largest of all 1,024, decided or not;
# - the rows this file decides (CHOICE_MARGIN): a sample's largest is
#   0.008-0.0414 over the 32 samples;
# - the router's logits in bfloat16 (``FAULTS``: ``router_bf16``, read
#   and not judged): 0.020-0.036 a sample: flips at the edge, no more.
# Over it, the weaker and the stronger sample of a check / the weakest
# check (a check holds only if both its samples do):
# - the next precision down, every matrix and every layer's input
#   rounded to float8_e4m3fn (``config["_round"]``): 0.110-0.138 / 0.116;
# - the yarn factor on the softmax's scale left out (``no_yarn_scale``):
#   0.117-0.152 / 0.130;
# - a held pick sent to the next held expert (``wrong_held``):
#   0.139-0.168 / 0.141;
# - softmax for sigmoid (``softmax``): 0.129-0.280 / 0.187;
# - the renormalisation left out (``no_renorm``) 0.556-0.716, the shared
#   expert left out (``no_shared``) 0.689-0.857, the query heads' norm
#   left out (``no_q_norm``) 0.886-1.054.
# The limit stands 1.49 x over the largest row a correct engine gave
# (1.98 x over its largest decided row) and 1.41 x under float8's
# weakest check, the weakest of all.
# **These readings are the draw's as much as the engine's**
# (``ray_tpu/models/sarvam.py:init_params`` has the other half: the
# draw also decides how evenly the router spreads its picks, which is
# the cell's work).  Sigmoid scores of a row's eight picks are 0.98-1,
# so after the renormalisation every pick weighs ~2.5 / 8 of an expert's
# output and a pick that rounding flips at the edge moves a row as
# leaving an expert out does.  Drawn as the sibling family is (W_o at 8,
# every down projection at 2.5, attention's logits at 1.87) the rows
# read 0.012-0.014, a quarter of them flipped a pick and read up to 0.10
# on 12 seeds, where float8 read 0.175 and ``wrong_held`` 0.178: no
# limit had room on both sides, and a margin that excused the flips
# decided under half the rows on four seeds of twelve.  Attention's
# logits at 0.94 halved what every row reads and the rows that flip
# (0.35 and 0.25 of 1.87 read no lower).  A flip and the routed part's
# two faults scale alike with the experts' down projections (a flip
# 0.167 at most, ``wrong_held`` 0.36 at least at 0.7 of the dense ones';
# 0.079 and 0.236 at 0.35), float8 and ``no_yarn_scale`` do not: at 0.24
# of the dense ones' all four faults stand level, 2.2-2.6 x over the
# largest flip.
LOGITS_TOL = 0.082

# A row is decided when no expert this chip holds stands within
# CHOICE_MARGIN of the top-k's edge in any expert layer.  The edge lies
# midway between the k-th and the (k+1)-th of ``s + b``; an expert's
# distance from it is taken in the logits' units (divided by the
# sigmoid's slope at the k-th pick, which is exact to first order and
# holds with a bias) over the standard deviation of the row's logits.
# 8 of 128 with 32 held is a dense field (four held experts a standard
# deviation at the edge, five layers): at 0.005 the 16 seeds decide
# 62-84 % of a check's 64 rows (median 70 %) and the largest decided row
# reads 0.0414 where the largest of all reads 0.0549; at 0.0075 a check
# decided 55 %, at 0.01 one check in three decides under the harness's
# half.  The limit holds the undecided rows too, so the margin is room
# and not the check.
CHOICE_MARGIN = 0.005


_MEMO: Dict[Any, Any] = {}


def _settings(config: Dict[str, Any]) -> Tuple:
    """The hashable part of ``config`` the forward needs."""
    held = config["model"]["kwargs"]["held_experts"]
    rs = config["rope_scaling"]
    return (int(config["num_experts_per_tok"]),
            float(config["routed_scaling_factor"]),
            tuple(int(e) for e in held), float(config["rms_norm_eps"]),
            float(config["rope_theta"]), int(config["qk_rope_head_dim"]),
            (float(rs["factor"]), int(rs["original_max_position_embeddings"]),
             float(rs["beta_fast"]), float(rs["beta_slow"]),
             float(rs["mscale"]), float(rs["mscale_all_dim"])),
            str(config.get("_round", "")))


# ``config["_fault"]``: the planted faults, the controls that have to
# come out not correct (``benchmark/controls/sarvam_check.py`` reads
# them through the harness's comparison).  A fault is (the shared
# expert's part x, softmax for sigmoid, the renormalisation left out,
# the yarn factor on the softmax's scale left out, the query heads' norm
# left out, a held pick goes to the expert this many places on, the
# router's logits in bfloat16), and arrives as an array, so that every
# control runs the executable the check compiled.
FAULTS = {"": (1, 0, 0, 0, 0, 0, 0), "no_shared": (0, 0, 0, 0, 0, 0, 0),
          "softmax": (1, 1, 0, 0, 0, 0, 0),
          "no_renorm": (1, 0, 1, 0, 0, 0, 0),
          "no_yarn_scale": (1, 0, 0, 1, 0, 0, 0),
          "no_q_norm": (1, 0, 0, 0, 1, 0, 0),
          "wrong_held": (1, 0, 0, 0, 0, 1, 0),
          "router_bf16": (1, 0, 0, 0, 0, 0, 1)}


def _fault(config: Dict[str, Any]):
    return np.asarray(FAULTS[config.get("_fault", "")], np.float32)


def _rounder(name: str):
    """``config["_round"]``: the next precision down, for the reading
    that has to fail.  Every matrix and every layer's input are rounded
    to the named dtype and widened again."""
    if not name:
        return lambda a: a
    dtype = jnp.dtype(name)
    return lambda a: a.astype(dtype).astype(F32)


def _reader(layers, layer):
    """``lp(name, *index, cols=None, rows=None)``: ``layers[name][layer,
    *index]``, optionally ``(start, width)`` of its last or its
    second-to-last axis, sliced from the stacked array where it stands:
    no layer's weights are ever copied out whole."""
    def lp(name, *index, cols=None, rows=None):
        a = layers[name]
        at = (layer,) + index
        start = list(at) + [0] * (a.ndim - len(at))
        size = [1] * len(at) + list(a.shape[len(at):])
        if cols is not None:
            start[-1], size[-1] = cols
        if rows is not None:
            start[-2], size[-2] = rows
        return jax.lax.dynamic_slice(a, start, size).reshape(
            size[len(at):])

    lp.shape = lambda name: layers[name].shape
    return lp


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(D: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """``deepseek_yarn``'s frequencies of ``D`` rotary channels: the
    plain ``theta ** (-2c / D)`` where a channel turns more than
    ``beta_fast`` times over the ``original`` positions, that over
    ``factor`` where it turns fewer than ``beta_slow`` times, a linear
    blend in the channel's index between."""
    half = D // 2
    plain = theta ** (-np.arange(half, dtype=np.float64) / half)

    def channel(turns):
        return (D * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(channel(beta_fast)), 0)
    high = min(math.ceil(channel(beta_slow)), D - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return (plain * (1 - ramp) + plain / factor * ramp).astype(np.float32)


def rope(x, positions, freqs, gain: float):
    """x [S, ..., D]: rotate the pairs (i, i + D/2) by position *
    freqs[i]; cos and sin times ``gain``."""
    half = x.shape[-1] // 2
    ang = positions.astype(F32)[:, None] * jnp.asarray(freqs)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang) * gain, jnp.sin(ang) * gain
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, lp, positions, st, rd, fault):
    """The latent-attention sublayer on h [S, d] (already normed), one
    head at a time: a head's projections, scores and output are the only
    things of its size alive."""
    eps, theta, rope_d = st[3:6]
    factor, original, fast, slow, mscale, mscale_all = st[6]
    S = h.shape[0]
    freqs = yarn_inv_freq(rope_d, theta, factor, original, fast, slow)
    gain = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all)
    w = lambda name, *at: rd(lp(name, *at).astype(F32))    # noqa: E731
    kv = h @ w("wkv_a")
    c = rmsnorm(kv[:, :-rope_d], lp("kv_norm").astype(F32), eps)
    k_rot = rope(kv[:, -rope_d:], positions, freqs, gain)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    H, qk = lp.shape("wq")[-2:]
    scale = qk ** -0.5 * jnp.where(
        fault[3] > 0, 1.0, yarn_mscale(factor, mscale_all) ** 2)
    q_norm = lp("q_head_norm").astype(F32)

    def head(hd):
        wq = jax.lax.dynamic_index_in_dim(lp("wq"), hd, 1, False)
        q = h @ rd(wq.astype(F32))
        q = jnp.where(fault[4] > 0, q, rmsnorm(q, q_norm, eps))
        q_nope, q_rot = q[:, :-rope_d], rope(q[:, -rope_d:], positions,
                                             freqs, gain)
        scores = (q_nope @ (c @ w("wk_b", hd)).T + q_rot @ k_rot.T) * scale
        scores = jnp.where(causal, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ (c @ w("wv_b", hd))

    o = jax.lax.map(head, jnp.arange(H))
    return jnp.moveaxis(o, 0, 1).reshape(S, -1) @ w("wo")


_CHUNK = 2048


def swiglu(h, lp, rd, prefix, *at):
    """A swiglu FFN, ``_CHUNK`` of its hidden columns at a time, each
    sliced from the stored matrix where it stands."""
    f = lp.shape(prefix + "gate")[-1]
    width = min(_CHUNK, f)

    def chunk(k, out):
        cols = lambda name: rd(lp(                        # noqa: E731
            prefix + name, *at, cols=(k * width, width)).astype(F32))
        down = rd(lp(prefix + "down", *at,
                     rows=(k * width, width)).astype(F32))
        return out + (jax.nn.silu(h @ cols("gate"))
                      * (h @ cols("up"))) @ down

    return jax.lax.fori_loop(0, f // width, chunk, jnp.zeros_like(h))


def moe(u, lp, st, rd, fault):
    """The expert layer with its shared expert on u [S, d] -> (s [S, d],
    margin [S]): this chip's part of the routed sum and the whole shared
    expert, and how far the nearest held expert stands from the top-k's
    edge (``CHOICE_MARGIN``).  ``fault`` is ``FAULTS[""]`` but in a
    control."""
    top_k, scale, held = st[:3]
    moved = fault[5].astype(jnp.int32)
    router = rd(lp("router").astype(F32))
    logits = u @ router                                      # [S, E]
    low = (u.astype(jnp.bfloat16) @ router.astype(jnp.bfloat16)
           ).astype(F32)
    logits = jnp.where(fault[6] > 0, low, logits)
    score = jnp.where(fault[1] > 0, jax.nn.softmax(logits, axis=-1),
                      jax.nn.sigmoid(logits))
    biased = score + lp("router_bias").astype(F32)
    kth = jax.lax.top_k(biased, top_k + 1)[0]               # [S, k+1]
    chosen = biased >= kth[:, top_k - 1:top_k]              # [S, E]
    weight = jnp.where(chosen, score, 0.0)
    total = jnp.sum(weight, -1, keepdims=True)
    weight = scale * weight / jnp.where(fault[2] > 0, 1.0, total)
    ids = jnp.asarray(held, jnp.int32)

    def expert(local, out):
        at = (local + moved) % len(held)
        w = jnp.take(weight, ids[local], axis=1)[:, None]
        return out + w * swiglu(u, lp, rd, "e_", at)

    out = jax.lax.fori_loop(0, len(held), expert,
                            fault[0] * swiglu(u, lp, rd, "s_"))
    # the margin: a held expert's distance from the edge of ``s + b``,
    # in the logits' units by the sigmoid's slope at the k-th pick
    ours = np.zeros((biased.shape[-1],), bool)
    ours[list(held)] = True
    edge = 0.5 * (kth[:, top_k - 1] + kth[:, top_k])
    s_k = jnp.min(jnp.where(chosen, score, jnp.inf), -1)
    slope = jnp.maximum(s_k * (1.0 - s_k), 1e-30)
    gap = jnp.abs(biased - edge[:, None]) / (
        slope * jnp.std(logits, -1))[:, None]
    margin = jnp.min(jnp.where(jnp.asarray(ours), gap, jnp.inf), -1)
    return out, margin


def hidden(params: Dict[str, Any], tokens, st, fault=None):
    """tokens [S] -> (final normed hidden [S, d], margin [S]: the least
    over the expert layers)."""
    rd, eps = _rounder(st[7]), st[3]
    fault = jnp.asarray(FAULTS[""] if fault is None else fault, F32)
    positions = jnp.arange(tokens.shape[0])
    x = params["embed"][tokens].astype(F32)

    def attend(x, lp):
        x = rd(x)
        return x + attention(rmsnorm(x, lp("ln_attn").astype(F32), eps),
                             lp, positions, st, rd, fault)

    def dense(x, layer):
        lp = _reader(params["dense"], layer)
        y = attend(x, lp)
        return y + swiglu(rmsnorm(y, lp("ln_ffn").astype(F32), eps), lp,
                          rd, "w_"), None

    def routed(x, layer):
        lp = _reader(params["layers"], layer)
        y = attend(x, lp)
        s, margin = moe(rmsnorm(y, lp("ln_ffn").astype(F32), eps), lp, st,
                        rd, fault)
        return y + s, margin

    x, _ = jax.lax.scan(dense, x,
                        jnp.arange(params["dense"]["wo"].shape[0]))
    x, margins = jax.lax.scan(routed, x,
                              jnp.arange(params["layers"]["wo"].shape[0]))
    return (rmsnorm(x, params["ln_f"].astype(F32), eps),
            jnp.min(margins, 0))


_HEAD_CHUNK = 8192


def _head(x, lm_head):
    """x [n, d] @ lm_head [d, V], a block of columns at a time."""
    V = lm_head.shape[1]
    width = min(_HEAD_CHUNK, V)
    if V % width:
        return x @ lm_head.astype(F32)

    def block(k):
        return x @ jax.lax.dynamic_slice_in_dim(
            lm_head, k * width, width, 1).astype(F32)

    out = jax.lax.map(block, jnp.arange(V // width))        # [n_blocks, n, w]
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], V)


def _last_rows(params, tokens, last: int, config):
    """(logits, margins) of the last rows; the newest result is kept, so
    that ``logits_last`` and ``decided_rows`` of one sample are one
    forward."""
    st, fault = _settings(config), _fault(config)
    key = (id(params["embed"]), np.asarray(tokens).tobytes(), last, st,
           fault.tobytes())
    if key not in _MEMO:
        _MEMO.clear()
        _MEMO[key] = _last_rows_jit(params, jnp.asarray(tokens), last, st,
                                    fault)
    return _MEMO[key]


@functools.partial(jax.jit, static_argnames=("last", "st"))
def _last_rows_jit(params, tokens, last: int, st, fault):
    with jax.default_matmul_precision("highest"):
        def one(t):
            x, margin = hidden(params, t, st, fault)
            return _head(x[-last:], params["lm_head"]), margin[-last:]
        return jax.lax.map(one, tokens)


def logits_last(params, tokens, last: int, config):
    """Logits [B, last, V] at the last ``last`` positions of a full
    forward over tokens [B, S]: what prefill-then-decode through a cache
    must reproduce."""
    return _last_rows(params, jnp.asarray(tokens), last, config)[0]


def decided_rows(params, tokens, last: int, config):
    """bool[last] for tokens [1, S]: the rows no held expert stands
    within ``CHOICE_MARGIN`` of the top-k's edge in."""
    margin = _last_rows(params, jnp.asarray(tokens), last, config)[1][0]
    return np.asarray(margin) >= CHOICE_MARGIN
