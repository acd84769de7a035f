"""Shared substrate for the Pallas kernel families.

Four kernel families grew up in this tree — flash attention (fwd/bwd +
decode), two-head lane packing (pack2), flash-CE, and the fused norm
epilogues — and by round 12 each carried its own copy of the same
infrastructure: an interpret-mode policy, lane-padded row-stats
conventions, block/grid validation and env-knob config plumbing.
Copies drift.

This module is the single home for all of it.  A new kernel (quantized
KV strips, ragged prefill, the next norm fusion) should be a page of
code on top of these pieces, not a subsystem:

- :func:`use_interpret` — the one interpret-mode policy: kernels run
  interpreted only where the CPU was asked for (``JAX_PLATFORMS=cpu``,
  the parity suite); a non-TPU backend nobody asked for is an error.
- :func:`compile_for_tpu` — lowers the kernels for Mosaic while the
  process itself runs on the CPU (AOT checks against a TPU topology
  description, ``tests/test_tpu_aot.py``).
- :data:`NEG_INF` / :data:`STATS_LANES` — masking constant and the
  lane-padded row-stats width shared by every online-softmax kernel.
- :func:`round_up` / :func:`resolve_blocks` / :func:`stats_in` —
  lane/sublane padding and the ``[num_n, bn, STATS_LANES]``
  stats-block convention.
- :class:`Support` — block/grid validation verdicts that carry a
  *reason*, so dispatch gates can decline loudly and tests can assert
  on why.
- :func:`env_int` / :func:`env_flag` — env-knob
  readers for the per-family config dataclasses
  (``attention_config()`` / ``ce_config()``).

A kernel that Mosaic refuses is declined by its own gate, from shapes,
before tracing — never by catching a compile error.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

# masking constant for online-softmax kernels (finite: -inf would turn
# fully-masked rows into NaN through exp/max arithmetic)
NEG_INF = -1e30

# per-row statistics (lse, delta, rstd, ...) are stored as
# [.., rows, STATS_LANES] lane-broadcast blocks: a (rows, 8) block
# satisfies the TPU tiling rule (sublane div 8, lane equal to array
# dim) where a 1-D (rows,) column cannot
STATS_LANES = 8

CompilerParams = pltpu.CompilerParams

_COMPILE_FOR_TPU = contextvars.ContextVar("compile_for_tpu", default=False)


def cpu_requested() -> bool:
    """Whether the CPU backend was asked for by name (``JAX_PLATFORMS=cpu``
    or the equivalent ``jax_platforms`` config), as opposed to being what
    jax fell back to when no accelerator initialised."""
    platforms = jax.config.jax_platforms or ""
    return platforms.split(",")[0].strip() == "cpu"


def use_interpret() -> bool:
    """Whether pallas_calls should run in interpret mode.

    The one policy for every kernel family: compiled on a TPU (and inside
    :func:`compile_for_tpu`), interpreted where the CPU was asked for so
    the parity suite executes the same kernel bodies the chip will, and
    an error anywhere else — any other backend means the chip failed to
    initialise (or a worker was started without it), and the kernels
    would quietly run somewhere their numbers mean nothing."""
    if _COMPILE_FOR_TPU.get():
        return False
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu" and cpu_requested():
        return True
    raise RuntimeError(
        f"Pallas kernel dispatch: jax's default backend is {backend!r} "
        "but neither a TPU was found nor the CPU asked for "
        f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). Set "
        "JAX_PLATFORMS=cpu to run on the CPU deliberately (Pallas "
        "kernels then run in interpret mode); otherwise the TPU did not "
        "initialise in this process — is it held by another process, or "
        "was this worker started without the TPU resource?")


@contextlib.contextmanager
def compile_for_tpu():
    """Trace kernels for Mosaic regardless of the process's own backend.

    For ahead-of-time compilation against a TPU topology description
    (``jax.experimental.topologies.get_topology_desc``) from a CPU-only
    process: inside the block :func:`use_interpret` is false, so
    ``jit(f).lower(...).compile()`` hands the real kernels to Mosaic."""
    token = _COMPILE_FOR_TPU.set(True)
    try:
        yield
    finally:
        _COMPILE_FOR_TPU.reset(token)


# ---------------------------------------------------------------------------
# lane/sublane padding helpers
# ---------------------------------------------------------------------------

def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def resolve_blocks(N: int, V: int, block_n: int, block_v: int,
                   *, row_align: int = 16,
                   lane_align: int = 128) -> Tuple[int, int, int, int]:
    """Resolve ``(bn, bv, Np, Vp)``: actual block sizes and padded dims.

    Blocks shrink to the (tile-aligned) problem size for small shapes;
    otherwise N/V round up to the block grid and the callers pad."""
    bn = min(block_n, round_up(N, row_align))
    bv = min(block_v, round_up(V, lane_align))
    return bn, bv, round_up(N, bn), round_up(V, bv)


def stats_in(a, num_n: int, bn: int):
    """[Np] row stats -> [num_n, bn, STATS_LANES] lane-broadcast layout
    (the input-side mirror of the kernels' stats output blocks)."""
    return jnp.broadcast_to(a[:, None], (num_n * bn, STATS_LANES)) \
        .reshape(num_n, bn, STATS_LANES)


# ---------------------------------------------------------------------------
# dispatch gates with reasons
# ---------------------------------------------------------------------------

class Support(NamedTuple):
    """A dispatch-gate verdict that carries its reason.

    Truthy iff the kernel path applies; ``reason`` states why not (or
    which path was chosen) so fallbacks are loud and testable — the
    dispatch tests assert on these strings, which keeps "silently took
    the slow path" a failing state."""
    ok: bool
    reason: str = ""

    def __bool__(self) -> bool:          # Support(...) gates directly
        return self.ok


def supported(reason: str = "") -> Support:
    return Support(True, reason)


def unsupported(reason: str) -> Support:
    return Support(False, reason)


# ---------------------------------------------------------------------------
# env-knob config plumbing
# ---------------------------------------------------------------------------

def env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, str(default)))


def env_flag(name: str, default: bool = True) -> bool:
    """Boolean env knob: unset -> ``default``; ``"0"`` is the one
    falsey spelling (matches every existing ``RAY_TPU_*`` gate)."""
    return os.environ.get(name, "1" if default else "0") != "0"
