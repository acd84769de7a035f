"""Ulysses sequence parallelism — all-to-all head resharding.

Parity target: DeepSpeed-Ulysses as integrated by the reference's
long-context stacks (SURVEY.md §2.4 row "Ulysses / all-to-all").  The
alternative to ring attention (``ring_attention.py``): instead of
rotating K/V blocks around the ``sp`` ring, one ``all_to_all`` trades
the sequence shard for a head shard, every device runs *full-sequence*
attention on ``H/sp`` heads, and a second ``all_to_all`` restores the
sequence sharding.  Two collectives per layer instead of ``sp`` ring
steps — better when heads are plentiful and ICI all-to-all is cheap;
ring wins when S is huge and overlap matters.

Composes with tp (heads are split over ``(tp, sp)``) via partial-manual
shard_map: only ``sp`` is manual here.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel.compat import shard_map
from ray_tpu.parallel.ring_attention import local_attention


def make_ulysses_attention_fn(mesh, *, causal: bool = True,
                              scale: Optional[float] = None,
                              attn_impl=None):
    """Returns ``fn(q, k, v) -> out`` for [B, S, H, D] inputs whose seq
    dim is sharded over ``sp``.  Drop-in for
    ``make_ring_attention_fn`` / ``make_flash_attention_fn``.

    ``attn_impl(q, k, v, causal=..., scale=...)`` runs the local
    full-sequence attention (default: the einsum path; pass
    ``ops.attention.flash_attention`` on real TPU).
    """
    sp = mesh.shape.get("sp", 1)
    inner = attn_impl or local_attention
    if sp <= 1:
        return functools.partial(inner, causal=causal, scale=scale)

    # partial-manual: specs name only the manual axis; dp/tp
    # shardings propagate automatically through the auto axes
    spec = P(None, "sp", None, None)

    @functools.partial(shard_map, mesh=mesh, in_specs=(spec,) * 3,
                       out_specs=spec, axis_names={"sp"})
    def fn(q, k, v):
        H = q.shape[2]
        if H % sp:
            raise ValueError(f"heads={H} not divisible by sp={sp}")
        # [B, S/sp, H, D] -> [B, S, H/sp, D]: trade seq shard for heads
        q, k, v = (lax.all_to_all(t, "sp", split_axis=2, concat_axis=1,
                                  tiled=True) for t in (q, k, v))
        out = inner(q, k, v, causal=causal, scale=scale)
        # [B, S, H/sp, D] -> [B, S/sp, H, D]
        return lax.all_to_all(out, "sp", split_axis=1, concat_axis=2,
                              tiled=True)

    return fn
