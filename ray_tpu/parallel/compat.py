"""``shard_map`` as this tree uses it: replication checking off."""

from __future__ import annotations

import jax


def shard_map(f=None, *, mesh=None, in_specs=None, out_specs=None,
              check: bool = False, axis_names=None):
    """``jax.shard_map`` with the replication checker disabled.

    The manual collectives here (ppermute rings, all_to_all) confuse the
    checker; numerical tests cover correctness instead.

    ``axis_names``: partial-manual mode — only the named mesh axes are
    manual inside the body; the rest stay automatic, so sharding
    constraints on them still propagate (the pipeline layer is manual
    over ``pp`` while dp/fsdp/tp compose automatically).
    """
    def wrap(fn):
        kw = {}
        if axis_names is not None:
            kw["axis_names"] = frozenset(axis_names)
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=check, **kw)

    if f is None:
        return wrap
    return wrap(f)
