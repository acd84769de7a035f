"""Device-mesh construction — the substrate of every parallelism strategy.

TPU-native replacement for the reference's NCCL communicator world: instead
of process groups + communicator objects (reference
``python/ray/util/collective/collective_group/nccl_collective_group.py``),
parallelism is expressed as named axes of a ``jax.sharding.Mesh`` and XLA
inserts the collectives.  Axis convention (see scaling-book recipe):

    dcn   the inter-pod tier (data-center network): pure data
          parallelism across pods — params replicated per pod, grads
          all-reduced over the slow links
    dp    data parallelism (gradient psum)
    fsdp  parameter/optimizer sharding (ZeRO-3-style)
    tp    tensor parallelism (megatron-style sharded matmuls)
    sp    sequence/context parallelism (ring attention)
    pp    pipeline stages
    ep    expert parallelism (MoE all-to-all), usually folded over dp

ICI topology note: axes earlier in the tuple change slowest; put the axis
with the heaviest collective traffic (tp) innermost so it rides the
densest ICI links.  ``dcn`` is outermost by construction — it is the
slowest tier, and the hierarchical collectives in ``parallel/overlap.py``
depend on every ICI axis being contiguous *inside* one dcn slice.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

AXIS_ORDER = ("dcn", "pp", "dp", "fsdp", "sp", "ep", "tp")

# Axes that live on the intra-pod ICI fabric; "dcn" is the only
# cross-pod axis.  ``mesh_tiers`` buckets a live mesh by this split.
ICI_AXES = tuple(a for a in AXIS_ORDER if a != "dcn")


class MeshAxisError(ValueError):
    """A mesh-axis string was malformed; ``axis`` names the offender.

    Raised by :func:`parse_mesh_axes` (and ``MeshSpec.create``) with the
    offending axis attached so a CLI surface can point at the exact
    token instead of the whole argument."""

    def __init__(self, msg: str, *, axis: Optional[str] = None):
        super().__init__(msg)
        self.axis = axis


@dataclass(frozen=True)
class MeshSpec:
    """A named logical mesh shape, resolvable against any device set."""

    axes: Tuple[Tuple[str, int], ...]

    @classmethod
    def create(cls, **sizes: int) -> "MeshSpec":
        unknown = set(sizes) - set(AXIS_ORDER)
        if unknown:
            bad = sorted(unknown)[0]
            raise MeshAxisError(
                f"unknown mesh axis {bad!r}; valid: {AXIS_ORDER}",
                axis=bad)
        axes = tuple((a, int(sizes[a])) for a in AXIS_ORDER if a in sizes)
        return cls(axes)

    @classmethod
    def from_mesh(cls, mesh) -> "MeshSpec":
        """The logical shape of a live ``jax.sharding.Mesh`` (or a
        MeshSpec, passed through) — what a checkpoint sidecar records
        as the *writing* topology so a restore onto a different mesh
        can be refused or resharded deliberately."""
        if isinstance(mesh, cls):
            return mesh
        return cls(tuple((str(a), int(s))
                         for a, s in dict(mesh.shape).items()))

    # ----------------------------------------------- sidecar (de)serialization
    def to_dict(self) -> Dict[str, int]:
        """JSON-safe image for checkpoint sidecars (axis order is the
        identity: ``{"fsdp": 8}`` and ``{"fsdp": 4, "tp": 2}`` are
        different topologies even at equal size)."""
        return {a: s for a, s in self.axes}

    @classmethod
    def from_dict(cls, d: Dict[str, int]) -> "MeshSpec":
        # not create(): a sidecar written by a future axis set must
        # still round-trip for the mismatch report instead of raising
        # an unknown-axis error before the real message
        return cls(tuple((str(a), int(s)) for a, s in d.items()))

    def describe(self) -> str:
        return ",".join(f"{a}={s}" for a, s in self.axes) or "dp=1"

    @property
    def size(self) -> int:
        return math.prod(s for _, s in self.axes) if self.axes else 1

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return tuple(a for a, _ in self.axes)

    def resolve(self, num_devices: int) -> "MeshSpec":
        """Fill at most one ``-1`` axis from the device count."""
        wild = [a for a, s in self.axes if s == -1]
        if len(wild) > 1:
            raise ValueError("at most one mesh axis may be -1")
        fixed = math.prod(s for _, s in self.axes if s != -1)
        if wild:
            if num_devices % fixed:
                raise ValueError(
                    f"{num_devices} devices not divisible by fixed axes "
                    f"product {fixed}")
            fill = num_devices // fixed
            return MeshSpec(tuple((a, fill if s == -1 else s)
                                  for a, s in self.axes))
        if fixed > num_devices:
            raise ValueError(
                f"mesh size {fixed} exceeds device count {num_devices}")
        return self  # smaller meshes use the first `fixed` devices

    # ----------------------------------------------------------- tier split
    def tier_split(self) -> Tuple[int, int]:
        """``(dcn_size, pod_size)`` — the cross-pod tier and the per-pod
        ICI product.  A flat (single-pod) spec is ``(1, size)``.  This
        is what the checkpoint sidecar round-trips so an r18 cross-mesh
        restore can tell ``dcn=2,fsdp=4`` from flat ``fsdp=8`` even at
        equal device count."""
        d = dict(self.axes)
        dcn = int(d.get("dcn", 1))
        return dcn, self.size // max(dcn, 1)


def mesh_tiers(mesh) -> Dict[str, Tuple[str, ...]]:
    """Bucket a live mesh's >1-sized axes by fabric tier:
    ``{"ici": (...), "dcn": (...)}``.  The hierarchical collectives and
    the per-tier byte accounting share this split so they cannot
    disagree about which wire a collective rides."""
    shape = dict(mesh.shape)
    return {
        "ici": tuple(a for a in ICI_AXES if shape.get(a, 1) > 1),
        "dcn": tuple(a for a in ("dcn",) if shape.get(a, 1) > 1),
    }


def parse_mesh_axes(arg: str) -> Dict[str, int]:
    """``"dcn=2,fsdp=4"`` -> ``{"dcn": 2, "fsdp": 4}`` (CLI mesh
    syntax).

    Rejections all raise :class:`MeshAxisError` naming the offending
    axis: unknown names, duplicates, non-positive sizes (``-1`` is the
    one allowed wildcard), and ``dcn`` anywhere but first — the slow
    tier must be the outermost (slowest-varying) axis or the per-pod
    device blocks ``make_mesh`` carves would interleave pods."""
    sizes: Dict[str, int] = {}
    order: List[str] = []
    for part in arg.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise MeshAxisError(
                f"bad mesh axis {part!r} (want e.g. 'dcn=2,fsdp=4')",
                axis=part)
        name, _, value = part.partition("=")
        name = name.strip()
        try:
            size = int(value)
        except ValueError:
            raise MeshAxisError(
                f"mesh axis {name!r} has non-integer size {value!r}",
                axis=name) from None
        if name in sizes:
            raise MeshAxisError(
                f"duplicate mesh axis {name!r}", axis=name)
        if size == 0 or size < -1:
            raise MeshAxisError(
                f"mesh axis {name!r} has non-positive size {size} "
                "(only -1 is allowed as a wildcard)", axis=name)
        sizes[name] = size
        order.append(name)
    if "dcn" in order and order.index("dcn") != 0:
        raise MeshAxisError(
            "mesh axis 'dcn' must be outermost (first): the cross-pod "
            f"tier is the slowest axis, got order {order}", axis="dcn")
    MeshSpec.create(**sizes)   # validates axis names
    return sizes


def make_mesh(spec: Optional[MeshSpec] = None, devices=None,
              **sizes: int):
    """Build a ``jax.sharding.Mesh`` from a spec or axis sizes.

    ``make_mesh(dp=2, tp=4)``; pass one ``-1`` to absorb remaining devices:
    ``make_mesh(dp=-1, tp=2)``.
    """
    import jax
    from jax.sharding import Mesh

    if spec is None:
        if not sizes:
            sizes = {"dp": -1}
        spec = MeshSpec.create(**sizes)
    if devices is None:
        devices = jax.devices()
    spec = spec.resolve(len(devices))
    shape = [s for _, s in spec.axes]
    import numpy as np
    dev_array = np.asarray(devices[: spec.size]).reshape(shape)
    return Mesh(dev_array, spec.axis_names)


def single_device_mesh(device=None):
    import jax
    from jax.sharding import Mesh
    import numpy as np
    if device is None:
        device = jax.devices()[0]
    return Mesh(np.asarray([device]).reshape(1), ("dp",))


def mesh_axis_size(mesh, axis: str) -> int:
    return mesh.shape.get(axis, 1) if hasattr(mesh, "shape") else 1


def suggest_accum_steps(batch: int, div: int,
                        prefer: int = 1) -> Optional[int]:
    """The gradient-accumulation factor that would make ``batch``
    legal on a mesh whose data axes multiply to ``div``: each of the
    ``k`` microbatches (``batch / k`` rows) must be whole AND divide
    evenly over the data axes, so legal ``k`` are exactly the divisors
    of ``batch // div``.  Returns the legal ``k`` closest to
    ``prefer`` (ties go up — more microbatches, less memory), or
    ``None`` when ``div`` does not divide ``batch`` at all: no
    accumulation factor can fix plain indivisibility, only a batch or
    mesh change can."""
    if div <= 0 or batch % div:
        return None
    per = batch // div
    legal = [k for k in range(1, per + 1) if per % k == 0]
    return min(legal, key=lambda k: (abs(k - prefer), -k))


def validate_divisibility(mesh, *, batch: Optional[int] = None,
                          seq: Optional[int] = None,
                          d_model: Optional[int] = None,
                          n_heads: Optional[int] = None,
                          accum_steps: int = 1) -> None:
    """Fail fast on shape/axis mismatches instead of inside XLA.

    ``accum_steps``: gradient-accumulation microbatch count — the
    batch check then validates the *microbatch* (``batch /
    accum_steps`` must be whole and divide the data axes), and a
    failure names the failing axes with their sizes and suggests the
    ``accum_steps`` that would make this mesh legal (the elastic
    degraded-restore path: an 8->4 shrink keeps the global batch by
    doubling accumulation instead of dying here)."""
    accum_steps = int(accum_steps)
    if accum_steps < 1:
        raise ValueError(f"accum_steps={accum_steps} must be >= 1")
    checks = [
        (seq, ("sp",), "sequence length"),
        (n_heads, ("tp",), "attention heads"),
        (d_model, ("tp",), "d_model"),
    ]
    for value, axes, label in checks:
        if value is None:
            continue
        div = math.prod(mesh.shape.get(a, 1) for a in axes)
        if value % div:
            present = ", ".join(
                f"{a}={mesh.shape.get(a, 1)}" for a in axes
                if mesh.shape.get(a, 1) > 1) or "all size 1"
            raise ValueError(
                f"{label}={value} not divisible by mesh axes {axes} "
                f"({present}; product {div})")
    if batch is None:
        return
    axes = ("dcn", "dp", "fsdp")
    div = math.prod(mesh.shape.get(a, 1) for a in axes)
    if batch % (div * accum_steps) == 0:
        return
    present = ", ".join(f"{a}={mesh.shape.get(a, 1)}" for a in axes
                        if mesh.shape.get(a, 1) > 1) or "all size 1"
    suggestion = suggest_accum_steps(batch, div, prefer=accum_steps)
    if suggestion is None:
        hint = (f"no accum_steps can fix this — the data axes "
                f"(product {div}) do not divide the global batch; "
                "change the batch or the mesh")
    else:
        hint = (f"accum_steps={suggestion} would make this mesh "
                f"legal (microbatch {batch // suggestion})")
    raise ValueError(
        f"batch={batch} with accum_steps={accum_steps} not divisible "
        f"by mesh data axes {axes} ({present}; product {div}): each "
        f"microbatch must be whole and shard evenly — {hint}")
