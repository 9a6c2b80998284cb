"""Multi-backend DIMM-axis sharding — the fleet pipeline's scaling layer.

The ROADMAP's production target is million-module fleets, and every stage
of the pipeline is *embarrassingly parallel over DIMMs*: the sweep
characterizes each module independently, the controller advances each
module's registers independently, and trace scoring reduces per-DIMM
quantities. Because PR 1/2 already forced all fleet state into
struct-of-arrays pytrees whose leading (or otherwise fixed) axis is the
DIMM axis, distributing the pipeline is mechanical: partition that ONE
axis across a 1-D device mesh with ``shard_map`` and let every device run
the exact single-device computation on its slice. This module is that
mechanism, shared by :func:`repro.core.fleet.sweep` (``mesh=``),
:func:`repro.core.controller.replay` (``mesh=``) and
:func:`repro.core.perfmodel.trace_score` (``mesh=``):

* :func:`fleet_mesh` (re-exported from :mod:`repro.launch.mesh`) builds
  the 1-D ``("dimm",)`` mesh from available devices — TPU chips in
  production, host-platform CPU devices under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for CI and
  laptops.
* **Padding + validity masks** handle fleet sizes that do not divide the
  device count (including ``n_dimms < n_devices``): :func:`pad_dimm`
  grows the DIMM axis to :func:`padded_size` by *edge replication* —
  padding entries are copies of the last real DIMM, so they flow through
  every kernel as benign, finite values (never NaN, never the profiler's
  negative sentinel) — and :func:`dimm_mask` marks the real entries for
  reductions. Map-like consumers simply slice padding off the outputs;
  reduction-like consumers (trace scoring) multiply by the mask before
  the ``psum``.
* :func:`sharded_dimm_map` is the one entry point: it wraps a
  single-device array function with (pad → ``shard_map`` over the
  ``"dimm"`` axis → slice), given each argument's/output's DIMM-axis
  position. Per-DIMM arithmetic is untouched — each shard executes the
  same jitted computation the single-device path runs (including the
  fused Pallas charge-sweep kernel, which tiles and pads *within* each
  shard exactly as it does globally) — so sharded results are BIT-EXACT
  against single-device results, which the property tests
  (tests/test_shard.py) and the ``--sharded`` benchmark gates pin.

Cross-device reductions (the gather-free ``trace_score`` path) use
:func:`psum` / :func:`pmin` over :data:`DIMM_AXIS` on mask-weighted local
partials, so a million-DIMM score never materializes a gathered fleet
array on one device.

Mesh-sizing guide: the DIMM axis is pure data parallelism — no collective
traffic except the trace-score scalars — so size the mesh to memory, not
to interconnect: per device, a sweep holds O(padded_n/D · T · P · 4)
floats and a replay O(padded_n/D · S · 2 · 4). Divisibility is handled
here (padding ≤ D−1 wasted lanes); prefer D that keeps the padded share
small when fleets are tiny.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import Array
from jax.sharding import Mesh, PartitionSpec as P

from repro.launch.mesh import fleet_mesh  # noqa: F401  (the mesh builder)

#: The one mesh-axis name of the fleet data mesh. Every ``mesh=`` kwarg in
#: the pipeline expects a mesh carrying this axis.
DIMM_AXIS: str = "dimm"


def n_shards(mesh: Mesh) -> int:
    """Size of the mesh's DIMM axis (raises if the axis is absent)."""
    if DIMM_AXIS not in mesh.axis_names:
        raise ValueError(
            f"mesh axes {mesh.axis_names} carry no {DIMM_AXIS!r} axis; "
            "build the fleet mesh with repro.core.shard.fleet_mesh()"
        )
    return int(mesh.shape[DIMM_AXIS])


def padded_size(n: int, shards: int) -> int:
    """Smallest multiple of ``shards`` that is >= ``n`` (and >= shards:
    a fleet smaller than the device count pads up to one DIMM per lane)."""
    if n < 1:
        raise ValueError(f"n must be >= 1, got {n}")
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    return -(-n // shards) * shards


def dimm_mask(n: int, padded: int) -> Array:
    """(padded,) bool validity mask: True for the ``n`` real DIMMs."""
    return jnp.arange(padded) < n


def pad_dimm(tree: Any, target: int, axis: int = 0) -> Any:
    """Pad every leaf's DIMM axis to ``target`` entries by edge replication.

    Padding rows are copies of the LAST real DIMM — benign, finite values
    that flow through the charge model, the grid search and the controller
    scan without special-casing (no NaN poisoning, no accidental negative
    sentinel). Map-like callers slice the padding off afterwards
    (:func:`slice_dimm`); reduction-like callers mask it out
    (:func:`dimm_mask`). A leaf already at ``target`` passes through."""

    def one(a: Array) -> Array:
        a = jnp.asarray(a)
        pad = target - a.shape[axis]
        if pad < 0:
            raise ValueError(
                f"DIMM axis {axis} has {a.shape[axis]} entries > target {target}"
            )
        if pad == 0:
            return a
        edge = jax.lax.slice_in_dim(a, a.shape[axis] - 1, a.shape[axis], axis=axis)
        return jnp.concatenate([a, jnp.repeat(edge, pad, axis=axis)], axis=axis)

    return jax.tree.map(one, tree)


def slice_dimm(tree: Any, n: int, axis: int = 0) -> Any:
    """Slice every leaf back to the first ``n`` entries along ``axis``."""
    return jax.tree.map(lambda a: jax.lax.slice_in_dim(a, 0, n, axis=axis), tree)


def _spec(axis: Optional[int]) -> P:
    """PartitionSpec placing :data:`DIMM_AXIS` at position ``axis``
    (``None`` = fully replicated). Used as a pytree-prefix spec, so one
    entry covers a whole argument pytree whose leaves share the axis."""
    if axis is None:
        return P()
    return P(*([None] * axis + [DIMM_AXIS]))


def psum(x: Array) -> Array:
    """Sum a local partial across the DIMM mesh axis (inside a shard)."""
    return jax.lax.psum(x, DIMM_AXIS)


def pmin(x: Array) -> Array:
    """Min of a local partial across the DIMM mesh axis (inside a shard)."""
    return jax.lax.pmin(x, DIMM_AXIS)


def sharded_dimm_map(
    fn: Callable[..., Tuple],
    mesh: Mesh,
    in_axes: Sequence[Optional[int]],
    out_axes: Sequence[Optional[int]],
    n_dimms: int,
) -> Callable[..., Tuple]:
    """Wrap a single-device array function as a DIMM-sharded computation.

    ``fn(*args) -> tuple`` must be pure, with per-DIMM-independent
    arithmetic along each argument's DIMM axis (the fleet pipeline's
    design invariant — "no Python branches on array values" makes every
    stage exactly that). ``in_axes`` / ``out_axes`` give the DIMM-axis
    position per argument / output (``None`` = replicated; an argument may
    be a pytree whose leaves all share the position, e.g. ``CellParams``
    or ``ControllerState``).

    The wrapper pads every DIMM-carrying argument to a multiple of the
    mesh's shard count (edge replication — see :func:`pad_dimm`), runs
    ``fn`` under ``shard_map`` with each shard holding a contiguous block
    of DIMMs, and slices outputs back to ``n_dimms``. Outputs declared
    ``None`` (replicated scalars, e.g. ``psum`` partials) pass through
    unsliced. Because per-DIMM arithmetic is identical to the unsharded
    call, sliced outputs are bit-exact against it.

    Reduction-style callers that psum across shards must pass a
    pre-padded :func:`dimm_mask` as one of the arguments (a mask of
    length ``n_dimms`` would be edge-replicated to all-True padding).

    The mapped computation is jitted, so repeated calls of the SAME
    returned wrapper hit the compile cache — hold on to it (the pipeline
    entry points lru_cache their wrappers per (mesh, fleet-size) for
    exactly this reason)."""
    shards = n_shards(mesh)
    target = padded_size(n_dimms, shards)
    in_axes = tuple(in_axes)
    out_axes = tuple(out_axes)
    mapped = jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=tuple(_spec(a) for a in in_axes),
        out_specs=tuple(_spec(a) for a in out_axes),
        check_vma=False,
    ))

    def run(*args):
        if len(args) != len(in_axes):
            raise ValueError(f"expected {len(in_axes)} args, got {len(args)}")
        padded = tuple(
            arg if ax is None else pad_dimm(arg, target, axis=ax)
            for arg, ax in zip(args, in_axes)
        )
        outs = mapped(*padded)
        return tuple(
            out if ax is None else slice_dimm(out, n_dimms, axis=ax)
            for out, ax in zip(outs, out_axes)
        )

    return run
