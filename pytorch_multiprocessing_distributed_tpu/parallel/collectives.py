"""Collective communication over the device mesh.

The NCCL analogue (SURVEY.md §5 "Distributed communication backend").
Two API levels:

1. **In-context primitives** (``psum``/``pmean``/``all_gather``/
   ``reduce_scatter``/``ppermute``) — used inside a ``shard_map``/``pmap``
   body where a mesh axis is bound. These are thin, typed wrappers over
   ``jax.lax`` collectives; XLA lowers them to ICI all-reduce rings
   (intra-slice) or DCN transfers (cross-slice) depending on where the
   axis lives — there is no hand-written transport layer to get wrong,
   which is the point of the TPU-native design.

2. **Host-level ops** (``all_reduce``, ``reduce_tensor``) — take a mesh
   and an array and run the collective as a standalone jitted program, the
   moral equivalent of calling ``dist.all_reduce`` outside any step
   function. ``reduce_tensor`` is the live, tested version of the
   reference's dead helper (``main.py:173-177``: clone → all_reduce(SUM)
   → /world_size).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..runtime import fleet as graftfleet
from ..runtime import scope as graftscope
from .mesh import DATA_AXIS

AxisName = Union[str, Sequence[str]]


# ---------------------------------------------------------------- in-context

def psum(x, axis_name: AxisName = DATA_AXIS):
    """Sum over the mesh axis (DDP's gradient all-reduce, ref main.py:109)."""
    return jax.lax.psum(x, axis_name)


def pmean(x, axis_name: AxisName = DATA_AXIS):
    """Mean over the mesh axis (all_reduce(SUM)/world_size, ref main.py:173-177)."""
    return jax.lax.pmean(x, axis_name)


def all_gather(x, axis_name: AxisName = DATA_AXIS, *, axis: int = 0,
               tiled: bool = False):
    """Gather shards from every member of the axis."""
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: AxisName = DATA_AXIS, *, scatter_axis: int = 0,
                   tiled: bool = True):
    """Sum-reduce then scatter shards along ``scatter_axis``."""
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=scatter_axis,
                                tiled=tiled)


def ppermute(x, perm, axis_name: AxisName = DATA_AXIS):
    """Point-to-point ring permutation (building block of ring attention)."""
    return jax.lax.ppermute(x, axis_name, perm)


def axis_index(axis_name: AxisName = DATA_AXIS):
    """This shard's coordinate along the axis (the reference's ``rank``)."""
    return jax.lax.axis_index(axis_name)


# ---------------------------------------------------------------- host-level

_REDUCERS = {
    "sum": jax.lax.psum,
    "mean": jax.lax.pmean,
    "max": jax.lax.pmax,
    "min": jax.lax.pmin,
}


@partial(jax.jit, static_argnums=(1, 2, 3))
def _all_reduce_program(x, mesh: Mesh, axis_name: str, op: str):
    def body(v):  # v: [1, ...] — this member's value
        return _REDUCERS[op](v[0], axis_name)

    shard = shard_map(
        body, mesh=mesh, in_specs=P(axis_name), out_specs=P(), check_vma=False
    )
    return shard(x)


def all_reduce(x, mesh: Mesh, axis_name: str = DATA_AXIS, op: str = "sum"):
    """Standalone all-reduce of stacked per-member values over a mesh axis.

    ``x`` has shape ``[axis_size, ...]`` — element ``i`` is member ``i``'s
    value, mirroring "each rank holds its own tensor" in
    ``dist.all_reduce``. Returns the reduced ``[...]`` value (replicated).
    ``op``: ``sum`` | ``mean`` | ``max`` | ``min``.

    The compiled program is cached (jit with static mesh/axis/op), so
    per-iteration calls don't re-trace.
    """
    if op not in _REDUCERS:
        raise ValueError(f"unknown reduce op {op!r}; one of {sorted(_REDUCERS)}")
    x = jnp.asarray(x)
    if x.shape[0] != mesh.shape[axis_name]:
        raise ValueError(
            f"leading dim {x.shape[0]} != size of mesh axis "
            f"{axis_name!r} ({mesh.shape[axis_name]})"
        )
    # graftfleet: stamp this rank's arrival at the boundary with the
    # STATIC per-member payload bytes — host metadata (.nbytes), never
    # a device read (it matches the psum bytes the graftcheck budget
    # commits for this program). The emitted event is an INSTANT, not
    # a span: the jitted call below is dispatch-only, and timing it
    # here would be exactly the async-dispatch lie GL115 flags.
    per_member_bytes = int(x.nbytes // x.shape[0]) if x.shape[0] else 0
    graftfleet.note_arrival(f"all_reduce@{axis_name}", axis=axis_name,
                            nbytes=per_member_bytes)
    graftscope.emit("collective.all_reduce", cat="collective",
                    axis=axis_name, op=op, nbytes=per_member_bytes)
    return _all_reduce_program(x, mesh, axis_name, op)


def reduce_tensor(tensor, mesh: Mesh, axis_name: str = DATA_AXIS):
    """all_reduce(SUM) / world_size — the reference's ``reduce_tensor``.

    In the reference this helper exists but is never called (``main.py:
    173-177``), which is why its reported eval accuracy is divided by
    world_size. Here it is live and tested — the canonical way to average
    stacked per-member metrics outside a step (the trainer itself reduces
    metrics in-step via ``psum``, which is cheaper).
    """
    return all_reduce(tensor, mesh, axis_name, op="mean")


# ------------------------------------------------------------- graftcheck

def audit_programs():
    """graftcheck registration hook (``analysis/programs.py``): the
    host-level ``all_reduce`` program — the simplest budget in the
    registry, pinned inline to exactly one payload-sized ``psum``. If
    this ever reads 2, someone double-reduced the moral equivalent of
    ``dist.all_reduce``."""
    def build():
        import jax.numpy as jnp

        from .mesh import audit_mesh

        mesh = audit_mesh(data=4, model=2)
        stacked = jax.ShapeDtypeStruct((4, 16), jnp.float32)

        def fn(x):
            return _all_reduce_program(x, mesh, DATA_AXIS, "sum")

        return {
            "fn": fn,
            "args": (stacked,),
            # one psum of the per-member [16] f32 payload = 64 bytes
            "expect_collectives": {
                "psum@data": {"count": 1, "bytes": 64}},
        }

    return [{"name": "collectives_all_reduce", "min_devices": 8,
             "build": build}]
