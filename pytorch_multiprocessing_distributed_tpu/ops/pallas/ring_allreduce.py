"""RDMA ring all-reduce as a Pallas TPU kernel — the NCCL-analogue demo.

The production gradient all-reduce is ``lax.psum`` (XLA already emits
bandwidth-optimal ICI rings for it — :mod:`..parallel.collectives`).
This kernel exists because SURVEY.md §2.2 names a hand-built collective
layer as part of the reference's implicit native stack (NCCL), and
because a visible, steppable ring is the right vehicle for benchmarking
ICI against XLA's lowering (``benchmarks/allreduce_bw.py``).

Algorithm (classic two-phase ring, 2·(n-1)/n · bytes over the wire):
  1. reduce-scatter: n-1 hops; at hop t rank r sends chunk (r - t) mod n
     rightward and accumulates incoming chunk (r - t - 1) mod n, so after
     the phase rank r holds the fully-reduced chunk (r + 1) mod n;
  2. all-gather: n-1 hops circulating the finished chunks.

Each hop is one ``make_async_remote_copy`` into the right neighbor's
double-buffered landing slot. Flow control is NCCL-style credit-based:
a receiver acks each consumed delivery back to its sender (left
neighbor), and a sender re-using a landing slot first waits for the ack
of its previous delivery into that slot — so a fast rank can never
overwrite data its neighbor has not yet consumed, regardless of ring
skew. An entry barrier keeps a rank from RDMA-ing into a kernel its
neighbor hasn't entered; a final drain rebalances the credit semaphores
to zero before exit.

Call inside ``shard_map`` with the target axis bound. Runs compiled on a
real multi-chip ICI ring; runs under Pallas interpret mode on the
virtualized CPU mesh (the test path, ``tests/test_pallas_kernels.py``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128


def _ring_kernel(x_ref, o_ref, comm, send_sem, recv_sem, ack_sem, *,
                 axis_name, flow_control):
    """``flow_control=False`` only under interpret mode, whose lockstep
    execution makes the barrier/credit protocol unnecessary (and remote
    ``semaphore_signal`` is not implemented there)."""
    my = jax.lax.axis_index(axis_name)
    n = jax.lax.axis_size(axis_name)
    right = jax.lax.rem(my + 1, n)
    left = jax.lax.rem(my + n - 1, n)
    chunk = x_ref.shape[0] // n  # rows per chunk (pre-padded by caller)

    if flow_control:
        # Entry barrier: both neighbors' buffers exist before any RDMA.
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, inc=1, device_id=left,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_signal(barrier, inc=1, device_id=right,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(barrier, 2)

    o_ref[:] = x_ref[:]

    def hop(g, send_idx, recv_idx, accumulate):
        """One ring hop at global step ``g`` (slot parity g % 2)."""
        slot = jax.lax.rem(g, 2)

        if flow_control:
            # Credit: my previous delivery into right's comm[slot] (hop
            # g-2) must be consumed before I overwrite it.
            @pl.when(g >= 2)
            def _():
                pltpu.semaphore_wait(ack_sem.at[slot], 1)

        rdma = pltpu.make_async_remote_copy(
            src_ref=o_ref.at[pl.ds(send_idx * chunk, chunk), :],
            dst_ref=comm.at[slot],
            send_sem=send_sem.at[slot],
            recv_sem=recv_sem.at[slot],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()  # my send delivered + left's symmetric delivery arrived

        if accumulate:
            o_ref[pl.ds(recv_idx * chunk, chunk), :] = (
                o_ref[pl.ds(recv_idx * chunk, chunk), :] + comm[slot]
            )
        else:
            o_ref[pl.ds(recv_idx * chunk, chunk), :] = comm[slot]

        if flow_control:
            # Consumed — return the credit to the sender (left neighbor).
            pltpu.semaphore_signal(
                ack_sem.at[slot], inc=1, device_id=left,
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )

    # Phase 1 — reduce-scatter.
    def rs_body(t, _):
        hop(
            t,
            jax.lax.rem(my - t + 2 * n, n),
            jax.lax.rem(my - t - 1 + 2 * n, n),
            accumulate=True,
        )
        return 0

    jax.lax.fori_loop(0, n - 1, rs_body, 0)

    # Phase 2 — all-gather: rank r owns reduced chunk (r + 1) mod n.
    def ag_body(t, _):
        hop(
            n - 1 + t,  # global step: slot parity continues across phases
            jax.lax.rem(my + 1 - t + 2 * n, n),
            jax.lax.rem(my - t + 2 * n, n),
            accumulate=False,
        )
        return 0

    jax.lax.fori_loop(0, n - 1, ag_body, 0)

    if flow_control:
        # Drain: the final delivery on each slot was acked by my right but
        # never waited on — consume both so the semaphores exit at zero.
        # (2·(n-1) >= 2 hops for n >= 2, so both slots saw >= 1 send.)
        pltpu.semaphore_wait(ack_sem.at[0], 1)
        pltpu.semaphore_wait(ack_sem.at[1], 1)


def ring_all_reduce(
    x: jax.Array,
    axis_name: str,
    *,
    interpret: Optional[bool] = None,
    collective_id: int = 7,
) -> jax.Array:
    """Sum-all-reduce ``x`` over ``axis_name`` via an explicit RDMA ring.

    Must be called inside ``shard_map``/``pmap`` with ``axis_name``
    bound. Semantically identical to ``jax.lax.psum(x, axis_name)``.
    """
    if interpret is None:
        from . import default_interpret

        interpret = default_interpret()
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x

    orig_shape, orig_dtype = x.shape, x.dtype
    size = math.prod(orig_shape) if orig_shape else 1
    flat = x.astype(jnp.float32).reshape(-1)
    # rows must split into n equal chunks of whole (8, 128)-tile rows
    rows = -(-flat.size // _LANE)
    rows = -(-rows // (8 * n)) * (8 * n)
    pad = rows * _LANE - flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    x2 = flat.reshape(rows, _LANE)
    chunk = rows // n

    kernel = functools.partial(
        _ring_kernel, axis_name=axis_name, flow_control=not interpret
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _LANE), jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, chunk, _LANE), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            collective_id=collective_id, has_side_effects=True),
        interpret=interpret,
        name="ring_all_reduce",
    )(x2)
    return out.reshape(-1)[:size].reshape(orig_shape).astype(orig_dtype)
