"""Pallas TPU ring all-reduce: the FedAvg reduction as an explicit RDMA
kernel (SURVEY.md §7 step 4 — the educational ICI analogue of the
reference's rank-0 gather/average/bcast, FL_CustomMLP...:101-120).

fedtpu.parallel.ring spells the ring schedule out in XLA collectives
(``ppermute``); this module goes one level lower and spells out the
*transport*: each hop is a ``pltpu.make_async_remote_copy`` — the actual
inter-chip RDMA primitive ICI collectives are built from — with
double-buffered communication slots and DMA-semaphore synchronization, per
the TPU Pallas ring-collective pattern. One kernel invocation per shard
performs the whole N-1-hop rotate-and-accumulate reduction.

Synchronization (compiled path): chips launch unsynchronized and DMA skew
propagates around the ring, so the kernel uses the canonical two-part
protocol — a neighbor barrier at kernel start (``get_barrier_semaphore`` +
remote signals, gated on ``collective_id``) so no RDMA lands before the
destination kernel is live, and per-slot capacity semaphores (the receiver
credits its LEFT neighbor after a slot is accumulated AND forwarded) so a
fast sender can never overwrite an unconsumed slot. The interpret-mode
interpreter does not implement remote semaphore signals, so on CPU test
meshes the kernel runs with the data schedule only (interpret mode
serializes devices, which makes the sync redundant there). The sync path
is held to the v5e's compiler on a described 2x2 topology in
tests/test_aot_tpu_compile.py, and it has EXECUTED on a four-chip v5e
2x2 host: ``python chip_smoke.py --chips 4`` (PR 21) runs it once on the
model's 11,352-float delta under ``check_vma=True`` and holds it to
``psum`` (max difference 9.5e-7 in that run; the first call, compile
excluded, took 48 ms). One call on one payload is all that has run — no
repeated calls, no other ring sizes, no timing against ``psum``.

Scope: a tested library collective, NOT a round-engine backend. Pallas
kernels cannot run inside ``shard_map``'s ``lax.scan`` in interpret mode
(the same constraint that keeps the fused-MLP eval kernel out of the
in-round path, see fedtpu.orchestration.loop), and the production reduction
is psum either way — XLA emits fused, double-buffered versions of exactly
this schedule. Use :func:`pallas_ring_all_reduce_sum` directly under
``shard_map``; in interpret mode the enclosing ``shard_map`` needs
``check_vma=False`` (the interpreter is not varying-manual-axes-aware).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedtpu.parallel.ring import flatten_pad, unpad_reshape

_LANES = 128
_SUBLANES = 8


def _residual_credits(axis_size: int):
    """Capacity credits left un-consumed per slot parity at loop end (each
    must be drained so regular semaphores end the kernel at zero)."""
    n = axis_size
    received = [0, 0]
    consumed = [0, 0]
    for s in range(n - 1):
        received[s % 2] += 1              # right neighbor frees slot s%2
        if s >= 2:
            consumed[(s + 1) % 2] += 1    # we waited before writing it
    return [received[p] - consumed[p] for p in (0, 1)]


def _ring_kernel(axis_name: str, axis_size: int, with_sync: bool,
                 x_ref, acc_ref, comm_buf, send_sem, recv_sem, cap_sem):
    """acc = sum over the ring of every shard's x. Rotate-and-accumulate:
    at hop s this shard forwards the value it received at hop s-1 (starting
    from its own x) to the right neighbor and folds the incoming one in."""
    my_id = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(my_id + 1, axis_size)
    left = jax.lax.rem(my_id + axis_size - 1, axis_size)

    if with_sync:
        # Start barrier: no RDMA may land before the destination kernel
        # (and its scratch) is live on every neighbor.
        bar = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(bar, inc=1, device_id=left)
        pltpu.semaphore_signal(bar, inc=1, device_id=right)
        pltpu.semaphore_wait(bar, 2)

    acc_ref[...] = x_ref[...]
    comm_buf[0] = x_ref[...]

    for step in range(axis_size - 1):
        send_slot = step % 2
        recv_slot = (step + 1) % 2
        if with_sync and step >= 2:
            # Right's slot of this parity was written at step-2; wait for
            # right's credit that it has been accumulated and forwarded.
            pltpu.semaphore_wait(cap_sem.at[recv_slot], 1)
        rdma = pltpu.make_async_remote_copy(
            src_ref=comm_buf.at[send_slot],
            dst_ref=comm_buf.at[recv_slot],
            send_sem=send_sem.at[send_slot],
            recv_sem=recv_sem.at[recv_slot],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()
        acc_ref[...] += comm_buf[recv_slot]
        if with_sync:
            # Our slot `send_slot` is consumed (accumulated at step-1, read
            # out by this hop's send) — credit the writer (left neighbor).
            pltpu.semaphore_signal(cap_sem.at[send_slot], inc=1,
                                   device_id=left)

    if with_sync:
        # Drain leftover credits so the regular semaphores end at zero.
        for p, residual in enumerate(_residual_credits(axis_size)):
            if residual:
                pltpu.semaphore_wait(cap_sem.at[p], residual)


def pallas_ring_all_reduce_sum(x: jax.Array, axis_name: str, axis_size: int,
                               interpret: bool | None = None,
                               collective_id: int = 0) -> jax.Array:
    """Ring all-reduce of ``x`` over ``axis_name`` as ONE Pallas kernel per
    shard. Call inside ``shard_map``. Arbitrary shapes: the payload is
    flattened and zero-padded to (rows, 128) float32 tiles.

    ``interpret=None`` auto-selects interpret mode off-TPU (CPU test
    meshes); interpret mode runs the data schedule without the barrier /
    capacity synchronization (see module docstring)."""
    if axis_size == 1:
        return x
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    with_sync = not interpret

    shape, dtype = x.shape, x.dtype
    flat, pad = flatten_pad(x, _LANES * _SUBLANES, dtype=jnp.float32)
    payload = flat.reshape(-1, _LANES)            # rows % 8 == 0

    # The output varies over the ring axis like the input (vma carried
    # through so check_vma=True callers type-check on real TPU).
    out = pl.pallas_call(
        functools.partial(_ring_kernel, axis_name, axis_size, with_sync),
        out_shape=jax.ShapeDtypeStruct(payload.shape, jnp.float32,
                                       vma=jax.typeof(payload).vma),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2,) + payload.shape, jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR((2,)),
        ],
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
        interpret=interpret,
    )(payload)

    return unpad_reshape(out.reshape(-1), pad, shape, dtype=dtype)
