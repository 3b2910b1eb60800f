"""Pallas TPU kernel: one decode step's update of one state tensor of the
pool, at the slots that hold a live sequence only, in place.

The state pool (``ops/state_pool.py``) holds a tensor as ``(slots,
*shape)``: a slot's state is one contiguous block. A recurrence advances a
slot's block from that block and a few small per-slot values and reads one
small value out of it, so a step needs nothing of a slot that holds no
sequence — and at a deployment's load that is half the pool.

The grid is the step's live slots — its size a value of the step, not of
the program: one program whatever the live count — one slot's block a grid
step, with the step's plan as the scalar-prefetch operand (``live_plan``:
the live slots' indices, in order, and the live count). The block index maps
read the plan, so grid step ``i`` fetches slot ``plan[i]``'s block, runs the
family's ``body`` on it and writes its successor back to the same place; a
dead slot is no grid step and costs nothing. The tensor is aliased input to
output, so a dead slot's state is neither read nor written and stays what it
was (``state_pool.insert`` replaces a slot's state whole before anything
reads it). With nothing live the grid is one step that hands the block it
was given (slot 0's) through as it was.

``body(state_ref, *operand_refs, readout_ref, successor_ref)`` is the
family's own recurrence on ONE slot's block, in VMEM: it reads
``state_ref`` and the slot's operands, writes the block's successor and the
slot's read-out. It is the family's to lay out so that the vector units
keep up with the blocks' DMA (a head or a lane tile at a time, nothing of
the block's size held as a value).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lowering import resolve_interpret

# Beyond the blocks themselves: Mosaic's own scratch and the spills of a
# body's unrolled loop.
VMEM_HEADROOM_BYTES = 8 << 20


def live_plan(position):
    """The plan of a step over the slots at ``position`` (slots,) — a slot
    is live iff its position is > 0, the convention of
    ``decode_attention.block_plan``: (slots + 2,) int32, the live slots'
    indices in order, then the last live slot's index repeated (slot 0 with
    nothing live) through entry ``slots`` — no grid step reads those, but a
    block index worked out one step ahead stays a slot's —, and last the
    live count. The same for every tensor of a step: XLA computes it
    once."""
    slots = position.shape[0]
    live = position > 0
    index = jnp.arange(slots + 1)
    count = jnp.cumsum(live)
    # the i-th live slot is the first whose running count passes i
    order = (count[None, :] <= index[:, None]).sum(axis=1)
    last = jnp.max(jnp.where(live, index[:slots], 0))
    return jnp.concatenate([jnp.where(index < count[-1], order, last),
                            count[-1:]]).astype(jnp.int32)


def vmem_bytes(block_bytes: int, small_bytes: int) -> int:
    """What one call holds in VMEM: the tensor's block double-buffered in
    and out, the slot's operands and read-out double-buffered, and the
    headroom — also the limit the call asks Mosaic for."""
    return 4 * block_bytes + 2 * small_bytes + VMEM_HEADROOM_BYTES


def _kernel(plan_ref, state_ref, *refs, body):
    *operand_refs, readout_ref, successor_ref = refs
    live = plan_ref[plan_ref.shape[0] - 1]

    @pl.when(live > 0)
    def _advance():
        body(state_ref, *operand_refs, readout_ref, successor_ref)

    @pl.when(live == 0)
    def _hand_through():   # the one grid step of a step with nothing live
        successor_ref[...] = state_ref[...]


def _block(shape: tuple):
    """One slot's block of a ``(slots, *shape)`` array, taken at the slot
    the plan names for the grid step."""
    zeros = (0,) * len(shape)
    return pl.BlockSpec((None, *shape), lambda i, plan: (plan[i], *zeros))


def _lifted(shape: tuple) -> tuple:
    """A per-slot shape as the kernel holds it: at least two dimensions, so
    that a block's last two are the array's own."""
    return (1,) * (2 - len(shape)) + tuple(shape)


@partial(jax.jit, static_argnames=("body", "readout", "interpret"))
def _update(tensor, operands, plan, *, body, readout, interpret: bool):
    """Jitted on its own so that the layers of a step program share one
    traced and lowered kernel."""
    slots, *shape = tensor.shape
    out_shape, out_dtype = readout
    operands = [op.reshape(slots, *_lifted(op.shape[1:])) for op in operands]
    small = sum(int(np.prod(op.shape[1:])) * op.dtype.itemsize
                for op in operands) + int(np.prod(out_shape)) * np.dtype(
                    out_dtype).itemsize
    out, tensor = pl.pallas_call(
        partial(_kernel, body=body),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(jnp.maximum(plan[slots + 1], 1),),
            in_specs=[_block(tuple(shape)),
                      *(_block(op.shape[1:]) for op in operands)],
            out_specs=[_block(_lifted(out_shape)), _block(tuple(shape))]),
        out_shape=[
            jax.ShapeDtypeStruct((slots, *_lifted(out_shape)), out_dtype),
            jax.ShapeDtypeStruct(tensor.shape, tensor.dtype)],
        # operand 0 is the plan: the tensor is operand 1, result 1
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_bytes(
                int(np.prod(shape)) * tensor.dtype.itemsize, small)),
        interpret=interpret,
        name="state_update",
    )(plan, tensor, *operands)
    return out.reshape(slots, *out_shape), tensor


def live_update(tensor, operands, plan, body, readout, *,
                interpret: bool | None = None):
    """Advance ``tensor`` (slots, *shape) at the live slots of ``plan``
    (``live_plan``), in place where the caller donates it. ``operands``:
    per-slot arrays ``(slots, ...)``, each handed to ``body`` as its one
    slot's block (a vector as ``(1, n)``); ``readout``: ``(shape a slot,
    dtype)`` of what ``body`` reads out. Returns ``(read-out (slots, *shape),
    the tensor's successor)``; a dead slot's read-out is never written and
    holds whatever the buffer did."""
    shape, dtype = readout
    return _update(tensor, tuple(operands), plan, body=body,
                   readout=(tuple(shape), jnp.dtype(dtype)),
                   interpret=resolve_interpret("state_update", interpret))
