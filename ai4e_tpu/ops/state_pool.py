"""The decode path's state pool — what a slot keeps that does not grow
with its sequence.

A family whose layers keep a fixed-size state a sequence (a recurrence's
matrix, a convolution's last inputs) declares each as ``(name, shape a
slot, dtype)`` in its ``cache_spec()`` (``kv_pool.SlotSpec.state``). The
pool holds one tensor a name::

    state[name] : (slots, *shape)

so a slot is an index of the leading axis of every tensor, as it is a row of
the K/V pool, and one slot's state is one contiguous block of a tensor. The
step program takes ``state[name]`` and returns its successor, donated, so
every write lands where the tensor lies — no slice of a pool, no write back
into one, and nothing else of the pool's size. A tensor a family names in
``SlotSpec.live`` it advances through ``update_live``: one Pallas call
(``ops/pallas/state_update.py``) whose grid is the step's live slots, the
tensor aliased input to output, so the step reads and writes the state of
the slots that hold a sequence and no other — a dead slot's block is
neither fetched nor written and stays what it was. A tensor it does not
name it reads and returns whole in ``jax.numpy`` (every slot's bytes move:
right for a tensor of a few KB a slot, where a kernel's launch costs more).
A prefill returns a sequence's state after its ``length`` tokens as
``{name: (1, *shape)}`` and ``insert`` lands it in the slot: the whole of a
slot's state is replaced, so nothing of the sequence that held the slot
before is left. A family that declares nothing has the empty pool, ``{}``,
which adds nothing to any program.

This file owns that layout; ``runtime/kvcache.py`` allocates, inserts and
counts through it, and a model reads and returns the tensors it declared.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .pallas import state_update


def allocate(state: tuple, slots: int) -> dict:
    """The clean pool of a ``SlotSpec.state`` declaration."""
    return {name: jnp.zeros((slots, *shape), dtype)
            for name, shape, dtype in state}


def nbytes(state: tuple, slots: int) -> int:
    """Resident bytes of the pool ``allocate`` gives."""
    return sum(slots * int(np.prod(shape)) * np.dtype(dtype).itemsize
               for _, shape, dtype in state)


def insert(pool: dict, block: dict, slot) -> dict:
    """Land one sequence's state (``{name: (1, *shape)}``, a prefill's) in
    ``slot`` — which may be traced: one program, any slot."""
    with jax.named_scope("state_insert"):
        return {name: jax.lax.dynamic_update_slice(
            tensor, block[name].astype(tensor.dtype),
            (slot, *(0,) * (tensor.ndim - 1)))
            for name, tensor in pool.items()}


def update_live(tensor, operands, position, body, readout,
                interpret: bool | None = None):
    """The step's one way to advance a state tensor at its live slots only.
    ``tensor``: ``(slots, *shape)`` as ``allocate`` made it; ``position``:
    (slots,) — a slot is live iff its position is > 0, the convention of
    the K/V read (the engine hands every inactive slot position 0, and
    ``PagedDecodeRuntime.launch`` refuses an active one there);
    ``operands``: the step's per-slot values the recurrence needs, each
    ``(slots, ...)``; ``body(state_ref, *operand_refs, readout_ref,
    successor_ref)``: the family's recurrence on one slot's block
    (``pallas/state_update.py`` says what it is handed); ``readout``:
    ``(shape a slot, dtype)`` of what it reads out. Returns ``(read-out
    (slots, *shape) — zeros at a dead slot —, the tensor's successor)``: a
    dead slot's state is bit for bit what it was. The plan is the same for
    every tensor of a step: XLA computes it once. Mosaic on the chip, the
    interpreter elsewhere, unless ``interpret`` says."""
    out, tensor = state_update.live_update(
        tensor, operands, state_update.live_plan(position), body, readout,
        interpret=interpret)
    live = (position > 0).reshape(-1, *(1,) * len(readout[0]))
    # a dead slot's read-out was never written: defined here, read nowhere
    return jnp.where(live, out, 0), tensor


def slot_bytes(state: tuple, live: tuple) -> tuple:
    """Bytes of state a slot holds, ``(in the tensors named in ``live``, in
    the others)``: a decode step with ``n`` live slots of ``slots`` reads and
    writes ``n`` times the first (``update_live``) and ``slots`` times the
    second, each once in and once out."""
    sizes = {name: nbytes(((name, shape, dtype),), 1)
             for name, shape, dtype in state}
    sparse = sum(size for name, size in sizes.items() if name in live)
    return sparse, sum(sizes.values()) - sparse
