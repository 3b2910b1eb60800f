"""The decode path's state pool — what a slot keeps that does not grow
with its sequence.

A family whose layers keep a fixed-size state a sequence (a recurrence's
matrix, a convolution's last inputs) declares each as ``(name, shape a
slot, dtype)`` in its ``cache_spec()`` (``kv_pool.SlotSpec.state``). The
pool holds one tensor a name::

    state[name] : (slots, *shape)

so a slot is an index of the leading axis of every tensor, as it is a row of
the K/V pool, and a tensor a layer keeps the step's update whole: the step
program reads ``state[name]`` and returns its successor, donated, so XLA
writes it where it lies — no slice of a pool, no write back into one, and
nothing else of the pool's size. A prefill returns a sequence's state
after its ``length`` tokens as ``{name: (1, *shape)}`` and ``insert`` lands
it in the slot: the whole of a slot's state is replaced, so nothing of the
sequence that held the slot before is left. A family that declares nothing
has the empty pool, ``{}``, which adds nothing to any program.

This file owns that layout; ``runtime/kvcache.py`` allocates and inserts
through it, and a model reads and returns the tensors it declared.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


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
