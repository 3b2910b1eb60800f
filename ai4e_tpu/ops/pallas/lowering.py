"""How a Pallas kernel lowers — decided in one place, said once.

Every kernel here defaults to Mosaic on a TPU backend and to the Pallas
interpreter anywhere else, which is what lets CPU tests run the same code.
That default must never be how a chip run degrades without anyone knowing,
so the choice is logged at trace time, once per kernel and mode: a worker's
log shows ``pallas normalize_image: Mosaic`` or ``... interpreter``.
"""

from __future__ import annotations

import functools
import logging

import jax
from jax.sharding import PartitionSpec as P

log = logging.getLogger("ai4e_tpu.pallas")


def resolve_interpret(kernel: str, interpret: bool | None) -> bool:
    """The ``interpret`` flag to pass to ``pallas_call``: the caller's
    explicit choice, else interpreter iff the default backend is not a
    TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _log_once(kernel, interpret)
    return interpret


@functools.cache
def _log_once(kernel: str, interpret: bool) -> None:
    log.info("pallas %s: %s (default backend %s)", kernel,
             "interpreter" if interpret else "Mosaic", jax.default_backend())


def shard_over_batch(kernel, mesh, interpret: bool, replicated_args: int = 0):
    """``kernel(batched, *replicated)`` made safe inside a jit whose batch is
    sharded over ``mesh``'s data axes — which is every servable
    ``ModelRuntime`` registers on more than one chip.

    GSPMD cannot partition a Mosaic custom call ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map" — what
    the deployed landcover worker died of at warm-up on a four-chip v5e
    host). The kernels here are independent per example, so each device runs
    the kernel on its own slice of the leading dimension; ``replicated_args``
    trailing arguments are handed to every device whole. Returned unchanged
    without a mesh, on one data shard, or in the interpreter, whose plain XLA
    ops partition by themselves."""
    if mesh is None or interpret:
        return kernel
    axes = tuple(a for a in ("dp", "fsdp") if mesh.shape.get(a, 1) > 1)
    if not axes:
        return kernel
    # check_vma off: pallas_call's out_shape carries no varying-axes type,
    # and there is nothing to check — no collective, one slice in, one out.
    return jax.shard_map(kernel, mesh=mesh,
                         in_specs=(P(axes), *[P()] * replicated_args),
                         out_specs=P(axes), check_vma=False)
