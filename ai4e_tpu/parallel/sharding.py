"""Device mesh + sharding helpers — the framework's parallelism vocabulary.

The reference scales by replicating opaque GPU containers behind a queue
(SURVEY.md §2 parallelism inventory); here parallelism is first-class and
in-process: a named ``jax.sharding.Mesh`` over the TPU slice, with
``NamedSharding`` annotations and XLA-inserted collectives over ICI.

Axis conventions (scaling-book style):
- ``dp``   — data parallel: batch dimension sharded across replicas;
- ``fsdp`` — fully-sharded data parallel: parameters sharded on the same axis
  as data, all-gathered per layer;
- ``tp``   — tensor parallel: hidden/feature dimensions sharded; matmuls
  produce partial sums reduced with ``psum`` over ICI;
- ``sp``   — sequence parallel: long-context sequence dimension sharded (ring
  attention lives on this axis, see ``ring_attention.py``);
- ``ep``   — expert parallel: MoE experts sharded (reserved).

On a single host the mesh covers local devices; multi-host slices initialise
``jax.distributed`` first (``init_distributed``) and build the mesh over
``jax.devices()`` which then spans all hosts — the data plane the reference
never had (its NCCL-equivalent was HTTPS+queues, SURVEY.md §5).
"""

from __future__ import annotations

import dataclasses
import logging
import math
import os

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

log = logging.getLogger("ai4e_tpu.parallel")

AXES = ("dp", "fsdp", "tp", "sp", "ep")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape. Zero/one-sized axes are kept in the mesh (size 1)
    so PartitionSpecs referencing them always resolve."""

    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1

    @property
    def size(self) -> int:
        return self.dp * self.fsdp * self.tp * self.sp * self.ep

    @classmethod
    def data_parallel(cls, n_devices: int) -> "MeshSpec":
        return cls(dp=n_devices)

    @classmethod
    def auto(cls, n_devices: int, model_parallel: int = 1,
             sequence_parallel: int = 1) -> "MeshSpec":
        """Fill dp with whatever model/sequence parallelism leaves over."""
        denom = model_parallel * sequence_parallel
        if n_devices % denom:
            raise ValueError(
                f"{n_devices} devices not divisible by tp*sp={denom}")
        return cls(dp=n_devices // denom, tp=model_parallel, sp=sequence_parallel)


def make_mesh(spec: MeshSpec | None = None,
              devices: list | None = None) -> Mesh:
    """Build the named mesh. Default: all local devices on ``dp``.

    Axis order places ``tp`` innermost so tensor-parallel collectives ride the
    fastest ICI links (nearest-neighbour on a v5e torus), with ``sp`` next —
    the layout guidance of the scaling-book recipe.
    """
    devices = devices if devices is not None else jax.devices()
    if spec is None:
        spec = MeshSpec.data_parallel(len(devices))
    if spec.size != len(devices):
        raise ValueError(f"mesh spec {spec} needs {spec.size} devices, "
                         f"got {len(devices)}")
    arr = np.array(devices).reshape(spec.dp, spec.fsdp, spec.ep, spec.sp, spec.tp)
    return Mesh(arr, ("dp", "fsdp", "ep", "sp", "tp"))


# -- sharding builders -----------------------------------------------------

def batch_sharding(mesh: Mesh, ndim: int = 2) -> NamedSharding:
    """Shard the leading (batch) dim over dp+fsdp, replicate the rest."""
    return NamedSharding(mesh, P(("dp", "fsdp"), *([None] * (ndim - 1))))

def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def spec_for_param(path: tuple, value, tp_rules=None) -> P:
    """PartitionSpec for one parameter by name-path match.

    Two rule forms, both first-match-wins on the ``/``-joined param path:

    - ``dict`` — substring → PartitionSpec (the original form; e.g.
      ``{"mlp/up": P(None, "tp")}``). No match: replicate.
    - ``list``/``tuple`` of ``(regex, PartitionSpec)`` pairs — the
      checkpoint-tree mapping the mesh serving plane declares
      (docs/mesh_serving.md#partition-rules): ``re.search`` per rule in
      order. Scalar (rank-0) leaves always replicate without consulting
      the rules; a non-scalar leaf NO rule matches raises ValueError at
      placement time — a regex rule set is a complete declaration, and a
      silently replicated tp param would serve wrong math on a split
      mesh, so the gap must fail registration, not the request path.
      End the list with ``(".*", P())`` to opt into replicate-by-default.

    This is the annotate-and-let-XLA-insert-collectives workflow: params
    get specs, pjit does the rest.
    """
    if isinstance(tp_rules, (list, tuple)):
        if not hasattr(value, "ndim") or value.ndim == 0:
            return P()
        import re
        joined = "/".join(str(p) for p in path)
        for pattern, spec in tp_rules:
            if re.search(pattern, joined):
                return spec
        raise ValueError(
            f"no partition rule matches param {joined!r} — regex rule sets "
            f"must be complete (add a ('.*', P()) catch-all to replicate)")
    if tp_rules:
        joined = "/".join(str(p) for p in path)
        for needle, spec in tp_rules.items():
            if needle in joined:
                return spec
    return P()


def shard_params(params, mesh: Mesh, tp_rules=None):
    """Place a pytree of params onto the mesh per ``tp_rules`` (either
    rule form ``spec_for_param`` accepts)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    placed = []
    for path, leaf in flat:
        spec = spec_for_param(tuple(p.key if hasattr(p, "key") else p.idx
                                    for p in path), leaf, tp_rules)
        placed.append(jax.device_put(leaf, NamedSharding(mesh, spec)))
    return jax.tree_util.tree_unflatten(treedef, placed)


# -- multi-host ------------------------------------------------------------

def init_distributed(coordinator: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None) -> None:
    """Initialise the cross-host data plane (``jax.distributed``) — the DCN
    layer under multi-host meshes. No-op when single-process.

    Reads JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID when
    args are absent (typed-config-over-env, SURVEY.md §5 config system).
    """
    coordinator = coordinator or os.environ.get("JAX_COORDINATOR_ADDRESS")
    if not coordinator or jax.distributed.is_initialized():
        return
    num_processes = num_processes or int(os.environ.get("JAX_NUM_PROCESSES", "1"))
    process_id = process_id if process_id is not None else int(
        os.environ.get("JAX_PROCESS_ID", "0"))
    if num_processes <= 1:
        return
    jax.distributed.initialize(coordinator, num_processes, process_id)
    log.info("jax.distributed up: %d processes, this is %d",
             num_processes, process_id)


def pad_to_multiple(n: int, multiple: int) -> int:
    return int(math.ceil(n / multiple) * multiple)
