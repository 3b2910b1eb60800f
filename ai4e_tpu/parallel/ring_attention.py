"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

Long-context serving is first-class in this framework (the reference has no
sequence dimension at all — SURVEY.md §5 long-context; this is the TPU-native
capability that slot gets). Two interchangeable strategies over the mesh's
``sp`` axis:

- **Ring attention** (``ring_attention``): K/V blocks rotate around the sp
  ring via ``jax.lax.ppermute`` while each device holds its Q shard; softmax
  is accumulated online (flash-attention style running max/denominator), so
  attention over a sequence of length S costs each device O(S·S/n) FLOPs and
  only ever materialises S/n-sized K/V blocks — communication rides
  nearest-neighbour ICI links and overlaps with the block matmuls.
- **Ulysses** (``ulysses_attention``): ``jax.lax.all_to_all`` reshuffles the
  sequence shard into a heads shard, runs ordinary full-sequence attention on
  1/n of the heads, and shuffles back. Cheaper at moderate S (two all-to-alls
  instead of n-1 permutes), but caps sp at the head count.

Both are pure SPMD collectives — XLA schedules them on ICI; no NCCL-style
backend exists or is needed (SURVEY.md §5 distributed-communication).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def reference_attention(q, k, v, causal: bool = False):
    """Plain full attention — the correctness oracle for the parallel paths.

    Shapes: q (B, H, S, D), k/v (B, H, S, D).
    """
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        s_q, s_k = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool))
        scores = jnp.where(mask, scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v)


def _ring_attention_local(q, k, v, axis_name: str, causal: bool,
                          vary_axes: tuple = ()):
    """Per-device body under shard_map: q/k/v are the local seq shards
    (B, H, S/n, D). ``vary_axes`` lists every manual axis the inputs vary
    over (the sp axis plus any batch axes) — the scan carry init must be
    marked varying over all of them to match the collective-produced carry."""
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    s_local = q.shape[2]
    scale = q.shape[-1] ** -0.5

    q_pos = my_idx * s_local + jnp.arange(s_local)  # global positions of my Q

    def step(carry, t):
        o, m, l, k_blk, v_blk = carry
        # Which device's block do I currently hold? After t hops of a +1
        # rotation, block (my_idx - t) mod n.
        src = (my_idx - t) % n
        k_pos = src * s_local + jnp.arange(s_local)

        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk) * scale
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(mask[None, None], scores, -jnp.inf)

        blk_max = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, blk_max)
        # All -inf rows (nothing visible yet in causal mode) → keep m to
        # avoid NaNs from (-inf) - (-inf).
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(scores - m_safe)
        p = jnp.where(jnp.isfinite(scores), p, 0.0)
        correction = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)

        l_new = l * correction + jnp.sum(p, axis=-1, keepdims=True)
        o_new = o * correction + jnp.einsum("bhqk,bhkd->bhqd",
                                            p.astype(v_blk.dtype), v_blk)

        # Rotate K/V one hop around the ring (device i → i+1).
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return (o_new, m_new, l_new, k_next, v_next), None

    o0 = jnp.zeros_like(q)
    # Mark device-constant initial carries as axis-varying so the scan carry
    # type matches its (collective-produced, varying) outputs.
    vary = vary_axes or (axis_name,)
    m0 = jax.lax.pcast(jnp.full((*q.shape[:3], 1), -jnp.inf, q.dtype),
                       vary, to="varying")
    l0 = jax.lax.pcast(jnp.zeros((*q.shape[:3], 1), q.dtype), vary,
                       to="varying")
    (o, m, l, _, _), _ = jax.lax.scan(
        step, (o0, m0, l0, k, v), jnp.arange(n))
    return o / jnp.maximum(l, 1e-30)


def ring_attention(q, k, v, mesh: Mesh, causal: bool = False,
                   axis_name: str = "sp", batch_axes=None):
    """Sequence-parallel attention: inputs sharded (B, H, S@sp, D) on
    ``mesh``; output sharded the same way. ``batch_axes`` names mesh axes the
    batch dim is already sharded over (e.g. ``("dp", "fsdp")`` inside the
    serving runtime) so entering the shard_map doesn't force a gather."""
    spec = P(batch_axes, None, axis_name, None)
    if batch_axes is None:
        vary = (axis_name,)
    elif isinstance(batch_axes, str):
        vary = (batch_axes, axis_name)
    else:
        vary = (*batch_axes, axis_name)
    fn = shard_map(
        partial(_ring_attention_local, axis_name=axis_name, causal=causal,
                vary_axes=vary),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def _ulysses_local(q, k, v, axis_name: str, causal: bool):
    """Per-device: (B, H, S/n, D) → all-to-all → (B, H/n, S, D) → attention →
    back. Requires H % n == 0."""
    n = jax.lax.psum(1, axis_name)
    # Scatter heads (axis 1), gather sequence (axis 2).
    q2 = jax.lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2,
                            tiled=True)
    k2 = jax.lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2,
                            tiled=True)
    v2 = jax.lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2,
                            tiled=True)
    o2 = reference_attention(q2, k2, v2, causal=causal)
    # Scatter sequence back, gather heads.
    return jax.lax.all_to_all(o2, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)


def ulysses_attention(q, k, v, mesh: Mesh, causal: bool = False,
                      axis_name: str = "sp", batch_axes=None):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style)."""
    n = mesh.shape[axis_name]
    if q.shape[1] % n:
        raise ValueError(f"heads {q.shape[1]} not divisible by sp={n}")
    spec = P(batch_axes, None, axis_name, None)
    fn = shard_map(
        partial(_ulysses_local, axis_name=axis_name, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
