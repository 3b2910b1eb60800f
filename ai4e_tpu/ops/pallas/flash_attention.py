"""Pallas TPU kernel: fused flash attention (online-softmax, no S×S scores).

The long-context path's hottest op. Plain attention materialises a
(S_q, S_k) float32 score matrix per (batch, head) — at S=16k that is 1 GB
per head and pure HBM traffic. This kernel streams K/V blocks through VMEM
with a running max/denominator (the same online softmax the ring step uses
across devices, here applied across blocks within one device), so the score
matrix never exists: HBM traffic drops from O(S²) to O(S·D) and the two
matmuls land on the MXU back-to-back.

Differentiable (r5): a ``jax.custom_vjp`` with pallas backward kernels —
the FlashAttention-2 recurrence. The forward saves only O and the per-row
logsumexp (lane-replicated, the layout the TPU vector unit wants); the
backward recomputes P = exp(S - lse) blockwise, so training never
materialises the score matrix either. Before this, long-context TRAINING
fell back to full attention (``train/make_checkpoints.py`` trained seq-4096
against materialised 4096² scores); now the training plane matches the
serving plane.

Role in the stack (``models/seqformer.py`` / ``parallel/ring_attention.py``):

- single-device long-context serving: ``attention_for(..., "flash")`` (the
  ``auto`` default off sequence-parallel meshes);
- inside Ulysses, each device attends over the full gathered sequence with
  1/n of the heads — that inner call is exactly this kernel's shape.

Layout (pallas_guide.md): grid is (B·H, S_q/block_q, S_k/block_k) — the K
dimension is a *grid* axis, not a whole-S_k VMEM block, so VMEM holds only
(block_q, D) + (block_k, D) tiles plus the (block_q, D) accumulator
regardless of sequence length (S=32k works in the same footprint as S=1k).
TPU grids execute sequentially with the rightmost axis fastest, so the
accumulator/max/denominator live in VMEM scratch carried across the k-axis
steps; the output block is written on the last k step. D rides the 128-lane
axis; block_q rides sublanes.

Two more bodies serve the decode engine's long prefills (``ops/kv_pool.py``
``prompt_attention`` / ``prompt_index_scores``; inference only): a prompt's
causal attention whose keys and values differ in width, under a one-byte
mask (a learned selection) or a window, in the operands' own dtype on the
MXU — ``prompt_attention`` — and the index scores such a selection is made
from — ``index_scores``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lowering import resolve_interpret, shard_over_batch

NEG_INF = -1e30  # large-but-finite: avoids (-inf) - (-inf) NaNs in the kernel


# The logsumexp residual rides lane-replicated (the official TPU flash
# kernel's layout): a (block_q,) per-row scalar broadcast across the
# 128-lane axis, so stores/loads are plain vector ops, never a transpose.
LANES = 128


def _mask_causal(s, iq, ik, block_q: int, block_k: int):
    """Set above-diagonal scores to NEG_INF for the (iq, ik) block pair —
    the one mask construction shared by the forward and both backward
    kernels."""
    q_pos = (iq * block_q
             + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0))
    k_pos = (ik * block_k
             + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1))
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _block_relevant(iq, ik, block_q: int, block_k: int):
    """False iff the (iq, ik) block pair lies strictly above the causal
    diagonal (its bottom-left corner is masked) — such blocks contribute
    nothing and are skipped, halving causal work."""
    return (iq + 1) * block_q - 1 >= ik * block_k


def _flash_kernel(q_ref, k_ref, v_ref, out_ref, *rest,
                  n_k_blocks: int, causal: bool, scale: float,
                  save_lse: bool):
    # q_ref/out_ref: (1, block_q, D); k_ref/v_ref: (1, block_k, D);
    # scratch: acc (block_q, D), m/l (block_q, 1) — carried across the
    # sequential k-axis grid steps. With ``save_lse`` an extra
    # (1, block_q, LANES) output carries m + log(l) for the backward.
    if save_lse:
        lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        (acc_ref, m_ref, l_ref), lse_ref = rest, None
    block_q, d = q_ref.shape[1], q_ref.shape[2]
    block_k = k_ref.shape[1]
    # program_id must be read at the kernel's top level — inside a
    # pl.when branch it escapes the pallas trace (interpret mode lowers
    # the branch as plain XLA, where the primitive has no rule).
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _accumulate():
        q = q_ref[0].astype(jnp.float32) * scale
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        scores = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (bq, bk) on the MXU
        if causal:
            scores = _mask_causal(scores, iq, ik, block_q, block_k)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # Blocks strictly above the diagonal contribute nothing — skip
        # their matmuls entirely (half the grid at S_q == S_k).
        pl.when(_block_relevant(iq, ik, block_q, block_k))(_accumulate)
    else:
        _accumulate()

    @pl.when(ik == n_k_blocks - 1)
    def _finish():
        out_ref[0] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)
        if lse_ref is not None:
            lse = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))
            lse_ref[0] = jnp.broadcast_to(lse, (block_q, LANES))


def _bwd_recompute(q_ref, do_ref, lse_ref, di_ref, k_ref, v_ref,
                   iq, ik, causal: bool, scale: float):
    """Shared backward recompute — the FlashAttention-2 step both backward
    kernels start from: P = exp(S − lse) rebuilt blockwise (exact softmax
    probabilities; masked → 0) and dS = P ⊙ (dO·Vᵀ − Δ). Returns
    ``(p, ds, q, do, kb)`` — dK/dV contract against q/do, dQ against kb."""
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    kb = k_ref[0].astype(jnp.float32)
    vb = v_ref[0].astype(jnp.float32)
    lse = lse_ref[0][:, :1]  # (block_q, 1) from the lane-replicated block
    di = di_ref[0][:, :1]

    s = scale * jax.lax.dot_general(
        q, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # (bq, bk)
    if causal:
        s = _mask_causal(s, iq, ik, block_q, block_k)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(
        do, vb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    ds = p * (dp - di)
    return p, ds, q, do, kb


def _flash_bwd_dkv_kernel(q_ref, do_ref, lse_ref, di_ref, k_ref, v_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *,
                          n_q_blocks: int, causal: bool, scale: float):
    """dK/dV: grid (B·H, S_k/block_k, S_q/block_q) — for a fixed k-block,
    accumulate contributions from every q-block in VMEM scratch (the q axis
    is the fast, sequential one), writing dk/dv on the last q step.
    P is recomputed from the saved logsumexp — no score matrix in HBM."""
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    ik, iq = pl.program_id(1), pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _accumulate():
        p, ds, q, do, _ = _bwd_recompute(q_ref, do_ref, lse_ref, di_ref,
                                         k_ref, v_ref, iq, ik, causal, scale)
        # dV += Pᵀ·dO ; dK += scale·dSᵀ·Q
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(_block_relevant(iq, ik, block_q, block_k))(_accumulate)
    else:
        _accumulate()

    @pl.when(iq == n_q_blocks - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, do_ref, lse_ref, di_ref, k_ref, v_ref,
                         dq_ref, dq_acc, *,
                         n_k_blocks: int, causal: bool, scale: float):
    """dQ: grid (B·H, S_q/block_q, S_k/block_k) — the forward's own grid
    shape; accumulate over k-blocks, write dq on the last k step."""
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _accumulate():
        _, ds, _, _, kb = _bwd_recompute(q_ref, do_ref, lse_ref, di_ref,
                                         k_ref, v_ref, iq, ik, causal, scale)
        dq_acc[...] += scale * jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(_block_relevant(iq, ik, block_q, block_k))(_accumulate)
    else:
        _accumulate()

    @pl.when(ik == n_k_blocks - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _dividing_block(s: int, target: int) -> int:
    """Largest block size ≤ target that divides s (static shapes: the grid
    must tile the sequence exactly)."""
    for b in range(min(target, s), 0, -1):
        if s % b == 0:
            return b
    return 1


def default_blocks(d: int) -> tuple[int, int]:
    """Default (block_q, block_k) for head_dim ``d``.

    Tuned on TPU v5e at S=4096 D=128: 512/1024 measured 1.9x the 128/128
    blocks (74 vs 138 ms at B·H=128) at a ~3.4 MB double-buffered VMEM
    footprint (validate.py). VMEM cost scales linearly with D, so for
    D > 128 the tiles shrink proportionally (floor 128 — the sublane/lane
    minimum for fp32 tiling) to keep the footprint roughly constant rather
    than inheriting 4-8x bigger tiles that could exceed VMEM."""
    scale = max(1, d // 128)
    return max(128, 512 // scale), max(128, 1024 // scale)


def _forward_call(q3, k3, v3, causal: bool, block_q: int, block_k: int,
                  interpret: bool, save_lse: bool):
    """pallas_call for the forward on collapsed (B·H, S, D) operands;
    returns ``out`` or ``(out, lse)`` (lse lane-replicated f32)."""
    bh, s_q, d = q3.shape
    s_k = k3.shape[1]
    n_k_blocks = s_k // block_k
    out_shape = jax.ShapeDtypeStruct((bh, s_q, d), q3.dtype)
    out_spec = pl.BlockSpec((1, block_q, d), lambda b, iq, ik: (b, iq, 0))
    out_shapes, out_specs = out_shape, out_spec
    if save_lse:
        out_shapes = (out_shape,
                      jax.ShapeDtypeStruct((bh, s_q, LANES), jnp.float32))
        out_specs = (out_spec,
                     pl.BlockSpec((1, block_q, LANES),
                                  lambda b, iq, ik: (b, iq, 0)))
    return pl.pallas_call(
        partial(_flash_kernel, n_k_blocks=n_k_blocks, causal=causal,
                scale=d ** -0.5, save_lse=save_lse),
        grid=(bh, s_q // block_q, n_k_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, iq, ik: (b, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, iq, ik: (b, ik, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q3, k3, v3)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash3(q3, k3, v3, causal, block_q, block_k, interpret):
    return _forward_call(q3, k3, v3, causal, block_q, block_k, interpret,
                         save_lse=False)


def _flash3_fwd(q3, k3, v3, causal, block_q, block_k, interpret):
    out, lse = _forward_call(q3, k3, v3, causal, block_q, block_k, interpret,
                             save_lse=True)
    # Store one f32 per row (the lanes are replicas).
    return out, (q3, k3, v3, out, lse[..., 0])


def _flash3_bwd(causal, block_q, block_k, interpret, residuals, do):
    q3, k3, v3, out, lse = residuals
    bh, s_q, d = q3.shape
    s_k = k3.shape[1]
    scale = d ** -0.5
    n_q_blocks, n_k_blocks = s_q // block_q, s_k // block_k
    # Δ = rowsum(dO ⊙ O) — the softmax-jacobian correction, O(S·D)
    # elementwise; computed here (XLA) and fed lane-replicated.
    di = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    lse_r = jnp.broadcast_to(lse[..., None], (bh, s_q, LANES))
    di_r = jnp.broadcast_to(di[..., None], (bh, s_q, LANES))

    q_spec_by_q = pl.BlockSpec((1, block_q, d), lambda b, ik, iq: (b, iq, 0))
    lm_spec_by_q = pl.BlockSpec((1, block_q, LANES),
                                lambda b, ik, iq: (b, iq, 0))
    kv_spec = pl.BlockSpec((1, block_k, d), lambda b, ik, iq: (b, ik, 0))
    dk3, dv3 = pl.pallas_call(
        partial(_flash_bwd_dkv_kernel, n_q_blocks=n_q_blocks, causal=causal,
                scale=scale),
        grid=(bh, n_k_blocks, n_q_blocks),
        in_specs=[q_spec_by_q, q_spec_by_q, lm_spec_by_q, lm_spec_by_q,
                  kv_spec, kv_spec],
        out_specs=(kv_spec, kv_spec),
        out_shape=(jax.ShapeDtypeStruct((bh, s_k, d), k3.dtype),
                   jax.ShapeDtypeStruct((bh, s_k, d), v3.dtype)),
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )(q3, do, lse_r, di_r, k3, v3)

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, iq, ik: (b, iq, 0))
    lm_spec = pl.BlockSpec((1, block_q, LANES),
                           lambda b, iq, ik: (b, iq, 0))
    kv_spec_by_k = pl.BlockSpec((1, block_k, d),
                                lambda b, iq, ik: (b, ik, 0))
    dq3 = pl.pallas_call(
        partial(_flash_bwd_dq_kernel, n_k_blocks=n_k_blocks, causal=causal,
                scale=scale),
        grid=(bh, n_q_blocks, n_k_blocks),
        in_specs=[q_spec, q_spec, lm_spec, lm_spec,
                  kv_spec_by_k, kv_spec_by_k],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, s_q, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q3, do, lse_r, di_r, k3, v3)
    return dq3, dk3, dv3


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


def flash_attention(q, k, v, causal: bool = False, block_q: int | None = None,
                    block_k: int | None = None, interpret: bool | None = None,
                    mesh=None, batch_axes=None):
    """Fused attention: q (B, H, S_q, D), k/v (B, H, S_k, D) → (B, H, S_q, D).

    Differentiable: ``jax.grad`` through this op runs the pallas backward
    kernels (FlashAttention-2 recurrence — P recomputed from the saved
    logsumexp, no S×S matrix in either pass).

    Block sizes round DOWN to divisors of the sequence lengths, so any length
    works (prime lengths degrade toward block 1 — pad such sequences).
    ``block_q``/``block_k`` default per head_dim via :func:`default_blocks`
    (512/1024 at D≤128, shrinking for larger D to bound VMEM).
    ``interpret`` defaults to True off-TPU (CPU CI runs the pallas
    interpreter; on device it compiles to Mosaic). ``mesh``: the serving
    mesh when the batch is sharded over its data axes — each device then
    attends over its own examples (``lowering.shard_over_batch``);
    ``batch_axes`` is accepted so ``attention_for`` can treat this as a
    drop-in strategy alongside ring/Ulysses.
    """
    del batch_axes
    _, h, s_q, d = q.shape
    s_k = k.shape[2]
    if causal and s_q != s_k:
        raise ValueError("causal flash attention expects S_q == S_k")
    interpret = resolve_interpret("flash_attention", interpret)
    dq, dk = default_blocks(d)
    block_q = _dividing_block(s_q, block_q if block_q is not None else dq)
    block_k = _dividing_block(s_k, block_k if block_k is not None else dk)

    def call(qkv):
        q, k, v = qkv
        n = q.shape[0]  # this device's share of the batch
        out = _flash3(q.reshape(n * h, s_q, d), k.reshape(n * h, s_k, d),
                      v.reshape(n * h, s_k, d), causal, block_q, block_k,
                      interpret)
        return out.reshape(n, h, s_q, d)

    return shard_over_batch(call, mesh, interpret)((q, k, v))


# -- a prompt's attention under a mask: the decode engine's long prefill ----

# Queries and keys a grid step of ``prompt_attention``: a (512, 512) float32
# block of scores is 1 MB of VMEM.
PROMPT_BLOCK = 512
# What a call may hold in VMEM, and the limit it asks Mosaic for.
PROMPT_VMEM_BYTES = 32 * 1024 * 1024
# Heads a grid step at most (``_head_group``). The kernel is a straight line
# of them, which every start of a worker traces and lowers anew, compile
# cache or not: 8 heads a step cost ``dots3.longdoc`` 10.5 s of a warm 87 s
# set-up where 1 cost none, for 5 % of the kernel's time over 4 (PR 44).
PROMPT_HEADS = 4

# Rows of the list of pairs (``_prompt_pairs``).
IQ, IK, FIRST, LAST = range(4)


def _prompt_block(p: int) -> int:
    """The largest divisor of ``p`` up to ``PROMPT_BLOCK`` on whole lane
    tiles (a block of the mask has its keys on the lanes); ``p`` itself for
    a prompt no longer than one block."""
    if p <= PROMPT_BLOCK:
        return p
    for block in range(PROMPT_BLOCK, 0, -LANES):
        if p % block == 0:
            return block
    raise ValueError(f"a prompt of {p} positions has no block of whole lane "
                     f"tiles: pad it to a multiple of {LANES}")


def _prompt_pairs(blocks: int, block: int, window: int | None) -> np.ndarray:
    """The blocks of scores a prompt of ``blocks`` blocks computes, in the
    order the grid walks them: (4, pairs) int32 — the block of queries (IQ),
    the block of keys (IK), and whether the pair is its query block's FIRST
    and LAST. A query block reads the key blocks from the window's far edge
    (block 0 with no window) up to the diagonal, one after the other."""
    pairs = []
    for iq in range(blocks):
        lo = 0 if window is None else max(iq * block - (window - 1),
                                          0) // block
        pairs += [(iq, ik, ik == lo, ik == iq) for ik in range(lo, iq + 1)]
    return np.asarray(pairs, np.int32).T


def prompt_vmem_bytes(group: int, block: int, dqk: int, dv: int,
                      itemsize: int, masked: bool) -> int:
    """What a call that carries ``group`` heads a grid step holds in VMEM:
    the q, k, v and out blocks double-buffered, the accumulator, max and sum
    (a lane tile a row each), and what the heads share — the mask's block
    double-buffered, its float32 bias, and one head's scores, weights and
    their cast. A block's last dimension lies on whole lane tiles."""
    def lanes(d):
        return -(-d // LANES) * LANES

    operands = group * block * (2 * lanes(dqk) + 2 * lanes(dv)) * itemsize
    scratch = group * block * (lanes(dv) + 2 * LANES) * 4
    shared = block * lanes(block) * (2 * masked + 4 + 3 * 4)
    return 2 * operands + scratch + shared


def _head_group(heads: int, block: int, dqk: int, dv: int, itemsize: int,
                masked: bool) -> int:
    """Heads a grid step: the largest divisor of ``heads`` up to
    ``PROMPT_HEADS`` whose blocks fit ``PROMPT_VMEM_BYTES`` (one head where
    nothing larger divides or fits)."""
    return max(g for g in range(1, min(heads, PROMPT_HEADS) + 1)
               if heads % g == 0 and (
        g == 1 or prompt_vmem_bytes(g, block, dqk, dv, itemsize, masked)
        <= PROMPT_VMEM_BYTES))


def _across(column, width: int):
    """A row's value held on every lane, ``(rows, LANES)``, as ``(rows,
    width)``: whole lane tiles repeated — no lane moves —, or one lane
    spread where ``width`` is no multiple of a tile."""
    if width % LANES == 0:
        return pltpu.repeat(column, width // LANES, axis=1)
    return jnp.broadcast_to(column[:, :1], (column.shape[0], width))


def _prompt_kernel(pairs_ref, q_ref, k_ref, v_ref, *rest, block: int,
                   window: int | None, scale: float, masked: bool):
    # pairs_ref: (4, pairs) int32, the grid's second axis (``_prompt_pairs``);
    # q_ref, k_ref: (G, block, dqk); v_ref: (G, block, dv); mask_ref
    # (``masked``): (block, block) int8, nonzero where the query reads the
    # key; out_ref: (G, block, dv). Scratch: bias (block, block), what the
    # pair permits, the same for the G heads; carried across a query block's
    # pairs: acc (G, block, dv); m, l (G, block, LANES), a row's running max
    # and sum on every lane (the layout the vector unit subtracts and
    # multiplies by without moving a lane).
    if masked:
        mask_ref, out_ref, bias, acc, m, l = rest
    else:
        (out_ref, bias, acc, m, l), mask_ref = rest, None
    # program_id is read at the top level (``_flash_kernel``)
    t = pl.program_id(1)
    iq, ik = pairs_ref[IQ, t], pairs_ref[IK, t]

    @pl.when(pairs_ref[FIRST, t] == 1)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, NEG_INF)
        l[...] = jnp.zeros_like(l)

    q_pos = iq * block + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    k_pos = ik * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    allowed = k_pos <= q_pos
    if window is not None:
        allowed &= k_pos > q_pos - window
    if masked:
        allowed &= mask_ref[...].astype(jnp.int32) != 0
    # added to a head's float32 scores: s + 0.0 is s and s - 1e30 is -1e30
    # for any score a model makes, so a head reads what ``where`` would give
    bias[...] = jnp.where(allowed, 0.0, NEG_INF)

    # a straight line of G heads, not a loop: the scheduler runs one head's
    # products under another's softmax
    precision = (jax.lax.Precision.HIGHEST if v_ref.dtype == jnp.float32
                 else None)
    for g in range(q_ref.shape[0]):
        v = v_ref[g]
        scores = jax.lax.dot_general(
            q_ref[g], k_ref[g], (((1,), (1,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32) * scale + bias[...]
        m_prev = m[g]
        # held above NEG_INF: a query none of whose keys so far are allowed
        # would weigh them exp(0) = 1 otherwise
        m_new = jnp.maximum(
            jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True)),
            0.1 * NEG_INF)
        p = jnp.exp(scores - _across(m_new, block))
        shrink = jnp.exp(m_prev - m_new)
        l[g] = l[g] * shrink + p.sum(axis=-1, keepdims=True)
        m[g] = m_new
        acc[g] = acc[g] * _across(shrink, acc.shape[-1]) + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)

    @pl.when(pairs_ref[LAST, t] == 1)
    def _finish():
        for g in range(q_ref.shape[0]):
            total = _across(jnp.maximum(l[g], 1e-30), acc.shape[-1])
            out_ref[g] = (acc[g] / total).astype(out_ref.dtype)


@partial(jax.jit, static_argnames=("scale", "window", "interpret"))
def _prompt(q, k, v, mask, *, scale: float, window: int | None,
            interpret: bool):
    heads, p, dqk = q.shape
    dv = v.shape[-1]
    block = _prompt_block(p)
    group = _head_group(heads, block, dqk, dv, v.dtype.itemsize,
                        mask is not None)
    pairs = _prompt_pairs(p // block, block, window)

    def queries(h, t, pairs):
        return h, pairs[IQ, t], 0

    def keys(h, t, pairs):
        return h, pairs[IK, t], 0

    in_specs = [pl.BlockSpec((group, block, dqk), queries),
                pl.BlockSpec((group, block, dqk), keys),
                pl.BlockSpec((group, block, dv), keys)]
    operands = [q, k, v]
    if mask is not None:
        in_specs.append(pl.BlockSpec(
            (block, block), lambda h, t, pairs: (pairs[IQ, t], pairs[IK, t])))
        operands.append(mask.astype(jnp.int8))
    return pl.pallas_call(
        partial(_prompt_kernel, block=block, window=window, scale=scale,
                masked=mask is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(heads // group, pairs.shape[1]),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((group, block, dv), queries),
            scratch_shapes=[pltpu.VMEM((block, block), jnp.float32),
                            pltpu.VMEM((group, block, dv), jnp.float32),
                            pltpu.VMEM((group, block, LANES), jnp.float32),
                            pltpu.VMEM((group, block, LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((heads, p, dv), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=PROMPT_VMEM_BYTES),
        interpret=interpret,
        name="prompt_attention",
    )(jnp.asarray(pairs), *operands)


def prompt_attention(q, k, v, *, scale: float, mask=None,
                     window: int | None = None,
                     interpret: bool | None = None):
    """Causal attention of one prompt whose keys and values differ in width,
    under a mask and a window: q, k: (H, P, dqk); v: (H, P, dv) → (H, P, dv)
    in ``v``'s dtype. A query at ``t`` reads the keys ``s <= t`` — of them,
    where ``window`` is given, those with ``s > t - window``, and, where
    ``mask (P, P)`` is, those it marks nonzero (a learned selection reaches
    the kernel as data, one byte a pair, the same for every head). No score
    leaves VMEM: float32 scores and online softmax a block, the weights cast
    to ``v``'s dtype for the value product.

    A grid step carries a group of heads against ONE block of the mask: what
    the block permits — the diagonal, the window, the mask's bytes — is
    folded once into a float32 bias, and each head of the group adds it to
    its own scores; the mask's block is fetched once a group, not once a
    head. The group is a divisor of ``H`` up to ``PROMPT_HEADS`` that fits
    the kernel's VMEM at these widths (``_head_group``), laid out as a
    straight line so that one head's products run under another's softmax,
    and a head's output does not depend on the group it ran in. A row's running max and
    sum lie on every lane of a tile: the scores subtract them and the
    accumulator scales by them with no lane moved. The grid is as long as
    the blocks read: a static list of (query block, key block) pairs
    (``_prompt_pairs``), every block on or under the diagonal and, with a
    window, inside the band — none above or behind is fetched, computed or
    stepped over. ``P`` is one block or a multiple of 128
    (``_prompt_block``)."""
    if mask is not None and mask.shape != (q.shape[1], q.shape[1]):
        raise ValueError(f"mask {mask.shape} for {q.shape[1]} positions")
    return _prompt(q, k, v, mask, scale=float(scale), window=window,
                   interpret=resolve_interpret("prompt_attention", interpret))


def _index_kernel(first_ref, iq_ref, ik_ref, w_ref, out_ref, *, block: int):
    # iq_ref: (J, B, d), a block of queries' index heads; ik_ref: (block, d),
    # a block of keys; w_ref: (B, J) float32; out_ref: (B, block) float32.
    ik = pl.program_id(0)
    queries = out_ref.shape[0]
    below = ik * block <= first_ref[0] + queries - 1

    @pl.when(below)
    def _score():
        keys, w = ik_ref[...], w_ref[...]
        precision = (jax.lax.Precision.HIGHEST if keys.dtype == jnp.float32
                     else None)
        total = jnp.zeros(out_ref.shape, jnp.float32)
        for j in range(iq_ref.shape[0]):
            s = jax.lax.dot_general(
                iq_ref[j], keys, (((1,), (1,)), ((), ())),
                precision=precision, preferred_element_type=jnp.float32)
            total += jnp.maximum(s, 0.0) * w[:, j:j + 1]
        out_ref[...] = total

    @pl.when(jnp.logical_not(below))
    def _above():   # every key of the block lies after every query
        out_ref[...] = jnp.zeros_like(out_ref)


@partial(jax.jit, static_argnames=("interpret",))
def _index(iq, ik, w, first, *, interpret: bool):
    heads, queries, d = iq.shape
    p = ik.shape[0]
    block = _prompt_block(p)

    def keys(k, first):
        # a block above the diagonal is the diagonal's again: not fetched
        return jnp.minimum(k, (first[0] + queries - 1) // block), 0

    return pl.pallas_call(
        partial(_index_kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(p // block,),
            in_specs=[pl.BlockSpec((heads, queries, d),
                                   lambda k, first: (0, 0, 0)),
                      pl.BlockSpec((block, d), keys),
                      pl.BlockSpec((queries, heads),
                                   lambda k, first: (0, 0))],
            out_specs=pl.BlockSpec((queries, block),
                                   lambda k, first: (0, k))),
        out_shape=jax.ShapeDtypeStruct((queries, p), jnp.float32),
        interpret=interpret,
        name="index_scores",
    )(first.reshape(1).astype(jnp.int32), iq, ik, w.astype(jnp.float32))


def index_scores(iq, ik, w, first, *, interpret: bool | None = None):
    """The index scores of a block of a prompt's queries against its keys,
    ``I[t, s] = sum_j w[t, j] * relu(iq[j, t] . ik[s])`` in float32: iq (J,
    B, d), the ``J`` index heads of the ``B`` queries from position
    ``first`` (an int32 scalar, traced or not); ik (P, d), one key a
    position; w (B, J). Returns (B, P); a block of keys that lies wholly
    after the last query is neither fetched nor scored and reads 0 — the
    caller's causal mask leaves it out. The ``(B, J, P)`` products never
    leave VMEM. ``P`` is one block or a multiple of 128."""
    return _index(iq, ik, w, jnp.asarray(first, jnp.int32),
                  interpret=resolve_interpret("index_scores", interpret))
