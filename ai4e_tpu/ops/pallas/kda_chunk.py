"""Pallas TPU kernel: a Kimi-Delta-Attention layer's recurrence over one
prompt from a zero state, from the convolution's input on — ``CHUNK`` tokens
a grid step, a head's state and a chunk's algebra in VMEM.

Of the layer's ``[q | k | v]`` before the convolution (``(T, 3 · H · d)``,
the projection's output in its own dtype) a grid step takes a chunk's rows
and the few before them, and makes in VMEM what the recurrence reads: ``q, k,
v = SiLU(conv(·))`` (causal, depthwise: a tap a token back is the rows
turned one sublane down), a head's q and k divided by their norm, q times
``d^(−1/2)``, float32. Nothing of them goes through HBM.

A head's state ``S (d × d)``, float32; token ``t`` (``models/ling3.py``
``kda_step``)::

    S ← Diag(e^{g_t}) S;  δ = β_t (v_t − Sᵀ k_t);  S ← S + k_t ⊗ δ;  o_t = Sᵀ q_t

Within a chunk, with ``G_t`` the running sum of ``g`` (a vector over the key
channels): the tokens' corrections solve ``(I + L) Δ = β V − (β K ⊙ e^{G})
S_0`` with ``L_tj = β_t Σ_c k_tc k_jc e^{G_tc − G_jc}`` (``j < t``) — a unit
triangular system, inverted as ``(I − X)^{-1} = (I + X)(I + X²)(I + X⁴)…``
with ``X = −L`` nilpotent —, and ``o_t = S_0ᵀ(e^{G_t} ⊙ q_t) + Σ_{j≤t} (Σ_c
q_tc k_jc e^{G_tc − G_jc}) δ_j``; then ``S ← Diag(e^{G_last}) S_0 + (K ⊙
e^{G_last − G})ᵀ Δ``. A decay a channel does not factor out of those sums
as a scalar's does, and ``(x ⊙ e^{G})(k ⊙ e^{−G})ᵀ`` overflows; but ``g``
is bounded below (−5 a token: the configuration's ``gate_bound``), so about
the first row ``r`` of the ``SUB_BLOCK`` tokens a query lies in, ``e^{G_t −
G_r} ≤ 1`` and ``e^{G_r − G_j}`` is at most ``e^{5 (SUB_BLOCK − 1)}`` for a
key of the same tokens and at most 1 for an earlier one: each block of
``SUB_BLOCK`` queries is ONE product against the chunk's keys so far.

The grid is (head block, chunk): the chunk axis sequential, so that a head
block's states — the kernel's second output, one block for every chunk of
the axis — stay in VMEM from the first chunk, where they are zeroed, to the
last, after which they go out once. The convolution's input, g and o are
taken and written as the caller holds them, heads on the lanes — a head's
``(CHUNK, d)`` is whole lane tiles of a ``(CHUNK, head block · d)`` block, no
transpose on either side. Every product is float32 at ``Precision.HIGHEST``.
A chunk is a chain of some sixteen dependent products a head, each of 64
rows; the kernel works the chain a link at a time for every head of the
block, so that the scheduler has ``HEAD_BLOCK`` independent products to
overlap at each link (PR 49's chip runs, ``PERF.md`` section 6: 2.70 µs a
chunk a head with one head a block, 1.83 with two, 1.51 with four or eight).
The block is two heads and not four because the chain is unrolled a head:
each is one more copy to trace and lower in every prompt bucket's program
when a worker warms up, and with four the cell's ``setup_s`` rose by its
whole bound, with eight by a quarter.

A position with ``g = 0`` and ``β = 0`` leaves the state as it was
(padding: what ``kda_chunk`` itself adds up to whole chunks and head blocks,
and what a caller puts after a prompt's end).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lowering import resolve_interpret

CHUNK = 64       # tokens a grid step
SUB_BLOCK = 16   # tokens whose decays are factored about one of them
HEAD_BLOCK = 2   # heads a grid step
HALO = 16        # rows fetched before a chunk's for its convolution

HIGHEST = jax.lax.Precision.HIGHEST
_NN, _NT = ((1,), (0,)), ((1,), (1,))


def _dot(a, b, contract=_NN):
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(*refs, d, eps):
    mixed_refs, halo_refs, w_refs = refs[:3], refs[3:6], refs[6:9]
    g_ref, beta_ref, o_ref, state_ref = refs[9:]
    chunk, sub = CHUNK, SUB_BLOCK
    heads = range(beta_ref.shape[-1])
    first = pl.program_id(1) == 0

    @pl.when(first)
    def _zero():
        state_ref[...] = jnp.zeros_like(state_ref)

    token = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # the running sum of g down the chunk, every head of the block at once
    g_sums = _dot(jnp.where(row >= col, 1.0, 0.0), g_ref[...])
    # before the sequence's start the convolution reads zeros
    before = jnp.where(first, 0.0, 1.0)

    def convolved(part, lanes):
        """SiLU of the causal depthwise convolution of one head's q, k or v
        lanes over the chunk's tokens, (C, d): tap ``j`` reads the input
        ``taps − 1 − j`` tokens back — the chunk's own rows under the
        ``HALO`` before them, turned down the sublanes."""
        w_ref = w_refs[part]
        taps = w_ref.shape[0]
        rows = jnp.concatenate(
            [halo_refs[part][:, lanes].astype(jnp.float32) * before,
             mixed_refs[part][:, lanes].astype(jnp.float32)], axis=0)
        out = sum(
            (pltpu.roll(rows, taps - 1 - j, 0) if j < taps - 1 else rows
             )[HALO:] * w_ref[j:j + 1, lanes] for j in range(taps))
        return out * jax.nn.sigmoid(out)

    def unit(x):
        return x * jax.lax.rsqrt((x * x).sum(axis=-1, keepdims=True) + eps)

    def operands(h):
        """q, k — normalised, q scaled —, v, the running sum of g: (C, d);
        β: (C, 1)."""
        lanes = slice(h * d, (h + 1) * d)
        return (unit(convolved(0, lanes)) * d ** -0.5,
                unit(convolved(1, lanes)), convolved(2, lanes),
                g_sums[:, lanes], beta_ref[:, h:h + 1])

    head = list(map(operands, heads))

    def scores(h):
        """``sum_c x_tc k_jc e^{G_tc - G_jc}`` (C, C) for x = k — below the
        diagonal, times −β_t: the ``X`` of the solve — and for x = q, on and
        below it: a sub-block of both's queries a product."""
        q, k, _, g_sum, beta = head[h]
        by_block = g_sum.reshape(chunk // sub, sub, d)
        # a query's factor about its sub-block's first row: <= 1
        late = jnp.exp(by_block - by_block[:, :1]).reshape(chunk, d)
        score = []
        for a in range(0, chunk, sub):
            at = slice(a, a + sub)
            # a key's factor about this sub-block's first row, for the keys
            # of it and of the earlier ones; a later one's are no query's
            early = k * jnp.exp(jnp.where(
                token < a + sub, g_sum[a:a + 1] - g_sum, -jnp.inf))
            score.append(_dot(jnp.concatenate(
                [k[at] * late[at], q[at] * late[at]], axis=0), early, _NT))
        score_k, score_q = (
            jnp.concatenate([p[j * sub:(j + 1) * sub] for p in score], axis=0)
            for j in (0, 1))
        return (jnp.where(row > col, -score_k * beta, 0.0),
                jnp.where(row >= col, score_q, 0.0))

    x, within = zip(*map(scores, heads))
    # (I - X)^{-1} = (I + X)(I + X^2)(I + X^4)...: X^chunk = 0
    solve = [jnp.where(row == col, 1.0, 0.0) + x_h for x_h in x]
    for _ in range(chunk.bit_length() - 2):
        x = [_dot(x_h, x_h) for x_h in x]
        solve = [s_h + _dot(s_h, x_h) for s_h, x_h in zip(solve, x)]

    def read(h):
        """What the chunk reads of the state it starts from, (2 C, d): the
        keys' rows of the system's right side, then the queries' outputs."""
        q, k, _, g_sum, beta = head[h]
        decay = jnp.exp(g_sum)
        return _dot(jnp.concatenate([k * beta * decay, q * decay], axis=0),
                    state_ref[h])

    from_state = list(map(read, heads))
    v_new = []
    for h in heads:
        _, _, v, _, beta = head[h]
        v_new.append(_dot(solve[h], v * beta - from_state[h][:chunk]))
    inside = [_dot(within[h], v_new[h]) for h in heads]
    for h in heads:
        _, k, _, g_sum, _ = head[h]
        o_ref[:, h * d:(h + 1) * d] = from_state[h][chunk:] + inside[h]
        last = g_sum[chunk - 1:chunk]                           # (1, d)
        # the keys decayed to the chunk's end beside that decay itself,
        # turned so that a channel is a row of the state: (d, 2 C)
        turned = jnp.concatenate(
            [k * jnp.exp(last - g_sum),
             jnp.broadcast_to(jnp.exp(last), (chunk, d))], axis=0).T
        state_ref[h] = (state_ref[h] * turned[:, chunk:chunk + 1]
                        + _dot(turned[:, :chunk], v_new[h]))


@partial(jax.jit, static_argnames=("eps", "interpret"))
def _chunked(mixed, conv_w, g, beta, *, eps: float, interpret: bool):
    t, heads, d = g.shape
    taps = conv_w.shape[0]
    assert taps - 1 <= HALO and CHUNK % HALO == 0, (taps, HALO)
    block = min(HEAD_BLOCK, heads)
    if (block * d) % 128:      # a block's lanes: whole tiles, or every lane
        block = heads
    pad_t, pad_h = -t % CHUNK, -heads % block
    if pad_t or pad_h:
        mixed, conv_w = (
            jnp.pad(a.reshape(-1, 3, heads, d),
                    ((0, rows), (0, 0), (0, pad_h), (0, 0))).reshape(
                        a.shape[0] + rows, -1)
            for a, rows in ((mixed, pad_t), (conv_w, 0)))
        g, beta = (jnp.pad(a, ((0, pad_t), (0, pad_h))
                           + ((0, 0),) * (a.ndim - 2)) for a in (g, beta))
    rows, blocks = t + pad_t, (heads + pad_h) // block
    wide = pl.BlockSpec((CHUNK, block * d), lambda j, c: (c, j))

    def part(shape, index):   # q's, k's and v's lanes of [q | k | v]
        return [pl.BlockSpec(shape, partial(index, first=i * blocks))
                for i in range(3)]

    o, state = pl.pallas_call(
        partial(_kernel, d=d, eps=eps),
        grid=(blocks, rows // CHUNK),
        in_specs=[
            *part((CHUNK, block * d), lambda j, c, first: (c, first + j)),
            # the HALO rows before the chunk's (the first chunk masks them)
            *part((HALO, block * d), lambda j, c, first: (
                jnp.maximum(c * (CHUNK // HALO) - 1, 0), first + j)),
            *part((taps, block * d), lambda j, c, first: (0, first + j)),
            wide,
            pl.BlockSpec((None, CHUNK, block), lambda j, c: (j, c, 0))],
        out_specs=[wide, pl.BlockSpec((block, d, d), lambda j, c: (j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, blocks * block * d),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((blocks * block, d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="kda_chunk",
    )(*[mixed] * 6, *[conv_w] * 3, g.reshape(rows, -1),
      # a head block's β: (blocks, rows, block), the block's heads its lanes
      jnp.moveaxis(beta.reshape(rows, blocks, block), 1, 0))
    return o.reshape(rows, -1, d)[:t, :heads], state[:heads]


def kda_chunk(mixed, conv_w, g, beta, *, eps: float,
              interpret: bool | None = None):
    """The recurrence over one sequence from a zero state, from the
    convolution's input on. mixed: (T, 3 · H · d), ``[q | k | v]`` a token
    before the convolution, any float dtype; conv_w: (taps, 3 · H · d)
    float32, tap ``taps − 1`` the token's own; the log-decay a channel g:
    (T, H, d) and beta: (T, H), float32. ``q, k, v = SiLU(conv(mixed))``, a
    head's q and k divided by their norm (``eps`` under the root), q times
    ``d^(−1/2)``. Returns ``(o (T, H, d), state (H, d, d))`` after the last
    position. ``g`` must not fall below ``−80 / (SUB_BLOCK − 1)`` a token."""
    return _chunked(mixed, conv_w, g, beta, eps=eps,
                    interpret=resolve_interpret("kda_chunk", interpret))
