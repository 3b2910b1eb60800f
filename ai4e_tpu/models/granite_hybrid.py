"""Granite hybrid — a decoder LM for the decode engine that mixes by Mamba-2
(a state-space layer) in most layers and by grouped-query attention without
positions in a few, with a dense gated MLP after each mixer and four scalar
multipliers.

The block of ibm-granite/granite-4.0-h-micro (``model_type:
granitemoehybrid`` with no experts), written from its equations. ``n(x) = w
⊙ x · rsqrt(mean(x²) + eps)`` in float32, no biases but the convolution's.
With the multipliers ``m_e`` (embedding), ``m_r`` (residual), ``m_a``
(attention), ``m_l`` (logits)::

    h₀ = m_e · E[token]
    h ← h + m_r · Mixer_i(n₁(h));   h ← h + m_r · W_out(silu(a) ⊙ b),
                                     [a | b] = W_in n₂(h)
    logits = E · n_f(h) / m_l        (the head is the embedding table)

layer ``i`` mixes by attention iff ``i`` is in ``attention_layers``.

- **Attention** (``heads`` query heads on ``kv_heads`` K/V heads of
  ``head_dim``): ``q = W_q x``, ``k = W_k x``, ``v = W_v x``; no rotation
  and no position term of any kind; causal ``softmax(m_a · q kᵀ) v`` — the
  scores are multiplied by ``m_a``, not divided by ``√head_dim`` — query
  head ``j`` reading K/V head ``j // (heads / kv_heads)``; ``W_o``. The
  cache holds k and v as projected.
- **Mamba-2** (``ssm_heads`` heads ``H`` of ``ssm_head_dim`` ``P``, state
  ``ssm_state`` ``N``, one group, inner width ``I = H · P``, convolution
  ``conv`` wide over ``I + 2N`` channels): ``[z | xBC | dt] = W_in x``;
  ``xBC ← silu(conv(xBC) + bias)``, depthwise and causal; ``[x | B | C] =
  xBC``; ``Δ = softplus(dt + dt_bias)``, ``A = −exp(A_log)``, a head; per
  head a state ``S (P × N)``, float32; token ``t``::

      S ← e^{Δ_t A} S + Δ_t · x_t ⊗ B_t;   y_t = S C_t + D x_t

  then ``y ← n_g(y ⊙ silu(z))`` — the gate goes in BEFORE the norm, which
  runs over all ``I`` lanes — and ``W_out``.

What a slot holds (``cache_spec``): K/V of the attention layers only, and
per Mamba layer its state ``ssm<j>`` (float32, laid out ``(N, H · P)``: the
state's index on the sublanes, a head's lanes side by side — ``ssd_block``
says why) and the convolution's last ``conv − 1`` inputs ``conv<j>``
(``ops/state_pool.py``). ``decode_step`` is the recurrence as written, one
token a slot: ``ssm<j>`` advances at the live slots only, in place
(``ssd_update``: ``state_pool.update_live`` with ``ssd_block`` as the slot's
math), the convolution's few KB a slot at every slot. ``prefill`` runs the
same recurrence ``chunk`` tokens at a time (the "SSD"
form: inside a chunk one masked matrix product, across chunks the state
carried by a scan), with padded positions at ``Δ = 0`` — they neither decay
nor feed the state — and outside the convolution's tail, so the state it
returns is that of the prompt's ``length`` tokens whatever the bucket.

The embedding and the head are ONE parameter, ``embed``: the step gathers a
row a slot from it and reads it whole for the logits. Weights and K/V are
``dtype`` (bfloat16 as served), accumulation float32.
"""

from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops import kv_pool, state_pool
from .olmoe import norm_scale, seeded

# The seeded init's gains (``create_granite_hybrid_lm`` says why these): the
# deviation of each projection's output per unit of input; the embedding's
# deviation; the ends of the ramps ``A`` and ``Δ``'s bias are laid on.
INIT_GAINS = {"embed": 1.0 / 12, "wq": 4.0, "wk": 4.0, "wo": 4.0,
              "out_proj": 4.0, "w_out": 8.0, "conv_bias": 0.5,
              "a": (1.0, 16.0), "dt": (1e-3, 1e-1)}

HIGHEST = jax.lax.Precision.HIGHEST

# The ``jax.named_scope``s of this family's programs, for a trace's reader
# (``benchmark/lib/xplane_spans.summarize(scopes=...)``; the innermost
# declared scope names an operation).
TRACE_SCOPES = ("embedding", "ssm", "in_proj", "conv", "state_update",
                "gated_norm", "out_proj", "attention", "mlp", "head",
                "cache_update", "cache_insert", "state_insert")


def rms_norm(x, w, eps):
    """``w ⊙ x · rsqrt(mean(x²) + eps)`` in float32, cast back."""
    h = x.astype(jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return (h * w.astype(jnp.float32)).astype(x.dtype)


def _dot(eq, a, b):
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def _dot32(eq, a, b):
    """A float32 product at full precision (the MXU's default would round
    float32 operands to bfloat16: the state is kept in float32 for a
    reason)."""
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def ramp(lo: float, hi: float, stride: int = 1, log: bool = False,
         inverse_softplus: bool = False):
    """An initializer that lays a head's parameter on a geometric ramp from
    ``lo`` to ``hi`` (head ``h`` takes step ``h · stride`` modulo the
    heads), computed by numpy on the host — so every backend holds the same
    values — and stored as its logarithm or as the bias whose softplus it
    is."""
    def init(key, shape, dtype=jnp.float32):
        n, = shape
        values = np.geomspace(lo, hi, n)[(np.arange(n) * stride) % n]
        if log:
            values = np.log(values)
        if inverse_softplus:
            values = np.log(np.expm1(values))
        return jnp.asarray(values.astype(np.float32).astype(dtype))
    return init


def ssd_step(state, x, dt, a, b, c):
    """One token of the recurrence for every (slot, head), in ``jax.numpy``
    — the equation ``ssd_block`` is held to. state: (..., H, P, N) float32;
    x: (..., H, P); dt: (..., H) — ``Δ``; a: (H,) — ``A``; b, c: (..., N).
    Returns ``(S C (..., H, P), new state)``."""
    decay = jnp.exp(dt * a)[..., None, None]
    state = state * decay + (dt[..., None] * x)[..., None] * b[
        ..., None, None, :]
    return (state * c[..., None, None, :]).sum(axis=-1), state


def ssd_block(state_ref, packed_ref, y_ref, successor_ref):
    """``ssd_step`` on one slot's block, in VMEM
    (``state_pool.update_live``'s ``body``). state_ref, successor_ref: (N, W)
    with ``W = H · P``, lane ``h · P + p`` of row ``n`` holding ``S[h, p,
    n]``; packed_ref: (2 W / N + 2, N) — ``Δ A`` a lane, N lanes a row,
    then ``Δ x`` likewise, then B, then C; y_ref: (W / N, N) — ``S C``, N
    lanes a row.

    In this layout everything a lane needs of its head and channel is a row
    (``e^{Δ A}``, ``Δ x``: rows of the operand as XLA hands them) and the
    read-out ``S C`` is a sum down the sublanes, which the vector units do
    with adds; only B and C have to stand as columns, once a slot (a square
    transpose). With N on the lanes instead the read-out is a cross-lane sum
    a vector register and ``Δ x`` a lane broadcast a register, and the
    kernel ran at half the blocks' DMA rate on a v5e (PERF.md section 6, PR
    35). N lanes of all N rows at a time, so nothing of the block's size is
    held as a value."""
    n, width = state_ref.shape
    chunks = width // n
    b_col = jnp.broadcast_to(packed_ref[2 * chunks:2 * chunks + 1, :],
                             (n, n)).T                     # [k, :] = B[k]
    c_col = jnp.broadcast_to(packed_ref[2 * chunks + 1:, :], (n, n)).T
    for j in range(chunks):
        lanes = slice(j * n, (j + 1) * n)
        new = (state_ref[:, lanes] * jnp.exp(packed_ref[j:j + 1, :])
               + b_col * packed_ref[chunks + j:chunks + j + 1, :])
        successor_ref[:, lanes] = new
        y_ref[j:j + 1, :] = (new * c_col).sum(axis=0, keepdims=True)


def ssd_update(state, x, dt, a, b, c, position, interpret=None):
    """``ssd_step`` at the live slots of the pool (``position > 0``) only,
    in place. state: (S, N, H · P) — the pool's tensor; x: (S, H, P); dt:
    (S, H); a: (H,); b, c: (S, N). Returns ``(S C (S, H · P) — zeros at a
    dead slot —, the tensor's successor)``; a dead slot's state is what it
    was."""
    slots, n, width = state.shape

    def rows(v):   # (S, H, 1 or P) -> (S, H · P / N, N)
        return jnp.broadcast_to(v, x.shape).reshape(slots, -1, n)

    y, state = state_pool.update_live(
        state, (jnp.concatenate(
            [rows((dt * a)[..., None]), rows(dt[..., None] * x), b[:, None],
             c[:, None]], axis=1),),
        position, ssd_block, ((width // n, n), jnp.float32), interpret)
    return y.reshape(slots, width), state


def ssd_chunked(x, dt, a, b, c, chunk: int):
    """The same recurrence over a whole sequence from a zero state,
    ``chunk`` tokens at a time. x: (B, T, H, P); dt: (B, T, H); a: (H,); b,
    c: (B, T, N); float32. A position with ``dt = 0`` leaves the state as it
    was and feeds it nothing (padding). Returns ``(S C (B, T, H, P), state
    (B, H, P, N))`` after the last position.

    With ``G_i`` the running sum of ``Δ A`` inside a chunk and ``u_j = Δ_j
    x_j``: ``y_i = Σ_{j ≤ i} e^{G_i − G_j} (C_i · B_j) u_j + e^{G_i} S₀ C_i``
    and the chunk leaves ``S = e^{G_last} S₀ + Σ_j e^{G_last − G_j} u_j ⊗
    B_j`` — a masked matrix product within the chunk, a scan of ``S`` across
    chunks; all float32 at full precision."""
    bsz, t, h, p = x.shape
    chunk = min(chunk, t)
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (
            v.ndim - 2)) for v in (x, dt, b, c))
    n = (t + pad) // chunk

    def chunks(v):   # (B, T, ...) -> (B, n, chunk, ...)
        return v.reshape(bsz, n, chunk, *v.shape[2:])

    u = chunks(dt[..., None] * x)                              # (B,n,Q,H,P)
    g = jnp.cumsum(chunks(dt * a), axis=2)                     # (B,n,Q,H)
    b, c = chunks(b), chunks(c)                                # (B,n,Q,N)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    g_h = jnp.moveaxis(g, 3, 2)                                # (B,n,H,Q)
    decay = jnp.exp(jnp.where(
        lower, g_h[..., :, None] - g_h[..., None, :], -jnp.inf))
    within = _dot32("bnik,bnjk->bnij", c, b)[:, :, None] * decay
    y = _dot32("bnhij,bnjhp->bnihp", within, u)
    to_end = jnp.exp(g[:, :, -1:] - g)                         # (B,n,Q,H)
    fed = _dot32("bnjhp,bnjk->bnhpk", u * to_end[..., None], b)
    last = jnp.exp(g[:, :, -1])                                # (B,n,H)

    def body(state, xs):
        fed_i, last_i = xs
        return state * last_i[..., None, None] + fed_i, state

    state, entering = jax.lax.scan(
        body, jnp.zeros((bsz, h, p, b.shape[-1]), jnp.float32),
        (jnp.moveaxis(fed, 1, 0), jnp.moveaxis(last, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                    # (B,n,H,P,N)
    y = y + _dot32("bnhpk,bnik->bnihp", entering, c) * jnp.exp(g)[..., None]
    return y.reshape(bsz, n * chunk, h, p)[:, :t], state


class _Layer(nn.Module):
    """One block: a mixer (``attention``: grouped-query attention without
    positions, else Mamba-2) and the gated MLP."""

    attention: bool
    dim: int
    heads: int
    kv_heads: int
    head_dim: int
    mlp_dim: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    conv: int
    chunk: int
    residual_multiplier: float
    attention_multiplier: float
    eps: float
    dtype: jnp.dtype

    def setup(self):
        d, gains = self.dim, INIT_GAINS

        def p(name, init, *shape):
            return self.param(name, init, shape, self.dtype)

        self.norm_in = p("norm_in", norm_scale(1.0), d)
        self.norm_post = p("norm_post", norm_scale(1.0), d)
        if self.attention:
            hd = self.head_dim
            self.wq = p("wq", seeded(gains["wq"]), d, self.heads * hd)
            self.wk = p("wk", seeded(gains["wk"]), d, self.kv_heads * hd)
            self.wv = p("wv", seeded(1.0), d, self.kv_heads * hd)
            self.wo = p("wo", seeded(gains["wo"]), self.heads * hd, d)
        else:
            inner, h = self.inner, self.ssm_heads
            self.in_proj = p("in_proj", seeded(1.0), d,
                             2 * inner + 2 * self.ssm_state + h)
            self.conv_w = p("conv_w", seeded(1.0, fan_in_axis=0), self.conv,
                            self.channels)
            self.conv_b = p("conv_b", seeded(gains["conv_bias"],
                                             fan_in_axis=None), self.channels)
            self.a_log = p("a_log", ramp(*gains["a"], log=True), h)
            self.dt_bias = p("dt_bias", ramp(*gains["dt"], stride=37,
                                             inverse_softplus=True), h)
            self.d_skip = p("d_skip", norm_scale(1.0), h)
            self.norm_g = p("norm_g", norm_scale(1.0), inner)
            self.out_proj = p("out_proj", seeded(gains["out_proj"]), inner, d)
        self.w_in = p("w_in", seeded(1.0), d, 2 * self.mlp_dim)
        self.w_out = p("w_out", seeded(gains["w_out"]), self.mlp_dim, d)

    @property
    def inner(self):
        return self.ssm_heads * self.ssm_head_dim

    @property
    def channels(self):
        return self.inner + 2 * self.ssm_state

    def _add(self, x, branch):
        """``x + m_r · branch``: the product in float32, one rounding."""
        return (x.astype(jnp.float32)
                + self.residual_multiplier * branch).astype(self.dtype)

    def _mlp(self, x):
        with jax.named_scope("mlp"):
            h = rms_norm(x, self.norm_post, self.eps)
            ab = _dot("...d,de->...e", h, self.w_in)
            a, b = ab[..., :self.mlp_dim], ab[..., self.mlp_dim:]
            return self._add(x, _dot(
                "...e,ed->...d", (jax.nn.silu(a) * b).astype(self.dtype),
                self.w_out))

    # -- attention ----------------------------------------------------------

    def _qkv(self, x):
        """``x (..., D)`` → q ``(..., H, hd)``, k, v ``(..., KVH, hd)``."""
        h = rms_norm(x, self.norm_in, self.eps)

        def heads(w, n):
            return _dot("...d,de->...e", h, w).astype(self.dtype).reshape(
                *x.shape[:-1], n, self.head_dim)

        return (heads(self.wq, self.heads), heads(self.wk, self.kv_heads),
                heads(self.wv, self.kv_heads))

    def _attn_out(self, x, o):
        return self._add(x, _dot(
            "...e,ed->...d", o.reshape(*x.shape[:-1], -1), self.wo))

    # -- Mamba-2 ------------------------------------------------------------

    def _project(self, x):
        """``x (..., D)`` → the gate ``z (..., I)``, the convolution's input
        ``xBC (..., I + 2N)`` and ``Δ (..., H)`` (float32)."""
        with jax.named_scope("in_proj"):
            h = rms_norm(x, self.norm_in, self.eps)
            zxd = _dot("...d,de->...e", h, self.in_proj)
            cut = self.inner + self.channels
            dt = jax.nn.softplus(zxd[..., cut:]
                                 + self.dt_bias.astype(jnp.float32))
            return (zxd[..., :self.inner].astype(self.dtype),
                    zxd[..., self.inner:cut].astype(self.dtype), dt)

    def _split(self, mixed):
        """The convolution's output ``(..., I + 2N)`` (after SiLU, float32)
        → x ``(..., H, P)``, B, C ``(..., N)``."""
        i, n = self.inner, self.ssm_state
        x = mixed[..., :i].reshape(*mixed.shape[:-1], self.ssm_heads, -1)
        return x, mixed[..., i:i + n], mixed[..., i + n:]

    def _ssm_out(self, x_in, y, x, z):
        """``y = S C`` and the heads' inputs ``x``, both ``(..., I)`` — a
        head's lanes side by side — → the skip, the gate, the norm over all
        lanes, ``W_out`` and the residual."""
        with jax.named_scope("gated_norm"):
            y = y + jnp.repeat(self.d_skip.astype(jnp.float32),
                               self.ssm_head_dim) * x
            y = y * jax.nn.silu(z.astype(jnp.float32))
            y = rms_norm(y, self.norm_g, self.eps).astype(self.dtype)
        with jax.named_scope("out_proj"):
            return self._add(x_in, _dot("...e,ed->...d", y, self.out_proj))

    # -- the two programs ---------------------------------------------------

    def prefill(self, x, mask, length):
        """x: (B, P, D); mask: (B, P) valid tokens; length: (B,). Returns
        ``(y, cache)``: an attention layer's cache is ``(k, v)`` of (B, P,
        KVH, hd), a Mamba layer's ``(state (B, N, H · P) — the pool's layout
        —, tail (B, conv − 1, I + 2N))`` after ``length`` tokens."""
        p = x.shape[1]
        if self.attention:
            q, k, v = self._qkv(x)
            o = kv_pool.prefill_attention(q, k, v, mask,
                                          scale=self.attention_multiplier)
            x, cache = self._attn_out(x, o), (k, v)
        else:
            with jax.named_scope("ssm"):
                z, mixed, dt = self._project(x)
                keep = self.conv - 1
                with jax.named_scope("conv"):
                    # the last ``keep`` inputs before ``length``; zero
                    # before the sequence's start
                    at = length[:, None] - keep + jnp.arange(keep)[None, :]
                    tail = jnp.where(
                        (at >= 0)[..., None], jnp.take_along_axis(
                            mixed, jnp.maximum(at, 0)[..., None], axis=1), 0)
                    padded = jnp.pad(mixed, ((0, 0), (keep, 0), (0, 0)))
                    w = self.conv_w.astype(jnp.float32)
                    out = sum(padded[:, j:j + p].astype(jnp.float32) * w[j]
                              for j in range(self.conv))
                    out = jax.nn.silu(out + self.conv_b.astype(jnp.float32))
                with jax.named_scope("state_update"):
                    xs, b, c = self._split(out)
                    y, state = ssd_chunked(
                        xs, jnp.where(mask[..., None], dt, 0.0),
                        -jnp.exp(self.a_log.astype(jnp.float32)), b, c,
                        self.chunk)
                    # (B, H, P, N) as the scan leaves it -> the pool's
                    # (B, N, H · P): one transpose of 2 MB a layer a prompt
                    state = state.reshape(state.shape[0], -1,
                                          self.ssm_state).swapaxes(1, 2)
                x = self._ssm_out(x, y.reshape(*y.shape[:2], -1),
                                  out[..., :self.inner], z)
                cache = state, tail
        return self._mlp(x), cache

    def step(self, x, cache, position, bound):
        """One token per slot: x (S, D). An attention layer's ``cache`` is
        ``(k pool, v pool, its K/V layer)`` and it returns the new token's
        ``(k, v)`` (S, KVH, hd) for ``kv_pool.write_rows``; a Mamba layer's
        is ``(state, tail)`` of every slot and it returns their successors:
        the state advanced at the live slots (``position > 0``) only, a
        dead slot's as it was."""
        if self.attention:
            k_pool, v_pool, layer = cache
            q, k_new, v_new = self._qkv(x)
            o = kv_pool.decode_attention(
                q, k_new, v_new, k_pool, v_pool, layer, position, bound,
                scale=self.attention_multiplier)
            x, cache = self._attn_out(x, o), (k_new, v_new)
        else:
            state, tail = cache
            with jax.named_scope("ssm"):
                z, mixed, dt = self._project(x)
                with jax.named_scope("conv"):
                    window = jnp.concatenate([tail, mixed[:, None]], axis=1)
                    out = jax.nn.silu(
                        (window.astype(jnp.float32)
                         * self.conv_w.astype(jnp.float32)).sum(axis=1)
                        + self.conv_b.astype(jnp.float32))
                with jax.named_scope("state_update"):
                    xs, b, c = self._split(out)
                    y, state = ssd_update(
                        state, xs, dt,
                        -jnp.exp(self.a_log.astype(jnp.float32)), b, c,
                        position)
                x = self._ssm_out(x, y, out[..., :self.inner], z)
                cache = state, window[:, 1:]
        return self._mlp(x), cache


class GraniteHybridLM(nn.Module):
    """Causal LM over the hybrid block stack, with the serving entry points
    of an LM family (``runtime/kvcache.py`` ``LMServable``)."""

    vocab_size: int
    dim: int = 64
    depth: int = 4
    attention_layers: tuple = (3,)
    heads: int = 4
    kv_heads: int = 2
    head_dim: int = 16
    mlp_dim: int = 128
    ssm_heads: int = 4
    ssm_head_dim: int = 32
    ssm_state: int = 16
    ssm_groups: int = 1
    conv: int = 4
    chunk: int = 128
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 1.0 / 64
    logits_scaling: float = 8.0
    rms_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16

    def setup(self):
        self.embed = self.param(
            "embed", seeded(INIT_GAINS["embed"], fan_in_axis=None),
            (self.vocab_size, self.dim), self.dtype)
        shared = {field: getattr(self, field) for field in (
            "dim", "heads", "kv_heads", "head_dim", "mlp_dim", "ssm_heads",
            "ssm_head_dim", "ssm_state", "conv", "chunk",
            "residual_multiplier", "attention_multiplier", "dtype")}
        self.layers = [_Layer(attention=i in self.attention_layers,
                              eps=self.rms_eps, name=f"layer{i}", **shared)
                       for i in range(self.depth)]
        # Zero-centred: with the head tied to the embedding, a final norm
        # whose scales were all near 1 would make every position's largest
        # logit that of the token just fed (``create_granite_hybrid_lm``).
        self.norm_f = self.param(
            "norm_f", seeded(1.0, fan_in_axis=None), (self.dim,), self.dtype)

    @nn.nowrap
    def cache_spec(self):
        """What a slot holds (``kv_pool.SlotSpec``): K/V of the attention
        layers, and of the ``j``-th Mamba layer its state ``ssm<j>``
        (float32; stepped at the live slots only) and its convolution's last
        inputs ``conv<j>`` (stepped at every slot)."""
        inner = self.ssm_heads * self.ssm_head_dim
        mamba = range(self.depth - len(self.attention_layers))
        state = []
        for j in mamba:
            state += [(f"ssm{j}", (self.ssm_state, inner), jnp.float32),
                      (f"conv{j}", (self.conv - 1, inner + 2 * self.ssm_state),
                       self.dtype)]
        return kv_pool.kv_slot(
            len(self.attention_layers), self.kv_heads, self.head_dim,
            self.dtype, state, tuple(f"ssm{j}" for j in mamba))

    def _embed(self, tokens):
        with jax.named_scope("embedding"):
            return (self.embed[tokens].astype(jnp.float32)
                    * self.embedding_multiplier).astype(self.dtype)

    def _logits(self, h):
        with jax.named_scope("head"):
            return _dot("...d,vd->...v",
                        rms_norm(h, self.norm_f, self.rms_eps),
                        self.embed) / self.logits_scaling

    def _prefill(self, tokens, length):
        h = self._embed(tokens)
        mask = jnp.arange(tokens.shape[1])[None, :] < length[:, None]
        ks, vs, state = [], [], {}
        for layer in self.layers:
            h, cache = layer.prefill(h, mask, length)
            if layer.attention:
                ks.append(cache[0])
                vs.append(cache[1])
            else:
                j = len(state) // 2
                state[f"ssm{j}"], state[f"conv{j}"] = cache
        return h, kv_pool.prompt_block(ks), kv_pool.prompt_block(vs), state

    def _step(self, tokens, k_cache, v_cache, state, position, bound):
        h = self._embed(tokens)
        k_rows, v_rows, new_state = [], [], {}
        for layer in self.layers:
            if layer.attention:
                h, (k, v) = layer.step(
                    h, (k_cache, v_cache, len(k_rows)), position, bound)
                k_rows.append(k)
                v_rows.append(v)
            else:
                j = len(new_state) // 2
                h, cache = layer.step(
                    h, (state[f"ssm{j}"], state[f"conv{j}"]), position, bound)
                new_state[f"ssm{j}"], new_state[f"conv{j}"] = cache
        k_cache, v_cache = kv_pool.write_rows(
            (k_cache, v_cache), (k_rows, v_rows), position)
        return h, k_cache, v_cache, new_state

    def prefill(self, tokens, length):
        h, k, v, state = self._prefill(tokens, length)
        last = jnp.take_along_axis(
            h, (length - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        return (jnp.argmax(self._logits(last), axis=-1).astype(jnp.int32),
                k, v, state)

    def decode_step(self, tokens, k_cache, v_cache, state, position,
                    bound=None):
        """One token for every slot of the pool. Attention reads the cached
        positions ``< bound`` (``kv_pool.decode_attention``); the Mamba
        layers advance the state of the slots at a position > 0."""
        h, k_cache, v_cache, state = self._step(
            tokens, k_cache, v_cache, state, position, bound)
        return (jnp.argmax(self._logits(h), axis=-1).astype(jnp.int32),
                k_cache, v_cache, state)

    # Logits, for tests only: the serving programs ship ids.

    def prefill_logits(self, tokens, length):
        h, k, v, state = self._prefill(tokens, length)
        return self._logits(h), k, v, state

    def decode_logits(self, tokens, k_cache, v_cache, state, position,
                      bound=None):
        h, k_cache, v_cache, state = self._step(
            tokens, k_cache, v_cache, state, position, bound)
        return self._logits(h), k_cache, v_cache, state


def create_granite_hybrid_lm(rng=None, vocab_size: int = 512,
                             dtype=jnp.bfloat16, **dims):
    """Build the LM and its seeded params (``olmoe.seeded``: the same values
    on every backend; the two ramps by numpy on the host). ``dims``: the
    fields of ``GraniteHybridLM``; a key it does not know is an error. Norm
    scales are drawn away from 1, so one left out shows. The gains keep
    random weights in the regime of trained ones where a comparison with a
    float32 reference needs it (``olmoe.create_olmoe_lm`` has the argument):

    - the embedding deviates by ~0.1, so ``m_e · E`` is a stream of size ~1,
      and with ``m_r = 0.22`` every mixer and MLP adds about a fifth of it;
    - ``W_q`` and ``W_k`` give q and k that deviate by ~4 a lane, so the
      scores ``m_a · q·k`` deviate by ~2 at ``m_a = 1/64`` and attention
      picks tokens (at unit gain the softmax would be flat and neither
      ``m_a`` nor a rotation would show);
    - ``A`` lies on a geometric ramp from 1 to 16 over the heads and ``Δ``'s
      bias on one from 1e-3 to 1e-1 in another order (Mamba-2's own init
      draws both from these ranges), so a head forgets within a token or
      within thousands and both the decay and the state's precision show;
      ``D`` and the gated norm's scales are near 1;
    - the final norm's scales are centred on ZERO: the head is the embedding
      table, so with scales near 1 the logit of the token just fed is
      ``√dim`` times the embedding's share of the stream above every other
      and a random network would answer every prompt with its last token,
      whatever its layers compute — nothing of the model would show in a
      served id. A trained network learns that away; scales of either sign
      do it for a seeded one and change no equation."""
    dims = dict(dims)
    if "attention_layers" in dims:
        dims["attention_layers"] = tuple(sorted(dims["attention_layers"]))
    model = GraniteHybridLM(vocab_size=vocab_size, dtype=jnp.dtype(dtype),
                            **dims)
    if model.heads % model.kv_heads:
        raise ValueError("query heads must group onto K/V heads")
    if (model.ssm_heads * model.ssm_head_dim) % model.ssm_state:
        raise ValueError(
            f"the step lays the state out {model.ssm_state} lanes at a time: "
            f"{model.ssm_heads} heads of {model.ssm_head_dim} are no whole "
            "number of them")
    if model.ssm_groups != 1:
        raise ValueError(f"written for one group of B and C, not "
                         f"{model.ssm_groups}")
    if not all(0 <= i < model.depth for i in model.attention_layers):
        raise ValueError(f"attention layers {model.attention_layers} of "
                         f"{model.depth}")
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    params = jax.jit(partial(model.init, method="prefill"))(
        rng, np.zeros((1, 8), np.int32), np.ones((1,), np.int32))
    return model, params
