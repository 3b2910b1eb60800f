"""Qwen3-Next — a hybrid decoder LM for the decode engine: three Gated
DeltaNet (linear attention) layers to one gated grouped-query attention
layer, and a sparse-expert layer with a shared expert after each.

The block of Qwen/Qwen3-Next-80B-A3B (``model_type: qwen3_next``), written
from its equations. ``RMSNorm(x) = x · rsqrt(mean(x²) + eps) · (1 + w)`` in
float32 (a zero-centred weight), no biases. A layer: ``h = x +
Mixer(norm_in(x))``, ``y = h + MoE(norm_post(h))``; layer ``i`` mixes by full
attention iff ``(i + 1) % full_interval == 0``, else by linear attention.

- **Gated attention** (``heads`` query heads on ``kv_heads`` K/V heads of
  ``head_dim``): ``wq: D → heads × 2·head_dim``, a head's output read as
  ``[q | gate]``; ``wk, wv: D → kv_heads × head_dim``. ``q ← norm_q(q)``,
  ``k ← norm_k(k)`` (the RMSNorm above, over a head). The first
  ``rotary_dim`` lanes of each q and k head are rotated (rotate-half), the
  rest pass. Causal ``softmax(q kᵀ / √head_dim) v``, ``heads / kv_heads``
  query heads a K/V head. ``o ← o ⊙ sigmoid(gate)``; ``wo``. The cache holds
  k after norm and rotation.
- **Gated DeltaNet** (``lin_k_heads`` key heads, ``lin_v_heads`` value heads,
  ``lin_dim`` wide both, convolution ``conv``): ``in_qkvz: D → [q | k | v |
  z]``, ``in_ba: D → [b | a]``. ``[q|k|v]`` → depthwise causal convolution,
  no bias → SiLU. ``β = sigmoid(b)``; ``g = −exp(A_log) · softplus(a +
  dt_bias)`` in float32, a value head. q and k are repeated to the value
  heads (value head ``h`` reads key head ``h // (lin_v_heads /
  lin_k_heads)``), L2-normalised (eps 1e-6), ``q ← q / √lin_dim``. Per head a
  state ``S (lin_dim × lin_dim)``, float32; token ``t``::

      S ← e^{g_t} S;  δ = β_t (v_t − Sᵀ k_t);  S ← S + k_t ⊗ δ;  o_t = Sᵀ q_t

  then per head ``o ← w ⊙ o · rsqrt(mean(o²) + eps) ⊙ SiLU(z)``;
  ``out_proj``.
- **Experts** (``models/experts.py``): softmax over ``experts`` in float32,
  the ``experts_per_token`` largest divided by their sum, the part of the
  sum the ``experts_held`` experts from ``first_expert`` give; plus
  ``sigmoid(x w_s) · Expert_shared(x)``.
- Final norm, untied head, greedy argmax on the device. The multi-token
  prediction module of the published checkpoint is not here: serving
  without speculation loads none of it.

What a slot holds (``cache_spec``): K/V of the full-attention layers only,
and per linear layer its state ``S`` (float32) and the convolution's last
``conv − 1`` inputs — fixed-size tensors a slot, named ``delta<j>`` and
``conv<j>`` (``ops/state_pool.py``). ``decode_step`` is the recurrence as
written, one token a slot: ``delta<j>`` advances at the live slots only, in
place (``delta_rule_update``: ``state_pool.update_live`` with
``delta_rule_block`` as the slot's math; a dead slot's state stays what it
was until the next prefill's replaces it whole), the convolution's few KB a
slot at every slot. ``prefill`` runs
the same recurrence in chunks of ``CHUNK`` tokens (the WY form of the
published implementation: within a chunk the ``δ`` of every token is solved
at once from a triangular system, across chunks the state is carried), with
padded positions at ``g = 0``, ``β = 0`` and outside the convolution's tail,
so the state it returns is that of the prompt's ``length`` tokens whatever
the bucket. The experts' product is ``routed`` in a prefill and ``dense``
over the held experts in a step (``models/experts.py`` says why).

Weights and K/V are ``dtype`` (bfloat16 as served), accumulation float32;
the fused projections' layout is this file's own (it changes no equation).
"""

from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops import kv_pool, state_pool
from . import experts as expert_layer
from .olmoe import norm_scale, seeded

CHUNK = 64      # tokens a chunk of the prefill's recurrence
L2_EPS = 1e-6

# The seeded init's gains (``create_qwen3_next_lm`` says why these): the
# deviation of each projection's output per unit of input, and the centres
# of the decay's parameters.
INIT_GAINS = {"wo": 0.5, "out_proj": 0.35, "router": 2.0, "w_down": 0.5,
              "shared_down": 0.4, "dt_bias": -3.0, "qk_scale": 0.5}

HIGHEST = jax.lax.Precision.HIGHEST

# The ``jax.named_scope``s of this family's programs, for a trace's reader
# (``benchmark/lib/xplane_spans.summarize(scopes=...)``; the innermost
# declared scope names an operation).
TRACE_SCOPES = ("embedding", "linear_attention", "conv", "delta_rule",
                "state_update", "gated_norm", "attention", "rope", "qk_norm",
                "router", "experts", "shared_expert", "head", "cache_update",
                "cache_insert", "state_insert")


def rms_norm(x, w, eps):
    """``x · rsqrt(mean(x²) + eps) · (1 + w)`` in float32, cast back."""
    h = x.astype(jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return (h * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def partial_rope(x, position, theta, rotary_dim):
    """Rotate-half rotary embedding of the first ``rotary_dim`` lanes of
    ``x (..., heads, head_dim)`` at ``position (...)``, in float32, cast
    back; the other lanes pass."""
    half = rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = position.astype(jnp.float32)[..., None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    h = x.astype(jnp.float32)
    a, b, rest = h[..., :half], h[..., half:rotary_dim], h[..., rotary_dim:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1).astype(x.dtype)


def _dot(eq, a, b):
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def _dot32(eq, a, b):
    """A float32 product at full precision (the MXU's default would round
    float32 operands to bfloat16: the state is kept in float32 for a
    reason)."""
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def l2_norm(x):
    return x * jax.lax.rsqrt((x * x).sum(axis=-1, keepdims=True) + L2_EPS)


def delta_rule_step(state, q, k, v, g, beta):
    """One token of the gated delta rule for every (slot, head), in
    ``jax.numpy`` — the equation ``delta_rule_block`` is held to. state:
    (..., dk, dv) float32; q, k: (..., dk) — normalised, q scaled; v: (...,
    dv); g, beta: (...). Returns ``(o (..., dv), new state)``. Both readings
    of the old state (``Sᵀk``, ``Sᵀq``) are taken in one pass and the new
    state is written in another: ``o = e^g Sᵀq + (k·q) δ``."""
    decay = jnp.exp(g)[..., None]
    sk = (state * k[..., :, None]).sum(axis=-2)
    sq = (state * q[..., :, None]).sum(axis=-2)
    delta = beta[..., None] * (v - decay * sk)
    o = decay * sq + (k * q).sum(axis=-1, keepdims=True) * delta
    return o, state * decay[..., None] + k[..., :, None] * delta[..., None, :]


def delta_rule_block(state_ref, qk_ref, v_ref, gates_ref, o_ref,
                     successor_ref):
    """``delta_rule_step`` on one slot's block, in VMEM
    (``state_pool.update_live``'s ``body``). state_ref, successor_ref: (H,
    dk, dv); qk_ref: (2, dk, H) — q and k with a head a LANE, so that a
    head's q (k) is a column down the sublanes as the products with ``S``
    need it; v_ref, o_ref: (H, dv); gates_ref: (3, H) — ``g``, ``β`` and
    ``k · q``. A head at a time: both readings of the old state are sums
    down the sublanes, the new state one pass."""
    heads, _, dv = state_ref.shape

    def gate(i, h):   # a head's scalar as a row (Mosaic broadcasts one way)
        return jnp.broadcast_to(gates_ref[i:i + 1, h:h + 1], (1, dv))

    for h in range(heads):
        s = state_ref[h]
        q, k = qk_ref[0, :, h:h + 1], qk_ref[1, :, h:h + 1]   # (dk, 1)
        decay = jnp.exp(gate(0, h))
        sk = (s * k).sum(axis=0, keepdims=True)               # (1, dv)
        sq = (s * q).sum(axis=0, keepdims=True)
        delta = gate(1, h) * (v_ref[h:h + 1, :] - decay * sk)
        o_ref[h:h + 1, :] = decay * sq + gate(2, h) * delta
        successor_ref[h] = s * decay + k * delta


def delta_rule_update(state, q, k, v, g, beta, position, interpret=None):
    """``delta_rule_step`` at the live slots of the pool (``position > 0``)
    only, in place. state: (S, H, dk, dv) — the pool's tensor; the rest as
    ``delta_rule_step`` takes them, a slot each. Returns ``(o (S, H, dv) —
    zeros at a dead slot —, the tensor's successor)``; a dead slot's state
    is what it was."""
    return state_pool.update_live(
        state, (jnp.stack([q, k], axis=1).swapaxes(-1, -2), v,
                jnp.stack([g, beta, (k * q).sum(axis=-1)], axis=1)),
        position, delta_rule_block, (v.shape[1:], jnp.float32), interpret)


def delta_rule_chunked(q, k, v, g, beta, chunk: int = CHUNK):
    """The same recurrence over a whole sequence from a zero state, ``chunk``
    tokens at a time. q, k: (B, T, H, dk); v: (B, T, H, dv); g, beta: (B, T,
    H); float32. A position with ``g = 0`` and ``beta = 0`` leaves the state
    as it was (padding). Returns ``(o (B, T, H, dv), state (B, H, dk, dv))``
    after the last position.

    Within a chunk, with ``G_i`` the running sum of ``g`` and ``D_ij =
    e^{G_i − G_j}`` (``i ≥ j``): the tokens' corrections solve ``(I + L) U =
    β V − (β K e^{G}) S_0`` with ``L`` the strictly lower part of ``(β K Kᵀ)
    ⊙ D`` — a unit triangular system, inverted as ``Σ (−L)^i`` by repeated
    squaring (``L`` is nilpotent), all float32 at full precision."""
    b, t, h, dk = q.shape
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (
            a.ndim - 2)) for a in (q, k, v, g, beta))
    n = (t + pad) // chunk

    def chunks(a):   # (B, T, H, ...) -> (N, B, H, C, ...)
        a = a.reshape(b, n, chunk, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    g_sum = jnp.cumsum(g, axis=-1)                            # (N,B,H,C)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        lower, g_sum[..., :, None] - g_sum[..., None, :], -jnp.inf))
    k_beta, v_beta = k * beta[..., None], v * beta[..., None]
    x = -jnp.where(jnp.tril(lower, -1),
                   _dot32("...ik,...jk->...ij", k_beta, k) * decay, 0.0)
    solve = jnp.eye(chunk, dtype=jnp.float32) + x
    for _ in range(int(np.ceil(np.log2(chunk))) - 1):
        x = _dot32("...ij,...jk->...ik", x, x)
        solve = solve + _dot32("...ij,...jk->...ik", solve, x)
    u = _dot32("...ij,...jd->...id", solve, v_beta)
    w = _dot32("...ij,...jd->...id", solve,
               k_beta * jnp.exp(g_sum)[..., None])
    within = _dot32("...ik,...jk->...ij", q, k) * decay       # i >= j
    q_in = q * jnp.exp(g_sum)[..., None]
    k_out = k * jnp.exp(g_sum[..., -1:] - g_sum)[..., None]
    last = jnp.exp(g_sum[..., -1])[..., None, None]

    def body(state, xs):
        u_i, w_i, within_i, q_i, k_i, last_i = xs
        v_new = u_i - _dot32("...ck,...kd->...cd", w_i, state)
        o_i = (_dot32("...ck,...kd->...cd", q_i, state)
               + _dot32("...ij,...jd->...id", within_i, v_new))
        return state * last_i + _dot32("...ck,...cd->...kd", k_i, v_new), o_i

    state, o = jax.lax.scan(
        body, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
        (u, w, within, q_in, k_out, last))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2).reshape(b, n * chunk, h, -1)
    return o[:, :t], state


class _Layer(nn.Module):
    """One block: a mixer (``full``: gated attention, else Gated DeltaNet)
    and the expert layer."""

    full: bool
    dim: int
    heads: int
    kv_heads: int
    head_dim: int
    rotary_dim: int
    lin_k_heads: int
    lin_v_heads: int
    lin_dim: int
    conv: int
    experts: int
    experts_held: int
    first_expert: int
    experts_per_token: int
    expert_dim: int
    shared_dim: int
    eps: float
    theta: float
    dtype: jnp.dtype

    def setup(self):
        d, gains = self.dim, INIT_GAINS

        def p(name, init, *shape):
            return self.param(name, init, shape, self.dtype)

        self.norm_in = p("norm_in", norm_scale(0.0), d)
        self.norm_post = p("norm_post", norm_scale(0.0), d)
        if self.full:
            hd = self.head_dim
            self.wq = p("wq", seeded(1.0), d, self.heads * 2 * hd)
            self.wk = p("wk", seeded(1.0), d, self.kv_heads * hd)
            self.wv = p("wv", seeded(1.0), d, self.kv_heads * hd)
            self.norm_q = p("norm_q", norm_scale(gains["qk_scale"]), hd)
            self.norm_k = p("norm_k", norm_scale(gains["qk_scale"]), hd)
            self.wo = p("wo", seeded(gains["wo"]), self.heads * hd, d)
        else:
            self.in_qkvz = p("in_qkvz", seeded(1.0), d,
                             2 * self.key_dim + 2 * self.value_dim)
            self.in_ba = p("in_ba", seeded(1.0), d, 2 * self.lin_v_heads)
            self.conv_w = p("conv_w", seeded(1.0, fan_in_axis=0), self.conv,
                            2 * self.key_dim + self.value_dim)
            self.a_log = p("a_log", norm_scale(0.0), self.lin_v_heads)
            self.dt_bias = p("dt_bias", norm_scale(gains["dt_bias"]),
                             self.lin_v_heads)
            self.norm_o = p("norm_o", norm_scale(1.0), self.lin_dim)
            self.out_proj = p("out_proj", seeded(gains["out_proj"]),
                              self.value_dim, d)
        e, f, s = self.experts_held, self.expert_dim, self.shared_dim
        self.router = p("router", seeded(gains["router"]), d, self.experts)
        self.w_gate = p("w_gate", seeded(1.0), e, d, f)
        self.w_up = p("w_up", seeded(1.0), e, d, f)
        self.w_down = p("w_down", seeded(gains["w_down"]), e, f, d)
        self.shared_gate = p("shared_gate", seeded(1.0), d, 1)
        self.s_gate = p("s_gate", seeded(1.0), d, s)
        self.s_up = p("s_up", seeded(1.0), d, s)
        self.s_down = p("s_down", seeded(gains["shared_down"]), s, d)

    @property
    def key_dim(self):
        return self.lin_k_heads * self.lin_dim

    @property
    def value_dim(self):
        return self.lin_v_heads * self.lin_dim

    # -- the expert layer ---------------------------------------------------

    def _moe(self, x, routed: bool):
        """``x (rows, D)`` → ``x + MoE(norm_post(x))`` and the rows' chosen
        experts ``(rows, K)``, ids over all ``experts``."""
        h = rms_norm(x, self.norm_post, self.eps)
        top_e, top_p = expert_layer.route(h, self.router,
                                          self.experts_per_token, True)
        weights = (self.w_gate, self.w_up, self.w_down)
        if routed:
            y = expert_layer.routed(h, top_e, top_p, *weights,
                                    total=self.experts,
                                    first_held=self.first_expert)
        else:
            gate = expert_layer.gate_matrix(top_e, top_p, self.experts_held,
                                            self.first_expert)
            y = expert_layer.dense(h, gate, *weights)
        y = y + expert_layer.shared(h, self.shared_gate, self.s_gate,
                                    self.s_up, self.s_down)
        return x + y, top_e

    # -- gated attention ----------------------------------------------------

    def _qkv(self, x, position):
        """``x (..., D)`` at ``position (...)`` → q ``(..., H, hd)``, its
        gate ``(..., H * hd)``, k, v ``(..., KVH, hd)``: q and k normed then
        rotated."""
        h = rms_norm(x, self.norm_in, self.eps)
        lead, hd = x.shape[:-1], self.head_dim
        qg = _dot("...d,de->...e", h, self.wq).astype(self.dtype).reshape(
            *lead, self.heads, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:]
        k = _dot("...d,de->...e", h, self.wk).astype(self.dtype).reshape(
            *lead, self.kv_heads, hd)
        v = _dot("...d,de->...e", h, self.wv).astype(self.dtype).reshape(
            *lead, self.kv_heads, hd)
        with jax.named_scope("qk_norm"):
            q = rms_norm(q, self.norm_q, self.eps)
            k = rms_norm(k, self.norm_k, self.eps)
        with jax.named_scope("rope"):
            q = partial_rope(q, position, self.theta, self.rotary_dim)
            k = partial_rope(k, position, self.theta, self.rotary_dim)
        return q, gate.reshape(*lead, -1), k, v

    def _attn_out(self, x, o, gate):
        o = (o.reshape(gate.shape).astype(jnp.float32)
             * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(self.dtype)
        return x + _dot("...e,ed->...d", o, self.wo).astype(self.dtype)

    # -- gated delta net ----------------------------------------------------

    def _project(self, x):
        """``x (..., D)`` → the convolution's input ``[q|k|v] (..., C)``,
        ``z (..., Hv, dv)``, ``β``, ``g (..., Hv)`` (float32)."""
        h = rms_norm(x, self.norm_in, self.eps)
        qkvz = _dot("...d,de->...e", h, self.in_qkvz).astype(self.dtype)
        ba = _dot("...d,de->...e", h, self.in_ba)
        cut = 2 * self.key_dim + self.value_dim
        z = qkvz[..., cut:].reshape(*x.shape[:-1], self.lin_v_heads, -1)
        b, a = ba[..., :self.lin_v_heads], ba[..., self.lin_v_heads:]
        g = -jnp.exp(self.a_log.astype(jnp.float32)) * jax.nn.softplus(
            a + self.dt_bias.astype(jnp.float32))
        return qkvz[..., :cut], z, jax.nn.sigmoid(b), g

    def _heads(self, mixed):
        """The convolution's output ``(..., C)`` (after SiLU, float32) → q,
        k ``(..., Hv, dk)`` repeated to the value heads, normalised, q
        scaled; v ``(..., Hv, dv)``."""
        lead, kd = mixed.shape[:-1], self.key_dim
        rep = self.lin_v_heads // self.lin_k_heads

        def keyed(a):
            a = l2_norm(a.reshape(*lead, self.lin_k_heads, self.lin_dim))
            return jnp.repeat(a, rep, axis=-2)

        q = keyed(mixed[..., :kd]) * self.lin_dim ** -0.5
        k = keyed(mixed[..., kd:2 * kd])
        return q, k, mixed[..., 2 * kd:].reshape(*lead, self.lin_v_heads, -1)

    def _lin_out(self, x, o, z):
        with jax.named_scope("gated_norm"):
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + self.eps)
            o = (o * self.norm_o.astype(jnp.float32)).astype(self.dtype)
            o = (o.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
                 ).astype(self.dtype)
        return x + _dot("...e,ed->...d", o.reshape(*x.shape[:-1], -1),
                        self.out_proj).astype(self.dtype)

    # -- the two programs ---------------------------------------------------

    def prefill(self, x, mask, length):
        """x: (B, P, D); mask: (B, P) valid tokens; length: (B,). Returns
        ``(y, cache, passes)``: a full layer's cache is ``(k, v)`` of (B, P,
        KVH, hd), a linear layer's ``(state (B, Hv, dk, dv), tail (B, conv −
        1, C))`` after ``length`` tokens; ``passes``: what the expert
        product took (``experts.window_passes``)."""
        b, p, _ = x.shape
        if self.full:
            q, gate, k, v = self._qkv(
                x, jnp.broadcast_to(jnp.arange(p), (b, p)))
            o = kv_pool.prefill_attention(q, k, v, mask)
            x, cache = self._attn_out(x, o, gate), (k, v)
        else:
            with jax.named_scope("linear_attention"):
                mixed, z, beta, g = self._project(x)
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
                    out = jax.nn.silu(out)
                with jax.named_scope("delta_rule"):
                    q, k, v = self._heads(out)
                    o, state = delta_rule_chunked(
                        q, k, v, jnp.where(mask[..., None], g, 0.0),
                        jnp.where(mask[..., None], beta, 0.0))
                x, cache = self._lin_out(x, o, z), (state, tail)
        y, top_e = self._moe(x.reshape(b * p, -1), routed=True)
        return y.reshape(x.shape), cache, expert_layer.window_passes(
            top_e, self.experts_held, self.experts, self.first_expert)

    def step(self, x, cache, position, bound):
        """One token per slot: x (S, D). A full layer's ``cache`` is ``(k
        pool, v pool, its K/V layer)`` and it returns the new token's ``(k,
        v)`` (S, KVH, hd) for ``kv_pool.write_rows``; a linear layer's is
        ``(state, tail)`` of every slot and it returns their successors: the
        state advanced at the live slots (``position > 0``) only, a dead
        slot's as it was. Then ``(y, new cache, experts (S, K))``."""
        if self.full:
            k_pool, v_pool, layer = cache
            q, gate, k_new, v_new = self._qkv(x, position)
            o = kv_pool.decode_attention(q, k_new, v_new, k_pool, v_pool,
                                         layer, position, bound)
            x, cache = self._attn_out(x, o, gate), (k_new, v_new)
        else:
            state, tail = cache
            with jax.named_scope("linear_attention"):
                mixed, z, beta, g = self._project(x)
                with jax.named_scope("conv"):
                    window = jnp.concatenate([tail, mixed[:, None]], axis=1)
                    out = jax.nn.silu(
                        (window.astype(jnp.float32)
                         * self.conv_w.astype(jnp.float32)).sum(axis=1))
                with jax.named_scope("delta_rule"):
                    q, k, v = self._heads(out)
                    with jax.named_scope("state_update"):
                        o, state = delta_rule_update(state, q, k, v, g, beta,
                                                     position)
                x, cache = self._lin_out(x, o, z), (state, window[:, 1:])
        y, experts = self._moe(x, routed=False)
        return y, cache, experts


class Qwen3NextLM(nn.Module):
    """Causal LM over the hybrid block stack, with the serving entry points
    of an LM family (``runtime/kvcache.py`` ``LMServable``). ``decode_step``
    returns its ids followed by every layer's chosen experts, in one int32
    vector, so the routing counters ride the fetch the step makes anyway
    (``step_report``)."""

    vocab_size: int
    dim: int = 64
    depth: int = 4
    full_interval: int = 4
    heads: int = 4
    kv_heads: int = 2
    head_dim: int = 32
    rotary_dim: int = 8
    lin_k_heads: int = 2
    lin_v_heads: int = 4
    lin_dim: int = 16
    conv: int = 4
    experts: int = 16
    experts_held: int = 16
    first_expert: int = 0
    experts_per_token: int = 2
    expert_dim: int = 32
    shared_dim: int = 32
    rms_eps: float = 1e-6
    rope_theta: float = 1e7
    dtype: jnp.dtype = jnp.bfloat16

    def setup(self):
        self.embed = self.param("embed", seeded(1.0, fan_in_axis=None),
                                (self.vocab_size, self.dim), self.dtype)
        shared = {field: getattr(self, field) for field in (
            "dim", "heads", "kv_heads", "head_dim", "rotary_dim",
            "lin_k_heads", "lin_v_heads", "lin_dim", "conv", "experts",
            "experts_held", "first_expert", "experts_per_token", "expert_dim",
            "shared_dim", "dtype")}
        self.layers = [_Layer(full=self.is_full(i), eps=self.rms_eps,
                              theta=self.rope_theta, name=f"layer{i}",
                              **shared) for i in range(self.depth)]
        self.norm_f = self.param("norm_f", norm_scale(0.0), (self.dim,),
                                 self.dtype)
        self.lm_head = self.param("lm_head", seeded(1.0),
                                  (self.dim, self.vocab_size), self.dtype)

    @nn.nowrap
    def is_full(self, i: int) -> bool:
        return (i + 1) % self.full_interval == 0

    @nn.nowrap
    def cache_spec(self):
        """What a slot holds (``kv_pool.SlotSpec``): K/V of the
        full-attention layers, and of the ``j``-th linear layer its state
        ``delta<j>`` (float32; stepped at the live slots only) and its
        convolution's last inputs ``conv<j>`` (stepped at every slot)."""
        full = sum(map(self.is_full, range(self.depth)))
        channels = (2 * self.lin_k_heads + self.lin_v_heads) * self.lin_dim
        state = []
        for j in range(self.depth - full):
            state += [(f"delta{j}", (self.lin_v_heads, self.lin_dim,
                                     self.lin_dim), jnp.float32),
                      (f"conv{j}", (self.conv - 1, channels), self.dtype)]
        return kv_pool.kv_slot(
            full, self.kv_heads, self.head_dim, self.dtype, state,
            tuple(f"delta{j}" for j in range(self.depth - full)))

    def _logits(self, h):
        with jax.named_scope("head"):
            return _dot("...d,dv->...v",
                        rms_norm(h, self.norm_f, self.rms_eps), self.lm_head)

    def _prefill(self, tokens, length):
        with jax.named_scope("embedding"):
            h = self.embed[tokens]
        mask = jnp.arange(tokens.shape[1])[None, :] < length[:, None]
        ks, vs, state, passes = [], [], {}, []
        for layer in self.layers:
            h, cache, taken = layer.prefill(h, mask, length)
            passes.append(taken)
            if layer.full:
                ks.append(cache[0])
                vs.append(cache[1])
            else:
                j = len(state) // 2
                state[f"delta{j}"], state[f"conv{j}"] = cache
        return (h, kv_pool.prompt_block(ks), kv_pool.prompt_block(vs), state,
                expert_layer.pass_report(passes))

    def _step(self, tokens, k_cache, v_cache, state, position, bound):
        with jax.named_scope("embedding"):
            h = self.embed[tokens]
        k_rows, v_rows, experts, new_state = [], [], [], {}
        for layer in self.layers:
            if layer.full:
                h, (k, v), e = layer.step(
                    h, (k_cache, v_cache, len(k_rows)), position, bound)
                k_rows.append(k)
                v_rows.append(v)
            else:
                j = len(new_state) // 2
                h, cache, e = layer.step(
                    h, (state[f"delta{j}"], state[f"conv{j}"]), position,
                    bound)
                new_state[f"delta{j}"], new_state[f"conv{j}"] = cache
            experts.append(e)
        k_cache, v_cache = kv_pool.write_rows(
            (k_cache, v_cache), (k_rows, v_rows), position)
        return h, k_cache, v_cache, new_state, jnp.stack(experts)

    def prefill(self, tokens, length):
        h, k, v, state, passes = self._prefill(tokens, length)
        last = jnp.take_along_axis(
            h, (length - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        ids = jnp.argmax(self._logits(last), axis=-1).astype(jnp.int32)
        return jnp.concatenate([ids, passes]), k, v, state

    def decode_step(self, tokens, k_cache, v_cache, state, position,
                    bound=None):
        """One token for every slot of the pool. Attention reads the cached
        positions ``< bound`` (``kv_pool.decode_attention``); the linear
        layers advance the state of the slots at a position > 0."""
        h, k_cache, v_cache, state, experts = self._step(
            tokens, k_cache, v_cache, state, position, bound)
        ids = jnp.argmax(self._logits(h), axis=-1).astype(jnp.int32)
        return (jnp.concatenate([ids, experts.astype(jnp.int32).reshape(-1)]),
                k_cache, v_cache, state)

    # Logits, for tests only: the serving programs ship ids.

    def prefill_logits(self, tokens, length):
        h, k, v, state, _ = self._prefill(tokens, length)
        return self._logits(h), k, v, state

    def decode_logits(self, tokens, k_cache, v_cache, state, position,
                      bound=None):
        h, k_cache, v_cache, state, _ = self._step(
            tokens, k_cache, v_cache, state, position, bound)
        return self._logits(h), k_cache, v_cache, state

    # What ``step_report`` returns, as the decode engine exposes it.
    step_report_series = expert_layer.step_report_series
    # What ``prefill`` appends to its first id (``experts.pass_report``).
    prefill_report_kinds = expert_layer.prefill_report_kinds

    @nn.nowrap
    def step_report(self, extra: np.ndarray, active) -> dict[str, float]:
        """What ``decode_step`` appended to its ids, over the LIVE slots and
        the experts HELD here: a MoE layer's held experts with at least one
        live token, its fullest held expert's tokens over the mean load
        (live × K ÷ all experts), each the mean over the layers of this
        step; and the share of the live tokens' picks that land here."""
        live = np.flatnonzero(active)
        if not live.size:
            return {}
        picks = extra.reshape(self.depth, -1, self.experts_per_token)[:, live]
        return expert_layer.load_report(picks, self.experts,
                                        self.experts_held, self.first_expert)


def create_qwen3_next_lm(rng=None, vocab_size: int = 512, dtype=jnp.bfloat16,
                         **dims):
    """Build the LM and its seeded params (``olmoe.seeded``: the same values
    on every backend). ``dims``: the fields of ``Qwen3NextLM``; a key it does
    not know is an error. Norm weights are drawn away from their neutral
    value, so one left out shows. The gains keep random weights in the
    regime of trained ones where a comparison with a float32 reference needs
    it (``olmoe.create_olmoe_lm`` has the argument): each mixer and each
    expert layer adds a fraction of the residual stream; router logits
    deviate by ~2, so the ten renormalised weights differ; the decay
    ``e^g`` sits near 0.95 a token (``dt_bias`` near −3), so a state
    remembers tens of tokens and both the decay and the state's precision
    show in the logits."""
    model = Qwen3NextLM(vocab_size=vocab_size, dtype=jnp.dtype(dtype), **dims)
    if model.heads % model.kv_heads or model.lin_v_heads % model.lin_k_heads:
        raise ValueError("query heads must group onto K/V heads, value heads "
                         "onto key heads")
    if model.rotary_dim % 2 or model.rotary_dim > model.head_dim:
        raise ValueError(f"rotary_dim {model.rotary_dim} of a head of "
                         f"{model.head_dim}")
    if not (0 < model.experts_per_token <= model.experts and
            0 <= model.first_expert
            and model.first_expert + model.experts_held <= model.experts):
        raise ValueError("experts held must lie within the experts routed")
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    params = jax.jit(partial(model.init, method="prefill"))(
        rng, np.zeros((1, 8), np.int32), np.ones((1,), np.int32))
    return model, params
