"""ling3 — a hybrid decoder LM for the decode engine: Kimi Delta Attention
(a linear-attention recurrence whose state decays by a gate of its own for
every key channel) in all layers but every ``group``-th, which is latent
attention behind a head-wise output gate; a sigmoid-routed expert layer whose
choice is limited to the best few groups of experts, with an ungated shared
expert.

The block of inclusionAI/Ling-3.0-flash (``model_type: bailing_hybrid``),
written from its configuration's equations. ``n(x) = w ⊙ x · rsqrt(mean(x²)
+ eps)`` in float32, no biases. A layer: ``h = x + Mixer(n_in(x))``, ``y = h
+ FFN(n_post(h))``; layer ``i`` mixes by latent attention iff ``(i + 1) %
group == 0``, else by KDA.

- **KDA** (``heads`` heads of ``head_dim`` = ``d``, keys and values alike;
  convolution ``conv``): with ``u = n_in(x)``, ``[q | k | v] = SiLU(conv([u
  W_q | u W_k | u W_v]))`` (depthwise, causal, no bias); ``q ← q/‖q‖ ·
  d^(−1/2)``, ``k ← k/‖k‖`` a head (eps 1e-6); ``β = σ(u W_β)`` a head;
  ``a = u W_a`` and the log-decay **a channel** ``g = gate_bound · σ(e^{A_h}
  (a + b))`` with ``gate_bound`` = −5, so ``g ∈ (−5, 0)`` (``A (H,)`` and
  ``b (H, d)`` float32). A head's state ``S (d × d)``, float32; token ``t``::

      S ← Diag(e^{g_t}) S;  δ = β_t (v_t − Sᵀ k_t);  S ← S + k_t ⊗ δ;  o_t = Sᵀ q_t

  then a head ``o ← n_o(o) ⊙ σ(z)``, ``z = u W_z``; ``W_o``. No positions.
- **Latent attention** (``heads`` heads, rank ``kv_rank``, head widths
  ``nope`` / ``rope_dim`` / ``v_dim``; no query rank): ``[q_nope | q_rope]_h
  = u W_q``; ``[c_kv | k_r] = u W_dkv``, ``c_kv ← n_kv(c_kv)``; ``q_rope``
  and ``k_r`` rotated on neighbouring lanes (``olmoe.rope(interleave=True)``),
  ``k_r`` shared by every head; ``k_nope,h = c_kv W_uk,h``, ``v_h = c_kv
  W_uv,h``; a causal softmax of ``(q_nope · k_nope + q_rope · k_r) · (nope +
  rope)^(−1/2)``; ``o_h ← o_h · σ(u w_g,h)``, one scalar a head a token;
  ``W_o``. A position caches ``[c_kv | k_r]`` after norm and rotation — ONE
  row every head shares, whose first ``kv_rank`` lanes are its value too.
- **FFN**: the first ``dense_layers`` a dense SwiGLU; the others
  (``models/experts.py``) ``sigmoid`` scores over ``experts``; chosen by
  score + bias inside the ``route_groups[1]`` best of ``route_groups[0]``
  groups, ``experts_per_token`` of them; weights the scores renormalised
  times ``route_scale``; this process sums the terms of the ``experts_held``
  experts from ``first_expert``; plus an ungated shared expert.
- Final norm, untied head, greedy argmax on the device. The published
  multi-token-prediction module is not here (the main model's logits do not
  depend on it), and the clamps inside the experts' SwiGLU that the last
  published layers carry are not either: ``create_ling3_lm`` refuses a
  non-zero limit.

What a slot holds (``cache_spec``): one tensor of latent rows for the latent
layers (padded to whole lane tiles), and per KDA layer its state ``kda<j>``
(float32) and the convolution's last ``conv − 1`` inputs ``conv<j>``.
``decode_step`` is the recurrence as written, one token a slot: ``kda<j>``
advances at the live slots only, in place (``kda_update``:
``state_pool.update_live`` with ``kda_block`` as the slot's math), the
latent read is the absorbed form (``kv_pool.latent_decode_attention``).
``prefill`` runs a KDA layer's convolution and recurrence in chunks, one
kernel (``ops/pallas/kda_chunk.py``), and the latent layers in the published
form (``kv_pool.prompt_attention``), one prompt a call. The experts' product is ``routed`` in a prefill and ``dense`` over the
held experts in a step.

Weights, activations and the latent rows are ``dtype`` (bfloat16 as served);
the state, the gates, routing and accumulation float32.
"""

from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops import kv_pool, state_pool
from ..ops.pallas.kda_chunk import CHUNK, SUB_BLOCK, kda_chunk
from . import experts as expert_layer
from .dots3 import padded
from .olmoe import norm_scale, rms_norm, rope, seeded
from .qwen3_next import L2_EPS, l2_norm

# The seeded init's gains (``create_ling3_lm`` says why these).
INIT_GAINS = {"w_a": 0.5, "dt_bias": -5.5, "dt_spread": 1.0, "kda_out": 0.5,
              "w_q": 1.5, "w_g": 1.5, "w_o": 1.0, "router": 2.0,
              "router_bias": 0.2, "w_down": 0.2, "shared_down": 0.15,
              "mlp_down": 0.15}

# The ``jax.named_scope``s of this family's programs, for a trace's reader.
TRACE_SCOPES = ("embedding", "kda_proj", "conv", "kda_gate", "kda_chunk",
                "state_update", "gated_norm", "latent_q", "latent_kv", "rope",
                "attention", "head_gate", "out_proj", "router", "experts",
                "shared_expert", "mlp", "cache_update", "cache_insert",
                "state_insert", "head")


def _dot(eq, a, b):
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def _lane_pad(x, width: int):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def kda_step(state, q, k, v, g, beta):
    """One token of the recurrence for every (slot, head), in ``jax.numpy``
    — the equation ``kda_block`` and the prefill's ``kda_chunk`` kernel are
    held to. state:
    (..., dk, dv) float32; q, k: (..., dk) — normalised, q scaled; v: (...,
    dv); g: (..., dk), a channel's log-decay; beta: (...). Returns ``(o
    (..., dv), new state)``. Both readings of the decayed state (``Sᵀk``,
    ``Sᵀq``) are taken from the old one in one pass and the new state is
    written in another: ``o = Sᵀ(e^g q) + (k·q) δ``."""
    decay = jnp.exp(g)
    sk = (state * (decay * k)[..., :, None]).sum(axis=-2)
    sq = (state * (decay * q)[..., :, None]).sum(axis=-2)
    delta = beta[..., None] * (v - sk)
    o = sq + (k * q).sum(axis=-1, keepdims=True) * delta
    return o, (state * decay[..., :, None]
               + k[..., :, None] * delta[..., None, :])


def kda_block(state_ref, qkg_ref, v_ref, gates_ref, o_ref, successor_ref):
    """``kda_step`` on one slot's block, in VMEM (``state_pool
    .update_live``'s ``body``). state_ref, successor_ref: (H, dk, dv);
    qkg_ref: (3, dk, H) — q, k and the log-decay g with a head a LANE, so
    that a head's q (k, g) is a column down the sublanes: the column the
    products with ``S`` need, and the column that decays its rows; v_ref,
    o_ref: (H, dv); gates_ref: (2, H) — ``β`` and ``k · q``. A head at a
    time: both readings of the old state are sums down the sublanes, the new
    state one pass."""
    heads, _, dv = state_ref.shape

    def gate(i, h):   # a head's scalar as a row (Mosaic broadcasts one way)
        return jnp.broadcast_to(gates_ref[i:i + 1, h:h + 1], (1, dv))

    for h in range(heads):
        s = state_ref[h]
        q, k = qkg_ref[0, :, h:h + 1], qkg_ref[1, :, h:h + 1]   # (dk, 1)
        decay = jnp.exp(qkg_ref[2, :, h:h + 1])
        sk = (s * (decay * k)).sum(axis=0, keepdims=True)       # (1, dv)
        sq = (s * (decay * q)).sum(axis=0, keepdims=True)
        delta = gate(0, h) * (v_ref[h:h + 1, :] - sk)
        o_ref[h:h + 1, :] = sq + gate(1, h) * delta
        successor_ref[h] = s * decay + k * delta


def kda_update(state, q, k, v, g, beta, position, interpret=None):
    """``kda_step`` at the live slots of the pool (``position > 0``) only,
    in place. state: (S, H, dk, dv) — the pool's tensor; the rest as
    ``kda_step`` takes them, a slot each. Returns ``(o (S, H, dv) — zeros at
    a dead slot —, the tensor's successor)``; a dead slot's state is what it
    was."""
    return state_pool.update_live(
        state, (jnp.stack([q, k, g], axis=1).swapaxes(-1, -2), v,
                jnp.stack([beta, (k * q).sum(axis=-1)], axis=1)),
        position, kda_block, (v.shape[1:], jnp.float32), interpret)


def kda_params(p, dim: int, heads: int, head_dim: int, conv: int,
               lora: int = 0) -> dict:
    """Declare a KDA mixer's parameters through ``p(name, init, *shape,
    dtype=None)`` (a layer's ``self.param`` at its dtype) and return them by
    name. ``lora`` = 0: the decay's ``W_a`` and the output gate's ``W_z``
    are full rank, ``(D, H d)`` (``no_kda_lora``); else both go through that
    rank — ``a = (u W_a1) W_a2``, ``z = (u W_z1) W_z2 + b_z``, Kimi Linear's
    published form."""
    g, wide = INIT_GAINS, heads * head_dim
    out = {"in_qkv": p("in_qkv", seeded(1.0), dim, 3 * wide),
           "conv_w": p("conv_w", seeded(1.0, fan_in_axis=0), conv, 3 * wide)}
    if lora:
        out["w_a1"] = p("w_a1", seeded(1.0), dim, lora)
        out["w_a2"] = p("w_a2", seeded(g["w_a"]), lora, wide)
        out["w_z1"] = p("w_z1", seeded(1.0), dim, lora)
        out["w_z2"] = p("w_z2", seeded(1.0), lora, wide)
        out["b_z"] = p("b_z", norm_scale(0.0), wide)
    else:
        out["w_a"] = p("w_a", seeded(g["w_a"]), dim, wide)
        out["w_z"] = p("w_z", seeded(1.0), dim, wide)
    out["w_beta"] = p("w_beta", seeded(1.0), dim, heads)
    out["a_log"] = p("a_log", norm_scale(0.0), heads, dtype=jnp.float32)
    # around ``dt_bias``, SPREAD over a head's channels
    out["dt_bias"] = p("dt_bias", seeded(
        g["dt_spread"], g["dt_bias"], fan_in_axis=None), heads, head_dim,
        dtype=jnp.float32)
    out["norm_o"] = p("norm_o", norm_scale(1.0), head_dim)
    out["out_proj"] = p("out_proj", seeded(g["kda_out"]), wide, dim)
    return out


def kda_project(u, w: dict, gate_bound: float):
    """The normed input ``u (..., D)`` → the convolution's input ``[q|k|v]
    (..., 3 H d)``, the output gate's ``z (..., H, d)``, ``β (..., H)`` and
    the log-decay ``g (..., H, d)`` (float32) under ``w`` (``kda_params``)."""
    heads, head_dim = w["dt_bias"].shape
    dtype = w["in_qkv"].dtype
    split = (*u.shape[:-1], heads, head_dim)
    with jax.named_scope("kda_proj"):
        mixed = _dot("...d,de->...e", u, w["in_qkv"]).astype(dtype)
        if "w_a" in w:
            z = _dot("...d,de->...e", u, w["w_z"]).astype(dtype)
            a = _dot("...d,de->...e", u, w["w_a"])
        else:
            z = (_dot("...r,re->...e", _dot("...d,dr->...r", u, w[
                "w_z1"]).astype(dtype), w["w_z2"])
                + w["b_z"].astype(jnp.float32)).astype(dtype)
            a = _dot("...r,re->...e", _dot("...d,dr->...r", u, w[
                "w_a1"]).astype(dtype), w["w_a2"])
        beta = jax.nn.sigmoid(_dot("...d,dh->...h", u, w["w_beta"]))
    with jax.named_scope("kda_gate"):
        g = gate_bound * jax.nn.sigmoid(
            jnp.exp(w["a_log"])[:, None] * (a.reshape(split) + w["dt_bias"]))
    return mixed, z.reshape(split), beta, g


def kda_heads(mixed, heads: int, head_dim: int):
    """The convolution's output ``(..., 3 H d)`` (after SiLU, float32) → q, k
    normalised a head, q scaled; v: ``(..., H, d)`` each."""
    q, k, v = jnp.moveaxis(mixed.reshape(
        *mixed.shape[:-1], 3, heads, head_dim), -3, 0)
    return l2_norm(q) * head_dim ** -0.5, l2_norm(k), v


def kda_out(o, z, w: dict, eps: float):
    """The recurrence's read-out ``o (..., H, d)`` normed a head, gated by
    ``σ(z)`` and projected: the mixer's output ``(..., D)``."""
    dtype = w["out_proj"].dtype
    with jax.named_scope("gated_norm"):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
        o = (o * w["norm_o"].astype(jnp.float32)).astype(dtype)
        o = (o.astype(jnp.float32) * jax.nn.sigmoid(
            z.astype(jnp.float32))).astype(dtype)
    with jax.named_scope("out_proj"):
        return _dot("...e,ed->...d", o.reshape(*o.shape[:-2], -1),
                    w["out_proj"]).astype(dtype)


def kda_prompt(u, w: dict, mask, length, gate_bound: float, eps: float):
    """The mixer over ONE padded prompt, ``u (1, P, D)`` after its norm →
    its output ``(1, P, D)`` and ``(state (1, H, d, d), tail (1, conv − 1,
    3 H d))`` after ``length`` tokens."""
    mixed, z, beta, g = kda_project(u, w, gate_bound)
    keep = w["conv_w"].shape[0] - 1
    with jax.named_scope("conv"):
        # the last ``keep`` inputs before ``length``; zero before the
        # sequence's start
        at = length[:, None] - keep + jnp.arange(keep)[None, :]
        tail = jnp.where((at >= 0)[..., None], jnp.take_along_axis(
            mixed, jnp.maximum(at, 0)[..., None], axis=1), 0)
    with jax.named_scope("kda_chunk"):
        # the convolution, q's and k's norms and the recurrence, one
        # kernel; a padded position leaves the state as it was
        o, state = kda_chunk(
            mixed[0], w["conv_w"].astype(jnp.float32),
            jnp.where(mask[0, :, None, None], g[0], 0.0),
            jnp.where(mask[0, :, None], beta[0], 0.0), eps=L2_EPS)
    return kda_out(o[None], z, w, eps), (state[None], tail)


def kda_token(u, w: dict, state, tail, position, gate_bound: float,
              eps: float):
    """The mixer of one token a slot, ``u (S, D)`` after its norm → its
    output ``(S, D)``, the successors of ``(state, tail)`` and the step's
    log-decay ``g (S, H, d)``."""
    mixed, z, beta, g = kda_project(u, w, gate_bound)
    with jax.named_scope("conv"):
        window = jnp.concatenate([tail, mixed[:, None]], axis=1)
        out = jax.nn.silu((window.astype(jnp.float32)
                           * w["conv_w"].astype(jnp.float32)).sum(axis=1))
    q, k, v = kda_heads(out, *w["dt_bias"].shape)
    with jax.named_scope("state_update"):
        o, state = kda_update(state, q, k, v, g, beta, position)
    return kda_out(o, z, w, eps), (state, window[:, 1:]), g


class _Layer(nn.Module):
    """One block: a mixer (``latent``: latent attention, else KDA) and its
    FFN (``dense``: a SwiGLU; else experts)."""

    latent: bool
    dense: bool
    dim: int
    heads: int
    head_dim: int
    conv: int
    gate_bound: float
    kv_rank: int
    nope: int
    rope_dim: int
    v_dim: int
    mlp_dim: int
    experts: int
    experts_held: int
    first_expert: int
    experts_per_token: int
    route_groups: tuple
    expert_dim: int
    shared_dim: int
    route_scale: float
    eps: float
    theta: float
    dtype: jnp.dtype

    def setup(self):
        d, g, h = self.dim, INIT_GAINS, self.heads

        def p(name, init, *shape, dtype=None):
            return self.param(name, init, shape, dtype or self.dtype)

        self.norm_in = p("norm_in", norm_scale(1.0), d)
        self.norm_post = p("norm_post", norm_scale(1.0), d)
        if self.latent:
            self.w_q = p("w_q", seeded(g["w_q"]), d,
                         h * (self.nope + self.rope_dim))
            self.w_dkv = p("w_dkv", seeded(1.0), d,
                           self.kv_rank + self.rope_dim)
            self.norm_kv = p("norm_kv", norm_scale(1.0), self.kv_rank)
            self.w_uk = p("w_uk", seeded(1.0, fan_in_axis=0), self.kv_rank,
                          h, self.nope)
            self.w_uv = p("w_uv", seeded(1.0, fan_in_axis=0), self.kv_rank,
                          h, self.v_dim)
            self.w_g = p("w_g", seeded(g["w_g"]), d, h)
            self.w_o = p("w_o", seeded(g["w_o"]), h * self.v_dim, d)
        else:
            self.kda = kda_params(p, d, h, self.head_dim, self.conv)
        if self.dense:
            f = self.mlp_dim
            self.m_gate = p("m_gate", seeded(1.0), d, f)
            self.m_up = p("m_up", seeded(1.0), d, f)
            self.m_down = p("m_down", seeded(g["mlp_down"]), f, d)
        else:
            e, f, s = self.experts_held, self.expert_dim, self.shared_dim
            self.router = p("router", seeded(g["router"]), d, self.experts)
            self.router_bias = p("router_bias", seeded(
                g["router_bias"], fan_in_axis=None), self.experts,
                dtype=jnp.float32)
            self.w_gate = p("w_gate", seeded(1.0), e, d, f)
            self.w_up = p("w_up", seeded(1.0), e, d, f)
            self.w_down = p("w_down", seeded(g["w_down"]), e, f, d)
            self.s_gate = p("s_gate", seeded(1.0), d, s)
            self.s_up = p("s_up", seeded(1.0), d, s)
            self.s_down = p("s_down", seeded(g["shared_down"]), s, d)

    @property
    def row(self) -> int:
        """Lanes of the cached latent row, padded to whole tiles."""
        return padded(self.kv_rank + self.rope_dim)

    @property
    def scale(self) -> float:
        return float((self.nope + self.rope_dim) ** -0.5)

    # -- the FFN ---------------------------------------------------------------

    def _ffn(self, x, routed: bool):
        """``x (rows, D)`` → ``x + FFN(n_post(x))`` and, from an expert layer,
        the rows' chosen experts ``(rows, K)``, ids over all ``experts``
        (else None)."""
        h = rms_norm(x, self.norm_post, self.eps)
        if self.dense:
            with jax.named_scope("mlp"):
                a = (jax.nn.silu(_dot("...d,df->...f", h, self.m_gate))
                     * _dot("...d,df->...f", h, self.m_up)).astype(self.dtype)
                return x + _dot("...f,fd->...d", a, self.m_down).astype(
                    self.dtype), None
        top_e, top_p = expert_layer.route(
            h, self.router, self.experts_per_token, True, scoring="sigmoid",
            bias=self.router_bias, scale=self.route_scale,
            groups=self.route_groups)
        weights = (self.w_gate, self.w_up, self.w_down)
        if routed:
            y = expert_layer.routed(h, top_e, top_p, *weights,
                                    total=self.experts,
                                    first_held=self.first_expert)
        else:
            y = expert_layer.dense(h, expert_layer.gate_matrix(
                top_e, top_p, self.experts_held, self.first_expert), *weights)
        return x + y + expert_layer.shared(h, None, self.s_gate, self.s_up,
                                           self.s_down), top_e

    # -- Kimi Delta Attention --------------------------------------------------

    def _kda_prompt(self, x, mask, length):
        """The mixer over ONE padded prompt ``x (1, P, D)`` → ``x + KDA`` and
        ``(state (1, H, d, d), tail (1, conv − 1, 3 H d))`` after ``length``
        tokens."""
        y, cache = kda_prompt(rms_norm(x, self.norm_in, self.eps), self.kda,
                              mask, length, self.gate_bound, self.eps)
        return x + y, cache

    def _kda_token(self, x, state, tail, position):
        """The mixer of one token a slot: ``x (S, D)`` → ``x + KDA`` and the
        successors of ``(state, tail)``."""
        y, cache, g = kda_token(rms_norm(x, self.norm_in, self.eps), self.kda,
                                state, tail, position, self.gate_bound,
                                self.eps)
        return x + y, cache, g

    # -- latent attention ------------------------------------------------------

    def _rotate(self, x, position):
        with jax.named_scope("rope"):
            return rope(x, position, self.theta, interleave=True)

    def _latent_in(self, x, position):
        """``x (..., D)`` at ``position (...)`` → the normed input ``u``,
        ``q_nope (..., H, nope)``, ``q_rope (..., H, rope)`` rotated, and
        the row a position caches ``[c_kv | k_r] (..., r + rope)``: normed,
        ``k_r`` rotated."""
        u = rms_norm(x, self.norm_in, self.eps)
        with jax.named_scope("latent_q"):
            q = _dot("...d,de->...e", u, self.w_q).astype(self.dtype).reshape(
                *x.shape[:-1], self.heads, -1)
            q_nope, q_rope = (q[..., :self.nope],
                              self._rotate(q[..., self.nope:], position))
        with jax.named_scope("latent_kv"):
            kv = _dot("...d,dr->...r", u, self.w_dkv).astype(self.dtype)
            c_kv = rms_norm(kv[..., :self.kv_rank], self.norm_kv, self.eps)
            k_r = self._rotate(kv[..., None, self.kv_rank:],
                               position)[..., 0, :]
        return u, q_nope, q_rope, jnp.concatenate([c_kv, k_r], axis=-1)

    def _latent_out(self, x, u, o):
        """``o (..., H, v)`` gated a head and projected onto ``x``."""
        with jax.named_scope("head_gate"):
            gate = jax.nn.sigmoid(_dot("...d,dh->...h", u, self.w_g))
            o = (o.astype(jnp.float32) * gate[..., None]).astype(self.dtype)
        with jax.named_scope("out_proj"):
            return x + _dot("...e,ed->...d", o.reshape(*x.shape[:-1], -1),
                            self.w_o).astype(self.dtype)

    def _latent_prompt(self, x):
        """The mixer over one padded prompt ``x (P, D)``, as published →
        ``x + attention`` and the rows it caches ``(P, row)``."""
        position = jnp.arange(x.shape[0])
        u, q_nope, q_rope, row = self._latent_in(x, position)
        c_kv, k_r = row[:, :self.kv_rank], row[:, self.kv_rank:]
        with jax.named_scope("latent_kv"):
            k_nope = _dot("pr,rhn->phn", c_kv, self.w_uk).astype(self.dtype)
            v = _dot("pr,rhv->phv", c_kv, self.w_uv).astype(self.dtype)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_r[:, None], (*k_nope.shape[:2], self.rope_dim))], axis=-1)
        o = kv_pool.prompt_attention(
            jnp.concatenate([q_nope, q_rope], axis=-1), k, v, self.scale)
        return self._latent_out(x, u, o), _lane_pad(row, self.row)

    def _latent_token(self, x, pool, layer: int, position, bound: int):
        """The mixer of one token a slot, absorbed: ``x (S, D)`` against
        ``pool``'s ``layer`` → ``x + attention`` and the new rows."""
        u, q_nope, q_rope, row = self._latent_in(x, position)
        with jax.named_scope("latent_q"):
            q = jnp.concatenate(
                [_dot("shn,rhn->shr", q_nope, self.w_uk).astype(self.dtype),
                 q_rope], axis=-1)
        q, row = _lane_pad(q, self.row), _lane_pad(row, self.row)
        o = kv_pool.latent_decode_attention(
            q, row, pool, layer, position, value=self.kv_rank,
            bound=min(bound, pool.shape[2]), scale=self.scale)
        with jax.named_scope("latent_kv"):
            o = _dot("shr,rhv->shv", o, self.w_uv).astype(self.dtype)
        return self._latent_out(x, u, o), row

    # -- the two programs ------------------------------------------------------

    def prefill(self, x, mask, length):
        """``x (1, P, D)``, one prompt padded to its bucket; mask: (1, P)
        valid tokens; length: (1,). Returns ``(y, cache, passes)``: a latent
        layer's cache is its rows ``(P, row)``, a KDA layer's ``(state, tail)``
        after ``length`` tokens; ``passes``: what the expert product took
        (``experts.window_passes``; None from a dense layer)."""
        if self.latent:
            y, cache = self._latent_prompt(x[0])
            x = y[None]
        else:
            x, cache = self._kda_prompt(x, mask, length)
        y, top_e = self._ffn(x[0], routed=True)
        return y[None], cache, (
            None if top_e is None else expert_layer.window_passes(
                top_e, self.experts_held, self.experts, self.first_expert))

    def step(self, x, cache, position, bound):
        """One token a slot: ``x (S, D)``. A latent layer's ``cache`` is
        ``(pool, its layer in it)`` and it returns the new rows; a KDA layer's
        is ``(state, tail)`` of every slot and it returns their successors:
        the state advanced at the live slots (``position > 0``) only. Then
        ``(y, new cache, experts (S, K) or None, g (S, H, d) or None)``."""
        g = None
        if self.latent:
            x, cache = self._latent_token(x, *cache, position, bound)
        else:
            x, cache, g = self._kda_token(x, *cache, position)
        y, top_e = self._ffn(x, routed=False)
        return y, cache, top_e, g


class Ling3LM(nn.Module):
    """Causal LM over the hybrid block stack, with the serving entry points
    of an LM family (``runtime/kvcache.py`` ``LMServable``). ``decode_step``
    returns its ids followed by every expert layer's chosen experts and each
    slot's retention (float32 bits), in one int32 vector (``step_report``)."""

    vocab_size: int
    dim: int = 64
    depth: int = 4
    group: int = 4
    dense_layers: int = 1
    heads: int = 4
    head_dim: int = 16
    conv: int = 4
    gate_bound: float = -5.0
    kv_rank: int = 16
    nope: int = 16
    rope_dim: int = 8
    v_dim: int = 16
    mlp_dim: int = 96
    experts: int = 16
    experts_held: int = 16
    first_expert: int = 0
    experts_per_token: int = 2
    route_groups: tuple = (4, 2)
    expert_dim: int = 32
    shared_dim: int = 32
    route_scale: float = 2.5
    rms_eps: float = 1e-6
    rope_theta: float = 6e6
    dtype: jnp.dtype = jnp.bfloat16

    def setup(self):
        self.embed = self.param("embed", seeded(1.0, fan_in_axis=None),
                                (self.vocab_size, self.dim), self.dtype)
        shared = {field: getattr(self, field) for field in (
            "dim", "heads", "head_dim", "conv", "gate_bound", "kv_rank",
            "nope", "rope_dim", "v_dim", "mlp_dim", "experts", "experts_held",
            "first_expert", "experts_per_token", "expert_dim", "shared_dim",
            "route_scale", "dtype")}
        self.layers = [
            _Layer(latent=self.is_latent(i), dense=i < self.dense_layers,
                   route_groups=tuple(self.route_groups), eps=self.rms_eps,
                   theta=self.rope_theta, name=f"layer{i}", **shared)
            for i in range(self.depth)]
        self.norm_f = self.param("norm_f", norm_scale(1.0), (self.dim,),
                                 self.dtype)
        self.lm_head = self.param("lm_head", seeded(1.0),
                                  (self.dim, self.vocab_size), self.dtype)

    @nn.nowrap
    def is_latent(self, i: int) -> bool:
        return (i + 1) % self.group == 0

    @nn.nowrap
    def kinds(self) -> tuple:
        """``(latent layers, KDA layers)``."""
        latent = sum(map(self.is_latent, range(self.depth)))
        return latent, self.depth - latent

    @nn.nowrap
    def cache_spec(self):
        """What a slot holds (``kv_pool.SlotSpec``): the latent layers' row a
        position, whose value is its own first lanes — one tensor —, and of
        the ``j``-th KDA layer its state ``kda<j>`` (float32; stepped at the
        live slots only) and its convolution's last inputs ``conv<j>``
        (stepped at every slot)."""
        latent, linear = self.kinds()
        state = []
        for j in range(linear):
            state += [(f"kda{j}", (self.heads, self.head_dim, self.head_dim),
                       jnp.float32),
                      (f"conv{j}", (self.conv - 1,
                                    3 * self.heads * self.head_dim),
                       self.dtype)]
        return kv_pool.SlotSpec(
            (kv_pool.Rows("latent", latent,
                          padded(self.kv_rank + self.rope_dim), self.dtype,
                          kind="latent"),),
            tuple(state), tuple(f"kda{j}" for j in range(linear)))

    def _logits(self, h):
        with jax.named_scope("head"):
            return _dot("...d,dv->...v",
                        rms_norm(h, self.norm_f, self.rms_eps), self.lm_head)

    def _prefill(self, tokens, length):
        """One prompt: ``tokens (1, P)``."""
        with jax.named_scope("embedding"):
            h = self.embed[tokens]
        mask = jnp.arange(tokens.shape[1])[None, :] < length[:, None]
        rows, state, passes = [], {}, []
        for layer in self.layers:
            h, cache, taken = layer.prefill(h, mask, length)
            if taken is not None:
                passes.append(taken)
            if layer.latent:
                rows.append(cache)
            else:
                j = len(state) // 2
                state[f"kda{j}"], state[f"conv{j}"] = cache
        return (h, jnp.stack(rows)[:, None], state,
                expert_layer.pass_report(passes))

    def _step(self, tokens, latent, state, position, bound):
        with jax.named_scope("embedding"):
            h = self.embed[tokens]
        bound = latent.shape[2] if bound is None else bound
        rows, picks, gates, new_state = [], [], [], {}
        for layer in self.layers:
            if layer.latent:
                h, row, e, _ = layer.step(h, (latent, len(rows)), position,
                                          bound)
                rows.append(row)
            else:
                j = len(new_state) // 2
                h, cache, e, g = layer.step(
                    h, (state[f"kda{j}"], state[f"conv{j}"]), position, bound)
                new_state[f"kda{j}"], new_state[f"conv{j}"] = cache
                gates.append(g)
            if e is not None:
                picks.append(e)
        (latent,) = kv_pool.write_rows((latent,), (rows,), position)
        with jax.named_scope("kda_gate"):
            # what a slot's states keep of themselves this step
            retention = jnp.exp(jnp.stack(gates)).mean(axis=(0, 2, 3))
        return h, latent, new_state, jnp.stack(picks), retention

    def prefill(self, tokens, length):
        h, block, state, passes = self._prefill(tokens, length)
        last = jnp.take_along_axis(
            h, (length - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        ids = jnp.argmax(self._logits(last), axis=-1).astype(jnp.int32)
        return jnp.concatenate([ids, passes]), block, state

    def decode_step(self, tokens, latent, state, position, bound=None):
        """One token for every slot of the pool. The latent layers read the
        cached positions ``< bound``; the KDA layers advance the state of the
        slots at a position > 0."""
        h, latent, state, picks, retention = self._step(
            tokens, latent, state, position, bound)
        ids = jnp.argmax(self._logits(h), axis=-1).astype(jnp.int32)
        return (jnp.concatenate([
            ids, picks.astype(jnp.int32).reshape(-1),
            jax.lax.bitcast_convert_type(retention, jnp.int32)]),
            latent, state)

    # Logits, for tests only: the serving programs ship ids.

    def prefill_logits(self, tokens, length):
        h, block, state, _ = self._prefill(tokens, length)
        return self._logits(h), block, state

    def decode_logits(self, tokens, latent, state, position, bound=None):
        h, latent, state, _, _ = self._step(tokens, latent, state, position,
                                            bound)
        return self._logits(h), latent, state

    # What ``step_report`` returns: the routing series of the sparse-expert
    # families under the same names, and this family's own two.
    step_report_series = {
        **expert_layer.step_report_series,
        "kda_retention": (
            "The mean of e^g over a decode step's live slots, KDA layers, "
            "heads and key channels: the share of its recurrent state a step "
            "keeps (g is bounded to (gate_bound, 0): near 1 where the gates "
            "sit as a trained model's do, e^gate_bound where they saturate)",
            (0.01, 0.1, 0.5, 0.8, 0.9, 0.95, 0.98, 0.99, 0.995, 0.999,
             float("inf"))),
        "route_groups_held": (
            "Groups of experts held here that a live token's picks land in, "
            "a MoE layer a decode step (mean over the step's live tokens and "
            "layers; a token's picks lie in at most route_groups[1] groups): "
            "the spread behind held_picks_share under a group-limited router",
            (0.0, 0.25, 0.5, 0.75, 1.0, 1.25, 1.5, 1.75, 2.0, 3.0, 4.0,
             float("inf")))}
    # What ``prefill`` appends to its first id (``experts.pass_report``).
    prefill_report_kinds = expert_layer.prefill_report_kinds

    @nn.nowrap
    def step_report(self, extra: np.ndarray, active) -> dict[str, float]:
        """What ``decode_step`` appended to its ids, over the LIVE slots:
        ``experts.load_report`` of their picks over the experts HELD here,
        the groups held here their picks land in, and the mean of their
        retentions."""
        live = np.flatnonzero(active)
        if not live.size:
            return {}
        slots = len(active)
        picks = extra[:-slots].reshape(self.depth - self.dense_layers, slots,
                                       self.experts_per_token)[:, live]
        retention = np.ascontiguousarray(extra[-slots:], np.int32).view(
            np.float32)[live]
        groups = self.route_groups[0]
        group = picks // (self.experts // groups)
        held = ((picks >= self.first_expert)
                & (picks < self.first_expert + self.experts_held))
        groups_held = sum(((group == i) & held).any(axis=-1)
                          for i in range(groups))
        return {**expert_layer.load_report(picks, self.experts,
                                           self.experts_held,
                                           self.first_expert),
                "kda_retention": float(retention.mean()),
                "route_groups_held": float(groups_held.mean())}


def create_ling3_lm(rng=None, vocab_size: int = 512, dtype=jnp.bfloat16,
                    expert_swiglu_limits=(), shared_swiglu_limits=(), **dims):
    """Build the LM and its seeded params (``olmoe.seeded``: the same values
    on every backend). ``dims``: the fields of ``Ling3LM``; a key it does not
    know is an error. ``expert_swiglu_limits`` / ``shared_swiglu_limits``:
    the published per-layer clamps inside the experts' SwiGLU, of the held
    layers — every one has to be 0 (off): the configuration does not say
    what form a non-zero clamp takes, and none is guessed here.

    Norm weights are drawn away from 1, so one left out shows. The gains keep
    random weights where a comparison with a float32 reference can tell a
    fault from rounding at the published widths and thousands of positions,
    as the other families' do (``models/xing4.py``, ``models/qwen3_next.py``),
    AND make the parts this family adds do work:

    - ``dt_bias`` around −5.5 and SPREAD by ±2 over a head's channels,
      ``w_a`` at half gain and ``a_log`` near 0: ``g = −5 σ(·)`` runs from
      about −0.1 to −0.003 inside one head, so ``e^g`` from ~0.9 (a channel
      that forgets in ten tokens) to ~0.997 (one that remembers hundreds). A
      decay that were the same for every channel of a head would make a
      scalar gate — the block ``qwen3-next`` has — indistinguishable;
    - ``w_beta`` at unit gain: ``β`` between ~0.25 and ~0.75, away from 0
      (no write) and 1 (a full overwrite);
    - ``w_q`` at one and a half: the latent scores deviate by ~1.5, so
      attention picks positions and is no argmax (``xing4`` has the
      argument); ``w_g`` at one and a half: a head's gate runs from ~0.2 to
      ~0.8 between tokens, far from the ½ at which leaving it out is a
      rescaling;
    - router logits deviate by ~2 and the selection bias by ~0.2, so the
      kept groups differ between tokens and the group limit changes a good
      share of the picks against a plain top-K; the best experts' sigmoid
      scores saturate near 1, so the bias decides most of the order among
      them (left out, it moves half the served ids: ``references/ling3.py``
      ``FAULTS_MEASURED``);
    - ``out_proj`` a half, ``w_o`` one (behind a gate of ½ on average) and
      the FFNs' ``*_down`` small (``w_down`` 0.2, the shared expert's and
      the dense layer's 0.15), so that a mixer adds about a quarter of the
      stream and an FFN about a fifth. The FFN's share is what decides
      whether a float32 reference can judge a bfloat16 system here: a
      sigmoid router's eight picks all score near 1, so their renormalised
      weights are nearly equal (2.5 / 8 each) and the pick that rounding
      flips at the eighth place — or the group it flips at the fourth —
      swaps a whole expert's output, not a small tail weight. With
      ``w_down`` 0.8 / 0.4 / 0.4 (the first gains tried) an FFN added 0.3–0.6
      of the stream, and rounding the stream and the norms' outputs to
      bfloat16 INSIDE the float32 reference moved its own argmax on 14 % of
      the tokens (worst margin 1.6, 9 % beyond 0.05; the chip's first six
      runs read 16–21 %, 1.6–2.7, 11–17 %); with these, on 5 % (0.56,
      2 %) — the regime of the other families (``PERF.md`` section 6,
      PR 48)."""
    if any(expert_swiglu_limits) or any(shared_swiglu_limits):
        raise ValueError(
            "a non-zero SwiGLU limit: the clamp inside the experts' SwiGLU "
            "is not implemented (its form is not in the configuration)")
    if "route_groups" in dims:
        dims["route_groups"] = tuple(dims["route_groups"])
    model = Ling3LM(vocab_size=vocab_size, dtype=jnp.dtype(dtype), **dims)
    latent, linear = model.kinds()
    if not latent or not linear:
        raise ValueError(f"depth {model.depth} under group {model.group} "
                         "holds no whole period: a latent and a KDA layer")
    if model.rope_dim % 2:
        raise ValueError(f"a rotated width of {model.rope_dim}")
    if not 0 <= model.dense_layers < model.depth:
        raise ValueError("dense_layers leading dense FFNs of depth layers")
    groups, keep = model.route_groups
    if (model.experts % groups or not 0 < keep <= groups
            or model.experts // groups < 2
            or not 0 < model.experts_per_token <= keep * (
                model.experts // groups)):
        raise ValueError(f"route_groups {model.route_groups} over "
                         f"{model.experts} experts, "
                         f"{model.experts_per_token} a token")
    if not (0 <= model.first_expert
            and model.first_expert + model.experts_held <= model.experts):
        raise ValueError("experts held must lie within the experts routed")
    if CHUNK % SUB_BLOCK or -model.gate_bound * (SUB_BLOCK - 1) > 80:
        raise ValueError(f"gate_bound {model.gate_bound}: a sub-block of "
                         f"{SUB_BLOCK} tokens would overflow float32")
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    params = jax.jit(partial(model.init, method="prefill"))(
        rng, np.zeros((1, 8), np.int32), np.ones((1,), np.int32))
    return model, params
