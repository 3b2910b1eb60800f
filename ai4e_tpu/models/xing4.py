"""xing4 — a decoder LM for the decode engine whose residual path is ``n``
streams a token, mixed around every sublayer by manifold-constrained
hyper-connections; dense latent attention under YaRN; a sigmoid-routed expert
layer, every expert held, with an ungated shared expert.

The block of XingChen-AGI/Xing4.0-29B-A4B (``model_type: xing4_0``), written
from its configuration's equations. ``n(x) = w ⊙ x · rsqrt(mean(x²) + eps)``
in float32, no biases.

- **Streams** (``ops/mhc.py``): a token's state is ``X (n, D)``, ``X_0 = (e,
  e, .., e)`` for its embedding ``e``. Each layer is two sublayers — the mixer
  with its input norm, the FFN with its norm —, each with hyper-connection
  parameters of its own: ``u = H_pre X``, ``y = F(u)``, ``X' = H_res X +
  H_postᵀ y`` with ``H_pre = σ(·)``, ``H_post = 2σ(·)`` and ``H_res`` made
  doubly stochastic by ``sinkhorn_iters`` Sinkhorn iterations, a token its
  own, all from the flat-normed streams. After the last layer ``h = Σ_i
  X_i``, the final norm, the untied head, greedy argmax on the device. A
  step holds its slots' streams as ``(S, n, D)``, a prefill its prompt's as
  rows ``(P, n·D)`` from the embedding to the head (``mhc.pre_rows``).
- **Latent attention** (``models/latent.py``, the mixer ``axk1`` shares;
  ``H`` heads, ranks ``r_q`` / ``r_kv``, head widths
  ``nope`` / ``rope`` / ``v``): ``c_q = n_q(u W_dq)``; ``[q_nope | q_rope]_h
  = c_q W_uq``; ``[c_kv | k_r] = u W_dkv``, ``c_kv ← n_kv(c_kv)``; ``q_rope``
  and ``k_r`` rotated, ``k_r`` shared by every head; ``k_nope,h = c_kv
  W_uk,h``, ``v_h = c_kv W_uv,h``; a causal softmax of ``(q_nope · k_nope +
  q_rope · k_r) · s``; ``W_o``. What a position caches is ``[c_kv | k_r]``
  after norm and rotation — ONE row every head shares, whose first ``r_kv``
  lanes are its value too.
- **YaRN** (``olmoe.yarn_inv_freq``): the rotary frequencies blended between
  ``θ^(−2i/rope)`` and that over ``rope_factor``; the rotated lanes times
  ``m(mscale) / m(mscale_all_dim)``; ``s = (nope + rope)^(−1/2) ·
  m(mscale_all_dim)²`` with ``m(a) = 0.1 a ln(rope_factor) + 1``.
- **FFN**: the first ``dense_layers`` a dense SwiGLU; the others
  (``models/experts.py``) ``sigmoid`` scores over ``experts``, the
  ``experts_per_token`` largest of score + bias, weights the scores
  renormalised times ``route_scale``, plus an ungated shared expert. Every
  expert is held here.
- The multi-token-prediction module is not here: the main model's logits do
  not depend on it.

What a slot holds (``cache_spec``): one tensor of latent rows, padded to
whole lane tiles (576 → 640 lanes as published). ``decode_step`` is the
absorbed form (``kv_pool.latent_decode_attention``: one kernel, all heads on
one row, every block under a slot's position, no selection); ``prefill`` the
published form (``kv_pool.prompt_attention``). The experts' product is
``routed`` in a prefill and ``dense`` in a step.

Weights, streams and the cache are ``dtype`` (bfloat16 as served); the
hyper-connections' coefficients, accumulation and routing float32.
"""

from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops import kv_pool, mhc
from . import experts as expert_layer
from .latent import Latent, row_lanes
from .olmoe import norm_scale, rms_norm, seeded

# The seeded init's gains (``create_xing4_lm`` says why these).
INIT_GAINS = {"w_uq": 0.75, "w_o": 1.0, "w_down": 0.4, "shared_down": 0.3,
              "mlp_down": 0.4, "router": 2.0, "router_bias": 0.2,
              "hc_phi": 0.5, "hc_bias": 0.5, "hc_diagonal": 2.0}

# The ``jax.named_scope``s of this family's programs, for a trace's reader
# (``sinkhorn``: a step's only — a prompt's iterations run inside the
# ``mhc_pre`` kernel and are booked there).
TRACE_SCOPES = ("embedding", "mhc_pre", "sinkhorn", "mhc_post", "latent_q",
                "latent_kv", "attention", "out_proj", "router", "experts",
                "shared_expert", "mlp", "cache_update", "cache_insert",
                "stream_sum", "head")


def _dot(eq, a, b):
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def _hc_bias(streams: int):
    """A sublayer's ``bias``: ``b_pre``, ``b_post`` around 0 and ``b_res``
    around ``hc_diagonal`` times the identity, each ± ``hc_bias``."""
    g = INIT_GAINS
    center = np.concatenate([np.zeros(2 * streams, np.float32), g[
        "hc_diagonal"] * np.eye(streams, dtype=np.float32).reshape(-1)])

    def init(key, shape, dtype=jnp.float32):
        return seeded(g["hc_bias"], fan_in_axis=None)(key, shape,
                                                      dtype) + center
    return init


def hyper_params(p, name: str, streams: int, dim: int) -> dict:
    """Declare one sublayer's hyper-connection parameters (``ops/mhc.py``)
    through ``p(name, init, *shape, dtype=None)`` — a layer's ``self.param``
    at its dtype — as ``<name>_phi``, ``<name>_alpha``, ``<name>_bias``."""
    n = streams
    return {"phi": p(f"{name}_phi", seeded(INIT_GAINS["hc_phi"]), n * dim,
                     2 * n + n * n),
            "alpha": p(f"{name}_alpha", norm_scale(1.0), 3,
                       dtype=jnp.float32),
            "bias": p(f"{name}_bias", _hc_bias(n), 2 * n + n * n,
                      dtype=jnp.float32)}


class _Layer(nn.Module):
    """One block: latent attention and its FFN (``dense``: a SwiGLU; else
    experts), each between the two halves of its hyper-connection."""

    dense: bool
    dim: int
    streams: int
    sinkhorn_iters: int
    hc_eps: float
    hc_clamp: float
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope_dim: int
    v_dim: int
    theta: float
    rope_factor: float
    rope_original: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    mlp_dim: int
    experts: int
    experts_per_token: int
    expert_dim: int
    shared_dim: int
    route_scale: float
    eps: float
    dtype: jnp.dtype

    def setup(self):
        d, g, n = self.dim, INIT_GAINS, self.streams

        def p(name, init, *shape, dtype=None):
            return self.param(name, init, shape, dtype or self.dtype)

        self.hc_attn = hyper_params(p, "hc_attn", n, d)
        self.hc_ffn = hyper_params(p, "hc_ffn", n, d)
        self.norm_in = p("norm_in", norm_scale(1.0), d)
        self.norm_post = p("norm_post", norm_scale(1.0), d)
        self.mixer = Latent.of(self)
        self.latent = self.mixer.declare(p, g)
        if self.dense:
            f = self.mlp_dim
            self.m_gate = p("m_gate", seeded(1.0), d, f)
            self.m_up = p("m_up", seeded(1.0), d, f)
            self.m_down = p("m_down", seeded(g["mlp_down"]), f, d)
        else:
            e, f, s = self.experts, self.expert_dim, self.shared_dim
            self.router = p("router", seeded(g["router"]), d, e)
            self.router_bias = p("router_bias", seeded(
                g["router_bias"], fan_in_axis=None), e, dtype=jnp.float32)
            self.w_gate = p("w_gate", seeded(1.0), e, d, f)
            self.w_up = p("w_up", seeded(1.0), e, d, f)
            self.w_down = p("w_down", seeded(g["w_down"]), e, f, d)
            self.s_gate = p("s_gate", seeded(1.0), d, s)
            self.s_up = p("s_up", seeded(1.0), d, s)
            self.s_down = p("s_down", seeded(g["shared_down"]), s, d)

    @property
    def scale(self) -> float:
        return self.mixer.scale

    def _hyper(self, x, params, pre=mhc.pre):
        return pre(x, params, iters=self.sinkhorn_iters, eps=self.hc_eps,
                   clamp=self.hc_clamp, norm_eps=self.eps)

    # -- the FFN ---------------------------------------------------------------

    def _ffn(self, u, routed: bool):
        """``u (rows, D)`` → ``FFN(n_post(u))`` and, from an expert layer,
        the rows' chosen experts ``(rows, K)`` (else None)."""
        h = rms_norm(u, self.norm_post, self.eps)
        if self.dense:
            with jax.named_scope("mlp"):
                a = (jax.nn.silu(_dot("...d,df->...f", h, self.m_gate))
                     * _dot("...d,df->...f", h, self.m_up)).astype(self.dtype)
                return _dot("...f,fd->...d", a, self.m_down).astype(
                    self.dtype), None
        top_e, top_p = expert_layer.route(
            h, self.router, self.experts_per_token, True, scoring="sigmoid",
            bias=self.router_bias, scale=self.route_scale)
        weights = (self.w_gate, self.w_up, self.w_down)
        if routed:
            y = expert_layer.routed(h, top_e, top_p, *weights,
                                    total=self.experts)
        else:
            y = expert_layer.dense(h, expert_layer.gate_matrix(
                top_e, top_p, self.experts), *weights)
        return y + expert_layer.shared(h, None, self.s_gate, self.s_up,
                                       self.s_down), top_e

    # -- the block -------------------------------------------------------------

    def prefill(self, x):
        """``x (P, n·D)``, the rows of one prompt padded to its bucket
        (``mhc.pre_rows``) → the block's output, the rows it caches ``(P,
        row)`` and the passes its expert product took
        (``experts.window_passes``; None from a dense layer)."""
        u, coef = self._hyper(x, self.hc_attn, mhc.pre_rows)
        y, row = self.mixer.attend_prompt(
            self.latent, rms_norm(u, self.norm_in, self.eps))
        x = mhc.post_rows(x, y, coef)
        u, coef = self._hyper(x, self.hc_ffn, mhc.pre_rows)
        y, top_e = self._ffn(u, routed=True)
        return (mhc.post_rows(x, y, coef), row,
                None if top_e is None else expert_layer.window_passes(
                    top_e, self.experts, self.experts))

    def step(self, x, pool, layer: int, position, bound: int):
        """One token a slot: ``x (S, n, D)`` at ``position (S,)``; ``pool``
        read as it came in; ``layer``: this layer's index in it. Returns the
        block's output, the rows to write, the chosen experts and how far the
        slot's two ``H_res`` are from doubly stochastic ``(S,)``."""
        u, h_post, h_res = self._hyper(x, self.hc_attn)
        error = mhc.balance_error(h_res)
        y, row = self.mixer.attend_step(
            self.latent, rms_norm(u, self.norm_in, self.eps), pool, layer,
            position, bound)
        x = mhc.post(x, y, h_post, h_res)
        u, h_post, h_res = self._hyper(x, self.hc_ffn)
        error = jnp.maximum(error, mhc.balance_error(h_res))
        y, top_e = self._ffn(u, routed=False)
        return mhc.post(x, y, h_post, h_res), row, top_e, error


class Xing4LM(nn.Module):
    """Causal LM over the block stack, with the serving entry points of an
    LM family (``runtime/kvcache.py`` ``LMServable``). ``decode_step`` returns
    its ids followed by every expert layer's chosen experts and each slot's
    balance error (float32 bits), in one int32 vector (``step_report``)."""

    vocab_size: int
    dim: int = 64
    depth: int = 3
    dense_layers: int = 1
    streams: int = 4
    sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: float = 30.0
    heads: int = 4
    q_rank: int = 32
    kv_rank: int = 16
    nope: int = 16
    rope_dim: int = 8
    v_dim: int = 16
    rope_theta: float = 1e4
    rope_factor: float = 64.0
    rope_original: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0
    mlp_dim: int = 96
    experts: int = 16
    experts_per_token: int = 4
    expert_dim: int = 32
    shared_dim: int = 32
    route_scale: float = 2.0
    rms_eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16

    def setup(self):
        self.embed = self.param("embed", seeded(1.0, fan_in_axis=None),
                                (self.vocab_size, self.dim), self.dtype)
        shared = {field: getattr(self, field) for field in (
            "dim", "streams", "sinkhorn_iters", "hc_eps", "hc_clamp", "heads",
            "q_rank", "kv_rank", "nope", "rope_dim", "v_dim", "rope_factor",
            "rope_original", "beta_fast", "beta_slow", "mscale",
            "mscale_all_dim", "mlp_dim", "experts", "experts_per_token",
            "expert_dim", "shared_dim", "route_scale", "dtype")}
        self.layers = [
            _Layer(dense=i < self.dense_layers, theta=self.rope_theta,
                   eps=self.rms_eps, name=f"layer{i}", **shared)
            for i in range(self.depth)]
        self.norm_f = self.param("norm_f", norm_scale(1.0), (self.dim,),
                                 self.dtype)
        self.lm_head = self.param("lm_head", seeded(1.0),
                                  (self.dim, self.vocab_size), self.dtype)

    @nn.nowrap
    def cache_spec(self):
        """What a slot holds (``kv_pool.SlotSpec``): every layer's latent row
        a position, whose value is its own first lanes — one tensor."""
        return kv_pool.SlotSpec((kv_pool.Rows(
            "latent", self.depth, row_lanes(self.kv_rank, self.rope_dim),
            self.dtype, kind="latent"),))

    def _streams(self, tokens, rows: bool = False):
        """``X_0``: the embedding on every stream, ``(..., n, D)`` — a
        prompt's as ``rows (P, n·D)``, stream ``i`` the lanes from ``i·D``."""
        with jax.named_scope("embedding"):
            e = self.embed[tokens]
            if rows:
                return jnp.concatenate([e] * self.streams, axis=-1)
            return jnp.broadcast_to(e[..., None, :],
                                    (*e.shape[:-1], self.streams, self.dim))

    def _logits(self, x):
        """``x (..., n, D)`` → logits of the streams' sum."""
        with jax.named_scope("stream_sum"):
            h = x.astype(jnp.float32).sum(axis=-2).astype(self.dtype)
        with jax.named_scope("head"):
            return _dot("...d,dv->...v",
                        rms_norm(h, self.norm_f, self.rms_eps), self.lm_head)

    def _prefill(self, tokens):
        """One prompt: ``tokens (1, P)``; its streams come back as rows
        ``(P, n·D)``."""
        x = self._streams(tokens[0], rows=True)
        rows, passes = [], []
        for layer in self.layers:
            x, row, taken = layer.prefill(x)
            rows.append(row)
            if taken is not None:
                passes.append(taken)
        return x, jnp.stack(rows)[:, None], expert_layer.pass_report(passes)

    def _step(self, tokens, latent, position, bound):
        x = self._streams(tokens)
        bound = latent.shape[2] if bound is None else bound
        rows, picks, error = [], [], jnp.zeros(tokens.shape, jnp.float32)
        for i, layer in enumerate(self.layers):
            x, row, e, err = layer.step(x, latent, i, position, bound)
            rows.append(row)
            error = jnp.maximum(error, err)
            if e is not None:
                picks.append(e)
        (latent,) = kv_pool.write_rows((latent,), (rows,), position)
        return x, latent, jnp.stack(picks), error

    def prefill(self, tokens, length):
        x, block, passes = self._prefill(tokens)
        last = jax.lax.dynamic_slice_in_dim(x, length[0] - 1, 1).reshape(
            1, self.streams, self.dim)
        ids = jnp.argmax(self._logits(last), axis=-1).astype(jnp.int32)
        return jnp.concatenate([ids, passes]), block, {}

    def decode_step(self, tokens, latent, state, position, bound=None):
        """One token for every slot of the pool, each reading its cached
        positions ``< bound``."""
        x, latent, picks, error = self._step(tokens, latent, position, bound)
        ids = jnp.argmax(self._logits(x), axis=-1).astype(jnp.int32)
        return (jnp.concatenate([
            ids, picks.astype(jnp.int32).reshape(-1),
            jax.lax.bitcast_convert_type(error, jnp.int32)]), latent, state)

    # Logits, for tests only: the serving programs ship ids.

    def prefill_logits(self, tokens, length):
        x, block, _ = self._prefill(tokens)
        return (self._logits(x.reshape(1, -1, self.streams, self.dim)), block,
                {})

    def decode_logits(self, tokens, latent, state, position, bound=None):
        x, latent, _, _ = self._step(tokens, latent, position, bound)
        return self._logits(x), latent, state

    # What ``step_report`` returns: the routing series of the sparse-expert
    # families under the same names, and the hyper-connections' own.
    step_report_series = {
        **expert_layer.step_report_series,
        "mhc_balance_error": (
            "The largest |row sum - 1| or |column sum - 1| of a live slot's "
            "H_res after its Sinkhorn iterations, over a decode step's "
            "sublayers: whether the normalisation converged at the served "
            "precision",
            (1e-7, 1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 1e-1, float("inf")))}
    # What ``prefill`` appends to its first id (``experts.pass_report``).
    prefill_report_kinds = expert_layer.prefill_report_kinds

    @nn.nowrap
    def step_report(self, extra: np.ndarray, active) -> dict[str, float]:
        """What ``decode_step`` appended to its ids, over the LIVE slots:
        ``experts.load_report`` of their picks and the largest of their
        balance errors."""
        live = np.flatnonzero(active)
        if not live.size:
            return {}
        slots = len(active)
        picks = extra[:-slots].reshape(self.depth - self.dense_layers, slots,
                                       self.experts_per_token)[:, live]
        error = np.ascontiguousarray(extra[-slots:], np.int32).view(
            np.float32)[live]
        return {**expert_layer.load_report(picks, self.experts, self.experts),
                "mhc_balance_error": float(error.max())}


def create_xing4_lm(rng=None, vocab_size: int = 512, dtype=jnp.bfloat16,
                    **dims):
    """Build the LM and its seeded params (``olmoe.seeded``: the same values
    on every backend). ``dims``: the fields of ``Xing4LM``; a key it does not
    know is an error. Norm weights are drawn away from 1, so one left out
    shows. The gains keep random weights where a comparison with a float32
    reference can tell a fault from rounding at the published widths and
    thousands of positions, as the other families' do (``models/dots3.py``,
    ``models/olmoe.py``): ``w_uq`` three quarters, because YaRN's ``m²``
    doubles the scores — unit-gain queries against unit-gain keys over 192
    lanes would deviate by ~2, at three quarters by ~1.5: attention picks
    positions and is no argmax; ``w_o`` one and the FFNs' ``*_down`` a
    fraction (the routed weights sum to ``route_scale`` = 2, so ``w_down``
    is half the others' 0.8), so that a sublayer adds about a fifth of a
    stream; router logits deviate by ~2 and the selection bias by ~0.2, so
    the bias decides a good share of the picks and never most. The
    hyper-connections do work: ``phi`` at half gain and ``alpha`` near 1
    make the dynamic part of every coefficient deviate by ~0.5 between
    tokens and the biases by ~0.5 between streams (``H_pre`` 0.27-0.73,
    ``H_post`` 0.5-1.5), and ``b_res`` is ``hc_diagonal`` = 2 on the
    diagonal: a stream keeps ~0.56 of itself and hands ~0.11 ± 0.05 (token
    to token: ten times bfloat16's rounding) to each other — far from the
    identity, under which a wrong mix would be invisible. After ONE Sinkhorn
    iteration the columns are exact and the rows 17 % off at the median;
    after 20 the median is float32's rounding (1e-6), 99 % of the tokens
    within 1e-5 and the worst of 20,000 at 3e-4 (normal draws of these
    deviations on the CPU; with the dynamic part at unit gain 1 % of the
    tokens were still 4e-3 off after 20: a normalisation that has not
    converged is no doubly stochastic matrix, so the gain is a half)."""
    model = Xing4LM(vocab_size=vocab_size, dtype=jnp.dtype(dtype), **dims)
    if model.rope_dim % 2:
        raise ValueError(f"a rotated width of {model.rope_dim}")
    if not 0 < model.experts_per_token <= model.experts:
        raise ValueError(f"{model.experts_per_token} experts a token of "
                         f"{model.experts}")
    if not 0 < model.dense_layers < model.depth:
        raise ValueError("dense_layers leading dense FFNs of depth layers")
    if model.streams < 1 or model.sinkhorn_iters < 1:
        raise ValueError("at least one stream and one Sinkhorn iteration")
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    params = jax.jit(partial(model.init, method="prefill"))(
        rng, np.zeros((1, 8), np.int32), np.ones((1,), np.int32))
    return model, params
