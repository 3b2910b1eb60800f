"""axk1 — a decoder LM for the decode engine: dense latent attention under
YaRN on every layer, a plain pre-norm residual, one leading dense SwiGLU and
then sigmoid-routed experts of which this process holds a share, with an
ungated shared expert.

The block of skt/A.X-K1 (``model_type: axk1``: DeepSeek-V3's block at 64 heads
and 192 experts), written from its configuration's equations. ``n(x) = w ⊙ x ·
rsqrt(mean(x²) + eps)`` in float32, no biases. Layer ``i``: ``x ← x +
Mixer(n_in(x))``, ``x ← x + FFN_i(n_post(x))``; after the last layer the final
norm, the untied head, greedy argmax on the device.

- **Mixer** (``models/latent.py``, the one ``xing4`` runs inside its
  hyper-connections): ``c_q = n_q(h W_dq)``; ``[q_nope | q_rope]_h = c_q
  W_uq``; ``[c_kv | k_r] = h W_dkv``, ``c_kv ← n_kv(c_kv)``; ``q_rope`` and
  ``k_r`` rotated under YaRN, ``k_r`` shared by every head; a causal softmax
  of ``(q_nope · k_nope + q_rope · k_r) · s`` with ``s = (nope + rope)^(−1/2)
  · m(mscale_all_dim)²``; ``W_o``. A position caches ``[c_kv | k_r]`` after
  norm and rotation. The model serves positions beyond ``rope_original``:
  the blended frequencies and ``m²`` are what make them attend.
- **FFN**: the first ``dense_layers`` a dense SwiGLU; the others
  (``models/experts.py``) ``sigmoid`` scores over all ``experts``, the
  ``experts_per_token`` largest — NO bias on the choice (the published
  ``topk_method: "none"``), inside the ``route_groups[1]`` best of
  ``route_groups[0]`` groups where the field is given (None, the cell's
  reading: no limit) —, their scores renormalised times ``route_scale``, the
  terms of the ``experts_held`` experts from ``first_expert``, plus an
  ungated shared expert.

What a slot holds (``cache_spec``): one tensor of latent rows, padded to
whole lane tiles (576 → 640 lanes as published). ``decode_step`` is the
absorbed form, ``prefill`` the published one; the experts' product is
``routed`` (a window of the held pairs) in a prefill and ``dense`` in a step.

Weights, residual and cache are ``dtype`` (bfloat16 as served); accumulation,
norms and routing float32.
"""

from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops import kv_pool
from . import experts as expert_layer
from .latent import Latent, row_lanes
from .olmoe import norm_scale, rms_norm, seeded

# The seeded init's gains (``create_axk1_lm`` says why these).
INIT_GAINS = {"w_uq": 1.0, "w_o": 2.0, "w_down": 1.0, "shared_down": 0.3,
              "mlp_down": 0.4, "router": 2.0}

# The ``jax.named_scope``s of this family's programs, for a trace's reader.
TRACE_SCOPES = ("embedding", "latent_q", "latent_kv", "attention", "out_proj",
                "router", "experts", "shared_expert", "mlp", "cache_update",
                "cache_insert", "head")


def _dot(eq, a, b):
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


class _Layer(nn.Module):
    """One pre-norm block: latent attention, then its FFN (``dense``: a
    SwiGLU; else experts)."""

    dense: bool
    dim: int
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
    experts_held: int
    first_expert: int
    experts_per_token: int
    route_groups: tuple | None
    expert_dim: int
    shared_dim: int
    route_scale: float
    eps: float
    dtype: jnp.dtype

    def setup(self):
        d, g = self.dim, INIT_GAINS

        def p(name, init, *shape, dtype=None):
            return self.param(name, init, shape, dtype or self.dtype)

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
            e, f, s = self.experts_held, self.expert_dim, self.shared_dim
            self.router = p("router", seeded(g["router"]), d, self.experts)
            self.w_gate = p("w_gate", seeded(1.0), e, d, f)
            self.w_up = p("w_up", seeded(1.0), e, d, f)
            self.w_down = p("w_down", seeded(g["w_down"]), e, f, d)
            self.s_gate = p("s_gate", seeded(1.0), d, s)
            self.s_up = p("s_up", seeded(1.0), d, s)
            self.s_down = p("s_down", seeded(g["shared_down"]), s, d)

    def _ffn(self, x, routed: bool):
        """``x (rows, D)`` → ``x + FFN(n_post(x))`` and, from an expert
        layer, the rows' chosen experts ``(rows, K)`` (else None)."""
        h = rms_norm(x, self.norm_post, self.eps)
        if self.dense:
            with jax.named_scope("mlp"):
                a = (jax.nn.silu(_dot("...d,df->...f", h, self.m_gate))
                     * _dot("...d,df->...f", h, self.m_up)).astype(self.dtype)
                return x + _dot("...f,fd->...d", a, self.m_down).astype(
                    self.dtype), None
        top_e, top_p = expert_layer.route(
            h, self.router, self.experts_per_token, True, scoring="sigmoid",
            scale=self.route_scale, groups=self.route_groups)
        weights = (self.w_gate, self.w_up, self.w_down)
        if routed:
            y = expert_layer.routed(h, top_e, top_p, *weights,
                                    total=self.experts,
                                    first_held=self.first_expert)
        else:
            y = expert_layer.dense(h, expert_layer.gate_matrix(
                top_e, top_p, self.experts_held, self.first_expert), *weights)
        y = y + expert_layer.shared(h, None, self.s_gate, self.s_up,
                                    self.s_down)
        return x + y, top_e

    def prefill(self, x):
        """``x (P, D)``, one prompt padded to its bucket → the block's
        output, the rows it caches ``(P, row)`` and the passes its expert
        product took (``experts.window_passes``; None from a dense layer)."""
        y, row = self.mixer.attend_prompt(
            self.latent, rms_norm(x, self.norm_in, self.eps))
        x, top_e = self._ffn(x + y, routed=True)
        return x, row, None if top_e is None else expert_layer.window_passes(
            top_e, self.experts_held, self.experts, self.first_expert)

    def step(self, x, pool, layer: int, position, bound: int):
        """One token a slot: ``x (S, D)`` at ``position (S,)``; ``pool`` read
        as it came in; ``layer``: this layer's index in it. Returns the
        block's output, the rows to write and the chosen experts."""
        y, row = self.mixer.attend_step(
            self.latent, rms_norm(x, self.norm_in, self.eps), pool, layer,
            position, bound)
        x, top_e = self._ffn(x + y, routed=False)
        return x, row, top_e


class Axk1LM(nn.Module):
    """Causal LM over the block stack, with the serving entry points of an
    LM family (``runtime/kvcache.py`` ``LMServable``). ``decode_step`` returns
    its ids followed by every expert layer's chosen experts, in one int32
    vector (``step_report``)."""

    vocab_size: int
    dim: int = 64
    depth: int = 3
    dense_layers: int = 1
    heads: int = 4
    q_rank: int = 32
    kv_rank: int = 16
    nope: int = 16
    rope_dim: int = 8
    v_dim: int = 16
    rope_theta: float = 1e4
    rope_factor: float = 32.0
    rope_original: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0
    mlp_dim: int = 96
    experts: int = 16
    experts_held: int = 16
    first_expert: int = 0
    experts_per_token: int = 4
    route_groups: tuple | None = None
    expert_dim: int = 32
    shared_dim: int = 32
    route_scale: float = 2.5
    rms_eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16

    def setup(self):
        self.embed = self.param("embed", seeded(1.0, fan_in_axis=None),
                                (self.vocab_size, self.dim), self.dtype)
        shared = {field: getattr(self, field) for field in (
            "dim", "heads", "q_rank", "kv_rank", "nope", "rope_dim", "v_dim",
            "rope_factor", "rope_original", "beta_fast", "beta_slow",
            "mscale", "mscale_all_dim", "mlp_dim", "experts", "experts_held",
            "first_expert", "experts_per_token", "route_groups", "expert_dim",
            "shared_dim", "route_scale", "dtype")}
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

    def _logits(self, x):
        with jax.named_scope("head"):
            return _dot("...d,dv->...v",
                        rms_norm(x, self.norm_f, self.rms_eps), self.lm_head)

    def _prefill(self, tokens):
        """One prompt: ``tokens (1, P)``."""
        with jax.named_scope("embedding"):
            x = self.embed[tokens[0]]
        rows, passes = [], []
        for layer in self.layers:
            x, row, taken = layer.prefill(x)
            rows.append(row)
            if taken is not None:
                passes.append(taken)
        return x, jnp.stack(rows)[:, None], expert_layer.pass_report(passes)

    def _step(self, tokens, latent, position, bound):
        with jax.named_scope("embedding"):
            x = self.embed[tokens]
        bound = latent.shape[2] if bound is None else bound
        rows, picks = [], []
        for i, layer in enumerate(self.layers):
            x, row, e = layer.step(x, latent, i, position, bound)
            rows.append(row)
            if e is not None:
                picks.append(e)
        (latent,) = kv_pool.write_rows((latent,), (rows,), position)
        return x, latent, jnp.stack(picks)

    def prefill(self, tokens, length):
        x, block, passes = self._prefill(tokens)
        last = jax.lax.dynamic_slice_in_dim(x, length[0] - 1, 1)
        ids = jnp.argmax(self._logits(last), axis=-1).astype(jnp.int32)
        return jnp.concatenate([ids, passes]), block, {}

    def decode_step(self, tokens, latent, state, position, bound=None):
        """One token for every slot of the pool, each reading its cached
        positions ``< bound``."""
        x, latent, picks = self._step(tokens, latent, position, bound)
        ids = jnp.argmax(self._logits(x), axis=-1).astype(jnp.int32)
        return (jnp.concatenate([ids, picks.astype(jnp.int32).reshape(-1)]),
                latent, state)

    # Logits, for tests only: the serving programs ship ids.

    def prefill_logits(self, tokens, length):
        x, block, _ = self._prefill(tokens)
        return self._logits(x[None]), block, {}

    def decode_logits(self, tokens, latent, state, position, bound=None):
        x, latent, _ = self._step(tokens, latent, position, bound)
        return self._logits(x), latent, state

    # What ``step_report`` returns: the routing series of the sparse-expert
    # families, under the same names.
    step_report_series = expert_layer.step_report_series
    # What ``prefill`` appends to its first id (``experts.pass_report``).
    prefill_report_kinds = expert_layer.prefill_report_kinds

    @nn.nowrap
    def step_report(self, extra: np.ndarray, active) -> dict[str, float]:
        """What ``decode_step`` appended to its ids, over the LIVE slots and
        the experts HELD here (``experts.load_report``)."""
        live = np.flatnonzero(active)
        if not live.size:
            return {}
        picks = extra.reshape(self.depth - self.dense_layers, -1,
                              self.experts_per_token)[:, live]
        return expert_layer.load_report(picks, self.experts,
                                        self.experts_held, self.first_expert)


def create_axk1_lm(rng=None, vocab_size: int = 512, dtype=jnp.bfloat16,
                   **dims):
    """Build the LM and its seeded params (``olmoe.seeded``: the same values
    on every backend). ``dims``: the fields of ``Axk1LM``; a key it does not
    know is an error. Norm weights are drawn away from 1, so one left out
    shows. The gains keep random weights where a comparison with a float32
    reference can tell a fault from rounding at the published widths and
    over 14 k positions, as the other families' do (``models/xing4.py``,
    ``models/dots3.py``); ``seeded`` rounds a deviation to a power of two, so
    what acts at the published widths is given beside each:

    - ``w_uq`` one (acts as 1.0 at a query rank of 1,536): YaRN's ``m²`` =
      1.81 multiplies the scores, so a head's scores deviate by ~1.8 between
      positions — over ``T`` random rows the softmax puts its weight on
      about ``T e^(−1.8²)``, a twenty-fifth of them (150 of 4 k, 500 of 14
      k): attention picks positions and is neither flat nor an argmax, and
      which rows it picks moves with a wrong frequency or a missing ``m²``
      (without it a third of the rows share the weight);
    - ``w_o`` two (acts as 2.3): a head's output is a mean over those rows,
      ~0.08 of a value's deviation at 4 k and ~0.045 at 14 k, so at unit
      gain the mixers of a long stream would add a twentieth of the residual
      and no wrong attention could show; at two they add a fifth at 4 k and a
      tenth at 14 k (and most of a short stream's residual, where attention
      is a mean over few);
    - ``w_down`` one (acts as 0.87), the shared expert's 0.3 (0.22) and the
      dense layer's 0.4 (0.43): a token meets half a held expert a layer on
      average, each with a weight of ~2.5 / 8, so a held expert's term is
      about what the shared expert adds and the routed part shows where it
      is there at all; the pick that rounding flips at the eighth place
      matters one time in eight (when the expert that came or went is held);
    - router logits deviate by ~2; there is no choice bias (``topk_method:
      "none"``)."""
    dims = dict(dims)
    if dims.get("route_groups") is not None:
        dims["route_groups"] = tuple(dims["route_groups"])
    model = Axk1LM(vocab_size=vocab_size, dtype=jnp.dtype(dtype), **dims)
    if model.rope_dim % 2:
        raise ValueError(f"a rotated width of {model.rope_dim}")
    if not (0 < model.experts_per_token <= model.experts
            and 0 <= model.first_expert
            and model.first_expert + model.experts_held <= model.experts):
        raise ValueError("experts held must lie within the experts routed")
    if model.route_groups is not None:
        n, keep = model.route_groups
        if (model.experts % n or not 0 < keep <= n
                or keep * (model.experts // n) < model.experts_per_token):
            raise ValueError(f"route_groups {model.route_groups} of "
                             f"{model.experts} experts")
    if not 0 < model.dense_layers < model.depth:
        raise ValueError("dense_layers leading dense FFNs of depth layers")
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    params = jax.jit(partial(model.init, method="prefill"))(
        rng, np.zeros((1, 8), np.int32), np.ones((1,), np.int32))
    return model, params
