"""dots3 — a decoder LM for the decode engine whose every mixer is latent
attention: on the ``full`` layers with a learned selection of the positions
it reads, on the ``sliding`` ones over a window with a latent rank of its
own; a head-wise gate on each, and a sigmoid-routed expert layer with an
ungated shared expert.

The block of dots-studio/dots3-note-prev (``model_type: dots3_note``), written
from its configuration's equations. ``n(x) = w ⊙ x · rsqrt(mean(x²) + eps)``
in float32, no biases. A layer: ``h = x + Mixer(n_in(x))``, ``y = h +
FFN(n_post(h))``; layer ``i`` is full iff ``layer_types[i]`` says so; the
first ``dense_layers`` FFNs are a dense SwiGLU, the others the expert layer.

- **Latent attention** (``H`` heads, ranks ``r_q`` / ``r_kv``, head widths
  ``nope`` / ``rope`` / ``v``, each kind of layer its own): ``c_q = ρ_q ·
  n_q(x W_dq)``; ``[q_nope | q_rope]_h = c_q W_uq``, ``q_rope`` rotated;
  ``[c_kv | k_r] = x W_dkv``, ``c_kv ← ρ_kv · n_kv(c_kv)``, ``k_r`` rotated
  and shared by every head; ``k_nope,h = c_kv W_uk,h``, ``v_h = c_kv W_uv,h``;
  scores ``(q_nope · k_nope + q_rope · k_r) / √(nope + rope)``; a causal
  softmax over the layer's allowed set; ``o_h ← sigmoid(x W_g)_h · o_h``;
  ``W_o``. ``ρ = √(dim / rank)``. What a position caches is ``[c_kv | k_r]``
  after norm, rescale and rotation — ONE row every head shares, whose first
  ``r_kv`` lanes are its value too.
- **Sliding layers** allow ``{s : t − window < s ≤ t}``.
- **Full layers** allow the ``index_topk`` positions of largest index score
  (all of them while ``t < index_topk``; a tie to the lower position):
  ``q^I_j = c_q W^I_q`` (``index_heads`` heads of ``index_dim``), ``k^I =
  LayerNorm(x W^I_k)`` (one head, cached beside the latent row), the first
  ``rope`` lanes of both rotated; ``w = x W^I_w / √(index_heads ·
  index_dim)``; ``I_{t,s} = Σ_j w_{t,j} · relu(q^I_{t,j} · k^I_s)`` in float32.
- **Experts** (``models/experts.py``): ``sigmoid`` scores over all
  ``experts``, the ``experts_per_token`` largest of score + bias, weights the
  scores renormalised and scaled, the part the ``experts_held`` experts from
  ``first_expert`` give; plus an ungated shared expert.
- Final norm, untied head, greedy argmax on the device. The vision and audio
  towers and the multi-token-prediction module are not here.

What a slot holds (``cache_spec``): ``latent`` — the full layers' rows, padded
to whole lane tiles (576 → 640 lanes as published) —, ``index`` — their
indexer keys —, and ``window``: the sliding layers' rows as a ring of
``window − 1`` (a step joins the new token's own term itself, so the ring
never holds more than the window's other positions), position ``p`` at row
``p % (window − 1)``. ``decode_step`` is the absorbed form: ``q̃_h = q_nope,h
W_uk,hᵀ`` against the cached row, the output taken back through ``W_uv,h``
(``kv_pool.latent_decode_attention``: one kernel, all heads on one row); the
selection reaches the kernel as a mask over the positions it fetches.
``prefill`` is the published form, a group of heads at a time
(``kv_pool.prompt_attention``: one kernel, no score outside the chip's fast
memory at any length), banded for the sliding layers; for the full ones the
index scores and the exact top-k a block of queries, kept as one byte a pair. The
experts' product is ``routed`` in a prefill and ``dense`` over the held
experts in a step.

Weights and the cache are ``dtype`` (bfloat16 as served), accumulation, index
scores and the selection float32.
"""

from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops import kv_pool
from . import experts as expert_layer
from .olmoe import norm_scale, rms_norm, rope, seeded

LANES = 128
LN_EPS = 1e-6   # the indexer key's LayerNorm
# Heads whose un-absorbed queries, keys and values a prefill holds at once
# (a prompt of 12,288: 0.15 GB each of q and k, 0.1 each of v and the output;
# all 128 heads at once were 2 GB).
HEAD_GROUP = 32

# The seeded init's gains (``create_dots3_lm`` says why these).
INIT_GAINS = {"w_uq": 0.25, "w_o": 1.2, "w_down": 0.8, "shared_down": 0.3,
              "mlp_down": 0.4, "router": 2.0, "router_bias": 0.2,
              "index": 2.0}

# The ``jax.named_scope``s of this family's programs, for a trace's reader.
TRACE_SCOPES = ("embedding", "latent_q", "latent_kv", "indexer", "select",
                "attention", "attn_gate", "out_proj", "router", "experts",
                "shared_expert", "mlp", "cache_update", "cache_insert",
                "head")


def padded(width: int) -> int:
    """``width`` on whole lane tiles."""
    return -(-width // LANES) * LANES


def _dot(eq, a, b):
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def _lane_pad(x, width: int):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def layer_norm(x, w, b):
    h = x.astype(jnp.float32)
    h = h - h.mean(axis=-1, keepdims=True)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + LN_EPS)
    return (h * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def index_scores(iq, ik, w):
    """``I (..., Q, K) = Σ_j w_j · relu(q^I_j · k^I)`` in float32: ``iq (...,
    Q, J, d)``, ``ik (..., K, d)``, ``w (..., Q, J)`` float32."""
    with jax.named_scope("indexer"):
        s = _dot("...qjd,...kd->...qjk", iq, ik)
        return (jax.nn.relu(s) * w[..., None]).sum(axis=-2)


class _Layer(nn.Module):
    """One block: latent attention (``full``: with the indexer's selection;
    else over the window) and its FFN (``dense``: a SwiGLU; else experts)."""

    full: bool
    dense: bool
    dim: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope_dim: int
    v_dim: int
    theta: float
    window: int
    index_heads: int
    index_dim: int
    index_topk: int
    mlp_dim: int
    experts: int
    experts_held: int
    first_expert: int
    experts_per_token: int
    expert_dim: int
    shared_dim: int
    route_scale: float
    eps: float
    dtype: jnp.dtype

    def setup(self):
        d, g, h = self.dim, INIT_GAINS, self.heads

        def p(name, init, *shape, dtype=None):
            return self.param(name, init, shape, dtype or self.dtype)

        self.norm_in = p("norm_in", norm_scale(1.0), d)
        self.norm_post = p("norm_post", norm_scale(1.0), d)
        self.w_dq = p("w_dq", seeded(1.0), d, self.q_rank)
        self.norm_q = p("norm_q", norm_scale(1.0), self.q_rank)
        self.w_uq = p("w_uq", seeded(g["w_uq"]), self.q_rank,
                      h * (self.nope + self.rope_dim))
        self.w_dkv = p("w_dkv", seeded(1.0), d, self.kv_rank + self.rope_dim)
        self.norm_kv = p("norm_kv", norm_scale(1.0), self.kv_rank)
        self.w_uk = p("w_uk", seeded(1.0, fan_in_axis=0), self.kv_rank, h,
                      self.nope)
        self.w_uv = p("w_uv", seeded(1.0, fan_in_axis=0), self.kv_rank, h,
                      self.v_dim)
        self.w_g = p("w_g", seeded(1.0), d, h)
        self.w_o = p("w_o", seeded(g["w_o"]), h * self.v_dim, d)
        if self.full:
            self.wi_q = p("wi_q", seeded(g["index"]), self.q_rank,
                          self.index_heads * self.index_dim)
            self.wi_k = p("wi_k", seeded(1.0), d, self.index_dim)
            self.wi_norm = p("wi_norm", norm_scale(1.0), self.index_dim)
            self.wi_bias = p("wi_bias", norm_scale(0.0), self.index_dim)
            self.wi_w = p("wi_w", seeded(1.0), d, self.index_heads)
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

    # -- sizes -------------------------------------------------------------

    @property
    def row(self) -> int:
        """Lanes of the cached row, padded to whole tiles."""
        return padded(self.kv_rank + self.rope_dim)

    @property
    def scale(self) -> float:
        return float((self.nope + self.rope_dim) ** -0.5)

    # -- the FFN -----------------------------------------------------------

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
            bias=self.router_bias, scale=self.route_scale)
        weights = (self.w_gate, self.w_up, self.w_down)
        if routed:
            y = expert_layer.routed(h, top_e, top_p, *weights,
                                    total=self.experts,
                                    first_held=self.first_expert)
        else:
            gate = expert_layer.gate_matrix(top_e, top_p, self.experts_held,
                                            self.first_expert)
            y = expert_layer.dense(h, gate, *weights)
        y = y + expert_layer.shared(h, None, self.s_gate, self.s_up,
                                    self.s_down)
        return x + y, top_e

    # -- latent attention --------------------------------------------------

    def _down(self, x, position):
        """``x (..., D)`` after ``n_in`` at ``position (...)`` → the query's
        latent ``c_q (..., r_q)`` and the row a position caches, ``[c_kv |
        k_r]`` ``(..., r_kv + rope)``: normed, rescaled, ``k_r`` rotated."""
        with jax.named_scope("latent_q"):
            c_q = rms_norm(_dot("...d,dr->...r", x, self.w_dq).astype(
                self.dtype), self.norm_q, self.eps)
            c_q = (c_q.astype(jnp.float32)
                   * np.sqrt(self.dim / self.q_rank)).astype(self.dtype)
        with jax.named_scope("latent_kv"):
            kv = _dot("...d,dr->...r", x, self.w_dkv).astype(self.dtype)
            c_kv = rms_norm(kv[..., :self.kv_rank], self.norm_kv, self.eps)
            c_kv = (c_kv.astype(jnp.float32)
                    * np.sqrt(self.dim / self.kv_rank)).astype(self.dtype)
            k_r = rope(kv[..., None, self.kv_rank:], position,
                       self.theta)[..., 0, :]
            return c_q, jnp.concatenate([c_kv, k_r], axis=-1)

    def _queries(self, c_q, position, heads: slice = slice(None)):
        """The queries of ``heads``: ``q_nope (..., h, nope)`` and ``q_rope
        (..., h, rope)``, rotated."""
        with jax.named_scope("latent_q"):
            w_uq = self.w_uq.reshape(self.q_rank, self.heads, -1)[:, heads]
            q = _dot("...r,rhe->...he", c_q, w_uq).astype(self.dtype)
            return (q[..., :self.nope],
                    rope(q[..., self.nope:], position, self.theta))

    def _index(self, x, c_q, position):
        """The indexer's queries ``(..., J, d)``, key ``(..., d)`` and head
        weights ``(..., J)`` (float32) of the tokens ``x``."""
        with jax.named_scope("indexer"):
            lead = x.shape[:-1]
            iq = _dot("...r,re->...e", c_q, self.wi_q).astype(
                self.dtype).reshape(*lead, self.index_heads, self.index_dim)
            ik = layer_norm(_dot("...d,de->...e", x, self.wi_k).astype(
                self.dtype), self.wi_norm, self.wi_bias)
            split = self.rope_dim

            def rotated(a):
                return jnp.concatenate(
                    [rope(a[..., :split], position, self.theta),
                     a[..., split:]], axis=-1)

            iq = rotated(iq)
            ik = rotated(ik[..., None, :])[..., 0, :]
            w = _dot("...d,dj->...j", x, self.wi_w) * float(
                (self.index_heads * self.index_dim) ** -0.5)
            return iq, ik, w

    def _out(self, x, h, o):
        """``o (..., H, v)`` gated a head and projected onto ``x``."""
        with jax.named_scope("attn_gate"):
            gate = jax.nn.sigmoid(_dot("...d,dh->...h", h, self.w_g))
            o = (o.astype(jnp.float32) * gate[..., None]).astype(self.dtype)
        with jax.named_scope("out_proj"):
            return x + _dot("...e,ed->...d", o.reshape(*o.shape[:-2], -1),
                            self.w_o).astype(self.dtype)

    def prefill(self, x, length):
        """``x (P, D)``, one prompt of ``length`` tokens padded to its
        bucket → the block's output ``(P, D)``, what it caches — a full
        layer its latent rows ``(P, row)`` and index keys ``(P, d)``, a
        sliding one its ring ``(window − 1, row)`` — and the passes its
        expert product took (``experts.window_passes``; None from a dense
        layer)."""
        p = x.shape[0]
        position = jnp.arange(p)
        h = rms_norm(x, self.norm_in, self.eps)
        c_q, row = self._down(h, position)
        c_kv, k_r = row[:, :self.kv_rank], row[:, self.kv_rank:]
        row = _lane_pad(row, self.row)
        if self.full:
            window = None
            iq, ik, w = self._index(h, c_q, position)
            iq = jnp.swapaxes(iq, 0, 1)         # (J, P, d): a head a matrix

            def select(at, q_pos, k_pos):
                scores = kv_pool.prompt_index_scores(
                    jax.lax.dynamic_slice_in_dim(iq, at, q_pos.shape[0], 1),
                    ik, jax.lax.dynamic_slice_in_dim(w, at, q_pos.shape[0]),
                    at)
                with jax.named_scope("select"):
                    return kv_pool.select_top(
                        scores,
                        (k_pos[None, :] <= q_pos[:, None]).astype(jnp.int8),
                        self.index_topk)

            # once for every head, a byte a pair: 0.15 GB at 12,288
            allowed = kv_pool.query_blocks(select, p)
            cache = (row, ik)
        else:
            window, allowed, back = self.window, None, self.window - 1
            # the ring as a step finds it: the prompt's last ``back`` rows,
            # turned so that position s lies at row s % back — two slices,
            # no gather
            rows = jnp.pad(row, ((0, max(back - p, 0)), (0, 0)))
            start = jnp.clip(length - back, 0, rows.shape[0] - back)
            last = jax.lax.dynamic_slice_in_dim(rows, start, back)
            cache = (jax.lax.dynamic_slice_in_dim(
                jnp.concatenate([last, last]), back - start % back, back),)
        out = []
        for a in range(0, self.heads, HEAD_GROUP):   # what memory needs
            heads = slice(a, a + HEAD_GROUP)
            with jax.named_scope("latent_kv"):
                k_nope = _dot("pr,rhn->phn", c_kv,
                              self.w_uk[:, heads]).astype(self.dtype)
                v = _dot("pr,rhv->phv", c_kv,
                         self.w_uv[:, heads]).astype(self.dtype)
                k = jnp.concatenate([k_nope, jnp.broadcast_to(
                    k_r[:, None], (*k_nope.shape[:2], self.rope_dim))],
                    axis=-1)
            q = jnp.concatenate(self._queries(c_q, position, heads), axis=-1)
            out.append(kv_pool.prompt_attention(
                q, k, v, self.scale, mask=allowed, window=window))
        o = jnp.concatenate(out, axis=1)
        x = self._out(x, h, o)
        x, top_e = self._ffn(x, routed=True)
        return x, cache, None if top_e is None else expert_layer.window_passes(
            top_e, self.experts_held, self.experts, self.first_expert)

    def step(self, x, pools, layer: int, position, bound: int):
        """One token a slot: ``x (S, D)`` at ``position (S,)``; ``pools`` —
        a full layer's (latent, index), a sliding one's (window,) — read as
        they came in; ``layer``: this layer's index in them. Returns the
        block's output, the rows to write (as ``pools``) and the chosen
        experts."""
        h = rms_norm(x, self.norm_in, self.eps)
        c_q, row = self._down(h, position)
        q_nope, q_rope = self._queries(c_q, position)
        with jax.named_scope("latent_q"):
            q = jnp.concatenate(
                [_dot("shn,rhn->shr", q_nope, self.w_uk).astype(self.dtype),
                 q_rope], axis=-1)
        q, row = _lane_pad(q, self.row), _lane_pad(row, self.row)
        if self.full:
            latent, index = pools
            bound = min(bound, latent.shape[2])
            iq, ik, w = self._index(h, c_q, position)
            scores = jnp.concatenate(
                [index_scores(iq[:, None], index[layer, :, :bound],
                              w[:, None])[:, 0],
                 index_scores(iq[:, None], ik[:, None], w[:, None])[:, 0]],
                axis=-1)                          # the new token's own: last
            with jax.named_scope("select"):
                valid = jnp.arange(bound + 1)[None, :] < position[:, None]
                keep = kv_pool.select_top(
                    scores, valid.at[:, bound].set(True), self.index_topk)
            o = kv_pool.latent_decode_attention(
                q, row, latent, layer, position, value=self.kv_rank,
                bound=bound, scale=self.scale, keep=keep[:, :bound],
                own=keep[:, bound])
            new = (row, ik)
        else:
            (ring,) = pools
            o = kv_pool.latent_decode_attention(
                q, row, ring, layer, jnp.minimum(position, ring.shape[2]),
                value=self.kv_rank, bound=ring.shape[2], scale=self.scale)
            new = (row,)
        with jax.named_scope("latent_kv"):
            o = _dot("shr,rhv->shv", o, self.w_uv).astype(self.dtype)
        x = self._out(x, h, o)
        x, top_e = self._ffn(x, routed=False)
        return x, new, top_e


class Dots3LM(nn.Module):
    """Causal LM over the block stack, with the serving entry points of an
    LM family (``runtime/kvcache.py`` ``LMServable``). ``decode_step`` returns
    its ids followed by every expert layer's chosen experts, in one int32
    vector (``step_report``)."""

    vocab_size: int
    dim: int = 64
    layer_types: tuple = ("full", "full", "sliding", "sliding")
    dense_layers: int = 1
    heads: int = 4
    q_rank: int = 32
    kv_rank: int = 16
    nope: int = 16
    rope_dim: int = 8
    v_dim: int = 16
    rope_theta: float = 8e7
    swa_heads: int = 2
    swa_q_rank: int = 32
    swa_kv_rank: int = 32
    swa_nope: int = 24
    swa_rope_dim: int = 8
    swa_v_dim: int = 16
    swa_rope_theta: float = 5e4
    window: int = 5
    index_heads: int = 4
    index_dim: int = 16
    index_topk: int = 8
    mlp_dim: int = 96
    experts: int = 16
    experts_held: int = 16
    first_expert: int = 0
    experts_per_token: int = 2
    expert_dim: int = 32
    shared_dim: int = 32
    route_scale: float = 1.0
    rms_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16

    def setup(self):
        self.embed = self.param("embed", seeded(1.0, fan_in_axis=None),
                                (self.vocab_size, self.dim), self.dtype)
        shared = {field: getattr(self, field) for field in (
            "dim", "window", "index_heads", "index_dim", "index_topk",
            "mlp_dim", "experts", "experts_held", "first_expert",
            "experts_per_token", "expert_dim", "shared_dim", "route_scale",
            "dtype")}
        kinds = {
            True: dict(heads=self.heads, q_rank=self.q_rank,
                       kv_rank=self.kv_rank, nope=self.nope,
                       rope_dim=self.rope_dim, v_dim=self.v_dim,
                       theta=self.rope_theta),
            False: dict(heads=self.swa_heads, q_rank=self.swa_q_rank,
                        kv_rank=self.swa_kv_rank, nope=self.swa_nope,
                        rope_dim=self.swa_rope_dim, v_dim=self.swa_v_dim,
                        theta=self.swa_rope_theta)}
        self.layers = [
            _Layer(full=self.is_full(i), dense=i < self.dense_layers,
                   eps=self.rms_eps, name=f"layer{i}", **shared,
                   **kinds[self.is_full(i)])
            for i in range(len(self.layer_types))]
        self.norm_f = self.param("norm_f", norm_scale(1.0), (self.dim,),
                                 self.dtype)
        self.lm_head = self.param("lm_head", seeded(1.0),
                                  (self.dim, self.vocab_size), self.dtype)

    @nn.nowrap
    def is_full(self, i: int) -> bool:
        return self.layer_types[i] == "full"

    @nn.nowrap
    def cache_spec(self):
        """What a slot holds (``kv_pool.SlotSpec``): of the full layers the
        latent row a position (its value is its own first lanes: no second
        tensor; the selection keeps ``index_topk`` of them) and the indexer's
        key beside it (scored whole, in ``jax.numpy``); of the sliding layers
        the latent rows of the window's other positions, a ring."""
        full = sum(map(self.is_full, range(len(self.layer_types))))
        return kv_pool.SlotSpec((
            kv_pool.Rows("latent", full, padded(self.kv_rank + self.rope_dim),
                         self.dtype, kind="latent", select=self.index_topk),
            kv_pool.Rows("index", full, self.index_dim, self.dtype,
                         kind="index", whole=True),
            kv_pool.Rows("window", len(self.layer_types) - full,
                         padded(self.swa_kv_rank + self.swa_rope_dim),
                         self.dtype, length=self.window - 1, kind="window")))

    def _logits(self, h):
        with jax.named_scope("head"):
            return _dot("...d,dv->...v",
                        rms_norm(h, self.norm_f, self.rms_eps), self.lm_head)

    def _prefill(self, tokens, length):
        """One prompt: ``tokens (1, P)``, ``length (1,)``."""
        with jax.named_scope("embedding"):
            h = self.embed[tokens[0]]
        latent, index, ring, passes = [], [], [], []
        for layer in self.layers:
            h, cache, taken = layer.prefill(h, length[0])
            if layer.full:
                latent.append(cache[0])
                index.append(cache[1])
            else:
                ring.append(cache[0])
            if taken is not None:
                passes.append(taken)
        return (h[None], tuple(jnp.stack(rows)[:, None]
                               for rows in (latent, index, ring)),
                expert_layer.pass_report(passes))

    def _step(self, tokens, latent, index, ring, position, bound):
        with jax.named_scope("embedding"):
            h = self.embed[tokens]
        bound = latent.shape[2] if bound is None else bound
        rows, picks = ([], [], []), []
        for layer in self.layers:
            if layer.full:
                h, (row, key), e = layer.step(
                    h, (latent, index), len(rows[0]), position, bound)
                rows[0].append(row)
                rows[1].append(key)
            else:
                h, (row,), e = layer.step(h, (ring,), len(rows[2]), position,
                                          bound)
                rows[2].append(row)
            if e is not None:
                picks.append(e)
        latent, index = kv_pool.write_rows((latent, index), rows[:2],
                                           position)
        (ring,) = kv_pool.write_rows((ring,), rows[2:],
                                     position % ring.shape[2])
        return h, latent, index, ring, jnp.stack(picks)

    def prefill(self, tokens, length):
        h, blocks, passes = self._prefill(tokens, length)
        last = jnp.take_along_axis(
            h, (length - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        ids = jnp.argmax(self._logits(last), axis=-1).astype(jnp.int32)
        return jnp.concatenate([ids, passes]), *blocks, {}

    def decode_step(self, tokens, latent, index, ring, state, position,
                    bound=None):
        """One token for every slot of the pool: the full layers read the
        cached positions ``< bound`` their selection keeps, the sliding ones
        their ring."""
        h, latent, index, ring, picks = self._step(
            tokens, latent, index, ring, position, bound)
        ids = jnp.argmax(self._logits(h), axis=-1).astype(jnp.int32)
        return (jnp.concatenate([ids, picks.astype(jnp.int32).reshape(-1)]),
                latent, index, ring, state)

    # Logits, for tests only: the serving programs ship ids.

    def prefill_logits(self, tokens, length):
        h, blocks, _ = self._prefill(tokens, length)
        return (self._logits(h), *blocks, {})

    def decode_logits(self, tokens, latent, index, ring, state, position,
                      bound=None):
        h, latent, index, ring, _ = self._step(tokens, latent, index, ring,
                                               position, bound)
        return self._logits(h), latent, index, ring, state

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
        picks = extra.reshape(len(self.layer_types) - self.dense_layers, -1,
                              self.experts_per_token)[:, live]
        return expert_layer.load_report(picks, self.experts,
                                        self.experts_held, self.first_expert)


def create_dots3_lm(rng=None, vocab_size: int = 512, dtype=jnp.bfloat16,
                    **dims):
    """Build the LM and its seeded params (``olmoe.seeded``: the same values
    on every backend). ``dims``: the fields of ``Dots3LM``; a key it does not
    know is an error. Norm weights are drawn away from 1, so one left out
    shows. The gains keep random weights where a comparison with a float32
    reference can tell a fault from rounding at the published widths and
    thousands of positions (measured on the chip, PERF.md section 6, PR 39):
    ``w_uq`` a quarter, because ``rho`` (sqrt 5 on the query's latent, sqrt
    10 on the full layers' key's) makes unit-gain scores deviate by ~6 — a
    softmax that is nearly an argmax, under which bfloat16's rounding and a
    flip at the selection's edge move the logits by 0.13 on average (the
    float32 reference itself by 0.7 between two backends) and no fault can be
    told from them; at a quarter they deviate by ~1.5 and the logits move by
    0.01. ``w_o`` above one, so that the mixers — whose output, a mean over
    hundreds of values, is small — carry enough of the stream for a wrong
    selection to show; the FFNs' ``*_down`` a fraction, as in the other
    families; router logits deviate by ~2 and the selection bias by ~0.2
    around 0, so the bias decides a good share of the picks and never most;
    the indexer's queries deviate by ~2 per unit of ``c_q``, so a head's
    ``relu`` is open on about half the keys and the score spreads over
    positions instead of following recency."""
    dims = dict(dims)
    if "layer_types" in dims:
        dims["layer_types"] = tuple(dims["layer_types"])
    model = Dots3LM(vocab_size=vocab_size, dtype=jnp.dtype(dtype), **dims)
    if set(model.layer_types) - {"full", "sliding"}:
        raise ValueError(f"layer_types {model.layer_types}")
    for width in (model.rope_dim, model.swa_rope_dim):
        if width % 2:
            raise ValueError(f"a rotated width of {width}")
    if model.rope_dim > model.index_dim or model.window < 2:
        raise ValueError("the indexer rotates rope_dim of its index_dim "
                         "lanes; a window holds the token and one more")
    if not (0 < model.experts_per_token <= model.experts and
            0 <= model.first_expert
            and model.first_expert + model.experts_held <= model.experts):
        raise ValueError("experts held must lie within the experts routed")
    if not 0 < model.dense_layers < len(model.layer_types):
        raise ValueError("dense_layers leading dense FFNs of the layers")
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    params = jax.jit(partial(model.init, method="prefill"))(
        rng, np.zeros((1, 8), np.int32), np.ones((1,), np.int32))
    return model, params
