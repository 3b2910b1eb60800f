"""glm5 — a hybrid decoder LM for the decode engine that carries ``streams``
residual streams a token under manifold-constrained hyper-connections and
mixes, a layer, EITHER by Kimi Delta Attention or by latent attention without
positions over a learned selection whose indexer scores the keys pooled
``index_pool`` at a time; a sigmoid-routed expert layer with an ungated
shared expert, every SwiGLU clamped.

The block of zai-org/GLM-5.3-Flash (``model_type: glm5_next_text``), written
from its configuration's equations. ``n(x) = w ⊙ x · rsqrt(mean(x²) + eps)``
in float32, no biases. It owns only what no other family has; the rest is
imported:

- **Streams** (``ops/mhc.py``, parameters as ``models/xing4.py`` declares
  them): a token's state is ``X (n, D)``, the embedding on every stream;
  every sublayer ``F`` — a mixer or an FFN, each with its own input norm —
  runs as ``u = H_pre X; X ← H_res X + H_postᵀ F(n(u))``; after the last layer
  the final norm of the streams' sum, the untied head, greedy argmax on the
  device. A step holds its slots' streams as ``(S, n, D)``, a prefill its
  prompt's as rows ``(P, n·D)`` from the embedding to the head
  (``mhc.pre_rows``).
- **KDA** (``layer_types[i] == "kda"``): ``models/ling3.py``'s mixer
  (``kda_prompt`` / ``kda_token``: the chunked kernel in a prefill, the live
  slots' states advanced in place in a step) with the decay's and the output
  gate's projections through a rank of ``kda_lora``.
- **Sparse latent attention** (``"sparse"``; ``attn_heads`` heads, ranks
  ``q_rank`` / ``kv_rank``, head widths ``qk_dim`` / ``v_dim``, NO rotary
  part): ``c_q = n_q(u W_dq)``, ``q_h = c_q W_uq,h``; a position caches ``c =
  n_kv(u W_dkv)`` and nothing else; ``k_h = c W_uk,h``, ``v_h = c W_uv,h``;
  a softmax of ``q_h · k_h · qk_dim^(−1/2)`` over the selected positions
  ``S_t``; ``W_o``; no gate. A step absorbs ``W_uk`` into the query and
  ``W_uv`` after the sum, so it reads rows of ``kv_rank`` lanes whose value
  is the whole row (``kv_pool.latent_decode_attention`` with ``value`` the
  row's width: nothing padded).
- **Its indexer** (``index_heads`` heads of ``index_dim``): ``iq_j = c_q
  W_iq``, ``ik = LayerNorm(u W_ik)``, the first ``index_rope`` lanes of both
  rotated on neighbouring lane pairs at ``index_theta``; ``w = u W_w ·
  (index_heads · index_dim)^(−1/2)``. Block ``b`` = positions ``P b .. P b +
  P − 1`` (``P`` = ``index_pool``); its key ``ik̄_b`` the mean of its rotated
  keys (a float32 sum, stored in ``dtype``). ``I_{t,b} = Σ_j w_{t,j}
  relu(iq_{t,j} · ik̄_b)`` for the blocks closed before ``t``'s own, ``b <
  ⌊t/P⌋``. ``S_t`` = ``t``'s own block up to ``t`` (never scored) ∪ the
  positions of the ``index_topk / P − 1`` blocks of largest ``I`` (all of them
  while there are no more; a tie to the lower block).
- **FFN**: ``mlp_types[i] == "dense"`` a SwiGLU of ``mlp_dim``; else
  (``models/experts.py``) sigmoid scores over ``experts``, the
  ``experts_per_token`` largest of score + bias, the weights renormalised
  times ``route_scale``, the terms of the ``experts_held`` experts from
  ``first_expert``, plus an ungated shared expert. Every SwiGLU is
  ``silu(min(g, limit)) · clip(u, −limit, limit)`` (``experts.swiglu``).
- The published multi-token-prediction layer and vision tower are not here.

What a slot holds (``cache_spec``): ``latent`` — the sparse layers' row a
position —, ``index`` — their pooled keys, ONE row every ``index_pool``
positions (``kv_pool.Rows.every``) —, and as fixed-size state a KDA layer's
``kda<j>`` (float32; stepped at the live slots only) and ``conv<j>``, and a
sparse layer's ``isum<j>``: the float32 sum of the open block's rotated keys.
A step adds its key to that sum (from zero where it opens a block) and writes
the sum over ``index_pool`` at row ``⌊t/P⌋``: the block's mean once it closes,
never scored before.

Weights, streams and the cached rows are ``dtype`` (bfloat16 as served); the
KDA state, the running sum, the hyper-connections' coefficients, the index
scores, routing and accumulation float32.
"""

from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops import kv_pool, mhc
from ..ops.pallas.kda_chunk import CHUNK, SUB_BLOCK
from . import experts as expert_layer
from .dots3 import index_scores, layer_norm
from .ling3 import Ling3LM, kda_params, kda_prompt, kda_token
from .olmoe import norm_scale, rms_norm, rope, seeded
from .xing4 import Xing4LM, hyper_params

# Heads whose un-absorbed queries, keys and values a prefill holds at once (a
# prompt of 16,384: 0.13 GB each at 256 lanes; all 64 heads were 2.1 GB).
HEAD_GROUP = 16

# The seeded init's gains (``create_glm5_lm`` says why these).
INIT_GAINS = {"w_uq": 1.5, "w_o": 3.0, "index": 2.0, "w_down": 0.2,
              "shared_down": 0.15, "mlp_down": 0.15, "router": 2.0,
              "router_bias": 0.2}

# The ``jax.named_scope``s of this family's programs, for a trace's reader
# (``sinkhorn``: a step's only — a prompt's iterations run inside the
# ``mhc_pre`` kernel and are booked there).
TRACE_SCOPES = ("embedding", "mhc_pre", "sinkhorn", "mhc_post", "kda_proj",
                "conv", "kda_gate", "kda_chunk", "state_update", "gated_norm",
                "latent_q", "latent_kv", "indexer", "index_pool", "select",
                "attention", "out_proj", "router", "experts", "shared_expert",
                "mlp", "cache_update", "cache_insert", "state_insert",
                "stream_sum", "head")


def _dot(eq, a, b):
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


class _Layer(nn.Module):
    """One block: a mixer (``sparse``: the selected latent attention, else
    KDA) and its FFN (``dense``: a SwiGLU; else experts), each between the
    two halves of its hyper-connection."""

    sparse: bool
    dense: bool
    dim: int
    streams: int
    sinkhorn_iters: int
    hc_eps: float
    hc_clamp: float
    heads: int
    head_dim: int
    conv: int
    gate_bound: float
    kda_lora: int
    attn_heads: int
    q_rank: int
    kv_rank: int
    qk_dim: int
    v_dim: int
    index_heads: int
    index_dim: int
    index_rope: int
    index_theta: float
    index_topk: int
    index_pool: int
    mlp_dim: int
    experts: int
    experts_held: int
    first_expert: int
    experts_per_token: int
    expert_dim: int
    shared_dim: int
    route_scale: float
    swiglu_limit: float
    eps: float
    dtype: jnp.dtype

    def setup(self):
        d, g, h = self.dim, INIT_GAINS, self.attn_heads

        def p(name, init, *shape, dtype=None):
            return self.param(name, init, shape, dtype or self.dtype)

        self.hc_attn = hyper_params(p, "hc_attn", self.streams, d)
        self.hc_ffn = hyper_params(p, "hc_ffn", self.streams, d)
        self.norm_in = p("norm_in", norm_scale(1.0), d)
        self.norm_post = p("norm_post", norm_scale(1.0), d)
        if self.sparse:
            self.w_dq = p("w_dq", seeded(1.0), d, self.q_rank)
            self.norm_q = p("norm_q", norm_scale(1.0), self.q_rank)
            self.w_uq = p("w_uq", seeded(g["w_uq"], fan_in_axis=0),
                          self.q_rank, h, self.qk_dim)
            self.w_dkv = p("w_dkv", seeded(1.0), d, self.kv_rank)
            self.norm_kv = p("norm_kv", norm_scale(1.0), self.kv_rank)
            self.w_uk = p("w_uk", seeded(1.0, fan_in_axis=0), self.kv_rank,
                          h, self.qk_dim)
            self.w_uv = p("w_uv", seeded(1.0, fan_in_axis=0), self.kv_rank,
                          h, self.v_dim)
            self.w_o = p("w_o", seeded(g["w_o"]), h * self.v_dim, d)
            self.wi_q = p("wi_q", seeded(g["index"]), self.q_rank,
                          self.index_heads * self.index_dim)
            self.wi_k = p("wi_k", seeded(1.0), d, self.index_dim)
            self.wi_norm = p("wi_norm", norm_scale(1.0), self.index_dim)
            self.wi_bias = p("wi_bias", norm_scale(0.0), self.index_dim)
            self.wi_w = p("wi_w", seeded(1.0), d, self.index_heads)
        else:
            self.kda = kda_params(p, d, self.heads, self.head_dim, self.conv,
                                  self.kda_lora)
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
    def scale(self) -> float:
        return float(self.qk_dim ** -0.5)

    @property
    def kept_blocks(self) -> int:
        """Blocks the selection chooses: the query's own takes one of the
        ``index_topk / index_pool`` places."""
        return self.index_topk // self.index_pool - 1

    def _hyper(self, x, params, pre=mhc.pre):
        return pre(x, params, iters=self.sinkhorn_iters, eps=self.hc_eps,
                   clamp=self.hc_clamp, norm_eps=self.eps)

    # -- the FFN ---------------------------------------------------------------

    def _ffn(self, u, routed: bool):
        """``u (rows, D)`` → ``FFN(n_post(u))`` and, from an expert layer,
        the rows' chosen experts ``(rows, K)``, ids over all ``experts``
        (else None)."""
        h, limit = rms_norm(u, self.norm_post, self.eps), self.swiglu_limit
        if self.dense:
            with jax.named_scope("mlp"):
                a = expert_layer.swiglu(
                    _dot("...d,df->...f", h, self.m_gate),
                    _dot("...d,df->...f", h, self.m_up), limit).astype(
                        self.dtype)
                return _dot("...f,fd->...d", a, self.m_down).astype(
                    self.dtype), None
        top_e, top_p = expert_layer.route(
            h, self.router, self.experts_per_token, True, scoring="sigmoid",
            bias=self.router_bias, scale=self.route_scale)
        weights = (self.w_gate, self.w_up, self.w_down)
        if routed:
            y = expert_layer.routed(h, top_e, top_p, *weights,
                                    total=self.experts,
                                    first_held=self.first_expert, limit=limit)
        else:
            y = expert_layer.dense(h, expert_layer.gate_matrix(
                top_e, top_p, self.experts_held, self.first_expert), *weights,
                limit=limit)
        return y + expert_layer.shared(h, None, self.s_gate, self.s_up,
                                       self.s_down, limit=limit), top_e

    # -- sparse latent attention -----------------------------------------------

    def _down(self, h):
        """The normed input ``h (..., D)`` → the query's latent ``c_q (...,
        r_q)`` and the row a position caches, ``c (..., r)``: all value."""
        with jax.named_scope("latent_q"):
            c_q = rms_norm(_dot("...d,dr->...r", h, self.w_dq).astype(
                self.dtype), self.norm_q, self.eps)
        with jax.named_scope("latent_kv"):
            return c_q, rms_norm(_dot("...d,dr->...r", h, self.w_dkv).astype(
                self.dtype), self.norm_kv, self.eps)

    def _index(self, h, c_q, position):
        """The indexer's queries ``(..., J, d)``, key ``(..., d)`` — both
        rotated over their first ``index_rope`` lanes — and head weights
        ``(..., J)`` (float32) of the tokens ``h`` at ``position``."""
        with jax.named_scope("indexer"):
            iq = _dot("...r,re->...e", c_q, self.wi_q).astype(
                self.dtype).reshape(*h.shape[:-1], self.index_heads,
                                    self.index_dim)
            ik = layer_norm(_dot("...d,de->...e", h, self.wi_k).astype(
                self.dtype), self.wi_norm, self.wi_bias)
            split = self.index_rope

            def rotated(a):
                return jnp.concatenate(
                    [rope(a[..., :split], position, self.index_theta,
                          interleave=True), a[..., split:]], axis=-1)

            w = _dot("...d,dj->...j", h, self.wi_w) * float(
                (self.index_heads * self.index_dim) ** -0.5)
            return rotated(iq), rotated(ik[..., None, :])[..., 0, :], w

    def _out(self, o):
        with jax.named_scope("out_proj"):
            return _dot("...e,ed->...d", o.reshape(*o.shape[:-2], -1),
                        self.w_o).astype(self.dtype)

    def _sparse_prompt(self, u, length):
        """The mixer over one padded prompt ``u (P, D)`` of ``length`` tokens
        → its output ``(P, D)``, the rows it caches — latent ``(P, r)``,
        pooled keys ``(P / pool, d)`` — and the open block's sum ``(d,)``."""
        p, pool = u.shape[0], self.index_pool
        if p % pool:
            raise ValueError(f"a prompt bucket of {p} positions is no whole "
                             f"number of blocks of {pool}")
        position = jnp.arange(p)
        h = rms_norm(u, self.norm_in, self.eps)
        c_q, row = self._down(h)
        iq, ik, w = self._index(h, c_q, position)
        with jax.named_scope("index_pool"):
            pooled = (ik.astype(jnp.float32).reshape(p // pool, pool, -1).sum(
                axis=1) / pool).astype(self.dtype)
            # what the block ``length`` falls in holds before it
            first = length // pool * pool
            last = jax.lax.dynamic_slice_in_dim(
                jnp.pad(ik, ((0, pool), (0, 0))), first, pool)
            open_sum = jnp.where(
                (first + jnp.arange(pool) < length)[:, None],
                last.astype(jnp.float32), 0.0).sum(axis=0)
        iq = jnp.swapaxes(iq, 0, 1)             # (J, P, d): a head a matrix

        def select(at, q_pos, k_pos):
            scores = kv_pool.prompt_index_scores(
                jax.lax.dynamic_slice_in_dim(iq, at, q_pos.shape[0], 1),
                pooled, jax.lax.dynamic_slice_in_dim(w, at, q_pos.shape[0]),
                at)
            with jax.named_scope("select"):
                own = (q_pos // pool)[:, None]
                chosen = kv_pool.select_top(
                    scores, (jnp.arange(p // pool)[None, :] < own).astype(
                        jnp.int8), self.kept_blocks)
                # by blocks of ``pool`` positions; the query's own always
                return jnp.repeat(chosen, pool, axis=1) | (
                    (k_pos // pool)[None, :] == own).astype(jnp.int8)

        allowed = kv_pool.query_blocks(select, p)    # a byte a pair
        out = []
        for a in range(0, self.attn_heads, HEAD_GROUP):   # what memory needs
            heads = slice(a, a + HEAD_GROUP)
            with jax.named_scope("latent_q"):
                q = _dot("pr,rhe->phe", c_q, self.w_uq[:, heads]).astype(
                    self.dtype)
            with jax.named_scope("latent_kv"):
                k = _dot("pr,rhe->phe", row, self.w_uk[:, heads]).astype(
                    self.dtype)
                v = _dot("pr,rhv->phv", row, self.w_uv[:, heads]).astype(
                    self.dtype)
            out.append(kv_pool.prompt_attention(q, k, v, self.scale,
                                                mask=allowed))
        return (self._out(jnp.concatenate(out, axis=1)), (row, pooled),
                open_sum)

    def _sparse_token(self, u, latent, index, open_sum, layer: int, position,
                      bound: int):
        """The mixer of one token a slot, absorbed: ``u (S, D)`` against the
        pools' ``layer`` → its output ``(S, D)``, the rows to write (latent
        row, the open block's mean so far) and the open block's new sum."""
        pool = self.index_pool
        h = rms_norm(u, self.norm_in, self.eps)
        c_q, row = self._down(h)
        with jax.named_scope("latent_q"):
            q = _dot("sr,rhe->she", c_q, self.w_uq).astype(self.dtype)
            q = _dot("she,rhe->shr", q, self.w_uk).astype(self.dtype)
        iq, ik, w = self._index(h, c_q, position)
        with jax.named_scope("index_pool"):
            open_sum = jnp.where((position % pool == 0)[:, None], 0.0,
                                 open_sum) + ik.astype(jnp.float32)
            mean = (open_sum / pool).astype(self.dtype)
        bound = min(bound, latent.shape[2])
        blocks = -(-bound // pool)
        scores = index_scores(iq[:, None], index[layer, :, :blocks],
                              w[:, None])[:, 0]
        with jax.named_scope("select"):
            own = (position // pool)[:, None]
            chosen = kv_pool.select_top(
                scores, jnp.arange(blocks)[None, :] < own, self.kept_blocks)
            # by blocks of ``pool`` positions; the query's own always
            keep = (jnp.repeat(chosen, pool, axis=1) | (
                jnp.arange(blocks * pool)[None, :] // pool == own))[:, :bound]
        o = kv_pool.latent_decode_attention(
            q, row, latent, layer, position, value=self.kv_rank,
            bound=bound, scale=self.scale, keep=keep)
        with jax.named_scope("latent_kv"):
            o = _dot("shr,rhv->shv", o, self.w_uv).astype(self.dtype)
        return self._out(o), (row, mean), open_sum

    # -- the block -------------------------------------------------------------

    def prefill(self, x, mask, length):
        """``x (P, n·D)``, the rows of one prompt of ``length (1,)`` tokens
        padded to its bucket (``mhc.pre_rows``); mask: (1, P). Returns the
        block's output, what the mixer caches — a sparse layer ``((latent
        rows, pooled keys), open sum)``, a KDA layer ``(state, tail)`` — and
        the passes its expert product took (``experts.window_passes``; None
        from a dense layer)."""
        u, coef = self._hyper(x, self.hc_attn, mhc.pre_rows)
        if self.sparse:
            y, *cache = self._sparse_prompt(u, length[0])
        else:
            y, cache = kda_prompt(
                rms_norm(u, self.norm_in, self.eps)[None], self.kda, mask,
                length, self.gate_bound, self.eps)
            y = y[0]
        x = mhc.post_rows(x, y, coef)
        u, coef = self._hyper(x, self.hc_ffn, mhc.pre_rows)
        y, top_e = self._ffn(u, routed=True)
        return (mhc.post_rows(x, y, coef), tuple(cache),
                None if top_e is None else expert_layer.window_passes(
                    top_e, self.experts_held, self.experts,
                    self.first_expert))

    def step(self, x, cache, position, bound):
        """One token a slot: ``x (S, n, D)``. A sparse layer's ``cache`` is
        ``(latent pool, index pool, open sums, its layer in the pools)`` and
        it returns ``((latent row, pooled row), open sums)``; a KDA layer's is
        ``(state, tail)`` of every slot and it returns their successors. Then
        ``(y, new cache, experts (S, K) or None, g (S, H, d) or None, the
        slot's balance error (S,))``."""
        g = None
        u, h_post, h_res = self._hyper(x, self.hc_attn)
        error = mhc.balance_error(h_res)
        if self.sparse:
            y, *cache = self._sparse_token(u, *cache, position, bound)
        else:
            y, cache, g = kda_token(
                rms_norm(u, self.norm_in, self.eps), self.kda, *cache,
                position, self.gate_bound, self.eps)
        x = mhc.post(x, y, h_post, h_res)
        u, h_post, h_res = self._hyper(x, self.hc_ffn)
        error = jnp.maximum(error, mhc.balance_error(h_res))
        y, top_e = self._ffn(u, routed=False)
        return mhc.post(x, y, h_post, h_res), tuple(cache), top_e, g, error


class Glm5LM(nn.Module):
    """Causal LM over the block stack, with the serving entry points of an
    LM family (``runtime/kvcache.py`` ``LMServable``). ``decode_step`` returns
    its ids followed by every expert layer's chosen experts, each slot's
    balance error and each slot's retention (float32 bits), in one int32
    vector (``step_report``)."""

    vocab_size: int
    dim: int = 64
    layer_types: tuple = ("kda", "sparse", "kda", "kda")
    mlp_types: tuple = ("dense", "sparse", "sparse", "sparse")
    streams: int = 4
    sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: float = 30.0
    heads: int = 4
    head_dim: int = 16
    conv: int = 4
    gate_bound: float = -5.0
    kda_lora: int = 8
    attn_heads: int = 4
    q_rank: int = 32
    kv_rank: int = 16
    qk_dim: int = 16
    v_dim: int = 16
    index_heads: int = 4
    index_dim: int = 16
    index_rope: int = 8
    index_theta: float = 1e6
    index_topk: int = 16
    index_pool: int = 4
    mlp_dim: int = 96
    experts: int = 16
    experts_held: int = 16
    first_expert: int = 0
    experts_per_token: int = 2
    expert_dim: int = 32
    shared_dim: int = 32
    route_scale: float = 2.5
    swiglu_limit: float = 10.0
    rms_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16

    def setup(self):
        self.embed = self.param("embed", seeded(1.0, fan_in_axis=None),
                                (self.vocab_size, self.dim), self.dtype)
        skip = ("vocab_size", "layer_types", "mlp_types", "rms_eps", "parent",
                "name")
        shared = {field: getattr(self, field)
                  for field in self.__dataclass_fields__ if field not in skip}
        self.layers = [
            _Layer(sparse=kind == "sparse", dense=mlp == "dense",
                   eps=self.rms_eps, name=f"layer{i}", **shared)
            for i, (kind, mlp) in enumerate(zip(self.layer_types,
                                                self.mlp_types))]
        self.norm_f = self.param("norm_f", norm_scale(1.0), (self.dim,),
                                 self.dtype)
        self.lm_head = self.param("lm_head", seeded(1.0),
                                  (self.dim, self.vocab_size), self.dtype)

    @nn.nowrap
    def kinds(self) -> tuple:
        """``(sparse layers, KDA layers, expert layers)``."""
        sparse = sum(kind == "sparse" for kind in self.layer_types)
        return (sparse, len(self.layer_types) - sparse,
                sum(mlp != "dense" for mlp in self.mlp_types))

    @nn.nowrap
    def cache_spec(self):
        """What a slot holds (``kv_pool.SlotSpec``): the sparse layers' latent
        row a position — all of it value, ``index_topk`` of them kept, the
        query's own block of ``index_pool`` always — and their pooled index
        keys, a row every ``index_pool`` positions (scored whole, in
        ``jax.numpy``); of the ``j``-th KDA layer its state ``kda<j>``
        (float32; stepped at the live slots only) and convolution tail
        ``conv<j>``; of the ``j``-th sparse layer the open block's running
        sum ``isum<j>`` (float32)."""
        sparse, linear, _ = self.kinds()
        state = []
        for j in range(linear):
            state += [(f"kda{j}", (self.heads, self.head_dim, self.head_dim),
                       jnp.float32),
                      (f"conv{j}", (self.conv - 1,
                                    3 * self.heads * self.head_dim),
                       self.dtype)]
        state += [(f"isum{j}", (self.index_dim,), jnp.float32)
                  for j in range(sparse)]
        return kv_pool.SlotSpec(
            (kv_pool.Rows("latent", sparse, self.kv_rank, self.dtype,
                          kind="latent", select=self.index_topk),
             kv_pool.Rows("index", sparse, self.index_dim, self.dtype,
                          kind="index", whole=True, every=self.index_pool)),
            tuple(state), tuple(f"kda{j}" for j in range(linear)))

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

    def _prefill(self, tokens, length):
        """One prompt: ``tokens (1, P)``, ``length (1,)``; its streams come
        back as rows ``(P, n·D)``."""
        x = self._streams(tokens[0], rows=True)
        mask = jnp.arange(tokens.shape[1])[None, :] < length[:, None]
        latent, index, state, passes, kda = [], [], {}, [], 0
        for layer in self.layers:
            x, cache, taken = layer.prefill(x, mask, length)
            if layer.sparse:
                (row, pooled), open_sum = cache
                state[f"isum{len(latent)}"] = open_sum[None]
                latent.append(row)
                index.append(pooled)
            else:
                state[f"kda{kda}"], state[f"conv{kda}"] = cache
                kda += 1
            if taken is not None:
                passes.append(taken)
        return (x, (jnp.stack(latent)[:, None], jnp.stack(index)[:, None]),
                state, expert_layer.pass_report(passes))

    def _step(self, tokens, latent, index, state, position, bound):
        x = self._streams(tokens)
        bound = latent.shape[2] if bound is None else bound
        rows, keys, picks, gates, new_state = [], [], [], [], {}
        error, kda = jnp.zeros(tokens.shape, jnp.float32), 0
        for layer in self.layers:
            if layer.sparse:
                j = len(rows)
                x, ((row, key), open_sum), e, _, err = layer.step(
                    x, (latent, index, state[f"isum{j}"], j), position, bound)
                new_state[f"isum{j}"] = open_sum
                rows.append(row)
                keys.append(key)
            else:
                x, cache, e, g, err = layer.step(
                    x, (state[f"kda{kda}"], state[f"conv{kda}"]), position,
                    bound)
                new_state[f"kda{kda}"], new_state[f"conv{kda}"] = cache
                gates.append(g)
                kda += 1
            error = jnp.maximum(error, err)
            if e is not None:
                picks.append(e)
        latent, index = kv_pool.write_rows(
            (latent, index), (rows, keys), position,
            every=(1, self.index_pool))
        with jax.named_scope("kda_gate"):
            # what a slot's states keep of themselves this step
            retention = jnp.exp(jnp.stack(gates)).mean(axis=(0, 2, 3))
        return (x, latent, index, {name: new_state[name] for name in state},
                jnp.stack(picks), error, retention)

    def prefill(self, tokens, length):
        x, blocks, state, passes = self._prefill(tokens, length)
        last = jax.lax.dynamic_slice_in_dim(x, length[0] - 1, 1).reshape(
            1, self.streams, self.dim)
        ids = jnp.argmax(self._logits(last), axis=-1).astype(jnp.int32)
        return jnp.concatenate([ids, passes]), *blocks, state

    def decode_step(self, tokens, latent, index, state, position, bound=None):
        """One token for every slot of the pool. The sparse layers read the
        cached positions ``< bound`` their selection keeps; the KDA layers
        advance the state of the slots at a position > 0."""
        x, latent, index, state, picks, error, retention = self._step(
            tokens, latent, index, state, position, bound)
        ids = jnp.argmax(self._logits(x), axis=-1).astype(jnp.int32)
        return (jnp.concatenate([
            ids, picks.astype(jnp.int32).reshape(-1),
            jax.lax.bitcast_convert_type(error, jnp.int32),
            jax.lax.bitcast_convert_type(retention, jnp.int32)]),
            latent, index, state)

    # Logits, for tests only: the serving programs ship ids.

    def prefill_logits(self, tokens, length):
        x, blocks, state, _ = self._prefill(tokens, length)
        return (self._logits(x.reshape(1, -1, self.streams, self.dim)),
                *blocks, state)

    def decode_logits(self, tokens, latent, index, state, position,
                      bound=None):
        x, latent, index, state, _, _, _ = self._step(
            tokens, latent, index, state, position, bound)
        return self._logits(x), latent, index, state

    # What ``step_report`` returns: the routing series of the sparse-expert
    # families, the hyper-connections' and the KDA state's, each under the
    # name the family that brought it gave it.
    step_report_series = {
        **expert_layer.step_report_series,
        "mhc_balance_error": Xing4LM.step_report_series["mhc_balance_error"],
        "kda_retention": Ling3LM.step_report_series["kda_retention"]}
    # What ``prefill`` appends to its first id (``experts.pass_report``).
    prefill_report_kinds = expert_layer.prefill_report_kinds

    @nn.nowrap
    def step_report(self, extra: np.ndarray, active) -> dict[str, float]:
        """What ``decode_step`` appended to its ids, over the LIVE slots:
        ``experts.load_report`` of their picks over the experts HELD here,
        the largest of their balance errors and the mean of their
        retentions."""
        live = np.flatnonzero(active)
        if not live.size:
            return {}
        slots = len(active)
        picks = extra[:-2 * slots].reshape(self.kinds()[2], slots,
                                           self.experts_per_token)[:, live]
        error, retention = (np.ascontiguousarray(part, np.int32).view(
            np.float32)[live] for part in (extra[-2 * slots:-slots],
                                           extra[-slots:]))
        return {**expert_layer.load_report(picks, self.experts,
                                           self.experts_held,
                                           self.first_expert),
                "mhc_balance_error": float(error.max()),
                "kda_retention": float(retention.mean())}


def create_glm5_lm(rng=None, vocab_size: int = 512, dtype=jnp.bfloat16,
                   **dims):
    """Build the LM and its seeded params (``olmoe.seeded``: the same values
    on every backend). ``dims``: the fields of ``Glm5LM``; a key it does not
    know is an error. Norm weights are drawn away from 1, so one left out
    shows. The KDA mixer's gains are ``models/ling3.py``'s and the
    hyper-connections' ``models/xing4.py``'s, for the reasons given there;
    this family's own keep random weights where a float32 reference can tell
    a fault from rounding at the published widths and thousands of positions
    AND make the selection do work:

    - ``w_uq`` one and a half: ``c_q`` and the cached row are unit-RMS and
      ``w_uk`` unit gain, so a head's ``q · k / 16`` over 256 lanes deviates
      by ~1.5 between positions: attention picks positions and is no argmax;
    - ``w_o`` three: a softmax of that spread over 2,048 selected positions
      is a mean over ~200 effective ones, so a head's output is ~0.07 of a
      value's deviation — at unit gain the one sparse mixer of five would add
      a fifteenth of a stream and no wrong selection could show; at three it
      adds about a fifth, as the other sublayers do;
    - the indexer's queries at two (``models/dots3.py``'s): a head's ``relu``
      is open on about half the blocks; a pooled key is the mean of four
      rotated unit keys (deviation ~½ a lane), so the scores of a query's
      blocks deviate by ~0.7 between blocks — bfloat16's rounding of a key
      (2^-9) moves a score by ~0.003 and only the blocks within that of the
      511th change places, four positions of 2,048 each;
    - router logits deviate by ~2 and the selection bias by ~0.2; the FFNs'
      ``*_down`` small (``w_down`` 0.2, the shared expert's and the dense
      layer's 0.15: ``models/ling3.py`` has the measurement behind them), so
      that the pick rounding flips at the eighth place moves a stream
      little."""
    dims = dict(dims)
    for key in ("layer_types", "mlp_types"):
        if key in dims:
            dims[key] = tuple(dims[key])
    model = Glm5LM(vocab_size=vocab_size, dtype=jnp.dtype(dtype), **dims)
    sparse, linear, moe = model.kinds()
    if (set(model.layer_types) - {"kda", "sparse"}
            or set(model.mlp_types) - {"dense", "sparse"}
            or len(model.layer_types) != len(model.mlp_types)):
        raise ValueError(f"layer_types {model.layer_types} with mlp_types "
                         f"{model.mlp_types}")
    if not sparse or not linear or not moe:
        raise ValueError("the held layers need a sparse-attention layer, a "
                         "KDA layer and an expert layer")
    if (model.index_rope % 2 or model.index_rope > model.index_dim
            or model.index_pool < 1 or model.index_topk % model.index_pool
            or model.index_topk < 2 * model.index_pool):
        raise ValueError(
            f"the indexer rotates index_rope {model.index_rope} (even) of "
            f"its {model.index_dim} lanes and keeps index_topk "
            f"{model.index_topk} positions as whole blocks of "
            f"{model.index_pool}, the query's own and at least one more")
    if model.streams < 1 or model.sinkhorn_iters < 1 or model.kda_lora < 0:
        raise ValueError("at least one stream and one Sinkhorn iteration; "
                         "kda_lora a rank or 0")
    if not (0 < model.experts_per_token <= model.experts
            and 0 <= model.first_expert
            and model.first_expert + model.experts_held <= model.experts):
        raise ValueError("experts held must lie within the experts routed")
    if model.swiglu_limit < 0:
        raise ValueError(f"swiglu_limit {model.swiglu_limit}")
    if CHUNK % SUB_BLOCK or -model.gate_bound * (SUB_BLOCK - 1) > 80:
        raise ValueError(f"gate_bound {model.gate_bound}: a sub-block of "
                         f"{SUB_BLOCK} tokens would overflow float32")
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    params = jax.jit(partial(model.init, method="prefill"))(
        rng, np.zeros((1, 8), np.int32), np.ones((1,), np.int32))
    return model, params
