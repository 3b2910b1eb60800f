"""Dense latent attention under YaRN — the mixer ``xing4`` and ``axk1`` share.

``H`` heads, ranks ``r_q`` / ``r_kv``, head widths ``nope`` / ``rope`` / ``v``,
for a row ``h`` (after the layer's own input norm)::

    c_q = n_q(h W_dq);  [q_nope | q_rope]_h = c_q W_uq
    [c_kv | k_r] = h W_dkv;  c_kv <- n_kv(c_kv)
    q_rope, k_r rotated (rotate-half, YaRN's frequencies), k_r one head that
        every head shares
    k_nope,h = c_kv W_uk,h;  v_h = c_kv W_uv,h
    a causal softmax of (q_nope . k_nope + q_rope . k_r) . s;  W_o

YaRN (``olmoe.yarn_inv_freq``): the rotary frequencies blended between
``theta^(-2i/rope)`` and that over ``rope_factor``; the rotated lanes times
``m(mscale) / m(mscale_all_dim)``; ``s = (nope + rope)^(-1/2) .
m(mscale_all_dim)^2`` with ``m(a) = 0.1 a ln(rope_factor) + 1``.

What a position caches is ``[c_kv | k_r]`` after norm and rotation — ONE row
every head shares, whose first ``r_kv`` lanes are its value too —, padded to
whole lane tiles (576 -> 640 as published). ``attend_step`` is the absorbed
form (``kv_pool.latent_decode_attention``: one kernel, all heads on one row,
every block under a slot's position, no selection), ``attend_prompt`` the
published form (``kv_pool.prompt_attention``).

A layer owns its parameters: ``Latent.declare`` declares them through the
layer's own ``self.param`` (``xing4.hyper_params``' idiom), so the names, the
order and the seeded values are the layer's, and the functions here take them
as a dict. ``dots3``, ``ling3`` and ``glm5`` keep latent layers of their own
(a selection, no positions, an indexer on ``c_q``, no query rank, a gate):
``ROADMAP.md`` Design 15 says what joining them takes.
"""

from __future__ import annotations

from dataclasses import dataclass, fields

import jax
import jax.numpy as jnp

from ..ops import kv_pool
from .dots3 import padded
from .olmoe import (norm_scale, rms_norm, rope, seeded, yarn_inv_freq,
                    yarn_mscale)


def _dot(eq, a, b):
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def row_lanes(kv_rank: int, rope_dim: int) -> int:
    """Lanes of the row a position caches, ``[c_kv | k_r]`` padded to whole
    tiles: what a family's ``cache_spec`` declares."""
    return padded(kv_rank + rope_dim)


def _lane_pad(x, width: int):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


@dataclass(frozen=True)
class Latent:
    """The mixer's sizes (a layer's fields of the same names) and the
    functions over its parameters ``w`` (``declare``'s dict)."""

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
    eps: float
    dtype: jnp.dtype

    @classmethod
    def of(cls, layer) -> "Latent":
        """From a layer that carries every size as a field of its own."""
        return cls(**{f.name: getattr(layer, f.name) for f in fields(cls)})

    def declare(self, p, gains: dict) -> dict:
        """Declare the mixer's parameters through ``p(name, init, *shape,
        dtype=None)`` — a layer's ``self.param`` at its dtype — in this order:
        ``w_dq``, ``norm_q``, ``w_uq``, ``w_dkv``, ``norm_kv``, ``w_uk``,
        ``w_uv``, ``w_o``. ``gains``: the family's seeded gains of ``w_uq``
        and ``w_o``."""
        d, h = self.dim, self.heads
        return {
            "w_dq": p("w_dq", seeded(1.0), d, self.q_rank),
            "norm_q": p("norm_q", norm_scale(1.0), self.q_rank),
            "w_uq": p("w_uq", seeded(gains["w_uq"]), self.q_rank,
                      h * (self.nope + self.rope_dim)),
            "w_dkv": p("w_dkv", seeded(1.0), d, self.kv_rank + self.rope_dim),
            "norm_kv": p("norm_kv", norm_scale(1.0), self.kv_rank),
            "w_uk": p("w_uk", seeded(1.0, fan_in_axis=0), self.kv_rank, h,
                      self.nope),
            "w_uv": p("w_uv", seeded(1.0, fan_in_axis=0), self.kv_rank, h,
                      self.v_dim),
            "w_o": p("w_o", seeded(gains["w_o"]), h * self.v_dim, d)}

    # -- sizes and positions ---------------------------------------------------

    @property
    def row(self) -> int:
        """Lanes of the cached row, padded to whole tiles."""
        return row_lanes(self.kv_rank, self.rope_dim)

    @property
    def scale(self) -> float:
        """What multiplies the scores: YaRN's ``m(mscale_all_dim)²`` on the
        published ``(nope + rope)^(−1/2)``."""
        return float((self.nope + self.rope_dim) ** -0.5 * yarn_mscale(
            self.rope_factor, self.mscale_all_dim) ** 2)

    def rotate(self, x, position):
        """``x (..., heads, rope)`` rotated under YaRN."""
        x = rope(x, position, None, inv_freq=yarn_inv_freq(
            self.rope_dim, self.theta, self.rope_factor, self.rope_original,
            self.beta_fast, self.beta_slow))
        factor = (yarn_mscale(self.rope_factor, self.mscale)
                  / yarn_mscale(self.rope_factor, self.mscale_all_dim))
        return x if factor == 1.0 else (x.astype(jnp.float32)
                                        * factor).astype(x.dtype)

    # -- the projections -------------------------------------------------------

    def down(self, w, h, position):
        """``h (..., D)`` after the layer's input norm at ``position (...)``
        → the query's latent ``c_q (..., r_q)`` and the row a position
        caches, ``[c_kv | k_r]`` ``(..., r_kv + rope)``: normed, ``k_r``
        rotated."""
        with jax.named_scope("latent_q"):
            c_q = rms_norm(_dot("...d,dr->...r", h, w["w_dq"]).astype(
                self.dtype), w["norm_q"], self.eps)
        with jax.named_scope("latent_kv"):
            kv = _dot("...d,dr->...r", h, w["w_dkv"]).astype(self.dtype)
            c_kv = rms_norm(kv[..., :self.kv_rank], w["norm_kv"], self.eps)
            k_r = self.rotate(kv[..., None, self.kv_rank:],
                              position)[..., 0, :]
            return c_q, jnp.concatenate([c_kv, k_r], axis=-1)

    def queries(self, w, c_q, position):
        """``q_nope (..., H, nope)`` and ``q_rope (..., H, rope)``, rotated."""
        with jax.named_scope("latent_q"):
            q = _dot("...r,rhe->...he", c_q, w["w_uq"].reshape(
                self.q_rank, self.heads, -1)).astype(self.dtype)
            return (q[..., :self.nope],
                    self.rotate(q[..., self.nope:], position))

    def out(self, w, o):
        with jax.named_scope("out_proj"):
            return _dot("...e,ed->...d", o.reshape(*o.shape[:-2], -1),
                        w["w_o"]).astype(self.dtype)

    # -- the two forms ---------------------------------------------------------

    def attend_prompt(self, w, h):
        """The mixer over one padded prompt ``h (P, D)`` (normed) → its
        output ``(P, D)`` and the rows it caches ``(P, row)``."""
        position = jnp.arange(h.shape[0])
        c_q, row = self.down(w, h, position)
        c_kv, k_r = row[:, :self.kv_rank], row[:, self.kv_rank:]
        with jax.named_scope("latent_kv"):
            k_nope = _dot("pr,rhn->phn", c_kv, w["w_uk"]).astype(self.dtype)
            v = _dot("pr,rhv->phv", c_kv, w["w_uv"]).astype(self.dtype)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_r[:, None], (*k_nope.shape[:2], self.rope_dim))], axis=-1)
        q = jnp.concatenate(self.queries(w, c_q, position), axis=-1)
        o = kv_pool.prompt_attention(q, k, v, self.scale)
        return self.out(w, o), _lane_pad(row, self.row)

    def attend_step(self, w, h, pool, layer: int, position, bound: int):
        """The mixer of one token a slot, absorbed: ``h (S, D)`` (normed)
        against ``pool``'s ``layer`` → its output ``(S, D)`` and the new
        rows."""
        c_q, row = self.down(w, h, position)
        q_nope, q_rope = self.queries(w, c_q, position)
        with jax.named_scope("latent_q"):
            q = jnp.concatenate(
                [_dot("shn,rhn->shr", q_nope, w["w_uk"]).astype(self.dtype),
                 q_rope], axis=-1)
        q, row = _lane_pad(q, self.row), _lane_pad(row, self.row)
        o = kv_pool.latent_decode_attention(
            q, row, pool, layer, position, value=self.kv_rank,
            bound=min(bound, pool.shape[2]), scale=self.scale)
        with jax.named_scope("latent_kv"):
            o = _dot("shr,rhv->shv", o, w["w_uv"]).astype(self.dtype)
        return self.out(w, o), row
