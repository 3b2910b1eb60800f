"""Manifold-constrained hyper-connections — the residual path of a family
that carries ``n`` streams a token instead of one.

A token's state is ``X (n, D)``. Around a sublayer ``F`` (a mixer or an FFN
with its own input norm), with ``x = vec(X) (nD,)`` and ``x' = x ·
rsqrt(mean(x²) + norm_eps)`` (a flat norm without a learned weight: it folds
into ``φ``)::

    H~_pre  = α_pre  · x' φ_pre  + b_pre     (n,)
    H~_post = α_post · x' φ_post + b_post    (n,)
    H~_res  = α_res  · mat(x' φ_res) + b_res (n, n), row-major
    H_pre = σ(H~_pre);  H_post = 2 σ(H~_post)
    H_res = SK(clip(H~_res, −clamp, clamp))
    u  = H_pre X          the sublayer's input, (D,)
    y  = F(u)
    X' = H_res X + H_postᵀ y                 stream i gains H_post,i · y

``SK(A)``: ``M = exp(A)``, then ``iters`` times ``M ← M / (M 1 + eps)``
(rows) and ``M ← M / (1ᵀ M + eps)`` (columns) — Sinkhorn's normalisation
toward a doubly stochastic matrix, a token its own. The clamp holds ``exp``
finite in float32 (``e^30`` ≈ 1e13).

``pre`` is what comes before a sublayer and ``post`` what comes after it,
each one operation: plain ``jax.numpy`` in float32 whatever ``X``'s dtype
(``X`` itself is held in the family's dtype between sublayers), the
iterations unrolled over a ``(tokens, n, n)`` array so that XLA makes one
fusion of them, the mixes written as sums over streams (elementwise: a
``dot_general`` of 4 x 4 a token is not what the chip's matrix unit is for).
Scopes ``mhc_pre`` (norm, projection, the read mix), ``sinkhorn`` (inside
it) and ``mhc_post`` name the device side for a trace's reader.

A sublayer's parameters (``params``): ``phi (nD, 2n + n²)`` — the columns of
``φ_pre``, ``φ_post``, ``φ_res`` side by side —, ``alpha (3,)`` and ``bias
(2n + n²,)`` in the same order, the last two float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def sinkhorn(logits, iters: int, eps: float):
    """``SK`` of ``logits (..., n, n)`` (float32, clamped by the caller):
    ``exp``, then ``iters`` times rows then columns divided by their sums
    plus ``eps``. Unrolled: a few hundred elementwise operations on sixteen
    numbers a token."""
    with jax.named_scope("sinkhorn"):
        m = jnp.exp(logits)
        for _ in range(iters):
            m = m / (m.sum(axis=-1, keepdims=True) + eps)
            m = m / (m.sum(axis=-2, keepdims=True) + eps)
        return m


def balance_error(h_res):
    """How far ``h_res (..., n, n)`` is from doubly stochastic: the largest
    ``|row sum − 1|`` or ``|column sum − 1|``, ``(...)`` float32."""
    rows = jnp.abs(h_res.sum(axis=-1) - 1.0).max(axis=-1)
    columns = jnp.abs(h_res.sum(axis=-2) - 1.0).max(axis=-1)
    return jnp.maximum(rows, columns)


def pre(x, params, *, iters: int = 20, eps: float = 1e-6,
        clamp: float = 30.0, norm_eps: float = 1e-6):
    """``x (T, n, D)`` → the sublayer's input ``u (T, D)`` in ``x``'s dtype
    and the coefficients its ``post`` needs, float32: ``H_post (T, n)`` and
    ``H_res (T, n, n)``."""
    t, n, d = x.shape
    with jax.named_scope("mhc_pre"):
        h = x.astype(jnp.float32)
        inv = jax.lax.rsqrt(jnp.mean(h * h, axis=(1, 2)) + norm_eps)
        # the norm is one scalar a token: it scales the projection's result
        raw = jnp.einsum("tk,kc->tc", x.reshape(t, n * d), params["phi"],
                         preferred_element_type=jnp.float32) * inv[:, None]
        alpha = jnp.repeat(params["alpha"].astype(jnp.float32),
                           np.asarray([n, n, n * n]),
                           total_repeat_length=2 * n + n * n)
        raw = raw * alpha + params["bias"].astype(jnp.float32)
        h_pre = jax.nn.sigmoid(raw[:, :n])
        h_post = 2.0 * jax.nn.sigmoid(raw[:, n:2 * n])
        h_res = sinkhorn(jnp.clip(raw[:, 2 * n:], -clamp, clamp).reshape(
            t, n, n), iters, eps)
        u = (h * h_pre[:, :, None]).sum(axis=1)
        return u.astype(x.dtype), h_post, h_res


def post(x, y, h_post, h_res):
    """``X' = H_res X + H_postᵀ y``: ``x (T, n, D)``, the sublayer's output
    ``y (T, D)`` → ``(T, n, D)`` in ``x``'s dtype, summed in float32."""
    with jax.named_scope("mhc_post"):
        h = x.astype(jnp.float32)
        mixed = (h_res[:, :, :, None] * h[:, None, :, :]).sum(axis=2)
        return (mixed + h_post[:, :, None]
                * y.astype(jnp.float32)[:, None, :]).astype(x.dtype)
