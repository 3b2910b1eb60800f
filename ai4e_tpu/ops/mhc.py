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
float32 whatever ``X``'s dtype (``X`` itself is held in the family's dtype
between sublayers), the mixes written as sums over streams (elementwise: a
``dot_general`` of 4 x 4 a token is not what the chip's matrix unit is for;
the projection ``x' φ`` is, and takes it). One algorithm in two forms, by
the size of ``X``:

* **A step's streams** ``X (S, n, D)`` — a token a slot, a megabyte or two —
  ``pre`` / ``post``: plain ``jax.numpy``, the iterations unrolled over a
  ``(tokens, n, n)`` array. At that size every array stays on chip between
  XLA's fusions and the whole costs 0.18 ms of a 14 ms step.
* **A prompt's rows** ``X (P, n·D)`` — stream ``i`` the lanes ``i·D … (i+1)·D``
  of a token's row — ``pre_rows`` / ``post_rows``, which the families'
  ``prefill`` carry from the embedding to the head. The stream axis is
  never a tiled dimension of its own there (as the second-minor of ``(P, n,
  D)`` it is padded from 4 to 16 rows or re-laid streams-major, and the
  projection's ``(P, n·D)`` view is then a transposing copy), and from
  ``ROWS_KERNEL_BYTES`` on each half is ONE Pallas call
  (``pallas/mhc_rows.py``) that reads a block of rows from HBM once: the
  ``jax.numpy`` form at 8,192 tokens moves 4.3 times the bytes the halves
  need, a float32 copy of ``X`` among them (``PERF.md`` section 6, PR 52).
  The coefficients pass from ``pre_rows`` to ``post_rows`` as one float32
  lane tile a token (``mhc_rows.coefficient_lanes``), never as ``(P, n,
  n)``. Rows under the threshold, or whose ``D`` is not whole lane tiles,
  take the first form through a reshape.

Scopes ``mhc_pre`` (norm, projection, the coefficients, the read mix) and
``mhc_post`` name the device side for a trace's reader in both forms;
``sinkhorn`` is a scope of its own inside ``mhc_pre`` in the first form only
— in a kernel the iterations are part of the one call.

A sublayer's parameters (``params``): ``phi (nD, 2n + n²)`` — the columns of
``φ_pre``, ``φ_post``, ``φ_res`` side by side —, ``alpha (3,)`` and ``bias
(2n + n²,)`` in the same order, the last two float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .pallas import mhc_rows

# The bytes of a prompt's rows ``X`` from which its halves are the kernels':
# under it (a step's slots are 2 MB) the ``jax.numpy`` form.
ROWS_KERNEL_BYTES = 4 << 20


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


def _kernel_rows(x, d: int) -> bool:
    return (x.size * x.dtype.itemsize >= ROWS_KERNEL_BYTES
            and d % mhc_rows.LANES == 0)


def pre_rows(x, params, *, iters: int = 20, eps: float = 1e-6,
             clamp: float = 30.0, norm_eps: float = 1e-6):
    """``pre`` of a prompt's rows ``x (T, n·D)`` → ``u (T, D)`` and the
    coefficients its ``post_rows`` needs, one float32 lane tile a token
    (``mhc_rows.coefficient_lanes``)."""
    t, width = x.shape
    n = mhc_rows.streams(params["phi"].shape[1])
    with jax.named_scope("mhc_pre"):
        if _kernel_rows(x, width // n):
            return mhc_rows.pre(x, params["phi"], params["alpha"],
                                params["bias"], iters=iters, eps=eps,
                                clamp=clamp, norm_eps=norm_eps)
    u, h_post, h_res = pre(x.reshape(t, n, width // n), params, iters=iters,
                           eps=eps, clamp=clamp, norm_eps=norm_eps)
    lanes = mhc_rows.coefficient_lanes(n)[n:]
    return u, jnp.zeros((t, mhc_rows.LANES), jnp.float32).at[:, lanes].set(
        jnp.concatenate([h_post, h_res.reshape(t, n * n)], axis=1))


def post_rows(x, y, coef):
    """``post`` of a prompt's rows: ``x (T, n·D)``, ``y (T, D)`` and
    ``pre_rows``' coefficients → ``(T, n·D)``."""
    t, width = x.shape
    d = y.shape[1]
    n = width // d
    with jax.named_scope("mhc_post"):
        if _kernel_rows(x, d):
            return mhc_rows.post(x, y, coef)
    h = coef[:, mhc_rows.coefficient_lanes(n)[n:]]
    return post(x.reshape(t, n, d), y, h[:, :n],
                h[:, n:].reshape(t, n, n)).reshape(t, width)
