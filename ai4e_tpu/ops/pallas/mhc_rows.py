"""Pallas TPU kernels: a prompt's hyper-connection halves (``ops/mhc.py``),
each from ONE read of the streams.

A prompt's streams are rows ``X (T, n·D)`` — stream ``i`` is lanes ``i·D …
(i+1)·D`` of a token's row, so no tile of it is padded. As ``jax.numpy`` on
``(T, n, D)`` a half is three to five passes over ``X``, a float32 copy of it
and a transposing copy for the projection (``PERF.md`` section 6, PR 52).
Here a grid step holds ``TOKENS`` rows in VMEM:

* ``pre``: one loop over the row's lanes takes the mean square (float32, a
  lane tile's partial sums) and the projection ``x φ`` on the MXU (``φ``'s
  ``2n + n²`` columns spread over one lane tile, resident); the norm, ``α``
  and the bias on that ``(TOKENS, 128)`` tile; transposed, so that a
  coefficient's tokens lie along the lanes, the sigmoids and Sinkhorn's
  iterations run on ``n`` arrays of a row of ``H_res`` each — a row sum is a
  sum over sublanes, a column sum a sum of the arrays; transposed back, the
  coefficients leave as one float32 lane tile a token; a second loop over the
  lanes mixes ``u = Σ H_pre,i X_i`` from the block where it lies.
* ``post``: ``X'_i = Σ_j H_res,ij X_j + H_post,i y``, a chunk of lanes of
  all ``n`` streams at a time, written over ``X`` (aliased).

The coefficients' lanes (``coefficient_lanes``): ``H_pre`` at ``0 … n``,
``H_post`` at ``8 … 8 + n``, row ``i`` of ``H_res`` at ``16 + 8 i … 16 + 8 i +
n`` — every group starts a sublane tile of the transposed array.

Float32 throughout but the projection's operands, which keep their dtypes
(float32 accumulation); divides where ``ops/mhc.py`` divides.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lowering import resolve_interpret

TOKENS = 256      # rows a grid step
MAX_CHUNK = 1024  # lanes a step of the kernels' loops, at most
LANES = 128
GROUP = 8        # a sublane tile: the lanes a group of coefficients is given

# Beyond the blocks: Mosaic's own scratch.
VMEM_HEADROOM_BYTES = 8 << 20


def streams(columns: int) -> int:
    """``n`` of a sublayer whose ``φ`` has ``columns = 2n + n²`` columns."""
    return math.isqrt(columns + 1) - 1


def coefficient_lanes(n: int) -> np.ndarray:
    """The lane of each of ``ops/mhc.py``'s ``2n + n²`` coefficients, in
    ``φ``'s column order."""
    assert n <= GROUP and (2 + n) * GROUP <= LANES, n
    return (np.arange(2 + n)[:, None] * GROUP + np.arange(n)).ravel()


def _on_lanes(a, n: int):
    """``a (..., 2n + n²)`` in ``φ``'s column order → ``(..., 128)`` with
    every coefficient at its lane and zeros between: a product with a 0/1
    matrix — exact, every sum one term, and one pass of the matrix unit where
    a pad of the minor dimension is a re-laying copy of ``φ``."""
    place = np.zeros((a.shape[-1], LANES), np.float32)
    place[np.arange(a.shape[-1]), coefficient_lanes(n)] = 1.0
    return jnp.dot(a, place.astype(a.dtype),
                   precision=jax.lax.Precision.HIGHEST).astype(a.dtype)


def pre_vmem_bytes(n: int, d: int, itemsize: int = 2) -> int:
    """What one ``pre`` call holds in VMEM: a block of rows, ``φ`` on a lane
    tile, ``u`` and the coefficients, double-buffered, and the headroom —
    also the limit the call asks Mosaic for."""
    return (2 * itemsize * (TOKENS * n * d + n * d * LANES + TOKENS * d)
            + 2 * 4 * (TOKENS + 2) * LANES + VMEM_HEADROOM_BYTES)


def post_vmem_bytes(n: int, d: int, itemsize: int = 2) -> int:
    """What one ``post`` call holds: a block of rows in and out, ``y`` and
    the coefficients, double-buffered, and the headroom."""
    return (2 * itemsize * TOKENS * (2 * n + 1) * d + 2 * 4 * TOKENS * LANES
            + VMEM_HEADROOM_BYTES)


def _pre_kernel(x_ref, phi_ref, affine_ref, u_ref, coef_ref, *,
                n: int, d: int, chunk: int, iters: int, eps: float,
                clamp: float, norm_eps: float):
    rows = x_ref.shape[0]

    def at(c, stream=0):
        return pl.ds(pl.multiple_of(stream * d + c * chunk, chunk), chunk)

    def project(c, carry):
        squares, raw = carry
        x = x_ref[:, at(c)]
        h = x.astype(jnp.float32)
        for t in range(0, chunk, LANES):
            squares = squares + h[:, t:t + LANES] * h[:, t:t + LANES]
        return squares, raw + jnp.dot(x, phi_ref[at(c), :],
                                      preferred_element_type=jnp.float32)

    zeros = jnp.zeros((rows, LANES), jnp.float32)
    squares, raw = jax.lax.fori_loop(0, n * d // chunk, project,
                                     (zeros, zeros))
    inv = jax.lax.rsqrt(squares.sum(axis=1, keepdims=True) / (n * d)
                        + norm_eps)
    # α and the bias, a coefficient's at its lane: (LANES, rows) from here
    raw = (raw * inv * affine_ref[0:1, :] + affine_ref[1:2, :]).T
    # a group's sublanes past ``n`` hold no coefficient: zero, and zero still
    # after every division
    live = jax.lax.broadcasted_iota(jnp.int32, (GROUP, rows), 0) < n
    h_pre = jnp.where(live, jax.nn.sigmoid(raw[:GROUP]), 0.0)
    h_post = jnp.where(live, 2.0 * jax.nn.sigmoid(raw[GROUP:2 * GROUP]), 0.0)
    res = tuple(
        jnp.where(live, jnp.exp(jnp.clip(
            raw[(2 + i) * GROUP:(3 + i) * GROUP], -clamp, clamp)), 0.0)
        for i in range(n))

    def balance(_, res):
        res = tuple(r / (r.sum(axis=0, keepdims=True) + eps) for r in res)
        columns = sum(res[1:], res[0]) + eps
        return tuple(r / columns for r in res)

    res = jax.lax.fori_loop(0, iters, balance, res)
    coef = jnp.concatenate(
        [h_pre, h_post, *res,
         jnp.zeros((LANES - (2 + n) * GROUP, rows), jnp.float32)], axis=0).T
    coef_ref[...] = coef
    mix = [coef[:, i:i + 1] for i in range(n)]

    def read(c, _):
        u = mix[0] * x_ref[:, at(c)].astype(jnp.float32)
        for i in range(1, n):
            u = u + mix[i] * x_ref[:, at(c, i)].astype(jnp.float32)
        u_ref[:, at(c)] = u.astype(u_ref.dtype)
        return 0

    jax.lax.fori_loop(0, d // chunk, read, 0)


def _post_kernel(x_ref, y_ref, coef_ref, out_ref, *, n: int, d: int,
                 chunk: int):
    coef = coef_ref[...]
    gain = [coef[:, GROUP + i:GROUP + i + 1] for i in range(n)]
    mix = [[coef[:, (2 + i) * GROUP + j:(2 + i) * GROUP + j + 1]
            for j in range(n)] for i in range(n)]

    def at(c, stream=0):
        return pl.ds(pl.multiple_of(stream * d + c * chunk, chunk), chunk)

    def write(c, _):
        y = y_ref[:, at(c)].astype(jnp.float32)
        streams = [x_ref[:, at(c, j)].astype(jnp.float32) for j in range(n)]
        for i in range(n):
            mixed = mix[i][0] * streams[0]
            for j in range(1, n):
                mixed = mixed + mix[i][j] * streams[j]
            out_ref[:, at(c, i)] = (mixed + gain[i] * y).astype(out_ref.dtype)
        return 0

    jax.lax.fori_loop(0, d // chunk, write, 0)


def _padded(a, rows):
    return jnp.pad(a, ((0, rows), (0, 0))) if rows else a


def _block(lanes):
    """A grid step's ``TOKENS`` rows of an array ``lanes`` wide."""
    return pl.BlockSpec((TOKENS, lanes), lambda i: (i, 0))


@partial(jax.jit, static_argnames=("iters", "eps", "clamp", "norm_eps",
                                   "interpret"))
def _pre(x, phi, alpha, bias, *, iters, eps, clamp, norm_eps, interpret):
    """Jitted on its own so that the sublayers of a prefill program share one
    traced and lowered kernel."""
    t, width = x.shape
    n = streams(phi.shape[1])
    d = width // n
    # a coefficient's column of φ, its α and its bias at the coefficient's lane
    phi = _on_lanes(phi, n)
    affine = _on_lanes(jnp.stack([
        jnp.repeat(alpha.astype(jnp.float32), np.asarray([n, n, n * n]),
                   total_repeat_length=2 * n + n * n),
        bias.astype(jnp.float32)]), n)
    pad = -t % TOKENS
    whole = lambda a: pl.BlockSpec(a.shape, lambda i: (0, 0))
    u, coef = pl.pallas_call(
        partial(_pre_kernel, n=n, d=d, chunk=math.gcd(d, MAX_CHUNK),
                iters=iters, eps=eps, clamp=clamp, norm_eps=norm_eps),
        grid=((t + pad) // TOKENS,),
        in_specs=[_block(width), whole(phi), whole(affine)],
        out_specs=[_block(d), _block(LANES)],
        out_shape=[jax.ShapeDtypeStruct((t + pad, d), x.dtype),
                   jax.ShapeDtypeStruct((t + pad, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=pre_vmem_bytes(n, d, x.dtype.itemsize)),
        interpret=interpret,
        name="mhc_pre",
    )(_padded(x, pad), phi, affine)
    return u[:t], coef[:t]


@partial(jax.jit, static_argnames=("interpret",))
def _post(x, y, coef, *, interpret):
    t, width = x.shape
    d = y.shape[1]
    pad = -t % TOKENS
    out = pl.pallas_call(
        partial(_post_kernel, n=width // d, d=d,
                chunk=math.gcd(d, MAX_CHUNK)),
        grid=((t + pad) // TOKENS,),
        in_specs=[_block(width), _block(d), _block(LANES)],
        out_specs=_block(width),
        out_shape=jax.ShapeDtypeStruct((t + pad, width), x.dtype),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=post_vmem_bytes(width // d, d,
                                             x.dtype.itemsize)),
        interpret=interpret,
        name="mhc_post",
    )(_padded(x, pad), _padded(y, pad), _padded(coef, pad))
    return out[:t]


def pre(x, phi, alpha, bias, *, iters: int, eps: float, clamp: float,
        norm_eps: float, interpret: bool | None = None):
    """``x (T, n·D)``, ``D`` whole lane tiles → ``u (T, D)`` in ``x``'s
    dtype and the coefficients ``(T, 128)`` float32 (``coefficient_lanes``).
    ``phi (n·D, 2n + n²)``, ``alpha (3,)``, ``bias (2n + n²,)``."""
    return _pre(x, phi, alpha, bias, iters=iters, eps=eps, clamp=clamp,
                norm_eps=norm_eps,
                interpret=resolve_interpret("mhc_pre", interpret))


def post(x, y, coef, *, interpret: bool | None = None):
    """``X' (T, n·D)`` from ``x (T, n·D)``, the sublayer's ``y (T, D)`` and
    ``pre``'s coefficients."""
    return _post(x, y, coef,
                 interpret=resolve_interpret("mhc_post", interpret))
