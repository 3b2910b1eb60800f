"""A prompt's hyper-connection halves (``ops/mhc.py`` ``pre_rows`` /
``post_rows``, the kernels of ``ops/pallas/mhc_rows.py`` under the
interpreter) against the step's ``jax.numpy`` form ``pre`` / ``post``, which
is the oracle in the tree: the same coefficients, the same mixes, and the
form a call takes by the size of its rows alone.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from ai4e_tpu.models.xing4 import create_xing4_lm  # noqa: E402
from ai4e_tpu.ops import mhc  # noqa: E402
from ai4e_tpu.ops.pallas import mhc_rows  # noqa: E402

KNOBS = dict(iters=20, eps=1e-6, clamp=30.0, norm_eps=1e-6)


def _sublayer(t, n, d, dtype, seed=0):
    """A sublayer's parameters as the families seed them in scale (``φ``'s
    columns of unit norm over ``n·D``, a bias of order one), a prompt's
    streams and its sublayer's output."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    columns = 2 * n + n * n
    params = {
        "phi": (jax.random.normal(keys[0], (n * d, columns))
                * (n * d) ** -0.5).astype(dtype),
        "alpha": jnp.asarray([1.0, 0.7, 2.0], jnp.float32),
        "bias": jax.random.normal(keys[1], (columns,), jnp.float32)}
    x = jax.random.normal(keys[2], (t, n, d)).astype(dtype)
    y = jax.random.normal(keys[3], (t, d)).astype(dtype)
    return params, x, y


def _within_an_ulp(got, want, dtype):
    """``got`` is ``want`` to one unit in the last place of ``dtype`` — a sum
    of four or five float32 products in another order rounds to a neighbour
    — beside the float32 sums' own few units (2e-5 on terms of order one to
    ten, which is all that is left where they cancel)."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    ulp = float(jnp.finfo(dtype).eps) * np.maximum(np.abs(want), np.abs(got))
    np.testing.assert_array_less(np.abs(got - want), ulp + 2e-5)


@pytest.mark.parametrize("shape,dtype", [
    (shape, dtype) for shape in (
        (256, 4, 512), (1024, 4, 3584), (2048, 4, 4096),
        (200, 4, 256))   # 200: no multiple of the kernel's block of tokens
    for dtype in ("bfloat16", "float32")] + [
    # glm53.longctx's least bucket whole (float32 of it: 3 GB more a worker)
    ((4096, 4, 4096), "bfloat16")])
def test_a_prompts_rows_equal_the_streams_form(monkeypatch, shape, dtype):
    t, n, d = shape
    params, x, y = _sublayer(t, n, d, dtype, seed=t)
    monkeypatch.setattr(mhc, "ROWS_KERNEL_BYTES", 0)
    u, h_post, h_res = mhc.pre(x, params, **KNOBS)
    want = mhc.post(x, y, h_post, h_res)
    rows = x.reshape(t, n * d)
    got_u, coef = mhc.pre_rows(rows, params, **KNOBS)
    got = mhc.post_rows(rows, y, coef)
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda rows: mhc.pre_rows(rows, params, **KNOBS))(rows))
    assert got_u.dtype == got.dtype == x.dtype and got.shape == rows.shape
    assert coef.shape == (t, 128) and coef.dtype == jnp.float32
    lanes = mhc_rows.coefficient_lanes(n)
    held = np.asarray(coef)[:, lanes]
    # the projection's n·D float32 products sum block by block: 1e-6 from
    # bfloat16 operands, a few units of an H_post near 2 from float32 ones
    atol = 1e-6 if dtype == "bfloat16" else 2e-6
    np.testing.assert_allclose(held[:, n:2 * n], h_post, atol=atol, rtol=0)
    got_res = held[:, 2 * n:].reshape(t, n, n)
    np.testing.assert_allclose(got_res, h_res, atol=atol, rtol=0)
    np.testing.assert_allclose(mhc.balance_error(jnp.asarray(got_res)),
                               mhc.balance_error(h_res), atol=2e-6, rtol=0)
    # every lane that holds no coefficient is zero
    assert not np.delete(np.asarray(coef), lanes, axis=1).any()
    _within_an_ulp(got_u, u, dtype)
    _within_an_ulp(got.reshape(t, n, d), want, dtype)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_rows_under_the_threshold_are_the_streams_form_bit_for_bit(dtype):
    t, n, d = 64, 4, 256
    params, x, y = _sublayer(t, n, d, dtype)
    u, h_post, h_res = mhc.pre(x, params, **KNOBS)
    rows = x.reshape(t, n * d)
    got_u, coef = mhc.pre_rows(rows, params, **KNOBS)
    np.testing.assert_array_equal(np.asarray(got_u, np.float32),
                                  np.asarray(u, np.float32))
    np.testing.assert_array_equal(
        np.asarray(mhc.post_rows(rows, y, coef), np.float32),
        np.asarray(mhc.post(x, y, h_post, h_res).reshape(t, n * d),
                   np.float32))


def test_the_size_of_the_rows_alone_chooses_the_form():
    """A step's slots (64 of 4 x 4,096 bfloat16: 2 MB) and a prompt's rows
    (128 tokens and up of them) fall on the two sides of
    ``ROWS_KERNEL_BYTES``; a width that is no whole lane tile never reaches
    the kernel."""
    def form(t, n, d):
        params, x, y = jax.eval_shape(
            lambda: _sublayer(t, n, d, "bfloat16"))
        rows = jax.ShapeDtypeStruct((t, n * d), x.dtype)
        coef = jax.ShapeDtypeStruct((t, 128), jnp.float32)
        text = (str(jax.make_jaxpr(lambda rows, params: mhc.pre_rows(
            rows, params, **KNOBS))(rows, params)),
            str(jax.make_jaxpr(mhc.post_rows)(rows, y, coef)))
        calls = ["pallas_call" in part for part in text]
        assert calls[0] == calls[1]
        for part, name in zip(text, ("mhc_pre", "mhc_post")):
            assert not calls[0] or f"name={name}" in part, name
        return calls[0]

    assert mhc.ROWS_KERNEL_BYTES == 4 << 20
    assert not form(64, 4, 4096)      # glm53.longctx's step: 2 MB
    assert not form(32, 4, 3584)      # xing4.reason's step
    assert form(128, 4, 4096)         # 4 MB: the least prompt that does
    assert not form(128, 4, 3584)     # xing4.reason's 128 bucket: 3.5 MB
    assert form(256, 4, 3584)
    assert form(4096, 4, 4096)
    assert not form(65536, 4, 64)     # 32 MB of rows 64 lanes a stream


def test_a_prefill_through_the_kernels_is_the_prefill_without(monkeypatch):
    """``xing4``'s prefill at a width of whole lane tiles: the logits with
    every half a kernel's against those with every half the ``jax.numpy``
    form's, float32."""
    spec = dict(vocab_size=97, dim=128, depth=2, dense_layers=1, streams=4,
                sinkhorn_iters=20, heads=4, q_rank=32, kv_rank=16, nope=16,
                rope_dim=8, v_dim=16, rope_theta=1e4, rope_factor=64.0,
                rope_original=16, mlp_dim=96, experts=16, experts_per_token=4,
                expert_dim=32, shared_dim=32, route_scale=2.0, rms_eps=1e-6)
    model, params = create_xing4_lm(dtype="float32", **spec)
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, 97, (1, 24)))
    length = jnp.asarray([21])

    def logits():
        return np.asarray(model.apply(params, tokens, length,
                                      method="prefill_logits")[0])

    plain = logits()
    monkeypatch.setattr(mhc, "ROWS_KERNEL_BYTES", 0)
    np.testing.assert_allclose(logits(), plain, atol=2e-4, rtol=0)
    assert "pallas_call" in str(jax.make_jaxpr(lambda: model.apply(
        params, tokens, length, method="prefill"))())


def test_the_kernels_vmem_is_what_validate_accounts():
    from ai4e_tpu.ops.pallas import validate
    for n, d in ((4, 4096), (4, 3584)):
        assert validate.mhc_rows_vmem_bytes(n, d) == max(
            mhc_rows.pre_vmem_bytes(n, d), mhc_rows.post_vmem_bytes(n, d))
        assert (validate.mhc_rows_vmem_bytes(n, d)
                <= validate.VMEM_PHYSICAL_BYTES // 2)
