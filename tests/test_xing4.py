"""The ``xing4`` LM family (``models/xing4.py``) against its plain reference
(``benchmark/references/xing4.py``) at a small size on the CPU: logits of
prefill and of decode through the cache of latent rows on both sides of a
bucket's edge, a slot's reuse, the reference's faults, the hyper-connections'
own operations (``ops/mhc.py``), YaRN's frequencies and scale, the four-of-64
route, the cache's declaration, and the family through the worker's own
runtime and engine.
"""

import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from ai4e_tpu.models import experts as expert_layer  # noqa: E402
from ai4e_tpu.models import olmoe  # noqa: E402
from ai4e_tpu.models.xing4 import create_xing4_lm  # noqa: E402
from ai4e_tpu.ops import kv_pool, mhc  # noqa: E402
from benchmark.references import xing4 as reference  # noqa: E402

# One dense layer and two expert layers; YaRN fitted to 16 positions, so
# the test's sequences of up to 41 run past what it was fitted to and every
# frequency of the four pairs is a blended one or a divided one.
SPEC = dict(vocab_size=97, dim=64, depth=3, dense_layers=1, streams=4,
            sinkhorn_iters=20, heads=4, q_rank=32, kv_rank=16, nope=16,
            rope_dim=8, v_dim=16, rope_theta=1e4, rope_factor=64.0,
            rope_original=16, mlp_dim=96, experts=16, experts_per_token=4,
            expert_dim=32, shared_dim=32, route_scale=2.0, rms_eps=1e-6)
CACHE = 64
BUCKETS = (8, 16, 32)
# float32: both sides compute in float32 and differ in the order of their
# sums and in the absorbed form of the step. bfloat16: the same weights, the
# system rounds every activation and its four streams through three layers and
# now and then picks another fourth expert: that case guards the dtype's
# plumbing; the faults are held to the float32 pair.
TOLERANCE = {"float32": 2e-4, "bfloat16": 1.0}


def _family(dtype):
    """The model, its params and its two logits programs, compiled once a
    shape for the whole module."""
    model, params = create_xing4_lm(dtype=dtype, **SPEC)
    return SimpleNamespace(
        model=model, params=params, dtype=dtype, raw=params["params"],
        prefill=jax.jit(lambda *a: model.apply(params, *a,
                                               method="prefill_logits")),
        step=jax.jit(lambda *a: model.apply(params, *a,
                                            method="decode_logits")))


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def lm(request):
    return _family(request.param)


@pytest.fixture(scope="module")
def lm32():
    return _family("float32")


def _garbage_cache(model, slots, seed):
    """Pools of garbage: whatever a slot held before."""
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal(
        kv_pool.pool_shape(rows, slots, CACHE)), rows.dtype)
        for rows in model.cache_spec().rows)


def _served_logits(lm, seq, prompt_len, slot=1, slots=3, pools=None):
    """Logits of every position of ``seq`` as the serving path computes them:
    one prefill of the prompt (padded to its bucket) into ``slot`` of a cache
    of garbage, then one decode step a token, teacher-forced, the other slots
    riding along at position 0."""
    pools = pools or _garbage_cache(lm.model, slots, slot)
    bucket = next(b for b in BUCKETS if b >= prompt_len)
    padded = np.full((1, bucket), 7, np.int32)   # past the prompt: not zeros
    padded[0, :prompt_len] = seq[:prompt_len]
    logits, *blocks, _ = lm.prefill(padded,
                                    np.asarray([prompt_len], np.int32))
    out = [np.asarray(logits[0, :prompt_len], np.float32)]
    pools = kv_pool.insert_block(pools, tuple(blocks), slot)
    for position in range(prompt_len, len(seq)):
        tokens = np.zeros((slots,), np.int32)
        positions = np.zeros((slots,), np.int32)
        tokens[slot], positions[slot] = seq[position], position
        logits, *pools, _ = lm.step(tokens, *pools, {}, positions)
        out.append(np.asarray(logits[slot:slot + 1], np.float32))
    return np.concatenate(out), tuple(pools)


# A prompt of one token, prompts on both sides of each bucket's edge (8, 16),
# one of a bucket's exact size, and a decode that goes on long after.
@pytest.mark.parametrize("prompt_len,decoded", [
    (1, 6), (7, 4), (8, 4), (9, 6), (15, 3), (16, 5), (17, 12), (32, 9)])
def test_prefill_then_decode_logits_match_the_reference(lm, prompt_len,
                                                        decoded):
    rng = np.random.default_rng(prompt_len)
    seq = rng.integers(0, SPEC["vocab_size"],
                       size=prompt_len + decoded).tolist()
    want = reference.forward(lm.raw, SPEC, seq)
    got, _ = _served_logits(lm, seq, prompt_len)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOLERANCE[lm.dtype]


def test_a_slot_reused_after_a_longer_sequence_holds_nothing_of_it(lm32):
    rng = np.random.default_rng(5)
    long = rng.integers(0, 97, size=40).tolist()
    short = rng.integers(0, 97, size=14).tolist()
    _, pools = _served_logits(lm32, long, 30)
    got, _ = _served_logits(lm32, short, 6, pools=pools)
    want = reference.forward(lm32.raw, SPEC, short)
    assert np.abs(got - want).max() < TOLERANCE["float32"]


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_the_reference_faults_move_the_logits(lm32, fault):
    """Each wrong model the tolerance study computes is far outside the
    float32 pair's agreement: the comparison sees it."""
    seq = np.random.default_rng(3).integers(0, 97, size=40).tolist()
    right = reference.forward(lm32.raw, SPEC, seq)
    wrong = reference.forward(lm32.raw, SPEC, seq, fault=fault)
    assert np.abs(right - wrong).max() > 10 * TOLERANCE["float32"]


def test_the_reference_reads_logits_from_a_position_on(lm32):
    seq = np.random.default_rng(4).integers(0, 97, size=20).tolist()
    whole = reference.forward(lm32.raw, SPEC, seq)
    assert np.array_equal(reference.forward(lm32.raw, SPEC, seq, first=13),
                          whole[13:])


def test_streams_and_cache_hold_the_dtype_and_the_coefficients_float32():
    model, params = create_xing4_lm(dtype="bfloat16", **SPEC)
    layer = params["params"]["layer1"]
    assert layer["hc_attn_phi"].dtype == jnp.bfloat16
    assert layer["hc_attn_phi"].shape == (4 * 64, 4 + 4 + 16)
    assert layer["hc_ffn_alpha"].dtype == layer["hc_ffn_bias"].dtype == (
        jnp.float32)
    assert layer["router_bias"].dtype == jnp.float32
    x = jnp.ones((3, 4, 64), jnp.bfloat16)
    u, h_post, h_res = mhc.pre(x, {"phi": layer["hc_attn_phi"],
                                   "alpha": layer["hc_attn_alpha"],
                                   "bias": layer["hc_attn_bias"]})
    assert u.dtype == jnp.bfloat16
    assert h_post.dtype == h_res.dtype == jnp.float32
    assert mhc.post(x, u, h_post, h_res).dtype == jnp.bfloat16
    # the seeded init: b_res away from a multiple of the identity, and the
    # two sublayers' parameters their own
    b_res = np.asarray(layer["hc_attn_bias"][8:]).reshape(4, 4)
    assert np.abs(b_res - np.diag(np.diag(b_res))).max() > 0.1
    assert np.ptp(np.diag(b_res)) > 0.1
    assert not np.array_equal(layer["hc_attn_bias"], layer["hc_ffn_bias"])


# -- the hyper-connections' operations -----------------------------------------

def _hyper(rng, n=4, d=32, alpha=1.0):
    return {"phi": jnp.asarray(rng.standard_normal((n * d, 2 * n + n * n))
                               / np.sqrt(n * d), jnp.float32),
            "alpha": jnp.full((3,), alpha, jnp.float32),
            "bias": jnp.asarray(rng.standard_normal(2 * n + n * n) * 0.5,
                                jnp.float32)}


def test_h_res_is_doubly_stochastic_after_twenty_iterations_and_not_one():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((50, 4, 32)), jnp.float32)
    params = _hyper(rng, alpha=0.5)
    _, _, h_res = mhc.pre(x, params, iters=20)
    assert h_res.shape == (50, 4, 4) and float(h_res.min()) > 0
    assert np.abs(np.asarray(h_res.sum(axis=-1)) - 1).max() < 1e-5
    assert np.abs(np.asarray(h_res.sum(axis=-2)) - 1).max() < 1e-5
    assert float(mhc.balance_error(h_res).max()) < 1e-5
    _, _, once = mhc.pre(x, params, iters=1)
    assert float(mhc.balance_error(once).max()) > 1e-2
    # the columns are exact after any iteration (they come last): the rows'
    # sums are what one iteration leaves off
    assert np.abs(np.asarray(once.sum(axis=-2)) - 1).max() < 1e-5
    # a token its own matrix
    assert float(jnp.abs(h_res[0] - h_res[1]).max()) > 1e-3


def test_the_identity_coefficients_make_the_plain_residual_on_stream_0():
    """``α = 0`` and the biases that saturate every coefficient: ``H_res`` =
    I, ``H_pre`` = ``H_post`` = e_0 — ``post(pre(.))`` is ``x + F(x)`` on
    stream 0 and leaves the other streams as they were."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((9, 4, 32)), jnp.float32)
    params = _hyper(rng, alpha=0.0)
    params["bias"] = jnp.concatenate([
        jnp.asarray([30.0, -30, -30, -30]), jnp.asarray([0.0, -30, -30, -30]),
        (60.0 * jnp.eye(4) - 30.0).reshape(-1)])
    u, h_post, h_res = mhc.pre(x, params)
    assert np.abs(np.asarray(u) - np.asarray(x[:, 0])).max() < 1e-5
    y = jnp.tanh(u) * 3.0
    out = np.asarray(mhc.post(x, y, h_post, h_res))
    assert np.abs(out[:, 0] - np.asarray(x[:, 0] + y)).max() < 1e-5
    assert np.abs(out[:, 1:] - np.asarray(x[:, 1:])).max() < 1e-5


def test_the_clamp_holds_exp_finite_in_float32():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((6, 4, 32)), jnp.float32)
    params = _hyper(rng, alpha=1e6)      # logits of ±millions before the clamp
    u, h_post, h_res = mhc.pre(x, params)
    for a in (u, h_post, h_res):
        assert bool(jnp.isfinite(a).all())
    assert float(h_res.max()) <= 1.0 + 1e-5 and float(h_res.min()) >= 0.0
    unclamped = mhc.sinkhorn(jnp.full((1, 4, 4), 100.0).at[0, 0, 0].set(
        -100.0), 20, 1e-6)
    assert not bool(jnp.isfinite(unclamped).all())   # what the clamp is for
    clamped = mhc.sinkhorn(jnp.clip(jnp.full((1, 4, 4), 100.0).at[
        0, 0, 0].set(-100.0), -30, 30), 20, 1e-6)
    assert bool(jnp.isfinite(clamped).all())


# -- YaRN ----------------------------------------------------------------------

def test_yarn_frequencies_are_the_formulas():
    inv_freq = olmoe.yarn_inv_freq(64, 10000.0, 64.0, 4096, 32.0, 1.0)
    assert inv_freq.shape == (32,) and inv_freq.dtype == np.float32
    low = int(np.floor(64 * np.log(4096 / (2 * np.pi * 32))
                       / (2 * np.log(10000))))
    high = int(np.ceil(64 * np.log(4096 / (2 * np.pi * 1))
                       / (2 * np.log(10000))))
    assert (low, high) == (10, 23)
    for i in (0, 10, 16, 23, 31):
        f = 10000.0 ** (-2 * i / 64)
        r = min(max((i - low) / (high - low), 0.0), 1.0)
        assert inv_freq[i] == pytest.approx(f * (1 - r) + f / 64 * r,
                                            rel=1e-6)
    assert inv_freq[0] == 1.0 and inv_freq[31] == pytest.approx(
        10000.0 ** (-62 / 64) / 64, rel=1e-6)
    # the reference writes the formulas a second time
    spec = dict(SPEC, rope_dim=64, nope=128, rope_original=4096)
    again, factor, scale = reference.yarn(spec)
    assert np.allclose(again, inv_freq, rtol=1e-6) and factor == 1.0
    m = 0.1 * np.log(64) + 1
    assert olmoe.yarn_mscale(64.0) == pytest.approx(m)
    assert olmoe.yarn_mscale(1.0) == 1.0
    assert scale == pytest.approx(192 ** -0.5 * m * m)
    assert m * m == pytest.approx(2.005, abs=1e-3)


def test_the_softmax_scale_carries_m_squared_and_rope_keeps_its_default():
    model, params = create_xing4_lm(dtype="float32", **SPEC)
    layer = model.bind(params).layers[0]
    m = 0.1 * np.log(64) + 1
    assert layer.scale == pytest.approx((16 + 8) ** -0.5 * m * m)
    # ``rope`` without ``inv_freq`` is what it computed before the argument
    x = jnp.asarray(np.random.default_rng(0).standard_normal((5, 2, 8)),
                    jnp.float32)
    position = jnp.arange(5)
    plain = olmoe.rope(x, position, 1e4)
    same = olmoe.rope(x, position, None,
                      1e4 ** (-np.arange(4, dtype=np.float32) / 4))
    assert np.abs(np.asarray(plain - same)).max() < 1e-6
    scaled = olmoe.rope(x, position, None, olmoe.yarn_inv_freq(
        8, 1e4, 64.0, 16, 32.0, 1.0))
    assert np.abs(np.asarray(plain - scaled)).max() > 1e-2


# -- the route, the declaration, the runtime -----------------------------------

def test_the_four_of_64_route_picks_by_score_plus_bias_and_weighs_by_score():
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.standard_normal((40, 64)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((64, 64)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(64) * 0.2, jnp.float32)
    top_e, top_p = expert_layer.route(h, router, 4, True, scoring="sigmoid",
                                      bias=bias, scale=2.0)
    want_e, want_p = reference.route(np.asarray(h), np.asarray(router),
                                     np.asarray(bias), 4, 2.0)
    assert np.array_equal(np.asarray(top_e), want_e)
    assert np.abs(np.asarray(top_p) - want_p).max() < 1e-6
    assert np.abs(np.asarray(top_p).sum(axis=-1) - 2.0).max() < 1e-5
    plain_e, _ = expert_layer.route(h, router, 4, True, scoring="sigmoid")
    assert not np.array_equal(np.asarray(plain_e), want_e)   # the bias bites
    # every expert held and four picked: a prefill's window is every pair,
    # in one pass
    assert expert_layer.window_rows(2048, 4, 64, 64) == 2048 * 4
    assert int(expert_layer.window_passes(top_e, 64, 64)) == 1


def test_cache_spec_declares_one_kind_of_rows():
    model, _ = create_xing4_lm(dtype="bfloat16", **SPEC)
    spec = model.cache_spec()
    assert spec.state == () and spec.live == ()
    assert [(r.name, r.layers, r.width, r.length, r.kind, r.select, r.whole)
            for r in spec.rows] == [
        ("latent", 3, 128, None, "latent", None, False)]
    assert kv_pool.rows_nbytes(spec.rows, 3, 64) == 2 * 3 * 3 * 64 * 128
    attended, nbytes, selected = kv_pool.step_reads(
        spec.rows, 3, 64, [0, 19, 3], [False, True, True], 48)
    assert attended == 64 + 64 + 2 and selected is None
    assert nbytes == {"latent": 3 * 128 * 2 * (attended + 2)}
    assert kv_pool.prefill_pairs(spec.rows, 19) == {"latent": 190}


def _runtime(**kwargs):
    from ai4e_tpu.runtime.kvcache import PagedDecodeRuntime, build_lm_servable
    lm = build_lm_servable("xing4", max_len=CACHE, dtype="float32", **SPEC)
    return PagedDecodeRuntime(lm, slots=3, prompt_buckets=BUCKETS[:2],
                              **kwargs)


def test_the_runtime_serves_the_family_and_counts_its_cache():
    """Through ``PagedDecodeRuntime``: the ids of prefill + steps are the
    reference's argmax, the launch reports the latent bytes, the report
    carries the routing series and ``mhc_balance_error``, and nothing
    compiles after ``warm()``."""
    runtime = _runtime()
    runtime.warm()
    told = []
    runtime.phase_hook = lambda phase, seconds: told.append(phase)
    prompt = np.random.default_rng(11).integers(0, 97, size=13).tolist()
    out = [runtime.prefill_into(2, prompt)]
    for i in range(9):
        fresh, positions, active = [None] * 3, [0] * 3, [False] * 3
        fresh[2], positions[2], active[2] = out[-1], len(prompt) + i, True
        step = runtime.fetch(runtime.launch(fresh, positions, active))
        out.append(step.ids[2])
    assert "compile" not in told
    logits = reference.forward(runtime.servable.params["params"], SPEC,
                               prompt + out[:-1])
    assert out == logits[len(prompt) - 1:].argmax(axis=-1).tolist()
    assert set(step.cache_bytes) == {"latent", "state"}
    assert step.selected is None and step.state_bytes == {}
    assert set(step.report) == {"experts_touched", "expert_peak_load",
                                "held_picks_share", "mhc_balance_error"}
    assert step.report["held_picks_share"] == 1.0
    assert step.report["experts_touched"] == 4.0     # one live slot, four
    assert 0 <= step.report["mhc_balance_error"] < 1e-2
    assert runtime.report_kinds == ("first", "extra")
    assert runtime.prefill_report(13) == {
        "tokens": {"real": 13, "padded": 16},
        "pairs": {"latent": 13 * 14 // 2}}
    assert runtime.cache_nbytes() == kv_pool.rows_nbytes(
        runtime.rows_spec(), 3, CACHE)


def test_step_report_reads_the_live_slots_alone():
    model, _ = create_xing4_lm(dtype="float32", **SPEC)
    picks = np.zeros((2, 3, 4), np.int32)       # two expert layers
    picks[:, 1] = [1, 2, 3, 4]
    picks[:, 2] = [1, 2, 5, 6]
    error = np.asarray([0.5, 2e-6, 3e-5], np.float32)   # slot 0 is dead
    extra = np.concatenate([picks.reshape(-1), error.view(np.int32)])
    report = model.step_report(extra, [False, True, True])
    assert report["mhc_balance_error"] == pytest.approx(3e-5)
    assert report["experts_touched"] == 6.0
    assert model.step_report(extra, [False] * 3) == {}
    assert set(model.step_report_series) == set(report)


def test_the_engine_exposes_the_balance_error_and_counts_the_cache():
    import asyncio

    from ai4e_tpu.metrics.registry import MetricsRegistry
    from ai4e_tpu.runtime.decode import DecodeEngine

    async def main():
        reg = MetricsRegistry()
        engine = DecodeEngine(_runtime(), metrics=reg)
        await engine.start()
        try:
            await engine.submit([5, 9, 12, 4, 4, 8, 1, 2, 3, 6, 7], 6)
        finally:
            await engine.stop()
        return reg

    reg = asyncio.run(main())
    text = reg.render_prometheus()
    assert "ai4e_decode_mhc_balance_error_count" in text
    assert "ai4e_decode_experts_touched_count" in text
    kinds = reg.counter("ai4e_decode_cache_bytes_total")
    assert kinds.value(model="lm", kind="latent") > 0
    tokens = reg.counter("ai4e_decode_prefill_tokens_total")
    assert (tokens.value(model="lm", kind="real"),
            tokens.value(model="lm", kind="padded")) == (11, 16)
    pairs = reg.counter("ai4e_decode_prefill_pairs_total")
    assert pairs.value(model="lm", kind="latent") == 66
    passes = reg.counter("ai4e_decode_prefill_expert_passes_total")
    assert passes.value(model="lm", kind="first") == 2
    assert passes.value(model="lm", kind="extra") == 0
