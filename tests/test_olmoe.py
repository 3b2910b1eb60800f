"""The ``olmoe`` LM family (``models/olmoe.py``) against its plain reference
(``benchmark/references/olmoe.py``) at a small size on the CPU: logits of
prefill and of decode through the cache, the routing alone, the seeded
values, the step's report, and the family through the worker's own wiring.
"""

import asyncio
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

from ai4e_tpu.models.olmoe import OlmoeLM, create_olmoe_lm  # noqa: E402
from ai4e_tpu.ops import kv_pool  # noqa: E402
from benchmark.references import olmoe as reference  # noqa: E402

SPEC = dict(vocab_size=97, dim=64, depth=2, heads=4, experts=8,
            experts_per_token=2, expert_dim=32)
CACHE = 64

# Logits here deviate by ~0.8 over the vocabulary. float32: both sides
# compute in float32 and differ in the order of their sums. bfloat16: weights
# and cache are the same values on both sides, the system rounds every
# activation to 8 bits (2^-9 relative) through two layers and the head. The
# worst seen over 18 sequences is 1e-6 and 0.015; the limits are under three
# times that. Every fault of ``FAULTS`` below is more than three times
# outside the looser one — float8 weights, the nearest (0.18), among them.
TOLERANCE = {"float32": 1e-5, "bfloat16": 0.04}
FAULTS = ("float8", "no_qk_norm", "no_rope_on_k", "renormalised",
          "dropped_expert")
# The one thing rounding may change discontinuously is WHICH expert is a
# token's K-th: where the K-th and the next probability lie within bfloat16's
# rounding of each other the system may serve either, and the logits jump
# (here, with 2 experts of 8 a token, by up to 0.13). The sequences compared
# are those whose every routing decision is wider than that (no flip was
# seen above 0.0005).
ROUTING_GAP = 0.001


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def lm(request):
    model, params = create_olmoe_lm(dtype=request.param, **SPEC)
    return SimpleNamespace(model=model, params=params, dtype=request.param,
                           raw=params["params"])


def _routing_gap(raw, seq) -> float:
    """The narrowest K-th-to-next router probability gap of ``seq`` over
    its tokens and layers, read off the reference as it runs."""
    gaps, real = [], reference.route

    def spy(h, router, k):
        p = np.sort(np.asarray(jax.nn.softmax(h @ router, axis=-1)), axis=-1)
        gaps.append(float((p[:, -k] - p[:, -k - 1]).min()))
        return real(h, router, k)

    reference.route = spy
    try:
        reference.forward(raw, SPEC, seq)
    finally:
        reference.route = real
    return min(gaps)


def _sequences(raw=None, seed=0):
    """Three seeded token sequences; with ``raw``, three whose routing no
    rounding can flip (``ROUTING_GAP``)."""
    rng = np.random.default_rng(seed)
    out = []
    for n in (23, 40, 9):
        while True:
            seq = rng.integers(0, SPEC["vocab_size"], size=n).tolist()
            if raw is None or _routing_gap(raw, seq) >= ROUTING_GAP:
                break
        out.append(seq)
    return out


def _served_logits(lm, seq, prompt_len, slot, slots=3):
    """Logits of every position of ``seq`` as the serving path computes
    them: one prefill of the prompt (padded to a bucket) into ``slot`` of a
    pool of garbage, then one decode step a token, teacher-forced, the other
    slots riding along at position 0."""
    apply = lm.model.apply
    spec = lm.model.cache_spec().rows[0]
    dtype = spec.dtype
    rng = np.random.default_rng(slot)
    shape = kv_pool.pool_shape(spec, slots, CACHE)
    k = jnp.asarray(rng.standard_normal(shape), dtype)
    v = jnp.asarray(rng.standard_normal(shape), dtype)
    bucket = 16 if prompt_len <= 16 else 32
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :prompt_len] = seq[:prompt_len]
    logits, k_block, v_block = apply(
        lm.params, padded, np.asarray([prompt_len], np.int32),
        method="prefill_logits")
    out = [np.asarray(logits[0, :prompt_len], np.float32)]
    k, v = kv_pool.insert_block((k, v), (k_block, v_block), slot)
    step = jax.jit(lambda *a: apply(lm.params, *a, method="decode_logits"))
    for position in range(prompt_len, len(seq)):
        tokens = np.zeros((slots,), np.int32)
        positions = np.zeros((slots,), np.int32)
        tokens[slot], positions[slot] = seq[position], position
        logits, k, v = step(tokens, k, v, positions)
        out.append(np.asarray(logits[slot:slot + 1], np.float32))
    return np.concatenate(out)


def test_prefill_then_decode_logits_match_the_reference(lm):
    worst = 0.0
    for slot, seq in enumerate(_sequences(lm.raw)):
        want = reference.forward(lm.raw, SPEC, seq)
        got = _served_logits(lm, seq, prompt_len=len(seq) // 2, slot=slot)
        assert got.shape == want.shape
        worst = max(worst, float(np.abs(got - want).max()))
    assert worst < TOLERANCE[lm.dtype], worst


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_is_far_outside_the_tolerance(fault):
    """What each tolerance must catch, computed by the reference itself on
    the bfloat16 values: the faulty model's logits differ from the true
    ones by several times the looser limit."""
    _, params = create_olmoe_lm(**SPEC)
    seq = _sequences()[1]
    want = reference.forward(params["params"], SPEC, seq)
    wrong = reference.forward(params["params"], SPEC, seq, fault=fault)
    assert np.abs(wrong - want).max() > 3 * max(TOLERANCE.values())


@pytest.mark.parametrize("case", ["random", "ties"])
def test_routing_matches_the_reference(case):
    """Same experts, in the same order, with the same un-normalised
    weights; every row holds exactly K of them — also where the K-th and
    the next probabilities are EQUAL (two experts with one router column):
    the lower index wins on both sides."""
    model, params = create_olmoe_lm(dtype="float32", **SPEC)
    raw = jax.tree.map(np.asarray, params["params"])
    k = SPEC["experts_per_token"]
    if case == "ties":
        router = raw["layer0"]["router"].copy()
        router[:, 5] = router[:, 2]      # experts 2 and 5 always tie
        router[:, 7] = router[:, 2]      # ... and 7: a three-way tie
        raw["layer0"]["router"] = router
    h = np.random.default_rng(3).standard_normal(
        (50, SPEC["dim"])).astype(np.float32)
    layer = model.bind({"params": raw}).layers[0]
    top_e, gate = layer.route(jnp.asarray(h))
    top_e, gate = np.asarray(top_e), np.asarray(gate)
    want_e, want_p = reference.route(jnp.asarray(h),
                                     jnp.asarray(raw["layer0"]["router"]), k)
    np.testing.assert_array_equal(top_e, want_e)
    np.testing.assert_allclose(np.take_along_axis(gate, top_e, axis=-1),
                               want_p, rtol=1e-6)
    assert ((gate > 0).sum(axis=-1) == k).all()
    # not renormalised: a row's weights sum to its K probabilities, < 1
    np.testing.assert_allclose(gate.sum(axis=-1), want_p.sum(axis=-1),
                               rtol=1e-6)
    assert (gate.sum(axis=-1) < 0.999).all()
    if case == "ties":
        tied = np.isin(want_e, (2, 5, 7)).any(axis=-1)
        assert tied.any()
        # a tie that straddles the cut keeps the lower indices
        assert not (np.isin(want_e, 7).any(axis=-1)
                    & ~np.isin(want_e, 5).any(axis=-1)).any()


def test_every_row_reaches_all_of_its_experts_when_they_all_choose_the_same():
    """No capacity and no drop: 200 rows that all choose the same two
    experts get the reference's sum, each of them."""
    model, params = create_olmoe_lm(dtype="float32", **SPEC)
    raw = jax.tree.map(np.asarray, params["params"])
    router = np.zeros_like(raw["layer0"]["router"])
    router[:, 3], router[:, 6] = 0.5, 0.25    # the stream's mean picks them
    raw["layer0"]["router"] = router
    x = (1.0 + 0.1 * np.random.default_rng(4).standard_normal(
        (200, SPEC["dim"]))).astype(np.float32)
    layer = model.bind({"params": raw}).layers[0]
    y, top_e = layer._moe(jnp.asarray(x))
    assert set(np.asarray(top_e).ravel()) <= {3, 6}
    h = reference.rms_norm(jnp.asarray(x), raw["layer0"]["norm_post"], 1e-5)
    want = x + np.asarray(reference.moe(
        h, jax.tree.map(jnp.asarray, raw["layer0"]), 2))
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-4)


def test_seeded_values_are_the_same_small_integers_everywhere():
    """Every weight is ``centre + integer · 2^exponent`` with the integer
    in [-255, 255] — exact in bfloat16's 8 bits — and a second draw gives
    the same bits; every norm scale sits away from 1."""
    _, a = create_olmoe_lm(**SPEC)
    _, b = create_olmoe_lm(**SPEC)
    assert jax.tree.all(jax.tree.map(
        lambda x, y: bool((x == y).all()), a, b))
    w = np.asarray(a["params"]["layer1"]["w_down"], np.float32)
    unit = np.abs(w[w != 0]).min()
    assert float(np.log2(unit)).is_integer()
    assert np.abs(w / unit).max() <= 255
    assert (w / unit == np.round(w / unit)).all()
    layer = a["params"]["layer0"]
    for name in ("norm_in", "norm_post", "norm_q", "norm_k"):
        g = np.asarray(layer[name], np.float32)
        assert np.abs(g - 1.0).mean() > 0.05, name
    assert np.abs(np.asarray(a["params"]["norm_f"], np.float32)
                  - 1.0).mean() > 0.05


def test_step_report_counts_live_slots_only():
    model = OlmoeLM(**SPEC)
    k = SPEC["experts_per_token"]
    picks = np.zeros((SPEC["depth"], 4, k), np.int32)
    picks[0] = [[0, 1], [0, 2], [0, 3], [7, 7]]   # slot 3 is not live
    picks[1] = [[4, 5], [4, 5], [4, 5], [6, 6]]
    report = model.step_report(picks.reshape(-1),
                               [True, True, True, False])
    # layer 0: experts {0,1,2,3}, the fullest holds 3; layer 1: {4,5}, 3.
    assert report["experts_touched"] == (4 + 2) / 2
    mean_load = 3 * k / SPEC["experts"]
    assert report["expert_peak_load"] == pytest.approx(3 / mean_load)
    assert model.step_report(picks.reshape(-1), [False] * 4) == {}


def test_reference_counts_by_hand():
    """The decode step's operations and least bytes at the published
    widths, eight layers: the figures ISSUE 26 argues from."""
    spec = dict(family="olmoe", vocab_size=50304, dim=2048, depth=8,
                heads=16, experts=64, experts_per_token=8, expert_dim=1024)
    config = {"models": {"models": [spec]}}
    experts = 8 * 64 * 3 * 2048 * 1024 * 2
    attention = 8 * 4 * 2048 * 2048 * 2
    head = 2048 * 50304 * 2
    assert experts == pytest.approx(6.44e9, rel=1e-3)
    assert reference.weight_bytes(spec) == (
        experts + attention + head + 8 * 2048 * 64 * 2
        + (8 * 4 + 1) * 2048 * 2)
    flops, nbytes = reference.ops_and_bytes(config, 32, 10_000.0)
    per_slot = 8 * (4 * 2048 ** 2 + 2048 * 64 + 8 * 3 * 2048 * 1024) \
        + 2048 * 50304
    assert flops == 2.0 * per_slot * 32 + 4.0 * 2048 * 8 * 10_000.0
    kv_token = 2 * 8 * 2048 * 2
    assert nbytes == (reference.weight_bytes(spec) + 32 * 2048 * 2
                      + kv_token * (10_000.0 + 32))


def test_the_worker_serves_the_family_through_the_same_wiring(monkeypatch):
    """``"family": "olmoe"`` in a models spec: the same ``cli`` worker,
    ``DecodeEngine`` and ``PagedDecodeRuntime`` as ``seqformer-lm``; a
    bfloat16 pool; the two routing series observed from the step's own
    fetch, over live slots."""
    from ai4e_tpu.cli import build_worker
    from ai4e_tpu.metrics import MetricsRegistry
    # A registry of its own: the series below are counted from zero whatever
    # ran earlier in this process.
    monkeypatch.setattr("ai4e_tpu.service.app.DEFAULT_REGISTRY",
                        MetricsRegistry())
    from ai4e_tpu.config import FrameworkConfig
    from ai4e_tpu.runtime.decode import DecodeEngine
    from ai4e_tpu.runtime.kvcache import PagedDecodeRuntime
    config = FrameworkConfig()
    config.runtime.decode_enable = True
    config.runtime.kv_slots = 3
    config.runtime.kv_max_len = CACHE
    config.runtime.decode_prompt_buckets = (8,)
    worker, _batcher, _tm = build_worker(config, {
        "service_name": "w", "prefix": "v1/lm",
        "models": [dict(SPEC, family="olmoe", name="lm")]})
    engine, = worker.decode_engines
    backend = engine.backend
    assert type(engine) is DecodeEngine
    assert type(backend) is PagedDecodeRuntime
    assert backend.max_len == CACHE and backend.prompt_buckets == (8, CACHE)
    assert backend._rows[0].dtype == jnp.bfloat16
    assert backend.cache_nbytes() == 2 * 2 * 3 * 4 * CACHE * 16 * 2
    assert "/lm-stream-async" in worker.service.endpoints

    async def main():
        await engine.start()
        out = await asyncio.gather(engine.submit([1, 2, 3], 6),
                                   engine.submit([4, 5], 5))
        await engine.stop()
        engine.pool.check_conservation()
        return out

    a, b = asyncio.run(main())
    assert len(a) == 6 and len(b) == 5
    def series(name):
        (_, _, _, value), = engine.metrics._metrics[name].collect()
        return value["sum"], value["count"]

    touched, steps = series("ai4e_decode_experts_touched")
    assert steps == series("ai4e_decode_step_active_slots")[1] > 0
    assert SPEC["experts_per_token"] <= touched / steps <= SPEC["experts"]
    peak, _ = series("ai4e_decode_expert_peak_load")
    assert peak / steps >= 1.0


def test_an_unknown_key_of_the_spec_is_an_error():
    from ai4e_tpu.runtime.kvcache import build_lm_servable
    with pytest.raises(TypeError):
        build_lm_servable(family="olmoe", vocab_size=32, dim=16, heads=2,
                          depht=1)
    with pytest.raises(ValueError, match="unknown LM family"):
        build_lm_servable(family="olmo")
