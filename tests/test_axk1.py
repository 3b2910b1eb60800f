"""The ``axk1`` LM family (``models/axk1.py``) against its plain reference
(``benchmark/references/axk1.py``) at a small size on the CPU: logits of
prefill and of decode through the cache of latent rows on both sides of a
bucket's edge and PAST the length YaRN was fitted to, a slot's reuse, the
reference's faults, the shares of a small expert-parallel deployment, the
eight-of-192 route without a bias (and the group-limited reading as a field),
YaRN at factor 32, the cache's declaration, the scopes its programs open, the
family through the worker's own runtime and engine — and ``xing4`` through the
mixer the two families share (``models/latent.py``): its parameter tree, its
seeded values and its logits bit for bit what they were before the lift.
"""

import functools
import hashlib
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

from ai4e_tpu.models import axk1, dots3, glm5, ling3, xing4  # noqa: E402
from ai4e_tpu.models import experts as expert_layer  # noqa: E402
from ai4e_tpu.models import olmoe  # noqa: E402
from ai4e_tpu.models.axk1 import create_axk1_lm  # noqa: E402
from ai4e_tpu.models.latent import Latent  # noqa: E402
from ai4e_tpu.ops import kv_pool  # noqa: E402
from ai4e_tpu.runtime.families import LM_FAMILIES  # noqa: E402
from benchmark.references import axk1 as reference  # noqa: E402

# One dense layer and two expert layers; a rank's share of a four-rank
# deployment (experts 4-7 of 16); YaRN fitted to 16 positions, so the test's
# sequences of up to 50 run three times past what it was fitted to and every
# frequency of the four pairs is a blended or a divided one.
SPEC = dict(vocab_size=97, dim=64, depth=3, dense_layers=1, heads=4,
            q_rank=32, kv_rank=16, nope=16, rope_dim=8, v_dim=16,
            rope_theta=1e4, rope_factor=32.0, rope_original=16, mlp_dim=96,
            experts=16, experts_held=4, first_expert=4, experts_per_token=4,
            expert_dim=32, shared_dim=32, route_scale=2.5, rms_eps=1e-6)
CACHE = 64
BUCKETS = (8, 16, 32, 48)
# float32: both sides compute in float32 and differ in the order of their
# sums and in the absorbed form of the step: 2e-4 is ~100 x what the pair
# reads (2e-6) and a thousandth of what the mildest fault moves (0.6).
# bfloat16: the same weights, the system rounds every activation and the
# residual through three layers and now and then picks another fourth expert:
# that case guards the dtype's plumbing — and 1.0 is what float32 is NOT held
# to: computing the float32 case in bfloat16 reads ~0.1-0.5, a thousand times
# the float32 tolerance. The faults are held to the float32 pair.
TOLERANCE = {"float32": 2e-4, "bfloat16": 1.0}


@functools.lru_cache(maxsize=None)
def _family(dtype, **changed):
    """The model, its params and its two logits programs, compiled once a
    shape for the whole module (and built once a size)."""
    spec = dict(SPEC, **changed)
    model, params = create_axk1_lm(dtype=dtype, **spec)
    return SimpleNamespace(
        model=model, params=params, dtype=dtype, raw=params["params"],
        spec=spec,
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


def _sequence(seed, length):
    return np.random.default_rng(seed).integers(
        0, SPEC["vocab_size"], size=length).tolist()


# A prompt of one token, prompts on both sides of each bucket's edge (8, 16),
# one of a bucket's exact size, and decodes that go on long after — the last
# three prefill AND decode past ``rope_original`` = 16.
@pytest.mark.parametrize("prompt_len,decoded", [
    (1, 6), (7, 4), (8, 4), (9, 6), (15, 3), (16, 5), (17, 12), (32, 9),
    (41, 9)])
def test_prefill_then_decode_logits_match_the_reference(lm, prompt_len,
                                                        decoded):
    seq = _sequence(prompt_len, prompt_len + decoded)
    want = reference.forward(lm.raw, lm.spec, seq)
    got, _ = _served_logits(lm, seq, prompt_len)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOLERANCE[lm.dtype]


def test_a_slot_reused_after_a_longer_sequence_holds_nothing_of_it(lm32):
    long, short = _sequence(5, 40), _sequence(6, 14)
    _, pools = _served_logits(lm32, long, 30)
    got, _ = _served_logits(lm32, short, 6, pools=pools)
    want = reference.forward(lm32.raw, lm32.spec, short)
    assert np.abs(got - want).max() < TOLERANCE["float32"]


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_the_reference_faults_move_the_logits(lm32, fault):
    """Each wrong model the tolerance study computes is far outside the
    float32 pair's agreement: the comparison sees it."""
    seq = _sequence(3, 48)
    right = reference.forward(lm32.raw, lm32.spec, seq)
    wrong = reference.forward(lm32.raw, lm32.spec, seq, fault=fault)
    assert np.abs(right - wrong).max() > 100 * TOLERANCE["float32"]


@pytest.mark.parametrize("fault", ["no_yarn", "absorbed_scale",
                                   "group_limited"])
def test_a_stream_served_past_the_fitted_window_tells_the_readings_apart(
        lm32, fault):
    """Positions 16-49 lie past ``rope_original`` = 16: the SERVED logits
    there agree with the reference and disagree, by a hundred times the
    tolerance, with the model without YaRN, with the scores without ``m²``
    and with the group-limited route."""
    seq = _sequence(9, 50)
    got, _ = _served_logits(lm32, seq, 20)
    past = slice(SPEC["rope_original"], None)
    want = reference.forward(lm32.raw, lm32.spec, seq)
    assert np.abs(got - want)[past].max() < TOLERANCE["float32"]
    wrong = reference.forward(lm32.raw, lm32.spec, seq, fault=fault)
    assert np.abs(got - wrong)[past].max() > 100 * TOLERANCE["float32"]


def test_float32_served_in_bfloat16_fails_the_float32_tolerance():
    """What the float32 tolerance is for: the same network computed in the
    precision below is a thousand times outside it."""
    low = _family("bfloat16")      # the ``lm`` fixture's own
    seq = _sequence(2, 30)
    got, _ = _served_logits(low, seq, 17)
    want = reference.forward(low.raw, low.spec, seq)
    assert np.abs(got - want).max() > 100 * TOLERANCE["float32"]


def test_the_reference_reads_logits_from_a_position_on(lm32):
    seq = _sequence(4, 20)
    whole = reference.forward(lm32.raw, lm32.spec, seq)
    assert np.abs(reference.forward(lm32.raw, lm32.spec, seq, first=13)
                  - whole[13:]).max() < 1e-5


def test_the_reference_attends_in_blocks_as_it_does_whole(lm32, monkeypatch):
    """Queries a block at a time are only what memory needs: blocks of 8
    queries give what one block gives, from a position in a block on too."""
    seq = _sequence(8, 37)
    whole = reference.forward(lm32.raw, lm32.spec, seq)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 8)
    blocked = reference.forward(lm32.raw, lm32.spec, seq)
    assert np.abs(blocked - whole).max() < 1e-5
    assert np.abs(reference.forward(lm32.raw, lm32.spec, seq, first=21)
                  - whole[21:]).max() < 1e-5


# -- the shares of an expert-parallel layer ------------------------------------

def _ffn_part(family, x):
    """What an expert layer's FFN ADDS to ``x (rows, D)``, by the program."""
    out, _ = family.model.apply(
        family.params, x,
        method=lambda m, x: m.layers[1]._ffn(x, routed=False))
    return np.asarray(out - x)


def test_the_four_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Four ranks share a layer: the routed terms of the shares ``(0, 4)`` ..
    ``(12, 4)``, the shared expert — which every rank computes alike —
    counted once, are the layer that holds all sixteen: by the reference, and
    by the PROGRAM's layer against the reference's uncut one."""
    whole = _family("float32", experts_held=16, first_expert=0)
    layer = whole.raw["layer1"]
    x = jnp.asarray(np.random.default_rng(6).standard_normal((24, 64)),
                    jnp.float32)

    def w(a):
        return a.astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        h = reference.rms_norm(x, w(layer["norm_post"]), 1e-6)
        uncut = reference.ffn(h, layer, whole.spec, False, w, None)
        parts = sum(reference.ffn(h, layer, whole.spec, False, w, None,
                                  held=(first, 4))
                    for first in range(0, 16, 4))
        shared = reference.swiglu(h, w(layer["s_gate"]), w(layer["s_up"]),
                                  w(layer["s_down"]))
    np.testing.assert_allclose(parts, uncut, rtol=1e-5, atol=1e-5)
    served = 0.0
    for first in range(0, 16, 4):
        held = {name: layer[name][first:first + 4]
                for name in ("w_gate", "w_up", "w_down")}
        rank = SimpleNamespace(
            model=_family("float32", first_expert=first).model,
            params={"params": dict(whole.raw, layer1=dict(layer, **held))})
        served = served + _ffn_part(rank, x)
    np.testing.assert_allclose(served - 3 * np.asarray(shared), uncut,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(_ffn_part(whole, x), uncut, rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("first,held", [(0, 4), (12, 4), (14, 2)])
def test_any_share_matches_the_reference(first, held):
    share = _family("float32", first_expert=first, experts_held=held)
    seq = _sequence(first, 32)
    got, _ = _served_logits(share, seq, 25)
    want = reference.forward(share.raw, share.spec, seq)
    assert np.abs(got - want).max() < TOLERANCE["float32"]


def test_the_group_limited_reading_is_a_field_not_code():
    """``route_groups`` (8, 4) on the PROGRAM is the reference's
    ``group_limited`` fault: the reading not taken costs a field."""
    limited = _family("float32", route_groups=(8, 4))
    seq = _sequence(12, 30)
    got, _ = _served_logits(limited, seq, 21)
    plain = dict(limited.spec, route_groups=None)
    want = reference.forward(limited.raw, plain, seq, fault="group_limited")
    assert np.abs(got - want).max() < TOLERANCE["float32"]
    assert np.abs(got - reference.forward(limited.raw, limited.spec,
                                          seq)).max() < TOLERANCE["float32"]
    assert np.abs(got - reference.forward(limited.raw, plain, seq)).max() \
        > 100 * TOLERANCE["float32"]
    for wrong in ((3, 2), (8, 1), (8, 9)):   # 16 % 3; 1 x 2 < 4 picks; 9 > 8
        with pytest.raises(ValueError):
            create_axk1_lm(**dict(SPEC, route_groups=wrong))


# -- YaRN and the route at the published sizes -----------------------------------

def test_yarn_at_factor_32_is_the_formulas():
    inv_freq = olmoe.yarn_inv_freq(64, 10000.0, 32.0, 4096, 32.0, 1.0)
    low = int(np.floor(64 * np.log(4096 / (2 * np.pi * 32))
                       / (2 * np.log(10000))))
    high = int(np.ceil(64 * np.log(4096 / (2 * np.pi * 1))
                       / (2 * np.log(10000))))
    assert (low, high) == (10, 23)
    for i in (0, 10, 16, 23, 31):
        f = 10000.0 ** (-2 * i / 64)
        r = min(max((i - low) / (high - low), 0.0), 1.0)
        assert inv_freq[i] == pytest.approx(f * (1 - r) + f / 32 * r,
                                            rel=1e-6)
    # the reference writes the formulas a second time
    spec = dict(SPEC, rope_dim=64, nope=128, rope_original=4096)
    again, factor, scale = reference.yarn(spec)
    assert np.allclose(again, inv_freq, rtol=1e-6) and factor == 1.0
    m = 0.1 * np.log(32) + 1
    assert m * m == pytest.approx(1.8133, abs=1e-4)
    assert scale == pytest.approx(192 ** -0.5 * m * m)
    mixer = Latent(dim=7168, heads=64, q_rank=1536, kv_rank=512, nope=128,
                   rope_dim=64, v_dim=128, theta=1e4, rope_factor=32.0,
                   rope_original=4096, beta_fast=32.0, beta_slow=1.0,
                   mscale=1.0, mscale_all_dim=1.0, eps=1e-6,
                   dtype=jnp.bfloat16)
    assert mixer.scale == pytest.approx(scale) and mixer.row == 640
    assert reference.yarn(spec, "absorbed_scale")[2] == pytest.approx(
        192 ** -0.5)
    plain, _, unscaled = reference.yarn(spec, "no_yarn")
    assert unscaled == pytest.approx(192 ** -0.5)
    assert plain[31] == pytest.approx(32 * inv_freq[31], rel=1e-6)


def test_the_eight_of_192_route_has_no_bias_and_weighs_to_two_and_a_half():
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.standard_normal((40, 64)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((64, 192)) * 0.3, jnp.float32)
    top_e, top_p = expert_layer.route(h, router, 8, True, scoring="sigmoid",
                                      scale=2.5)
    want_e, want_p = reference.route(np.asarray(h), np.asarray(router), 8,
                                     2.5)
    assert np.array_equal(np.asarray(top_e), want_e)
    assert np.abs(np.asarray(top_p) - want_p).max() < 1e-6
    assert np.abs(np.asarray(top_p).sum(axis=-1) - 2.5).max() < 1e-5
    # the reading not taken: the best 4 of 8 groups of 24 by their two largest
    lim_e, lim_p = expert_layer.route(h, router, 8, True, scoring="sigmoid",
                                      scale=2.5, groups=(8, 4))
    ref_e, ref_p = reference.route(np.asarray(h), np.asarray(router), 8, 2.5,
                                   groups=reference.GROUP_LIMIT)
    assert np.array_equal(np.asarray(lim_e), ref_e)
    assert np.abs(np.asarray(lim_p) - ref_p).max() < 1e-6
    assert (np.asarray([len(set(row // 24)) for row in ref_e]) <= 4).all()
    assert not np.array_equal(ref_e, want_e)
    # a sixteenth held: a prompt's window is 1.5 x the even share, one pass
    assert expert_layer.window_rows(8192, 8, 12, 192) == 6144
    assert int(expert_layer.window_passes(top_e, 12, 192)) == 1


# -- the declaration, the scopes, the runtime ------------------------------------

def test_cache_spec_declares_one_kind_of_rows():
    model, _ = create_axk1_lm(dtype="bfloat16", **SPEC)
    spec = model.cache_spec()
    assert spec.state == () and spec.live == ()
    assert [(r.name, r.layers, r.width, r.length, r.kind, r.select, r.whole)
            for r in spec.rows] == [
        ("latent", 3, 128, None, "latent", None, False)]
    assert kv_pool.rows_nbytes(spec.rows, 3, 64) == 2 * 3 * 3 * 64 * 128
    assert kv_pool.prefill_pairs(spec.rows, 19) == {"latent": 190}


def _stacks(jaxpr, out):
    for eqn in jaxpr.eqns:
        out.update(str(eqn.source_info.name_stack).split("/"))
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _stacks(inner, out)
    return out


def test_trace_scopes_names_every_scope_the_programs_open(lm32):
    """The names under which a trace's reader books this family's device
    time: exactly the known scopes (any LM family's) that its prefill, step
    and insert programs open."""
    model, params = lm32.model, lm32.params
    pools = _garbage_cache(model, 3, 0)
    tokens, length = np.zeros((1, 16), np.int32), np.ones((1,), np.int32)
    opened = _stacks(jax.make_jaxpr(lambda *a: model.apply(
        params, *a, method="prefill"))(tokens, length).jaxpr, set())
    opened |= _stacks(jax.make_jaxpr(lambda *a: model.apply(
        params, *a, method="decode_step"))(
            np.zeros((3,), np.int32), *pools, {},
            np.zeros((3,), np.int32)).jaxpr, set())
    _, block, _ = lm32.prefill(tokens, length)
    opened |= _stacks(jax.make_jaxpr(lambda p, b: kv_pool.insert_block(
        p, b, 1))(pools, (block,)).jaxpr, set())
    known = set().union(*(family.TRACE_SCOPES for family in (
        axk1, dots3, glm5, ling3, xing4)))
    assert opened & known == set(axk1.TRACE_SCOPES)
    assert len(axk1.TRACE_SCOPES) == 12


def test_the_family_is_registered_and_holds_no_copy_of_the_mixer():
    assert "axk1" in LM_FAMILIES
    models = os.path.join(REPO, "ai4e_tpu", "models")
    for name in ("axk1.py", "xing4.py"):
        source = open(os.path.join(models, name)).read()
        assert "Latent.of(self)" in source
        for copied in ("def _down", "def _queries", "def _out", "w_dkv",
                       "yarn_inv_freq(", "latent_decode_attention("):
            assert copied not in source, (name, copied)
    runtime = os.path.join(REPO, "ai4e_tpu", "runtime")
    named = [name for name in os.listdir(runtime) if name.endswith(".py")
             and "axk1" in open(os.path.join(runtime, name)).read()]
    assert named == ["families.py"]


def _runtime(**kwargs):
    from ai4e_tpu.runtime.kvcache import PagedDecodeRuntime, build_lm_servable
    lm = build_lm_servable("axk1", max_len=CACHE, dtype="float32", **SPEC)
    return PagedDecodeRuntime(lm, slots=3, prompt_buckets=BUCKETS[:2],
                              **kwargs)


def test_the_runtime_serves_the_family_and_counts_its_cache():
    """Through ``PagedDecodeRuntime``: the ids of prefill + steps are the
    reference's argmax, the launch reports the latent bytes, the report
    carries the routing series over the four HELD experts, and nothing
    compiles after ``warm()``."""
    runtime = _runtime()
    runtime.warm()
    told = []
    runtime.phase_hook = lambda phase, seconds: told.append(phase)
    prompt = _sequence(11, 13)
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
                                "held_picks_share"}
    assert 0.0 <= step.report["held_picks_share"] <= 1.0
    assert step.report["experts_touched"] <= 4.0
    assert runtime.report_kinds == ("first", "extra")
    assert runtime.prefill_report(13) == {
        "tokens": {"real": 13, "padded": 16},
        "pairs": {"latent": 13 * 14 // 2}}
    assert runtime.cache_nbytes() == kv_pool.rows_nbytes(
        runtime.rows_spec(), 3, CACHE)


def test_step_report_reads_the_live_slots_and_the_held_experts_alone():
    model, _ = create_axk1_lm(dtype="float32", **SPEC)   # holds experts 4-7
    picks = np.zeros((2, 3, 4), np.int32)       # two expert layers
    picks[:, 0] = [4, 5, 6, 7]                  # slot 0 is dead
    picks[:, 1] = [1, 2, 4, 15]
    picks[:, 2] = [4, 5, 9, 10]
    report = model.step_report(picks.reshape(-1), [False, True, True])
    assert report["experts_touched"] == 2.0                 # 4 and 5
    assert report["held_picks_share"] == pytest.approx(3 / 8)
    assert report["expert_peak_load"] == pytest.approx(2 / (2 * 4 / 16))
    assert model.step_report(picks.reshape(-1), [False] * 3) == {}
    assert set(model.step_report_series) == set(report)


def test_the_engine_exposes_the_routing_series_and_counts_the_cache():
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
    for series in ("experts_touched", "expert_peak_load", "held_picks_share"):
        assert f"ai4e_decode_{series}_count" in text
    kinds = reg.counter("ai4e_decode_cache_bytes_total")
    assert kinds.value(model="lm", kind="latent") > 0
    tokens = reg.counter("ai4e_decode_prefill_tokens_total")
    assert (tokens.value(model="lm", kind="real"),
            tokens.value(model="lm", kind="padded")) == (11, 16)
    pairs = reg.counter("ai4e_decode_prefill_pairs_total")
    assert pairs.value(model="lm", kind="latent") == 66
    passes = reg.counter("ai4e_decode_prefill_expert_passes_total")
    assert passes.value(model="lm", kind="first") <= 2
    assert passes.value(model="lm", kind="extra") == 0


# -- xing4 through the lifted mixer ------------------------------------------------

# ``tests/test_xing4.py``'s size. The digests are the PARENT's (commit
# 169d7d7, ``models/xing4.py`` with the mixer in its ``_Layer``): sha256 over
# every parameter's path, dtype, shape and bytes in the tree's order, and over
# the float32 logits of a 17-token prefill and 12 decode steps.
XING4_SPEC = dict(vocab_size=97, dim=64, depth=3, dense_layers=1, streams=4,
                  sinkhorn_iters=20, heads=4, q_rank=32, kv_rank=16, nope=16,
                  rope_dim=8, v_dim=16, rope_theta=1e4, rope_factor=64.0,
                  rope_original=16, mlp_dim=96, experts=16,
                  experts_per_token=4, expert_dim=32, shared_dim=32,
                  route_scale=2.0, rms_eps=1e-6)
XING4_PARENT = {
    "float32": (
        "ffde409bebee30e80af05fcea6ec39f36a3047be32d743597eb3824a6610eae5",
        "4e264859e5b1eff747ffc14d18bac01a9274b78857b0858bc6c40fc49b54e5e9"),
    "bfloat16": (
        "9c5cb035f891dba99b07f1fba81dbc454cca5d65c5ba6c6652c707648b22eed5",
        "6973a1bb7f147a1788b480e0717376b306cc82fe799cebfdb02a1d3bb0ffb4bd")}
XING4_LAYER1 = [
    "hc_attn_alpha", "hc_attn_bias", "hc_attn_phi", "hc_ffn_alpha",
    "hc_ffn_bias", "hc_ffn_phi", "norm_in", "norm_kv", "norm_post", "norm_q",
    "router", "router_bias", "s_down", "s_gate", "s_up", "w_dkv", "w_down",
    "w_dq", "w_gate", "w_o", "w_uk", "w_up", "w_uq", "w_uv"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_xing4_through_the_shared_mixer_is_bit_for_bit_what_it_was(dtype):
    model, params = xing4.create_xing4_lm(dtype=dtype, **XING4_SPEC)
    raw = params["params"]
    assert sorted(raw["layer1"]) == XING4_LAYER1
    digest = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(raw)[0]
    assert len(leaves) == 70
    for path, leaf in leaves:
        name = "/".join(str(getattr(key, "key", key)) for key in path)
        for part in (name, str(leaf.dtype), str(leaf.shape)):
            digest.update(part.encode())
        digest.update(np.asarray(leaf).tobytes())
    assert digest.hexdigest() == XING4_PARENT[dtype][0]
    family = SimpleNamespace(
        model=model,
        prefill=jax.jit(lambda *a: model.apply(params, *a,
                                               method="prefill_logits")),
        step=jax.jit(lambda *a: model.apply(params, *a,
                                            method="decode_logits")))
    seq = np.random.default_rng(17).integers(0, 97, size=29).tolist()
    got, _ = _served_logits(family, seq, 17)
    assert hashlib.sha256(np.ascontiguousarray(got).tobytes()).hexdigest() \
        == XING4_PARENT[dtype][1]
