"""The ``granite-hybrid`` LM family (``models/granite_hybrid.py``) against its
plain reference (``benchmark/references/granite_hybrid.py``) at a small size
on the CPU: logits of prefill and of decode through the cache (K/V rows AND
recurrent state), the chunked scan against the token-by-token recurrence, the
convolution's tail, a slot's reuse, each multiplier and each part of the
Mamba-2 mixer as something the comparison sees, the tied table, the state
counters and the prefills a tick admits, the counts of the roofline, and the
family through the worker's own wiring.
"""

import asyncio
import json
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

from ai4e_tpu.models import granite_hybrid  # noqa: E402
from ai4e_tpu.models.granite_hybrid import create_granite_hybrid_lm  # noqa: E402
from ai4e_tpu.ops import kv_pool, state_pool  # noqa: E402
from benchmark.references import granite_hybrid as reference  # noqa: E402

# Seven Mamba-2 layers to one attention layer, which is neither first nor
# last; 8 query heads on 2 K/V heads — a group of 4, the published one — of
# 16 lanes: half of the 32-lane K/V row a head; a chunk of 8 tokens so that a
# bucket holds several; every multiplier away from the value that would hide
# it (1, and 1/√head_dim = 1/4 for the scores).
SPEC = dict(vocab_size=97, dim=64, depth=8, attention_layers=(5,), heads=8,
            kv_heads=2, head_dim=16, mlp_dim=96, ssm_heads=4, ssm_head_dim=16,
            ssm_state=16, conv=4, chunk=8, embedding_multiplier=12.0,
            residual_multiplier=0.22, attention_multiplier=1.0 / 64,
            logits_scaling=8.0, rms_eps=1e-5)
CACHE = 128
BUCKETS = (16, 32, 64)
CHUNK = SPEC["chunk"]
# Logits here deviate by ~0.08 over the vocabulary (dim 64: the tied table's
# deviation of 0.1 x √64 / m_l). float32: both sides compute in float32 and
# differ in the order of their sums (worst seen 3e-7). bfloat16: the same
# weights on both sides, the system rounds every activation to 8 bits through
# eight layers (worst seen 0.006): the bfloat16 case guards the dtype's
# plumbing, and the faults below are held to the float32 pair.
TOLERANCE = {"float32": 5e-6, "bfloat16": 0.02}


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def lm(request):
    model, params = create_granite_hybrid_lm(dtype=request.param, **SPEC)
    return SimpleNamespace(model=model, params=params, dtype=request.param,
                           raw=params["params"])


@pytest.fixture(scope="module")
def lm32():
    model, params = create_granite_hybrid_lm(dtype="float32", **SPEC)
    return SimpleNamespace(model=model, params=params, raw=params["params"])


def _garbage_cache(model, slots, seed):
    """A pool and a state pool of garbage: whatever a slot held before."""
    spec = model.cache_spec()
    rng = np.random.default_rng(seed)
    shape = kv_pool.pool_shape(spec.rows[0], slots, CACHE)
    k, v = (jnp.asarray(rng.standard_normal(shape), spec.rows[0].dtype)
            for _ in range(2))
    state = {name: jnp.asarray(rng.standard_normal((slots, *shape)), dtype)
             for name, shape, dtype in spec.state}
    return k, v, state


def _served_logits(lm, seq, prompt_len, slot=1, slots=3):
    """Logits of every position of ``seq`` as the serving path computes
    them: one prefill of the prompt (padded to its bucket) into ``slot`` of
    a cache of garbage, then one decode step a token, teacher-forced, the
    other slots riding along at position 0."""
    apply = lm.model.apply
    k, v, state = _garbage_cache(lm.model, slots, slot)
    bucket = next(b for b in BUCKETS if b >= prompt_len)
    padded = np.full((1, bucket), 7, np.int32)   # past the prompt: tokens
    padded[0, :prompt_len] = seq[:prompt_len]
    logits, k_block, v_block, state_block = apply(
        lm.params, padded, np.asarray([prompt_len], np.int32),
        method="prefill_logits")
    out = [np.asarray(logits[0, :prompt_len], np.float32)]
    k, v = kv_pool.insert_block((k, v), (k_block, v_block), slot)
    state = state_pool.insert(state, state_block, slot)
    step = jax.jit(lambda *a: apply(lm.params, *a, method="decode_logits"))
    for position in range(prompt_len, len(seq)):
        tokens = np.zeros((slots,), np.int32)
        positions = np.zeros((slots,), np.int32)
        tokens[slot], positions[slot] = seq[position], position
        logits, k, v, state = step(tokens, k, v, state, positions)
        out.append(np.asarray(logits[slot:slot + 1], np.float32))
    return np.concatenate(out)


# Prompts shorter than the convolution's three past inputs, on a chunk's edge
# and either side of it, on a bucket's edge and past it, and one whose decode
# crosses a K/V read block's edge (the block is cut to 32 positions for these
# tests: the pool's own is the whole tiny cache).
@pytest.mark.parametrize("prompt_len,decoded", [
    (1, 5), (2, 4), (CHUNK - 1, 4), (CHUNK, 4), (CHUNK + 1, 4), (16, 3),
    (17, 3), (29, 8)])
def test_prefill_then_decode_logits_match_the_reference(
        lm, monkeypatch, prompt_len, decoded):
    row = SPEC["kv_heads"] * SPEC["head_dim"] * jnp.dtype(lm.dtype).itemsize
    monkeypatch.setattr(kv_pool, "READ_BLOCK_BYTES", 32 * row)
    rng = np.random.default_rng(prompt_len)
    seq = rng.integers(0, SPEC["vocab_size"],
                       size=prompt_len + decoded).tolist()
    want = reference.forward(lm.raw, SPEC, seq)
    got = _served_logits(lm, seq, prompt_len)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOLERANCE[lm.dtype]


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_each_part_of_the_block_is_observable(lm32, fault):
    """The program against the reference computed wrongly — a multiplier left
    at its neutral value, the convolution's bias, ``D`` or the decay left
    out, the gate after the norm, a rotation this model does not have, a
    narrower weight or state — differs by far more than the float32 pair's
    agreement: the comparison sees every one. (A bfloat16 state over 40
    tokens moves the logits least, 3.7e-5: seven times the tolerance and
    ~200 times what the pair differs by.)"""
    seq = np.random.default_rng(3).integers(0, 97, size=40).tolist()
    got = _served_logits(lm32, seq, 29)
    wrong = reference.forward(lm32.raw, SPEC, seq, fault=fault)
    factor = 5 if fault == "bf16_state" else 100
    assert np.abs(got - wrong).max() > factor * TOLERANCE["float32"]


@pytest.mark.parametrize("multiplier", [
    "embedding_multiplier", "residual_multiplier", "attention_multiplier",
    "logits_scaling"])
def test_each_multiplier_reaches_the_program(lm32, multiplier):
    """The same parameters under another value of one multiplier give other
    logits, and the reference told the same value follows."""
    seq = np.random.default_rng(5).integers(0, 97, size=24).tolist()
    spec = dict(SPEC, **{multiplier: 2.0 * SPEC[multiplier]})
    model, _ = create_granite_hybrid_lm(dtype="float32", **spec)
    other = SimpleNamespace(model=model, params=lm32.params)
    got = _served_logits(other, seq, 17)
    assert np.abs(got - reference.forward(lm32.raw, spec, seq)).max() < (
        TOLERANCE["float32"])
    assert np.abs(got - reference.forward(lm32.raw, SPEC, seq)).max() > (
        100 * TOLERANCE["float32"])


@pytest.mark.parametrize("length", [1, 2, 3, CHUNK - 1, CHUNK, CHUNK + 1,
                                    3 * CHUNK - 5])
def test_chunked_scan_is_the_token_by_token_recurrence(length):
    """``ssd_chunked`` over a padded sequence = ``ssd_step`` token by token
    over its ``length`` tokens: outputs and the state, which is that after
    ``length`` tokens, not after the padding."""
    rng = np.random.default_rng(length)
    bsz, h, p, n = 2, 3, 8, 16
    padded = -(-length // CHUNK) * CHUNK + CHUNK   # a whole padded chunk too
    x = jnp.asarray(rng.standard_normal((bsz, padded, h, p)), jnp.float32)
    b, c = (jnp.asarray(rng.standard_normal((bsz, padded, n)), jnp.float32)
            for _ in range(2))
    dt = jnp.asarray(rng.uniform(1e-3, 0.5, (bsz, padded, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, (h,)), jnp.float32)
    real = (jnp.arange(padded) < length)[None, :, None]
    y, state = granite_hybrid.ssd_chunked(x, jnp.where(real, dt, 0.0), a, b,
                                          c, CHUNK)
    want_state = jnp.zeros((bsz, h, p, n), jnp.float32)
    for t in range(length):
        want_y, want_state = granite_hybrid.ssd_step(
            want_state, x[:, t], dt[:, t], a, b[:, t], c[:, t])
        assert np.abs(np.asarray(y[:, t] - want_y)).max() < 2e-5
    assert np.abs(np.asarray(state - want_state)).max() < 2e-5


@pytest.mark.parametrize("prompt_len", [1, 2, 3, 11, 16])
def test_the_convolution_tail_after_prefill_is_the_last_three_inputs(
        lm32, prompt_len):
    """``conv<j>`` holds the convolution's inputs of the prompt's last three
    tokens — those of ITS last tokens, not of the bucket's — and zeros
    before a sequence's start."""
    model, params = lm32.model, lm32.params
    rng = np.random.default_rng(prompt_len)
    padded = rng.integers(1, 97, size=(1, 16)).astype(np.int32)
    _, _, _, state = model.apply(
        params, padded, np.asarray([prompt_len], np.int32), method="prefill")
    # layer 0 is a Mamba layer: its input is the scaled embedding's norm
    layer = lm32.raw["layer0"]
    x = SPEC["embedding_multiplier"] * lm32.raw["embed"][padded[0]]
    h = reference.rms_norm(x, layer["norm_in"], SPEC["rms_eps"])
    inner = SPEC["ssm_heads"] * SPEC["ssm_head_dim"]
    channels = inner + 2 * SPEC["ssm_state"]
    mixed = np.asarray(h @ layer["in_proj"])[:, inner:inner + channels]
    want = np.zeros((3, channels), np.float32)
    have = min(3, prompt_len)
    want[3 - have:] = mixed[prompt_len - have:prompt_len]
    assert state["conv0"].shape == (1, 3, channels)
    assert np.abs(np.asarray(state["conv0"][0]) - want).max() < 1e-5


def _runtime(**kwargs):
    from ai4e_tpu.runtime.kvcache import PagedDecodeRuntime, build_lm_servable
    lm = build_lm_servable("granite-hybrid", max_len=96, dtype="float32",
                           **SPEC)
    return PagedDecodeRuntime(lm, slots=3, prompt_buckets=(16, 48), **kwargs)


def _generate(runtime, slot, prompt, n):
    out = [runtime.prefill_into(slot, prompt)]
    for i in range(n - 1):
        tokens, positions, active = [0] * 3, [0] * 3, [False] * 3
        tokens[slot], positions[slot] = out[-1], len(prompt) + i
        active[slot] = True
        out.append(runtime.step(tokens, positions, active)[slot])
    return out


def test_a_reused_slot_shows_nothing_of_the_sequence_before():
    """A slot that held a longer sequence, then a shorter one: the second's
    tokens and its state are those of a clean runtime. (An idle slot's state
    moves with every step: it is the insert that replaces it whole.)"""
    rng = np.random.default_rng(0)
    long, short = (rng.integers(0, 97, size=n).tolist() for n in (40, 2))
    used, clean = _runtime(), _runtime()
    _generate(used, 1, long, 12)
    _generate(used, 0, short, 5)          # slot 1 idles through these steps
    got = _generate(used, 1, short, 8)
    want = _generate(clean, 1, short, 8)
    assert got == want
    for name in used._state:
        assert np.array_equal(np.asarray(used._state[name][1]),
                              np.asarray(clean._state[name][1])), name


def test_the_served_ids_are_not_the_token_just_fed():
    """The head is the embedding table; the final norm's zero-centred
    scales keep a seeded network from answering with its input
    (``create_granite_hybrid_lm``): a stream's ids vary."""
    prompt = np.random.default_rng(2).integers(0, 97, size=9).tolist()
    tokens = _generate(_runtime(), 0, prompt, 24)
    repeats = sum(a == b for a, b in zip(tokens, tokens[1:]))
    assert len(set(tokens)) > 2 and repeats < 6, tokens


def test_the_embedding_and_the_head_are_one_array(lm32):
    """No ``lm_head``: the logits read ``embed``, so the device holds the
    table once; moving one row of it moves that token's embedding AND its
    logit."""
    assert set(lm32.raw) == {"embed", "norm_f"} | {
        f"layer{i}" for i in range(SPEC["depth"])}
    tokens = np.asarray([[3, 11, 42, 5]], np.int32)
    length = np.asarray([4], np.int32)
    before = lm32.model.apply(lm32.params, tokens, length,
                              method="prefill_logits")[0]
    moved = jax.tree.map(lambda a: a, lm32.params)
    moved["params"]["embed"] = lm32.raw["embed"].at[42].multiply(1.5)
    after = lm32.model.apply(moved, tokens, length,
                             method="prefill_logits")[0]
    # at position 1 (before token 42 is fed) only column 42 moved, by 1.5 x
    np.testing.assert_allclose(after[0, 1, 42], 1.5 * before[0, 1, 42],
                               rtol=1e-5)
    assert np.array_equal(np.asarray(after[0, 1, :42]),
                          np.asarray(before[0, 1, :42]))
    assert np.abs(np.asarray(after[0, 3] - before[0, 3])).max() > 1e-3


def test_cache_spec_declares_kv_of_attention_layers_and_state_of_the_rest():
    model, _ = create_granite_hybrid_lm(dtype="bfloat16", **SPEC)
    spec = model.cache_spec()
    assert spec.rows == tuple(kv_pool.Rows(n, 1, 2 * 16, jnp.bfloat16)
                              for n in "kv")
    assert [s[0] for s in spec.state] == [
        name for j in range(7) for name in (f"ssm{j}", f"conv{j}")]
    assert spec.state[0][1:] == ((16, 4 * 16), jnp.float32)
    assert spec.state[1][1:] == ((3, 4 * 16 + 2 * 16), jnp.bfloat16)
    # the step advances the recurrence's state at the live slots only
    assert spec.live == tuple(f"ssm{j}" for j in range(7))
    runtime = _runtime()
    kv = 2 * 1 * 3 * 96 * 32 * 4
    state = 7 * 3 * (4 * 16 * 16 * 4 + 3 * 96 * 4)
    assert runtime.cache_nbytes() == kv + state
    runtime.warm()
    stepped = runtime.fetch(runtime.launch([0] * 3, [5, 0, 9],
                                           [True, False, True]))
    # once in and once out: the recurrence's state of the two live slots,
    # the convolution's tail of all three
    ssm, tail = 7 * 4 * 16 * 16 * 4, 7 * 3 * 96 * 4
    assert stepped.cache_bytes["state"] == 2 * (2 * ssm + 3 * tail)
    assert stepped.state_bytes == {"moved": 2 * (2 * ssm + 3 * tail),
                                   "live": 2 * 2 * (ssm + tail)}
    assert 3 * (ssm + tail) == state


def test_roofline_counts_at_the_cell():
    """``ops_and_bytes`` at the configuration the benchmark runs: the
    arithmetic of ISSUE 34 (a Mamba layer 76.18 M parameters, an attention
    layer 60.82 M, the tied table once, 76.44 MB of state a slot, 1 KB a
    K/V row a layer)."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as f:
        config = json.load(f)
    spec = reference._model_spec(config)
    mlp = 2048 * 16384 + 8192 * 2048 + 2 * 2048
    mamba = (2048 * 8512 + 5 * 4352 + 3 * 64 + 4096 + 4096 * 2048) + mlp
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + mlp
    assert 76.1e6 < mamba < 76.3e6 and 60.8e6 < attention < 60.9e6
    weights = 2 * (36 * mamba + 4 * attention + 100352 * 2048 + 2048)
    assert reference.weight_bytes(spec) == weights
    assert 6.37e9 < weights < 6.39e9
    assert reference.kv_bytes_per_token(spec) == 2 * 4 * 512 * 2
    per_slot = 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    assert reference.state_bytes_per_slot(spec) == per_slot
    assert 76.4e6 < per_slot < 76.5e6
    flops, nbytes = reference.ops_and_bytes(config, 64, 9_000.0)
    assert nbytes == weights + 8192 * (9_000.0 + 64) + 2 * 64 * per_slot
    assert flops > 2.0 * 64 * (weights / 2 - 40 * 4096)
    # Told how many slots were live, the least bytes hold those slots' states.
    live = dict(config, derived=dict(config["derived"], live_slots=30.5))
    assert reference.ops_and_bytes(live, 64, 9_000.0) == (
        flops, nbytes - 2 * (64 - 30.5) * per_slot)


# -- the family through the deployed wiring ------------------------------------

def test_the_worker_serves_the_family_through_the_same_wiring(monkeypatch):
    """``"family": "granite-hybrid"`` in a models spec: the same ``cli``
    worker, ``DecodeEngine`` and ``PagedDecodeRuntime`` as the other LM
    families; a state pool beside the K/V pool; the state's bytes counted as
    moved and as live, and the prefills each tick admitted."""
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
    config.runtime.kv_slots = 4
    config.runtime.kv_max_len = 64
    config.runtime.decode_prompt_buckets = (8,)
    worker, _batcher, _tm = build_worker(config, {
        "service_name": "w", "prefix": "v1/lm",
        "models": [dict(SPEC, family="granite-hybrid", name="lm",
                        attention_layers=[5])]})
    engine, = worker.decode_engines
    backend = engine.backend
    assert type(engine) is DecodeEngine
    assert type(backend) is PagedDecodeRuntime
    assert backend.max_len == 64 and backend.prompt_buckets == (8, 64)
    assert backend._rows[0].shape == (1, 4, 64, 32)
    assert backend._rows[0].dtype == jnp.bfloat16
    assert backend._state["ssm0"].shape == (4, 16, 4 * 16)
    assert backend._state["ssm0"].dtype == jnp.float32
    assert "/lm-stream-async" in worker.service.endpoints

    async def main():
        await engine.start()
        out = await asyncio.gather(engine.submit([1, 2, 3], 6),
                                   engine.submit([4, 5], 5),
                                   engine.submit([6], 4))
        await engine.stop()
        engine.pool.check_conservation()
        return out

    a, b, c = asyncio.run(main())
    assert (len(a), len(b), len(c)) == (6, 5, 4)

    def series(name):
        (_, _, _, value), = engine.metrics._metrics[name].collect()
        return value["sum"], value["count"]

    live_slots, steps = series("ai4e_decode_step_active_slots")
    state = {labels["kind"]: value for _, _, labels, value in
             engine.metrics._metrics["ai4e_decode_state_bytes_total"
                                     ].collect()}
    # a step moves its live slots' recurrent state and every slot's tail
    sparse, dense = backend._state_slot_bytes
    assert sparse > dense > 0
    assert state["moved"] == 2 * (live_slots * sparse + steps * 4 * dense)
    assert state["live"] == 2 * live_slots * (sparse + dense)
    assert 0 < state["live"] < state["moved"]
    cache = {labels["kind"]: value for _, _, labels, value in
             engine.metrics._metrics["ai4e_decode_cache_bytes_total"
                                     ].collect()}
    assert cache["state"] == state["moved"]
    # three requests submitted together: every prefill was admitted on some
    # tick, and the ticks that admitted any were at most three
    joins, ticks = series("ai4e_decode_tick_joins")
    assert joins == 3 and 1 <= ticks <= 3


def test_a_family_without_state_counts_no_state_bytes():
    from ai4e_tpu.runtime.kvcache import PagedDecodeRuntime, build_lm_servable
    runtime = PagedDecodeRuntime(build_lm_servable(
        "seqformer-lm", vocab_size=64, max_len=32, dim=32, depth=1, heads=2),
        slots=2, prompt_buckets=(8,))
    runtime.warm()
    assert runtime.servable.model.cache_spec().live == ()
    assert runtime._state_slot_bytes == (0, 0)
    step = runtime.fetch(runtime.launch([1, 2], [3, 0], [True, False]))
    assert step.state_bytes == {} and step.cache_bytes["state"] == 0
    # with every slot live too: nothing of a state to count
    step = runtime.fetch(runtime.launch([1, 2], [3, 5], [True, True]))
    assert step.state_bytes == {} and step.cache_bytes["state"] == 0


def test_an_unknown_key_of_the_spec_is_an_error():
    from ai4e_tpu.runtime.kvcache import build_lm_servable
    with pytest.raises(TypeError):
        build_lm_servable("granite-hybrid", **dict(SPEC, rotary_dim=8))
    with pytest.raises(ValueError, match="one group"):
        build_lm_servable("granite-hybrid", **dict(SPEC, ssm_groups=2))
    with pytest.raises(ValueError, match="attention layers"):
        build_lm_servable("granite-hybrid",
                          **dict(SPEC, attention_layers=(8,)))
