"""The ``qwen3-next`` LM family (``models/qwen3_next.py``) against its plain
reference (``benchmark/references/qwen3_next.py``) at a small size on the CPU:
logits of prefill and of decode through the cache (K/V rows AND recurrent
state), the chunked recurrence against the token-by-token one, the
convolution's tail, a slot's reuse, a reload, the share of experts a process
holds against the uncut layer, the step's report, the counts of the roofline,
and the family through the worker's own wiring.
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

from ai4e_tpu.models import experts as expert_layer  # noqa: E402
from ai4e_tpu.models import qwen3_next  # noqa: E402
from ai4e_tpu.models.qwen3_next import create_qwen3_next_lm  # noqa: E402
from ai4e_tpu.ops import kv_pool, state_pool  # noqa: E402
from benchmark.references import qwen3_next as reference  # noqa: E402

# Two periods of the 3 : 1 pattern; half the experts held, not from 0, so a
# share that is taken for the whole shows; 4 query heads on 2 K/V heads; 4
# value heads on 2 key heads; a quarter of each head rotated.
SPEC = dict(vocab_size=97, dim=64, depth=8, full_interval=4, heads=4,
            kv_heads=2, head_dim=32, rotary_dim=8, lin_k_heads=2,
            lin_v_heads=4, lin_dim=16, conv=4, experts=16, experts_held=8,
            first_expert=4, experts_per_token=3, expert_dim=32, shared_dim=32,
            rms_eps=1e-6, rope_theta=1e7)
CACHE = 256
BUCKETS = (32, 128, 192)
CHUNK = qwen3_next.CHUNK
# Logits here deviate by ~0.8 over the vocabulary. float32: both sides
# compute in float32 and differ in the order of their sums (worst seen 5e-6).
# bfloat16: the same weights on both sides, the system rounds every
# activation to 8 bits through eight layers (0.03-0.08 where every expert is
# chosen) and now and then picks another third expert of a token's three —
# a weight of a third, so the logits jump (worst seen over these sequences
# 0.37): the bfloat16 case guards the dtype's plumbing, and the faults below
# are held to the float32 pair, where nothing hides them.
TOLERANCE = {"float32": 5e-5, "bfloat16": 0.6}


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def lm(request):
    model, params = create_qwen3_next_lm(dtype=request.param, **SPEC)
    return SimpleNamespace(model=model, params=params, dtype=request.param,
                           raw=params["params"])


@pytest.fixture(scope="module")
def lm32():
    model, params = create_qwen3_next_lm(dtype="float32", **SPEC)
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
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :prompt_len] = seq[:prompt_len]
    # what lies past the prompt in its bucket is other tokens, not zeros
    padded[0, prompt_len:] = 7
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


# Prompts that end inside their bucket, on a chunk's edge and either side of
# it, and one whose decode crosses a K/V read block's edge (the block is cut
# to 32 positions for these tests: the pool's own is the whole tiny cache).
@pytest.mark.parametrize("prompt_len,decoded", [
    (21, 6), (CHUNK - 1, 4), (CHUNK, 4), (CHUNK + 1, 4), (2 * CHUNK + 2, 3),
    (29, 8)])
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
def test_the_reference_faults_move_the_logits(lm32, fault):
    """Each wrong model the tolerance study computes is far outside the
    float32 pair's agreement: the comparison sees it."""
    seq = np.random.default_rng(3).integers(0, 97, size=90).tolist()
    right = reference.forward(lm32.raw, SPEC, seq)
    wrong = reference.forward(lm32.raw, SPEC, seq, fault=fault)
    assert np.abs(right - wrong).max() > 100 * TOLERANCE["float32"]


@pytest.mark.parametrize("length", [1, CHUNK - 1, CHUNK, CHUNK + 1,
                                    3 * CHUNK - 5])
def test_chunked_recurrence_is_the_token_by_token_one(length):
    """``delta_rule_chunked`` over a padded sequence = ``delta_rule_step``
    token by token over its ``length`` tokens: outputs and the state."""
    rng = np.random.default_rng(length)
    b, h, dk, dv = 2, 3, 16, 16
    padded = -(-length // CHUNK) * CHUNK + CHUNK   # a whole padded chunk too
    q, k = (qwen3_next.l2_norm(jnp.asarray(
        rng.standard_normal((b, padded, h, dk)), jnp.float32))
        for _ in range(2))
    v = jnp.asarray(rng.standard_normal((b, padded, h, dv)), jnp.float32)
    g = -jnp.asarray(rng.uniform(0.01, 0.3, (b, padded, h)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.1, 0.9, (b, padded, h)), jnp.float32)
    real = (jnp.arange(padded) < length)[None, :, None]
    o, state = qwen3_next.delta_rule_chunked(
        q, k, v, jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0))
    want_state = jnp.zeros((b, h, dk, dv), jnp.float32)
    for t in range(length):
        want_o, want_state = qwen3_next.delta_rule_step(
            want_state, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
        assert np.abs(np.asarray(o[:, t] - want_o)).max() < 2e-5
    assert np.abs(np.asarray(state - want_state)).max() < 2e-5


@pytest.mark.parametrize("prompt_len", [1, 2, 3, 21, 32])
def test_the_convolution_tail_after_prefill_is_the_last_three_inputs(
        lm32, prompt_len):
    """``conv<j>`` holds the convolution's inputs of the prompt's last three
    tokens — those of ITS last tokens, not of the bucket's — and zeros
    before a sequence's start."""
    model, params = lm32.model, lm32.params
    rng = np.random.default_rng(prompt_len)
    padded = rng.integers(1, 97, size=(1, 32)).astype(np.int32)
    _, _, _, state = model.apply(
        params, padded, np.asarray([prompt_len], np.int32), method="prefill")
    # layer 0 is a linear layer: its input is the embedding's norm
    layer = lm32.raw["layer0"]
    x = lm32.raw["embed"][padded[0]]
    h = reference.rms_norm(x, layer["norm_in"], SPEC["rms_eps"])
    channels = (2 * SPEC["lin_k_heads"] + SPEC["lin_v_heads"]) * SPEC[
        "lin_dim"]
    mixed = np.asarray(h @ layer["in_qkvz"])[:, :channels]
    want = np.zeros((3, channels), np.float32)
    have = min(3, prompt_len)
    want[3 - have:] = mixed[prompt_len - have:prompt_len]
    assert np.abs(np.asarray(state["conv0"][0]) - want).max() < 1e-5


def _runtime(params=None, **kwargs):
    from ai4e_tpu.runtime.kvcache import PagedDecodeRuntime, build_lm_servable
    lm = build_lm_servable("qwen3-next", max_len=96, dtype="float32", **SPEC)
    if params is not None:
        lm.params = params
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
    long, short = (rng.integers(0, 97, size=n).tolist() for n in (40, 9))
    used, clean = _runtime(), _runtime()
    _generate(used, 1, long, 12)
    _generate(used, 0, short, 5)          # slot 1 idles through these steps
    got = _generate(used, 1, short, 8)
    want = _generate(clean, 1, short, 8)
    assert got == want
    for name in used._state:
        assert np.array_equal(np.asarray(used._state[name][1]),
                              np.asarray(clean._state[name][1])), name


def test_reload_params_rebuilds_the_state():
    """After ``reload_params`` the engine resets the cache and re-prefills a
    live sequence from its history: state and K/V are then those of the new
    weights, and decoding goes on as a runtime born with them would."""
    _, other = create_qwen3_next_lm(rng=jax.random.PRNGKey(7),
                                    dtype="float32", **SPEC)
    prompt = np.random.default_rng(1).integers(0, 97, size=11).tolist()
    runtime = _runtime()
    history = prompt + _generate(runtime, 2, prompt, 6)
    before = {n: np.asarray(t[2]) for n, t in runtime._state.items()}
    assert runtime.reload_params(other) == 2
    runtime.reset_cache()                 # what the engine does at its tick
    assert all(not np.asarray(t).any() for t in runtime._state.values())
    got = _generate(runtime, 2, history, 5)
    assert got == _generate(_runtime(params=other), 2, history, 5)
    changed = [n for n, t in runtime._state.items()
               if not np.array_equal(np.asarray(t[2]), before[n])]
    assert sorted(changed) == sorted(runtime._state)


def test_cache_spec_declares_kv_of_full_layers_and_state_of_the_rest():
    model, _ = create_qwen3_next_lm(dtype="bfloat16", **SPEC)
    spec = model.cache_spec()
    assert spec.rows == tuple(kv_pool.Rows(n, 2, 2 * 32, jnp.bfloat16)
                              for n in "kv")
    assert [s[0] for s in spec.state] == [
        name for j in range(6) for name in (f"delta{j}", f"conv{j}")]
    assert spec.state[0][1:] == ((4, 16, 16), jnp.float32)
    assert spec.state[1][1:] == ((3, (2 * 2 + 4) * 16), jnp.bfloat16)
    runtime = _runtime()
    kv = 2 * 2 * 3 * 96 * 64 * 4
    state = 6 * 3 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    assert runtime.cache_nbytes() == kv + state
    runtime.warm()
    stepped = runtime.fetch(runtime.launch([0] * 3, [5, 0, 9],
                                           [True, False, True]))
    # one block of the whole tiny cache a live slot (the dead one reads
    # nothing) + its new token read + its row written, K and V of both K/V
    # layers; once in and once out the two live slots' recurrent state and
    # every slot's convolution tail
    assert spec.live == tuple(f"delta{j}" for j in range(6))
    delta, tail = 6 * 4 * 16 * 16 * 4, 6 * 3 * 128 * 4
    assert 3 * (delta + tail) == state
    assert stepped.cache_bytes == {
        "kv": 2 * 2 * 64 * 4 * (2 * 96 + 2 + 2),
        "state": 2 * (2 * delta + 3 * tail)}
    assert stepped.state_bytes == {"moved": 2 * (2 * delta + 3 * tail),
                                   "live": 2 * 2 * (delta + tail)}


# -- the share of experts a process holds -------------------------------------

@pytest.mark.parametrize("form", ["dense", "routed"])
def test_four_shares_and_one_shared_expert_sum_to_the_uncut_layer(form):
    """The guide's share test: the expert layer told it holds experts
    ``4s .. 4s + 3`` of 16, for ``s = 0 .. 3``, gives four partial results
    that, with the shared expert counted once, add up to the reference's
    layer over all 16 — in the form a step runs and in the form a prefill
    runs."""
    total, held, k, d, f = 16, 4, 3, 64, 32
    rng = np.random.default_rng(5)
    layer = {"router": rng.standard_normal((d, total)) * 0.3,
             "w_gate": rng.standard_normal((total, d, f)) / 8,
             "w_up": rng.standard_normal((total, d, f)) / 8,
             "w_down": rng.standard_normal((total, f, d)) / 6,
             "shared_gate": rng.standard_normal((d, 1)) / 8,
             "s_gate": rng.standard_normal((d, f)) / 8,
             "s_up": rng.standard_normal((d, f)) / 8,
             "s_down": rng.standard_normal((f, d)) / 6}
    layer = {n: jnp.asarray(a, jnp.float32) for n, a in layer.items()}
    h = jnp.asarray(rng.standard_normal((50, d)), jnp.float32)
    spec = dict(experts_per_token=k, experts_held=total, first_expert=0)
    with jax.default_matmul_precision("highest"):
        want = (reference.moe(h, layer, spec, lambda a: a)
                + reference.shared_expert(h, layer, lambda a: a))
        top_e, top_p = expert_layer.route(h, layer["router"], k, True)
        got = expert_layer.shared(h, layer["shared_gate"], layer["s_gate"],
                                  layer["s_up"], layer["s_down"])
        parts = []
        for first in range(0, total, held):
            weights = [layer[n][first:first + held]
                       for n in ("w_gate", "w_up", "w_down")]
            if form == "dense":
                gate = expert_layer.gate_matrix(top_e, top_p, held, first)
                parts.append(expert_layer.dense(h, gate, *weights))
            else:
                parts.append(expert_layer.routed(h, top_e, top_p, *weights,
                                                 total=total,
                                                 first_held=first))
            # a share is what the reference gives for the same share
            share = dict(layer, w_gate=weights[0], w_up=weights[1],
                         w_down=weights[2])
            assert np.abs(np.asarray(parts[-1] - reference.moe(
                h, share, spec, lambda a: a, held=(first, held)))
            ).max() < 1e-5
    assert np.abs(np.asarray(got + sum(parts) - want)).max() < 1e-5
    assert all(np.abs(np.asarray(p)).max() > 1e-3 for p in parts)


def test_step_report_counts_live_picks_on_held_experts():
    model, _ = create_qwen3_next_lm(dtype="bfloat16", **SPEC)
    rng = np.random.default_rng(2)
    picks = rng.integers(0, 16, size=(8, 5, 3))
    active = [True, False, True, True, False]
    got = model.step_report(picks.reshape(-1), active)
    live = picks[:, [0, 2, 3]]
    here = (live >= 4) & (live < 12)
    touched = [len(set(live[i][here[i]])) for i in range(8)]
    peak = [max(np.bincount(live[i][here[i]] - 4, minlength=8))
            for i in range(8)]
    assert got["experts_touched"] == pytest.approx(np.mean(touched))
    assert got["expert_peak_load"] == pytest.approx(
        np.mean(peak) / (3 * 3 / 16))
    assert got["held_picks_share"] == pytest.approx(here.mean())
    assert model.step_report(picks.reshape(-1), [False] * 5) == {}
    assert set(got) == set(model.step_report_series)


def test_roofline_counts_at_the_cell():
    """``ops_and_bytes`` at the configuration the benchmark runs: the
    arithmetic of ISSUE 32 (an expert 3.146 M parameters, 128 held a layer,
    37.9 M / 31.5 M outside them, 2.15 MB of state a slot a linear layer,
    1 KB a K/V row)."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        config = json.load(f)
    spec = reference._model_spec(config)
    expert = 3 * 2048 * 512
    assert expert == 3_145_728
    linear = (2048 * 12288 + 2048 * 64 + 4 * 8192 + 2 * 32 + 128
              + 4096 * 2048)
    full = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    moe = 2048 * 512 + 128 * expert + expert + 2048 + 2 * 2048
    weights = 2 * (9 * linear + 3 * full + 12 * moe + 2048 * 37984 + 2048)
    assert reference.weight_bytes(spec) == weights
    assert 10.4e9 < weights < 10.8e9          # + the embedding: 10.85 GB
    assert reference.kv_bytes_per_token(spec) == 2 * 3 * 512 * 2
    per_slot = 9 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    assert reference.state_bytes_per_slot(spec) == per_slot
    assert 2.1e6 < per_slot / 9 < 2.2e6
    flops, nbytes = reference.ops_and_bytes(config, 32, 20_000.0)
    assert nbytes == (weights + 32 * 2048 * 2 + 6144 * (20_000.0 + 32)
                      + 2 * 32 * per_slot)
    assert flops > 2.0 * 32 * (weights / 2 - 12 * 128 * expert)
    # Told how many slots were live, the least bytes hold those slots' states.
    live = dict(config, derived=dict(config["derived"], live_slots=12.5))
    assert reference.ops_and_bytes(live, 32, 20_000.0) == (
        flops, nbytes - 2 * (32 - 12.5) * per_slot)


# -- the family through the deployed wiring ------------------------------------

def test_the_worker_serves_the_family_through_the_same_wiring(monkeypatch):
    """``"family": "qwen3-next"`` in a models spec: the same ``cli`` worker,
    ``DecodeEngine`` and ``PagedDecodeRuntime`` as the other LM families; a
    state pool beside the K/V pool; the routing series and both kinds of
    cache bytes observed from the step's own fetch."""
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
    config.runtime.kv_max_len = 64
    config.runtime.decode_prompt_buckets = (8,)
    worker, _batcher, _tm = build_worker(config, {
        "service_name": "w", "prefix": "v1/lm",
        "models": [dict(SPEC, family="qwen3-next", name="lm")]})
    engine, = worker.decode_engines
    backend = engine.backend
    assert type(engine) is DecodeEngine
    assert type(backend) is PagedDecodeRuntime
    assert backend.max_len == 64 and backend.prompt_buckets == (8, 64)
    assert backend._rows[0].shape == (2, 3, 64, 64)
    assert backend._rows[0].dtype == jnp.bfloat16
    assert backend._state["delta0"].shape == (3, 4, 16, 16)
    assert backend._state["delta0"].dtype == jnp.float32
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
    assert 0 < touched / steps <= SPEC["experts_held"]
    share, _ = series("ai4e_decode_held_picks_share")
    assert 0.0 < share / steps < 1.0
    moved = {labels["kind"]: value for _, _, labels, value in
             engine.metrics._metrics["ai4e_decode_cache_bytes_total"
                                     ].collect()}
    sparse, dense = backend._state_slot_bytes
    live_slots, _ = series("ai4e_decode_step_active_slots")
    assert moved["state"] == 2 * (live_slots * sparse
                                  + steps * backend.slots * dense)
    assert moved["kv"] > 0


def test_the_other_families_declare_no_state():
    """``seqformer-lm`` and ``olmoe`` keep K/V only: an empty state pool,
    which adds no tensor to their programs, and no state bytes."""
    from ai4e_tpu.runtime.kvcache import PagedDecodeRuntime, build_lm_servable
    for family, extra in (("seqformer-lm", {}), ("olmoe", {"experts": 4})):
        runtime = PagedDecodeRuntime(build_lm_servable(
            family, vocab_size=64, max_len=32, dim=32, depth=1, heads=2,
            **extra), slots=2, prompt_buckets=(8,))
        assert runtime.state_spec() == ()
        runtime.warm()
        assert runtime._state == {}
        moved = runtime.fetch(runtime.launch([1, 2], [3, 0],
                                             [True, False])).cache_bytes
        assert moved["state"] == 0
        assert moved["kv"] > 0


def test_an_unknown_key_of_the_spec_is_an_error():
    from ai4e_tpu.runtime.kvcache import build_lm_servable
    with pytest.raises(TypeError):
        build_lm_servable("qwen3-next", **dict(SPEC, expert_capacity=4))
    with pytest.raises(ValueError, match="experts held"):
        build_lm_servable("qwen3-next", **dict(SPEC, first_expert=12))
