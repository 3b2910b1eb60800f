"""The ``glm5`` LM family (``models/glm5.py``) against its plain reference
(``benchmark/references/glm5.py``) at a small size on the CPU: logits of
prefill and of decode through the cache — across a block's close, past the
blocks the selection keeps, for a prompt of every length mod ``index_pool`` —,
the reference's faults, the shares of its experts, the state's dtype, the
clamp where it bites, a tensor of rows that keeps a row every four positions
through the pool's operations, the cache's declaration, and the family through
the worker's own runtime.
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
from ai4e_tpu.models.glm5 import TRACE_SCOPES, create_glm5_lm  # noqa: E402
from ai4e_tpu.ops import kv_pool, state_pool  # noqa: E402
from ai4e_tpu.runtime.families import LM_FAMILIES  # noqa: E402
from ai4e_tpu.runtime.kvcache import (  # noqa: E402
    PagedDecodeRuntime, build_lm_servable)
from benchmark.references import glm5 as reference  # noqa: E402

# A dense KDA layer, an expert sparse layer and an expert KDA layer: both
# mixers and both FFNs, in the configuration's order; a quarter of the 16
# experts held; index_topk cut to 16 positions = the query's own block and the
# three best of the closed ones, so a sequence of 20 positions already selects.
SPEC = dict(vocab_size=97, dim=64,
            layer_types=("kda", "sparse", "kda"),
            mlp_types=("dense", "sparse", "sparse"),
            streams=4, sinkhorn_iters=20, hc_eps=1e-6, hc_clamp=30.0, heads=4,
            head_dim=16, conv=4, gate_bound=-5.0, kda_lora=8, attn_heads=4,
            q_rank=32, kv_rank=16, qk_dim=16, v_dim=16, index_heads=4,
            index_dim=16, index_rope=8, index_theta=1e6, index_topk=16,
            index_pool=4, mlp_dim=96, experts=16, experts_held=4,
            first_expert=0, experts_per_token=3, expert_dim=32, shared_dim=32,
            route_scale=2.5, swiglu_limit=10.0, rms_eps=1e-5)
CACHE = 128
BUCKETS = (32, 128)
# Both sides compute in float32 and differ in the order of their sums, in the
# chunked form of the prefill's recurrence and in the absorbed form of the
# step.
TOLERANCE = 3e-4


def _family(dtype="float32", **changes):
    """The model, its params and its two logits programs, compiled once a
    shape."""
    spec = dict(SPEC, **changes)
    model, params = create_glm5_lm(dtype=dtype, **spec)
    return SimpleNamespace(
        model=model, params=params, spec=spec, raw=params["params"],
        prefill=jax.jit(lambda p, *a: model.apply(p, *a,
                                                  method="prefill_logits")),
        step=jax.jit(lambda p, *a: model.apply(p, *a,
                                               method="decode_logits")))


@pytest.fixture(scope="module")
def lm():
    return _family()


def _garbage_cache(model, slots, seed):
    """Pools of garbage: whatever a slot held before."""
    rng = np.random.default_rng(seed)
    spec = model.cache_spec()
    rows = tuple(jnp.asarray(rng.standard_normal(
        kv_pool.pool_shape(r, slots, CACHE)), r.dtype) for r in spec.rows)
    state = {name: jnp.asarray(rng.standard_normal((slots, *shape)), dtype)
             for name, shape, dtype in spec.state}
    return rows, state


def _served_logits(lm, seq, prompt_len, slot=1, slots=3, params=None):
    """Logits of every position of ``seq`` as the serving path computes them:
    one prefill of the prompt (padded to its bucket) into ``slot`` of a cache
    of garbage, then one decode step a token, teacher-forced, the other slots
    riding along at position 0."""
    params = lm.params if params is None else params
    rows, state = _garbage_cache(lm.model, slots, slot)
    bucket = next(b for b in BUCKETS if b >= prompt_len)
    padded = np.full((1, bucket), 7, np.int32)   # past the prompt: not zeros
    padded[0, :prompt_len] = seq[:prompt_len]
    logits, *blocks, state_block = lm.prefill(
        params, padded, np.asarray([prompt_len], np.int32))
    out = [np.asarray(logits[0, :prompt_len], np.float32)]
    rows = kv_pool.insert_block(rows, tuple(blocks), slot)
    state = state_pool.insert(state, state_block, slot)
    for position in range(prompt_len, len(seq)):
        tokens = np.zeros((slots,), np.int32)
        positions = np.zeros((slots,), np.int32)
        tokens[slot], positions[slot] = seq[position], position
        logits, *rows, state = lm.step(params, tokens, *rows, state,
                                       positions)
        out.append(np.asarray(logits[slot:slot + 1], np.float32))
    return np.concatenate(out)


def _sequence(seed, length):
    return np.random.default_rng(seed).integers(
        0, SPEC["vocab_size"], size=length).tolist()


# Prompts of every length mod the pool's four, one of a single token, on both
# sides of a bucket's edge (32) and of a chunk's (64); decodes that close
# blocks, and one of 64 steps that runs far past the four blocks kept. Two
# lengths in all, so the reference compiles two.
@pytest.mark.parametrize("prompt_len,decoded", [
    (1, 31), (12, 20), (13, 19), (14, 18), (15, 17), (32, 0), (17, 64),
    (33, 48), (64, 17), (65, 16), (81, 0)])
def test_prefill_then_decode_logits_match_the_reference(lm, prompt_len,
                                                        decoded):
    seq = _sequence(prompt_len, prompt_len + decoded)
    want = reference.forward(lm.raw, lm.spec, seq)
    got = _served_logits(lm, seq, prompt_len)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOLERANCE


@pytest.mark.parametrize("fault", [
    "float8", "no_pool", "no_tail", "rotary_latent", "scalar_gate",
    "one_stream", "no_shared"])
def test_the_reference_faults_fail_the_tolerance(lm, fault):
    """Each wrong model the tolerance study computes is far outside the
    float32 pair's agreement: the comparison sees it."""
    seq = _sequence(3, 32)
    got = _served_logits(lm, seq, 20)
    wrong = reference.forward(lm.raw, lm.spec, seq, fault)
    assert np.abs(got - wrong).max() > 10 * TOLERANCE


def test_a_bfloat16_state_moves_the_float32_pair(lm):
    """What the cell's check cannot hold (the configuration's ``controls``):
    the recurrent state is float32."""
    seq = _sequence(3, 32)
    got = _served_logits(lm, seq, 20)
    wrong = reference.forward(lm.raw, lm.spec, seq, "bf16_state")
    assert np.abs(got - wrong).max() > 10 * TOLERANCE
    spec = lm.model.cache_spec()
    assert all(dtype == jnp.float32 for name, _, dtype in spec.state
               if name.startswith(("kda", "isum")))


def test_the_clamp_bites_where_the_projections_pass_the_limit(lm):
    """A seeded network's gates stay under 10; with every FFN's gate and up
    projection scaled by 16 they do not, and the program still agrees with
    the reference's own second writing of the clamp, which the unclamped
    reading does not."""
    scaled = jax.tree_util.tree_map_with_path(
        lambda path, a: a * 16 if path[-1].key in (
            "m_gate", "m_up", "w_gate", "w_up", "s_gate", "s_up") else a,
        lm.params)
    seq = _sequence(5, 32)
    got = _served_logits(lm, seq, 20, params=scaled)
    raw = scaled["params"]
    assert np.abs(got - reference.forward(raw, lm.spec, seq)).max() \
        < 10 * TOLERANCE
    assert np.abs(got - reference.forward(raw, lm.spec, seq,
                                          "no_clamp")).max() > 0.05
    # and does nothing where they do not
    assert np.abs(reference.forward(lm.raw, lm.spec, seq)
                  - reference.forward(lm.raw, lm.spec, seq,
                                      "no_clamp")).max() == 0


@pytest.mark.parametrize("limit", [0.0, 10.0, 0.5])
def test_swiglu_clamps_the_gate_above_and_the_up_projection_both_sides(limit):
    g = jnp.asarray([-20.0, -0.3, 0.4, 9.0, 30.0])
    u = jnp.asarray([25.0, -0.7, -11.0, 0.2, -40.0])
    want = jax.nn.silu(jnp.minimum(g, limit) if limit else g) * (
        jnp.clip(u, -limit, limit) if limit else u)
    np.testing.assert_allclose(expert_layer.swiglu(g, u, limit), want)
    h = jnp.asarray(np.random.default_rng(0).standard_normal((5, 8)),
                    jnp.float32) * 6
    weights = [jnp.asarray(np.random.default_rng(i).standard_normal(shape),
                           jnp.float32)
               for i, shape in enumerate([(2, 8, 4), (2, 8, 4), (2, 4, 8)])]
    top_e = jnp.asarray([[0, 1]] * 5)
    top_p = jnp.full((5, 2), 0.5)
    dense = expert_layer.dense(h, expert_layer.gate_matrix(top_e, top_p, 2),
                               *weights, limit=limit)
    routed = expert_layer.routed(h, top_e, top_p, *weights, total=2,
                                 limit=limit)
    want = sum(0.5 * (expert_layer.swiglu(h @ weights[0][e], h @ weights[1][e],
                                          limit) @ weights[2][e])
               for e in range(2))
    np.testing.assert_allclose(dense, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(routed, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        expert_layer.shared(h, None, weights[0][0], weights[1][0],
                            weights[2][0], limit=limit),
        expert_layer.swiglu(h @ weights[0][0], h @ weights[1][0], limit)
        @ weights[2][0], rtol=1e-4, atol=1e-4)


def test_the_eight_shares_of_the_experts_add_up_to_the_uncut_layer(lm):
    """Eight chips share a layer: the routed terms of the shares ``(0, 2)``
    .. ``(14, 2)``, with the shared expert counted once, are the layer that
    holds all sixteen."""
    _, variables = create_glm5_lm(dtype="float32",
                                  **dict(SPEC, experts_held=16))
    whole = SimpleNamespace(raw=variables["params"],
                            spec=dict(SPEC, experts_held=16))
    layer = whole.raw["layer2"]
    h = jnp.asarray(np.random.default_rng(6).standard_normal((24, 64)),
                    jnp.float32)

    def w(a):
        return a.astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        uncut = reference.ffn(h, layer, whole.spec, False, w, None)
        shares = sum(reference.ffn(h, layer, whole.spec, False, w, None,
                                   held=(first, 2))
                     for first in range(0, 16, 2))
    np.testing.assert_allclose(shares, uncut, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("first", [14])
def test_a_share_that_does_not_start_at_zero_matches_the_reference(first):
    share = _family(first_expert=first, experts_held=2)
    seq = _sequence(first, 32)
    got = _served_logits(share, seq, 25)
    assert np.abs(got - reference.forward(share.raw, share.spec, seq)).max() \
        < TOLERANCE


# -- a tensor of rows that keeps one row every four positions -------------------

POOLED = kv_pool.Rows("index", 2, 8, jnp.float32, kind="index", whole=True,
                      every=4)
LATENT = kv_pool.Rows("latent", 2, 16, jnp.float32, kind="latent", select=16)


@pytest.mark.parametrize("max_len,rows", [(128, 32), (126, 32), (4, 1)])
def test_a_pooled_tensor_holds_a_row_every_four_positions(max_len, rows):
    assert kv_pool.pool_shape(POOLED, 3, max_len) == (2, 3, rows, 8)
    assert kv_pool.rows_nbytes((POOLED,), 3, max_len) == 2 * 3 * rows * 8 * 4
    assert kv_pool.pool_shape(LATENT, 3, max_len) == (2, 3, max_len, 16)


@pytest.mark.parametrize("prompt_len", [12, 13, 14, 15])
def test_a_pooled_tensor_through_insert_and_write(prompt_len):
    """A prompt's blocks land at the slot's first rows; a step's row lands at
    ``position // 4`` of the pooled tensor and at ``position`` of the other,
    and no other row of either moves."""
    pools = tuple(jnp.zeros(kv_pool.pool_shape(r, 3, 32), r.dtype)
                  for r in (LATENT, POOLED))
    blocks = (jnp.ones((2, 1, 16, 16)), 2 * jnp.ones((2, 1, 4, 8)))
    latent, pooled = kv_pool.insert_block(pools, blocks, 1)
    assert float(latent[:, 1, :16].min()) == 1 and float(latent.sum()) == 512
    assert float(pooled[:, 1, :4].min()) == 2 and float(pooled.sum()) == 128
    position = jnp.asarray([0, prompt_len, 0])
    new = ([3 * jnp.ones((3, 16))] * 2, [5 * jnp.ones((3, 8))] * 2)
    latent, pooled = kv_pool.write_rows((latent, pooled), new, position,
                                        every=(1, 4))
    assert float(latent[0, 1, prompt_len, 0]) == 3
    assert float(pooled[1, 1, prompt_len // 4, 0]) == 5
    assert float(pooled[:, 1].sum()) == 2 * (3 * 2 * 8 + 5 * 8)
    assert float(latent[:, 1].sum()) == 2 * (15 * 16 + 3 * 16)


@pytest.mark.parametrize("position", [12, 13, 14, 15, 40])
def test_a_step_counts_the_pooled_rows_the_tail_and_the_selection(position):
    spec = (LATENT, POOLED)
    attended, nbytes, selected = kv_pool.step_reads(
        spec, 3, 64, [0, position, 0], [False, True, False], 64)
    assert attended == 64 + 1      # the one block under the bound, and its own
    assert selected == min(position - position % 4, 12) + position % 4 + 1
    # every slot's 16 pooled rows under the bound, the live slot's new one
    # read and written
    assert nbytes["index"] == 2 * 8 * 4 * (3 * 16 + 1 + 1)
    assert nbytes["latent"] == 2 * 16 * 4 * (64 + 1 + 1)


@pytest.mark.parametrize("n", [0, 1, 4, 5, 13, 14, 15, 16, 100])
def test_prefill_pairs_count_the_closed_blocks_a_query_meets(n):
    pairs = kv_pool.prefill_pairs((LATENT, POOLED), n)
    assert pairs["index"] == sum(t // 4 for t in range(n))
    assert pairs["selected"] == sum(min(t + 1, 16) for t in range(n))


def test_rows_that_keep_every_position_are_what_they_were():
    """The defaults leave the other families' declarations alone."""
    plain = kv_pool.Rows("k", 2, 8, jnp.float32)
    assert plain.every == 1
    assert kv_pool.pool_shape(plain, 3, 50) == (2, 3, 50, 8)
    assert kv_pool.prefill_pairs((plain,), 9) == {"kv": 45}
    ring = kv_pool.Rows("w", 1, 8, jnp.float32, length=5, kind="window")
    assert kv_pool.step_reads((plain, ring), 2, 50, [7, 0], [True, False],
                              50)[2] is None
    # a selection over keys that are not pooled keeps min(p + 1, select)
    chosen = kv_pool.Rows("c", 1, 8, jnp.float32, select=6)
    assert kv_pool.step_reads((chosen,), 3, 50, [3, 9, 40],
                              [True, True, False], 50)[2] == 4 + 6


# -- the declaration and the runtime ---------------------------------------------

def test_the_cache_declares_rows_pooled_rows_states_and_sums(lm):
    spec = lm.model.cache_spec()
    latent, index = spec.rows
    assert (latent.width, latent.select, latent.every) == (16, 16, 1)
    assert (index.width, index.every, index.whole, index.kind) == (
        16, 4, True, "index")
    assert latent.layers == index.layers == 1
    names = [name for name, _, _ in spec.state]
    assert names == ["kda0", "conv0", "kda1", "conv1", "isum0"]
    assert spec.live == ("kda0", "kda1")
    assert {"index_pool", "indexer", "select", "mhc_pre", "sinkhorn",
            "state_update", "kda_chunk"} <= set(TRACE_SCOPES)


def test_the_family_is_registered_and_holds_no_copy_of_what_it_imports():
    assert "glm5" in LM_FAMILIES
    source = open(os.path.join(REPO, "ai4e_tpu", "models", "glm5.py")).read()
    for imported in ("kda_prompt", "kda_token", "hyper_params", "mhc.pre",
                     "mhc.post", "expert_layer.routed", "expert_layer.dense",
                     "kv_pool.select_top", "kv_pool.write_rows"):
        assert imported in source
    for copied in ("def kda_step", "def sinkhorn", "def select_top",
                   "ragged_dot", "def kda_block"):
        assert copied not in source
    for module in ("kvcache.py", "decode.py"):
        text = open(os.path.join(REPO, "ai4e_tpu", "runtime", module)).read()
        assert "glm5" not in text
    for module in ("kv_pool.py", "state_pool.py"):
        text = open(os.path.join(REPO, "ai4e_tpu", "ops", module)).read()
        assert "glm5" not in text


@pytest.fixture(scope="module")
def runtime():
    servable = build_lm_servable("glm5", max_len=64, dtype="float32", **SPEC)
    rt = PagedDecodeRuntime(servable, slots=3, prompt_buckets=(64,))
    rt.warm()
    return rt


def test_the_runtime_serves_the_reference_s_tokens(runtime, lm):
    """Greedy decoding through ``PagedDecodeRuntime``: every served id lies
    at the reference's maximum of its position, to the pair's tolerance."""
    prompt = _sequence(8, 21)
    served = [runtime.prefill_into(2, prompt)]
    for step in range(10):
        positions = [0, 0, len(prompt) + step]
        served.append(runtime.step([0, 0, served[-1]], positions,
                                   [False, False, True])[2])
    rows = reference.forward(lm.raw, lm.spec, (prompt + served)[:-1],
                             first=len(prompt) - 1)
    margin = rows.max(axis=-1) - rows[np.arange(len(served)), served]
    assert margin.max() < 10 * TOLERANCE


@pytest.mark.parametrize("position", [21, 22, 23, 24])
def test_the_runtime_counts_a_step_s_tail_and_pooled_bytes(runtime, position):
    step = runtime.fetch(runtime.launch(
        [1, None, 1], [0, 0, position], [False, False, True]))
    assert step.selected == 12 + position % 4 + 1
    assert step.cache_bytes["index"] == 16 * 4 * (3 * 64 // 4 + 2)
    report = step.report
    assert {"experts_touched", "held_picks_share", "mhc_balance_error",
            "kda_retention"} <= set(report)
    assert report["mhc_balance_error"] < 1e-3
    assert 0 < report["kda_retention"] < 1


def test_the_cache_s_bytes_count_a_quarter_of_the_pooled_rows(runtime):
    rows = 3 * 64 * 16 * 4 + 3 * (64 // 4) * 16 * 4
    state = 3 * (2 * (4 * 16 * 16 * 4 + 3 * 3 * 4 * 16 * 4) + 16 * 4)
    assert runtime.cache_nbytes() == rows + state
    assert runtime.prefill_report(20)["pairs"] == {
        "selected": sum(min(t + 1, 16) for t in range(20)),
        "index": sum(t // 4 for t in range(20))}


@pytest.mark.parametrize("change", [
    dict(layer_types=("kda", "kda", "kda")),
    dict(layer_types=("sparse",) * 3),
    dict(mlp_types=("dense",) * 3),
    dict(layer_types=("kda", "full", "kda")),
    dict(mlp_types=("dense", "sparse")),
    dict(index_rope=7), dict(index_rope=32), dict(index_topk=18),
    dict(index_topk=4), dict(experts_held=8, first_expert=12),
    dict(swiglu_limit=-1.0), dict(gate_bound=-9.0), dict(no_such_field=1)])
def test_a_configuration_the_family_cannot_run_is_refused(change):
    with pytest.raises((ValueError, TypeError)):
        create_glm5_lm(**dict(SPEC, **change))
