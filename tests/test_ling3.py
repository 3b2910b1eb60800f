"""The ``ling3`` LM family (``models/ling3.py``) against its plain reference
(``benchmark/references/ling3.py``) at a small size on the CPU: logits of
prefill and of decode through the cache on both sides of a bucket's, a
chunk's and a sub-block's edge, a slot's reuse, the recurrence's three forms
against each other with the gate at its bound, the reference's faults, the
interleaved rotation, the group-limited route and the shares of its experts,
the cache's declaration, and the family through the worker's own runtime and
engine.
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
from ai4e_tpu.models import ling3, olmoe  # noqa: E402
from ai4e_tpu.models.ling3 import create_ling3_lm  # noqa: E402
from ai4e_tpu.ops import kv_pool, state_pool  # noqa: E402
from benchmark.references import ling3 as reference  # noqa: E402

# A dense KDA layer, an expert KDA layer, an expert latent layer and an
# expert KDA layer after it: one whole period of 2 : 1 and the start of the
# next; half of the 16 experts held (groups 0 and 1 of 4), 3 a token inside
# the 2 best groups.
SPEC = dict(vocab_size=97, dim=64, depth=4, group=3, dense_layers=1, heads=4,
            head_dim=16, conv=4, gate_bound=-5.0, kv_rank=16, nope=16,
            rope_dim=8, v_dim=16, mlp_dim=96, experts=16, experts_held=8,
            first_expert=0, experts_per_token=3, route_groups=(4, 2),
            expert_dim=32, shared_dim=32, route_scale=2.5, rms_eps=1e-6,
            rope_theta=6e6)
CACHE = 160
BUCKETS = (16, 32, 64, 128)
# float32: both sides compute in float32 and differ in the order of their
# sums, in the chunked form of the prefill's recurrence and in the absorbed
# form of the step. bfloat16: the same weights, the system rounds every
# activation through four layers and now and then picks another third expert:
# that case guards the dtype's plumbing (a flip moves a logit by ~1; nine
# logits in ten stay within a twentieth of the limit); the faults are held to
# the float32 pair.
TOLERANCE = {"float32": 2e-4, "bfloat16": 2.0}


def _family(dtype):
    """The model, its params and its two logits programs, compiled once a
    shape for the whole module."""
    model, params = create_ling3_lm(dtype=dtype, **SPEC)
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
    spec = model.cache_spec()
    rows = tuple(jnp.asarray(rng.standard_normal(
        kv_pool.pool_shape(r, slots, CACHE)), r.dtype) for r in spec.rows)
    state = {name: jnp.asarray(rng.standard_normal((slots, *shape)), dtype)
             for name, shape, dtype in spec.state}
    return rows, state


def _served_logits(lm, seq, prompt_len, slot=1, slots=3, cache=None):
    """Logits of every position of ``seq`` as the serving path computes them:
    one prefill of the prompt (padded to its bucket) into ``slot`` of a cache
    of garbage, then one decode step a token, teacher-forced, the other slots
    riding along at position 0."""
    rows, state = cache or _garbage_cache(lm.model, slots, slot)
    bucket = next(b for b in BUCKETS if b >= prompt_len)
    padded = np.full((1, bucket), 7, np.int32)   # past the prompt: not zeros
    padded[0, :prompt_len] = seq[:prompt_len]
    logits, block, state_block = lm.prefill(
        padded, np.asarray([prompt_len], np.int32))
    out = [np.asarray(logits[0, :prompt_len], np.float32)]
    rows = kv_pool.insert_block(rows, (block,), slot)
    state = state_pool.insert(state, state_block, slot)
    for position in range(prompt_len, len(seq)):
        tokens = np.zeros((slots,), np.int32)
        positions = np.zeros((slots,), np.int32)
        tokens[slot], positions[slot] = seq[position], position
        logits, latent, state = lm.step(tokens, *rows, state, positions)
        rows = (latent,)
        out.append(np.asarray(logits[slot:slot + 1], np.float32))
    return np.concatenate(out), (rows, state)


# A prompt of one token; prompts on both sides of a sub-block's edge (16, also
# the first bucket's), of a bucket's edge (32) and of a chunk's (64); one that
# runs into a third chunk; and a decode that goes on after.
@pytest.mark.parametrize("prompt_len,decoded", [
    (1, 6), (15, 3), (16, 4), (17, 3), (31, 3), (32, 3), (33, 5), (63, 3),
    (64, 3), (65, 6), (128, 4), (101, 12)])
def test_prefill_then_decode_logits_match_the_reference(lm, prompt_len,
                                                        decoded):
    rng = np.random.default_rng(prompt_len)
    seq = rng.integers(0, SPEC["vocab_size"],
                       size=prompt_len + decoded).tolist()
    want = reference.forward(lm.raw, SPEC, seq)
    got, _ = _served_logits(lm, seq, prompt_len)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOLERANCE[lm.dtype]
    assert np.quantile(np.abs(got - want), 0.9) < TOLERANCE[lm.dtype] / 20


def test_a_slot_reused_after_a_longer_sequence_holds_nothing_of_it(lm32):
    rng = np.random.default_rng(5)
    long = rng.integers(0, 97, size=90).tolist()
    short = rng.integers(0, 97, size=14).tolist()
    _, cache = _served_logits(lm32, long, 70)
    got, _ = _served_logits(lm32, short, 6, cache=cache)
    want = reference.forward(lm32.raw, SPEC, short)
    assert np.abs(got - want).max() < TOLERANCE["float32"]


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_the_reference_faults_move_the_logits(lm32, fault):
    """Each wrong model the tolerance study computes is far outside the
    float32 pair's agreement: the comparison sees it."""
    seq = np.random.default_rng(3).integers(0, 97, size=60).tolist()
    right = reference.forward(lm32.raw, SPEC, seq)
    wrong = reference.forward(lm32.raw, SPEC, seq, fault=fault)
    assert np.abs(right - wrong).max() > 10 * TOLERANCE["float32"]


def test_the_reference_reads_logits_from_a_position_on(lm32):
    seq = np.random.default_rng(4).integers(0, 97, size=20).tolist()
    whole = reference.forward(lm32.raw, SPEC, seq)
    assert np.array_equal(reference.forward(lm32.raw, SPEC, seq, first=13),
                          whole[13:])


# -- the recurrence's three forms -----------------------------------------------

def _recurrence_inputs(seed, t, heads=3, d=16, gate="drawn"):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    q = ling3.l2_norm(draw(1, t, heads, d)) * d ** -0.5
    k = ling3.l2_norm(draw(1, t, heads, d))
    v = draw(1, t, heads, d)
    beta = jax.nn.sigmoid(draw(1, t, heads))
    g = {"bound": jnp.full((1, t, heads, d), -5.0),   # the gate AT its bound
         "near_0": jnp.full((1, t, heads, d), -1e-4),
         # a head's channels from one that forgets at once to one that keeps
         "drawn": -5.0 * jax.nn.sigmoid(3.0 * draw(1, t, heads, d) - 3.0),
         }[gate]
    return q, k, v, g, beta


def _recurrence(q, k, v, g, beta):
    """``kda_step`` token by token over one sequence from a zero state: q,
    k, v, g (t, H, d), beta (t, H) → ``(o (t, H, d), state (H, d, d))``."""
    def token(state, xs):
        o, state = ling3.kda_step(state, *xs)
        return state, o

    _, heads, d = q.shape
    state, o = jax.lax.scan(token, jnp.zeros((heads, d, d), jnp.float32),
                            (q, k, v, g, beta))
    return o, state


def _prompt_inputs(seed, t, heads=3, d=16, gate="drawn", dtype="float32"):
    """What the prefill's kernel takes of one sequence — the convolution's
    input ``[q | k | v] (t, 3 H d)`` in ``dtype``, its taps, g and β — and
    the q, k, v the recurrence sees of them, in ``jax.numpy``: SiLU of the
    causal depthwise convolution as a sliding product, q and k normalised a
    head, q scaled."""
    rng = np.random.default_rng(1000 + seed)
    mixed = jnp.asarray(rng.standard_normal((t, 3 * heads * d)), dtype)
    taps = jnp.asarray(0.35 * rng.standard_normal((4, 3 * heads * d)),
                       jnp.float32)
    g, beta = (a[0] for a in _recurrence_inputs(seed, t, heads, d, gate)[3:])
    shifted = jnp.pad(mixed.astype(jnp.float32), ((len(taps) - 1, 0), (0, 0)))
    out = jax.nn.silu(sum(shifted[j:j + t] * taps[j]
                          for j in range(len(taps))))
    q, k, v = (out[:, i * heads * d:(i + 1) * heads * d].reshape(t, heads, d)
               for i in range(3))
    return (mixed, taps, g, beta), (ling3.l2_norm(q) * d ** -0.5,
                                    ling3.l2_norm(k), v)


def _chunked(mixed, taps, g, beta):
    """The prefill's kernel (``ops/pallas/kda_chunk.py``; the interpreter
    here) on the one sequence."""
    return ling3.kda_chunk(mixed, taps, g, beta, eps=ling3.L2_EPS)


# both sides of a sub-block's edge, of a chunk's (64: exactly one), several
# chunks, and a good many
@pytest.mark.parametrize("t", [15, 16, 17, 63, 64, 65, 150, 1100])
@pytest.mark.parametrize("gate", ["bound", "near_0", "drawn"])
def test_kda_chunked_is_the_recurrence_token_by_token(gate, t):
    """With ``g = −5`` on every channel of every token the factors about a
    sub-block's first row reach ``e^75``: nothing overflows, nothing is NaN,
    and the outputs are the recurrence's."""
    inputs, qkv = _prompt_inputs(t, t, gate=gate)
    want_o, want_state = _recurrence(*qkv, *inputs[2:])
    got_o, got_state = jax.jit(_chunked)(*inputs)
    assert bool(jnp.isfinite(got_o).all() & jnp.isfinite(got_state).all())
    assert np.abs(np.asarray(got_o - want_o)).max() < 2e-6
    assert np.abs(np.asarray(got_state - want_state)).max() < 5e-6


def test_kda_chunked_leaves_the_state_alone_at_padded_positions():
    (mixed, taps, g, beta), (q, k, v) = _prompt_inputs(9, 100)
    real = 37
    mask = (jnp.arange(100) < real)[:, None]
    _, padded = _chunked(mixed, taps, jnp.where(mask[..., None], g, 0.0),
                         jnp.where(mask, beta, 0.0))
    _, want = _recurrence(*(a[:real] for a in (q, k, v, g, beta)))
    assert np.abs(np.asarray(padded - want)).max() < 5e-6


# a head block (2 heads of 64 are a lane tile) and a half of one; an odd
# count, one block whatever its lanes, over exactly one chunk; one head; the
# published head width, the convolution's input in bfloat16
@pytest.mark.parametrize("heads,d,t,dtype", [
    (3, 64, 100, "float32"), (5, 16, 64, "float32"), (1, 16, 70, "float32"),
    (2, 128, 130, "bfloat16")])
def test_kda_chunk_takes_any_count_of_heads(heads, d, t, dtype):
    inputs, qkv = _prompt_inputs(heads, t, heads=heads, d=d, dtype=dtype)
    want_o, want_state = _recurrence(*qkv, *inputs[2:])
    got_o, got_state = jax.jit(_chunked)(*inputs)
    assert got_o.shape == want_o.shape and got_state.shape == want_state.shape
    assert np.abs(np.asarray(got_o - want_o)).max() < 2e-6
    assert np.abs(np.asarray(got_state - want_state)).max() < 5e-6


@pytest.mark.parametrize("gate", ["bound", "drawn"])
def test_kda_block_is_the_step_at_the_live_slots_and_nowhere_else(gate):
    """``kda_update`` under the interpreter: a live slot's read-out and
    successor are ``kda_step``'s, a dead slot's state is bit for bit what it
    was and its read-out zero."""
    slots, heads, d = 5, 4, 16
    rng = np.random.default_rng(2)
    state = jnp.asarray(rng.standard_normal((slots, heads, d, d)),
                        jnp.float32)
    q, k, v, g, beta = (a[0] for a in _recurrence_inputs(
        7, slots, heads=heads, gate=gate))
    position = jnp.asarray([3, 0, 9, 0, 1], jnp.int32)
    o, successor = ling3.kda_update(state, q, k, v, g, beta, position,
                                    interpret=True)
    want_o, want_state = ling3.kda_step(state, q, k, v, g, beta)
    live = np.asarray(position) > 0
    assert np.abs(np.asarray(o - want_o))[live].max() < 1e-5
    assert np.abs(np.asarray(successor - want_state))[live].max() < 1e-5
    assert np.array_equal(np.asarray(successor)[~live],
                          np.asarray(state)[~live])
    assert not np.asarray(o)[~live].any()
    # a decay a channel, not a head: the rows of a head's state shrink apart
    if gate == "drawn":
        kept = np.asarray(jnp.exp(g))[0, 0]
        assert kept.max() / kept.min() > 10


def test_kda_step_is_the_equation_as_written():
    """``S ← Diag(e^g) S; δ = β (v − Sᵀk); S ← S + k ⊗ δ; o = Sᵀq`` — three
    passes, where ``kda_step`` takes both readings from the old state."""
    rng = np.random.default_rng(0)
    state = rng.standard_normal((2, 3, 8, 8)).astype(np.float32)
    q, k, v, g, beta = (np.asarray(a[0, :2]) for a in _recurrence_inputs(
        1, 2, d=8))
    decayed = state * np.exp(g)[..., :, None]
    delta = beta[..., None] * (v - np.einsum("...kv,...k->...v", decayed, k))
    want = decayed + k[..., :, None] * delta[..., None, :]
    o, got = ling3.kda_step(jnp.asarray(state), q, k, v, g, beta)
    assert np.abs(np.asarray(got) - want).max() < 1e-6
    assert np.abs(np.asarray(o) - np.einsum("...kv,...k->...v", want,
                                            q)).max() < 1e-6


# -- the rotation ---------------------------------------------------------------

def test_the_interleaved_rotation_is_the_complex_product():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((11, 2, 8)).astype(np.float32)
    position = np.arange(11) * 1000
    theta = 6e6
    got = np.asarray(olmoe.rope(jnp.asarray(x), jnp.asarray(position), theta,
                                interleave=True))
    z = x[..., 0::2] + 1j * x[..., 1::2]
    turn = np.exp(1j * position[:, None, None]
                  * theta ** (-np.arange(4) / 4)[None, None, :])
    want = np.stack([(z * turn).real, (z * turn).imag], axis=-1).reshape(
        x.shape)
    assert np.abs(got - want).max() < 2e-4     # float32 angles of thousands
    # the reference writes it a second time, at the tokens' own indices
    again = np.asarray(reference.rotate(jnp.asarray(x), theta))
    near = np.exp(1j * np.arange(11)[:, None, None]
                  * theta ** (-np.arange(4) / 4)[None, None, :])
    assert np.abs(again - np.stack(
        [(z * near).real, (z * near).imag], axis=-1).reshape(x.shape)
    ).max() < 1e-5
    # the other layout is another function of the same lanes
    half = np.asarray(olmoe.rope(jnp.asarray(x), jnp.asarray(position),
                                 theta))
    assert np.abs(half - got).max() > 0.1
    # without the argument ``rope`` is what it computed before
    a, b = x[..., :4], x[..., 4:]
    cos, sin = turn.real, turn.imag
    assert np.abs(half - np.concatenate(
        [a * cos - b * sin, b * cos + a * sin], axis=-1)).max() < 2e-4


# -- the route -------------------------------------------------------------------

def _route_before(h, router, k, renormalise=False, scoring="softmax",
                  bias=None, scale=1.0):
    """``experts.route`` as it stood before it took ``groups``."""
    with jax.named_scope("router"):
        logits = jnp.einsum("...d,de->...e", h, router,
                            preferred_element_type=jnp.float32)
        if scoring == "softmax":
            p = jax.nn.softmax(logits, axis=-1)
        else:
            p = jax.nn.sigmoid(logits)
        if bias is None:
            top_p, top_e = jax.lax.top_k(p, k)
        else:
            _, top_e = jax.lax.top_k(p + bias.astype(jnp.float32), k)
            top_p = jnp.take_along_axis(p, top_e, axis=-1)
        if renormalise:
            top_p = top_p / top_p.sum(axis=-1, keepdims=True)
        return top_e, top_p * scale if scale != 1.0 else top_p


@pytest.mark.parametrize("kwargs", [
    dict(k=8), dict(k=10, renormalise=True),
    dict(k=8, renormalise=True, scoring="sigmoid", bias=True),
    dict(k=4, renormalise=True, scoring="sigmoid", bias=True, scale=2.0)],
    ids=["olmoe", "qwen3-next", "dots3", "xing4"])
def test_route_without_groups_traces_to_the_program_it_was(kwargs):
    h = jnp.zeros((6, 32), jnp.bfloat16)
    router = jnp.zeros((32, 64), jnp.bfloat16)
    kwargs = dict(kwargs)
    if kwargs.pop("bias", False):
        kwargs["bias"] = jnp.zeros((64,), jnp.float32)
    k = kwargs.pop("k")
    now = jax.make_jaxpr(lambda h, r: expert_layer.route(
        h, r, k, groups=None, **kwargs))(h, router)
    before = jax.make_jaxpr(lambda h, r: _route_before(
        h, r, k, **kwargs))(h, router)
    assert str(now) == str(before)


def _route_by_loop(s, bias, k, groups, scale):
    """The group-limited choice one row and one group at a time."""
    n, keep = groups
    size = s.shape[1] // n
    experts, weights = [], []
    for row in range(s.shape[0]):
        choice = s[row] + bias
        score = []
        for i in range(n):
            members = sorted(choice[i * size:(i + 1) * size], reverse=True)
            score.append(members[0] + members[1])
        kept = sorted(range(n), key=lambda i: (-score[i], i))[:keep]
        allowed = [e for e in range(s.shape[1]) if e // size in kept]
        picked = sorted(allowed, key=lambda e: (-choice[e], e))[:k]
        experts.append(picked)
        total = sum(s[row, e] for e in picked)
        weights.append([s[row, e] / total * scale for e in picked])
    return np.asarray(experts), np.asarray(weights)


@pytest.mark.parametrize("ties", [False, True])
def test_the_group_limited_route_is_the_loop(ties):
    """8 of 512 inside the 4 best of 8 groups, against a loop over rows and
    groups — also with tied scores (a router of few distinct values: groups
    tie, experts tie, the lower index wins)."""
    rng = np.random.default_rng(6)
    h = jnp.asarray(rng.standard_normal((48, 32)), jnp.float32)
    if ties:
        h = jnp.round(h)
        router = jnp.asarray(rng.integers(-1, 2, size=(32, 512)) * 0.25,
                             jnp.float32)
        bias = jnp.asarray(rng.integers(0, 2, size=512) * 0.125, jnp.float32)
    else:
        router = jnp.asarray(rng.standard_normal((32, 512)) * 0.4,
                             jnp.float32)
        bias = jnp.asarray(rng.standard_normal(512) * 0.2, jnp.float32)
    with jax.default_matmul_precision("highest"):
        top_e, top_p = expert_layer.route(
            h, router, 8, True, scoring="sigmoid", bias=bias, scale=2.5,
            groups=(8, 4))
        s = np.asarray(jax.nn.sigmoid(h @ router))
    want_e, want_p = _route_by_loop(s, np.asarray(bias), 8, (8, 4), 2.5)
    assert np.array_equal(np.asarray(top_e), want_e)
    assert np.abs(np.asarray(top_p) - want_p).max() < 1e-5
    assert np.abs(np.asarray(top_p).sum(axis=-1) - 2.5).max() < 1e-5
    # a token's picks lie in at most four groups, and the limit bites: a
    # plain top-8 of all 512 picks otherwise
    assert max(len(set(row // 64)) for row in want_e) <= 4
    plain_e, _ = expert_layer.route(h, router, 8, True, scoring="sigmoid",
                                    bias=bias, scale=2.5)
    assert not np.array_equal(np.asarray(plain_e), want_e)
    # the reference writes the choice a third time
    again_e, again_p = reference.route(np.asarray(h), np.asarray(router),
                                       np.asarray(bias), 8, (8, 4), 2.5)
    if not ties:     # its logits are numpy's: a tie's members may differ
        assert np.array_equal(again_e, want_e)
        assert np.abs(again_p - want_p).max() < 1e-5


@pytest.mark.parametrize("form", ["dense", "routed"])
def test_four_shares_under_the_group_limit_sum_to_the_uncut_layer(form):
    """The guide's share test under ``(8, 4)``: the expert layer told it
    holds experts ``16s .. 16s + 15`` of 64 — groups ``2s`` and ``2s + 1`` of
    8 —, for ``s = 0 .. 3``, gives four partial results that, with the
    shared expert counted once, add up to the reference's layer over all 64
    — in the form a step runs and in the form a prefill runs. A token keeps
    none, one or both groups of a share, so the shares are uneven."""
    total, held, k, d, f, groups = 64, 16, 8, 64, 32, (8, 4)
    rng = np.random.default_rng(5)
    layer = {"router": rng.standard_normal((d, total)) * 0.3,
             "router_bias": rng.standard_normal(total) * 0.2,
             "w_gate": rng.standard_normal((total, d, f)) / 8,
             "w_up": rng.standard_normal((total, d, f)) / 8,
             "w_down": rng.standard_normal((total, f, d)) / 6,
             "s_gate": rng.standard_normal((d, f)) / 8,
             "s_up": rng.standard_normal((d, f)) / 8,
             "s_down": rng.standard_normal((f, d)) / 6}
    layer = {n: jnp.asarray(a, jnp.float32) for n, a in layer.items()}
    h = jnp.asarray(rng.standard_normal((50, d)), jnp.float32)
    spec = dict(experts=total, experts_per_token=k, experts_held=total,
                first_expert=0, route_groups=groups, route_scale=2.5)

    def same(a):
        return a

    with jax.default_matmul_precision("highest"):
        want = reference.ffn(h, layer, spec, False, same, None)
        top_e, top_p = expert_layer.route(
            h, layer["router"], k, True, scoring="sigmoid",
            bias=layer["router_bias"], scale=2.5, groups=groups)
        got = expert_layer.shared(h, None, layer["s_gate"], layer["s_up"],
                                  layer["s_down"])
        parts, picks_held = [], []
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
            assert np.abs(np.asarray(parts[-1] - reference.experts(
                h, share, spec, same, held=(first, held)))).max() < 1e-5
            local = np.asarray(top_e) - first
            picks_held.append(((local >= 0) & (local < held)).sum(axis=1))
    assert np.abs(np.asarray(got + sum(parts) - want)).max() < 1e-5
    assert all(np.abs(np.asarray(p)).max() > 1e-3 for p in parts)
    # every pick is in exactly one share; a token meets 0 to 8 of a share
    assert (sum(picks_held) == k).all()
    assert min(p.min() for p in picks_held) == 0
    assert max(p.max() for p in picks_held) > k // 4 + 1


# -- the init, the declaration, the runtime -------------------------------------

def test_the_seeded_init_spreads_the_decay_over_a_heads_channels():
    _, params = create_ling3_lm(dtype="bfloat16", **dict(
        SPEC, heads=8, head_dim=64))
    layer = params["params"]["layer1"]
    assert layer["dt_bias"].dtype == layer["a_log"].dtype == jnp.float32
    assert layer["dt_bias"].shape == (8, 64) and layer["a_log"].shape == (8,)
    assert layer["router_bias"].dtype == jnp.float32
    assert layer["in_qkv"].dtype == layer["w_a"].dtype == jnp.bfloat16
    # at a = 0: e^g of a head's channels runs from under 0.96 to over 0.985
    g = -5.0 * jax.nn.sigmoid(jnp.exp(layer["a_log"])[:, None]
                              * layer["dt_bias"])
    kept = np.asarray(jnp.exp(g))
    assert (kept.min(axis=1) < 0.96).all()
    assert (kept.max(axis=1) > 0.985).all()
    assert float(g.min()) > -5.0 and float(g.max()) < 0.0
    latent = params["params"]["layer2"]
    assert "w_g" in latent and "in_qkv" not in latent
    assert latent["w_g"].shape == (64, 8)
    assert "router" not in params["params"]["layer0"]      # the dense layer
    assert params["params"]["layer0"]["m_gate"].shape == (64, 96)


def test_a_non_zero_swiglu_limit_raises():
    for key in ("expert_swiglu_limits", "shared_swiglu_limits"):
        with pytest.raises(ValueError, match="SwiGLU limit"):
            create_ling3_lm(dtype="float32", **SPEC, **{key: [0, 0, 4, 0]})
    create_ling3_lm(dtype="float32", **SPEC, expert_swiglu_limits=[0] * 4,
                    shared_swiglu_limits=[0] * 4)


@pytest.mark.parametrize("bad", [
    dict(depth=2), dict(route_groups=(3, 2)), dict(route_groups=(4, 5)),
    dict(experts_per_token=9), dict(first_expert=12), dict(rope_dim=7),
    dict(gate_bound=-6.0), dict(window=4)])
def test_a_spec_the_family_cannot_hold_is_refused(bad):
    with pytest.raises((ValueError, TypeError)):
        create_ling3_lm(dtype="float32", **dict(SPEC, **bad))


def test_cache_spec_declares_one_kind_of_rows_and_the_kda_states_live():
    model, _ = create_ling3_lm(dtype="bfloat16", **SPEC)
    spec = model.cache_spec()
    assert [(r.name, r.layers, r.width, r.length, r.kind, r.select, r.whole)
            for r in spec.rows] == [
        ("latent", 1, 128, None, "latent", None, False)]
    assert [s[0] for s in spec.state] == [
        name for j in range(3) for name in (f"kda{j}", f"conv{j}")]
    assert spec.state[0][1:] == ((4, 16, 16), jnp.float32)
    assert spec.state[1][1:] == ((3, 3 * 4 * 16), jnp.bfloat16)
    assert spec.live == ("kda0", "kda1", "kda2")
    assert state_pool.slot_bytes(spec.state, spec.live) == (
        3 * 4 * 16 * 16 * 4, 3 * 3 * 192 * 2)
    assert kv_pool.prefill_pairs(spec.rows, 19) == {"latent": 190}


def _runtime(**kwargs):
    from ai4e_tpu.runtime.kvcache import PagedDecodeRuntime, build_lm_servable
    lm = build_lm_servable("ling3", max_len=CACHE, dtype="float32", **SPEC)
    return PagedDecodeRuntime(lm, slots=3, prompt_buckets=BUCKETS[:2],
                              **kwargs)


def test_the_runtime_serves_the_family_and_counts_its_cache_and_state():
    """Through ``PagedDecodeRuntime``: the ids of prefill + steps are the
    reference's argmax, the launch reports the latent and the state bytes,
    the report carries the routing series, ``kda_retention`` and
    ``route_groups_held``, and nothing compiles after ``warm()``."""
    runtime = _runtime()
    runtime.warm()
    told = []
    runtime.phase_hook = lambda phase, seconds: told.append(phase)
    prompt = np.random.default_rng(11).integers(0, 97, size=21).tolist()
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
    # one live slot of three: its states in and out, every slot's tails
    kda, tails = 3 * 4 * 16 * 16 * 4, 3 * 3 * 192 * 4
    assert runtime.cache_nbytes() == (
        kv_pool.rows_nbytes(runtime.rows_spec(), 3, CACHE)
        + 3 * (kda + tails))
    assert set(step.cache_bytes) == {"latent", "state"}
    assert step.cache_bytes["state"] == 2 * (kda + 3 * tails)
    assert step.state_bytes == {"moved": 2 * (kda + 3 * tails),
                                "live": 2 * (kda + tails)}
    assert set(step.report) == {"experts_touched", "expert_peak_load",
                                "held_picks_share", "kda_retention",
                                "route_groups_held"}
    assert 0.5 < step.report["kda_retention"] < 1.0
    assert 0 <= step.report["route_groups_held"] <= 2
    assert runtime.report_kinds == ("first", "extra")
    assert runtime.prefill_report(21) == {
        "tokens": {"real": 21, "padded": 32},
        "pairs": {"latent": 21 * 22 // 2}}


def test_step_report_reads_the_live_slots_alone():
    model, _ = create_ling3_lm(dtype="float32", **SPEC)
    picks = np.zeros((3, 3, 3), np.int32)       # three expert layers
    picks[:, 1] = [1, 2, 5]       # groups 0, 0, 1: both held here
    picks[:, 2] = [1, 9, 12]      # groups 0, 2, 3: one held here
    picks[:, 0] = [4, 5, 6]       # a dead slot's: not counted
    kept = np.asarray([0.1, 0.9, 0.98], np.float32)
    extra = np.concatenate([picks.reshape(-1), kept.view(np.int32)])
    report = model.step_report(extra, [False, True, True])
    assert report["kda_retention"] == pytest.approx(0.94)
    assert report["route_groups_held"] == pytest.approx(1.5)
    assert report["held_picks_share"] == pytest.approx(4 / 6)
    assert report["experts_touched"] == 3.0
    assert model.step_report(extra, [False] * 3) == {}
    assert set(model.step_report_series) == set(report)


def test_the_engine_exposes_the_retention_and_counts_cache_and_state():
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
    for series in ("kda_retention", "route_groups_held", "experts_touched",
                   "held_picks_share"):
        assert f"ai4e_decode_{series}_count" in text
    kinds = reg.counter("ai4e_decode_cache_bytes_total")
    assert kinds.value(model="lm", kind="latent") > 0
    assert kinds.value(model="lm", kind="state") > 0
    moved = reg.counter("ai4e_decode_state_bytes_total")
    assert moved.value(model="lm", kind="moved") >= moved.value(
        model="lm", kind="live") > 0
    tokens = reg.counter("ai4e_decode_prefill_tokens_total")
    assert (tokens.value(model="lm", kind="real"),
            tokens.value(model="lm", kind="padded")) == (11, 16)
    pairs = reg.counter("ai4e_decode_prefill_pairs_total")
    assert pairs.value(model="lm", kind="latent") == 66
    passes = reg.counter("ai4e_decode_prefill_expert_passes_total")
    assert passes.value(model="lm", kind="first") == 3
    assert passes.value(model="lm", kind="extra") == 0


def test_the_family_is_one_of_the_decode_engines_seven():
    from ai4e_tpu.runtime.families import LM_FAMILIES
    assert "ling3" in LM_FAMILIES and len(LM_FAMILIES) >= 7
    assert set(ling3.TRACE_SCOPES) >= {"kda_gate", "kda_chunk",
                                       "state_update", "head_gate"}
