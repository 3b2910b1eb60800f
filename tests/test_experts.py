"""``models/experts.py``'s ``routed`` — the product over a window of the
(row, pick) pairs that land on an expert held here — against ``dense`` and
against a plain loop over (row, pick), in float32 on the CPU: every routing
the window can meet (all experts held, a share from an offset, more held
pairs than a window holds, none, one expert taking every row, rows that fill
no whole tile), the window's size from shapes, and the lowered prefill of a
toy ``dots3``: one product an expert layer, over the window's rows.
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

from ai4e_tpu.models import experts as expert_layer  # noqa: E402
from ai4e_tpu.models.dots3 import create_dots3_lm  # noqa: E402

D, F = 24, 16


def layer(total: int, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    return {"router": rng.standard_normal((D, total)) * 0.5,
            "w_gate": rng.standard_normal((total, D, F)) / 4,
            "w_up": rng.standard_normal((total, D, F)) / 4,
            "w_down": rng.standard_normal((total, F, D)) / 4}


def plain(h, top_e, top_p, weights, first: int, held: int) -> np.ndarray:
    """The sum a pair at a time, in float64: the loop ``routed`` stands for."""
    w_gate, w_up, w_down = (np.asarray(w, np.float64) for w in weights)
    h, out = np.asarray(h, np.float64), np.zeros(h.shape, np.float64)
    for row, (experts, scores) in enumerate(zip(np.asarray(top_e),
                                                np.asarray(top_p))):
        for e, p in zip(experts - first, scores):
            if 0 <= e < held:
                g, u = h[row] @ w_gate[e], h[row] @ w_up[e]
                out[row] += p * ((g / (1 + np.exp(-g)) * u) @ w_down[e])
    return out


def both_forms(h, top_e, top_p, weights, total: int, first: int):
    held = weights[0].shape[0]
    with jax.default_matmul_precision("highest"):
        got = expert_layer.routed(h, top_e, top_p, *weights, total=total,
                                  first_held=first)
        gate = expert_layer.gate_matrix(top_e, top_p, held, first)
        return np.asarray(got), np.asarray(
            expert_layer.dense(h, gate, *weights))


# (total, held, first): all / a quarter from an offset / an eighth
SHARES = {"all": (8, 8, 0), "quarter": (16, 4, 4), "eighth": (32, 4, 20)}


@pytest.mark.parametrize("rows", [50, 1500])
@pytest.mark.parametrize("share", list(SHARES))
def test_routed_is_dense_and_the_plain_loop(share, rows):
    """As the router routes: ``rows`` 50 — every pair in one window — and
    1,500 of 4 picks — a window of whole tiles that is neither the pairs nor
    a divisor of them."""
    total, held, first = SHARES[share]
    k, raw = 4, layer(total)
    h = jnp.asarray(np.random.default_rng(1).standard_normal((rows, D)),
                    jnp.float32)
    top_e, top_p = expert_layer.route(h, jnp.asarray(raw["router"],
                                                     jnp.float32), k, True)
    weights = [jnp.asarray(raw[n][first:first + held], jnp.float32)
               for n in ("w_gate", "w_up", "w_down")]
    window = expert_layer.window_rows(rows, k, held, total)
    assert (window < rows * k) == (rows == 1500 and held < total)
    got, dense = both_forms(h, top_e, top_p, weights, total, first)
    want = plain(h, top_e, top_p, weights, first, held)
    assert np.abs(want).max() > 0.1
    assert np.abs(got - want).max() < 2e-5
    assert np.abs(got - dense).max() < 2e-5


def picks(kind: str, rows: int, k: int, total: int, held: int, first: int):
    """Routings no router of random weights gives, by hand."""
    rng = np.random.default_rng(3)
    elsewhere = np.setdiff1d(np.arange(total), np.arange(first, first + held))
    if kind == "every_pick_held":
        top_e = np.stack([rng.permutation(held)[:k] + first
                          for _ in range(rows)])
    elif kind == "none_held":
        top_e = np.stack([rng.permutation(elsewhere)[:k]
                          for _ in range(rows)])
    elif kind == "one_expert_takes_every_row":
        top_e = np.stack([rng.permutation(elsewhere)[:k]
                          for _ in range(rows)])
        top_e[np.arange(rows), rng.integers(0, k, rows)] = first + 1
    else:
        raise ValueError(kind)
    top_p = rng.uniform(0.05, 1.0, (rows, k))
    return (jnp.asarray(top_e, jnp.int32),
            jnp.asarray(top_p / top_p.sum(axis=1, keepdims=True),
                        jnp.float32))


@pytest.mark.parametrize("kind,total,passes", [
    ("every_pick_held", 32, 4),       # 6,000 held pairs, a window of 1,536
    ("none_held", 32, 0),
    ("one_expert_takes_every_row", 32, 1),   # 1,500 pairs, a window of 1,536
    ("one_expert_takes_every_row", 64, 2)])  # one group across two windows
def test_routed_is_exact_beyond_and_below_its_window(kind, total, passes):
    """No capacity, no drop: where more pairs are held than the window has
    rows the product takes them in further passes and gives the same sum;
    where none is, zeros and no NaN."""
    rows, k, held, first = 1500, 4, 4, 8
    top_e, top_p = picks(kind, rows, k, total, held, first)
    window = expert_layer.window_rows(rows, k, held, total)
    local = np.asarray(top_e) - first
    count = int(((local >= 0) & (local < held)).sum())
    assert -(-count // window) == passes
    assert int(expert_layer.window_passes(top_e, held, total, first)) == passes
    raw = layer(total, seed=2)
    h = jnp.asarray(np.random.default_rng(4).standard_normal((rows, D)),
                    jnp.float32)
    weights = [jnp.asarray(raw[n][first:first + held], jnp.float32)
               for n in ("w_gate", "w_up", "w_down")]
    got, dense = both_forms(h, top_e, top_p, weights, total, first)
    want = plain(h, top_e, top_p, weights, first, held)
    assert np.isfinite(got).all()
    assert (np.abs(want).max() > 0.1) == (passes > 0)
    assert np.abs(got - want).max() < 2e-5
    assert np.abs(got - dense).max() < 2e-5
    if not passes:
        assert not got.any()


@pytest.mark.parametrize("shape,window", [
    ((12544, 8, 32, 256), 18944),    # dots3.longdoc's widest prefill: 1.51 T
    ((6144, 8, 32, 256), 9216),
    ((3072, 8, 32, 256), 4608),
    ((2048, 10, 128, 512), 7680),    # qnext.docqa: 3.75 T of 10 T
    ((2048, 8, 64, 64), 16384),      # every expert held: every pair
    ((128, 10, 128, 512), 1280),     # few pairs: every one (a tile of rows
    ((256, 10, 128, 512), 2560),     # over 128 experts costs more than it
    ((512, 10, 128, 512), 2048),     # saves), up to eight tiles of them
    ((23, 2, 2, 16), 46),
    ((1500, 4, 4, 32), 1536)])
def test_the_window_is_a_function_of_shapes(shape, window):
    assert expert_layer.window_rows(*shape) == window


def test_a_prefills_report_counts_first_and_further_passes():
    """``pass_report`` over the layers' ``window_passes``: the layers that
    took a pass at all, and the passes beyond it."""
    report = expert_layer.pass_report(
        [jnp.int32(n) for n in (1, 0, 3, 1, 2)])
    assert report.dtype == jnp.int32
    assert report.tolist() == [4, 3]
    assert len(expert_layer.prefill_report_kinds) == report.shape[0]


def equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [
                    value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from equations(inner)


def test_a_toy_prefill_has_one_product_an_expert_layer_over_the_window():
    """The lowered prefill of a toy ``dots3`` that holds an eighth of its
    experts — a dense layer, then two expert layers — at 4,096 positions of
    2 picks: three ``ragged_dot``s an expert
    layer — gate, up, down: ONE product, no loop over chunks of rows — and
    each takes the window's 1,536 rows, not the 8,192 pairs."""
    spec = dict(vocab_size=97, dim=32, layer_types=("full", "full", "sliding"),
                dense_layers=1, heads=2, q_rank=16, kv_rank=16, nope=8,
                rope_dim=8, v_dim=8, rope_theta=8e7, swa_heads=2,
                swa_q_rank=16, swa_kv_rank=16, swa_nope=8, swa_rope_dim=8,
                swa_v_dim=8, swa_rope_theta=5e4, window=5, index_heads=2,
                index_dim=16, index_topk=8, mlp_dim=32, experts=16,
                experts_held=2, first_expert=2, experts_per_token=2,
                expert_dim=16, shared_dim=16, route_scale=1.0, rms_eps=1e-5)
    model, params = create_dots3_lm(dtype="float32", **spec)
    rows = 4096
    assert expert_layer.window_rows(rows, 2, 2, 16) == 1536
    jaxpr = jax.make_jaxpr(lambda p, t, n: model.apply(
        p, t, n, method="prefill"))(
            params, jnp.zeros((1, rows), jnp.int32),
            jnp.asarray([rows], jnp.int32))
    products = [eqn.invars[0].aval.shape for eqn in equations(jaxpr.jaxpr)
                if eqn.primitive.name == "ragged_dot_general"]
    assert sorted(products) == sorted(2 * [(1536, 32), (1536, 32), (1536, 16)])
