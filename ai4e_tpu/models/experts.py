"""The expert product of the sparse-expert LM families — one owner of
routing over ``total`` experts and of the part of the result the experts
held here give.

A MoE layer, for a row ``h`` (after its norm)::

    p = softmax(h W_r)                      over ALL ``total`` experts, float32
        (or sigmoid(h W_r), an expert's own score, where the family says)
    the K largest p and their experts       (a tie to the lower index; of
        p + b where the family keeps a bias b for the choice alone; inside
        the best few of the experts' groups where the family limits them)
    p <- p / sum of the K                   where the family renormalises
    y = sum over the row's K experts e of p_e * W_down,e(silu(W_gate,e h) * W_up,e h)

Where the family's configuration clamps its SwiGLU (``limit`` > 0: the
published ``swiglu_limit``), the gate is cut from above and the up
projection on both sides before the product, ``silu(min(g, limit)) * clip(u,
-limit, limit)`` (``swiglu``); 0, every family's default, is no clamp and the
program it always was.

and this process holds the experts ``first_held .. first_held + held - 1``
(``held`` = the leading axis of the weights it is given): it routes over all
``total`` — the router keeps its published width — and sums the terms of the
experts it holds. What the others would add is another chip's part of an
expert-parallel layer (its exchange is not here: on one chip the layer runs
without it). A family that holds every expert passes ``first_held = 0`` and
all of them.

Two forms of the same sum, chosen by the caller for the program it builds:

- ``dense``: every held expert computes every row, and a row's un-chosen
  experts are multiplied by zero before the down projection. Each expert's
  weights are read exactly once; the least work where nearly every expert is
  touched anyway, or where the read of the weights bounds the program (a
  decode step).
- ``routed``: the (row, pick) pairs that land on a held expert are sorted
  by expert and each expert multiplies its own rows only
  (``jax.lax.ragged_dot``), so the multiplies are the published ``K`` a
  row — what a prefill of thousands of rows over 128 held experts needs:
  dense there is ``held / (K * held / total)`` times the work. Its work is
  in proportion to the pairs held HERE, not to ``T x K``: all pairs are
  ranked (one sort of int32 keys), and a pass gathers, multiplies and
  combines a WINDOW of ranked pairs — ``window_rows``, a static size from
  the operands' shapes: one and a half times the ``T x K x held / total``
  an even router sends here, on whole tiles of 512 rows (1.5 T of 8 T
  where an eighth is held, 3.75 T of 10 T where a quarter is), and every
  pair where all experts are held or the pairs are few. Beyond the window
  nothing is dropped: the product takes as many passes as the held pairs
  need (``window_passes``), each reading the held experts' weights once —
  one on any router near even, none where nothing is held, ``T x K /
  window`` where every pick is. A prompt goes through in one call.

No capacity, no drop, in either form.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# ``routed``'s window is whole tiles of this many rows.
TILE = 512
# Columns ``routed`` adds into its result at once: XLA's scatter on the chip
# adds a row of 5,120 float32 in 1.45 us and one of at most 2,048 in 0.1 us
# (PERF.md section 6, PR 40).
COMBINE_COLUMNS = 1024


def _dot(eq, a, b):
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def swiglu(g, u, limit: float = 0.0):
    """``silu(g) * u`` in float32 — with ``limit`` > 0 the clamped form:
    ``silu(min(g, limit)) * clip(u, -limit, limit)``."""
    if limit:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return jax.nn.silu(g) * u


def kept_groups(choice, groups: tuple):
    """The group limit of a router: ``choice (..., total)`` — what the
    experts are chosen by — in ``groups = (n, keep)``: ``n`` groups of
    ``total / n`` neighbours, a group scored by the sum of its two largest,
    the ``keep`` best groups kept (a tie to the lower group). Returns
    ``choice`` with the experts of the other groups at ``-inf``."""
    n, keep = groups
    per_group = choice.reshape(*choice.shape[:-1], n, -1)
    best_two, _ = jax.lax.top_k(per_group, 2)
    _, kept = jax.lax.top_k(best_two.sum(axis=-1), keep)
    allowed = jax.nn.one_hot(kept, n, dtype=jnp.bool_).any(axis=-2)
    return jnp.where(allowed[..., None], per_group,
                     -jnp.inf).reshape(choice.shape)


def route(h, router, k: int, renormalise: bool = False,
          scoring: str = "softmax", bias=None, scale: float = 1.0,
          groups: tuple | None = None):
    """``h (..., D)`` (after the layer's norm), ``router (D, total)`` → the
    chosen experts ``(..., K)``, ids over ``total``, and their weights
    ``(..., K)`` in float32: the scores — ``scoring``: the softmax
    probabilities, or each expert's own ``sigmoid`` — of the K largest,
    divided by their sum where ``renormalise`` and multiplied by ``scale``.
    ``bias (total,)``, where given, is added to the scores for the CHOICE
    alone: the weights are the scores without it. ``groups = (n, keep)``,
    where given, limits the choice to the ``keep`` best of ``n`` groups of
    experts (``kept_groups``). ``top_k`` breaks a tie toward the lower
    expert index and returns exactly K."""
    with jax.named_scope("router"):
        logits = _dot("...d,de->...e", h, router)
        if scoring == "softmax":
            p = jax.nn.softmax(logits, axis=-1)
        elif scoring == "sigmoid":
            p = jax.nn.sigmoid(logits)
        else:
            raise ValueError(f"unknown scoring {scoring!r}")
        if bias is None and groups is None:
            top_p, top_e = jax.lax.top_k(p, k)
        else:
            choice = p if bias is None else p + bias.astype(jnp.float32)
            if groups is not None:
                choice = kept_groups(choice, groups)
            _, top_e = jax.lax.top_k(choice, k)
            top_p = jnp.take_along_axis(p, top_e, axis=-1)
        if renormalise:
            top_p = top_p / top_p.sum(axis=-1, keepdims=True)
        return top_e, top_p * scale if scale != 1.0 else top_p


def gate_matrix(top_e, top_p, held: int, first_held: int = 0):
    """``P (..., held)``: each row's weights at the columns of its chosen
    experts that are held here, zero elsewhere (an expert held elsewhere has
    no column: ``one_hot`` of an index out of range is zero)."""
    with jax.named_scope("router"):
        local = top_e - first_held if first_held else top_e
        chosen = jax.nn.one_hot(local, held, dtype=jnp.float32)
        return (chosen * top_p[..., None]).sum(axis=-2)


def dense(h, gate, w_gate, w_up, w_down, limit: float = 0.0):
    """``(silu(h W_gate) * h W_up * P) W_down`` over the held experts:
    ``h (..., D)``, ``gate`` = ``gate_matrix``'s ``P (..., held)``, weights
    ``(held, D, F)`` and ``(held, F, D)``; ``limit``: ``swiglu``'s clamp.
    Returns ``(..., D)`` in ``h``'s dtype."""
    with jax.named_scope("experts"):
        g = _dot("...d,edf->...ef", h, w_gate)
        u = _dot("...d,edf->...ef", h, w_up)
        a = (swiglu(g, u, limit) * gate[..., None]).astype(h.dtype)
        return _dot("...ef,efd->...d", a, w_down).astype(h.dtype)


def window_rows(rows: int, k: int, held: int, total: int) -> int:
    """The (row, pick) pairs ``routed`` multiplies in one pass, from shapes
    alone: one and a half times the ``rows x k x held / total`` an even
    router sends to the experts held here, on whole tiles of ``TILE`` — and
    every pair where that would be no fewer (all experts held) or where the
    pairs are few: the product over one tile of rows that 128 experts share
    is dearer on the chip than the pairs it leaves out (PERF.md section 6,
    PR 40)."""
    pairs = rows * k
    if pairs <= 8 * TILE:
        return pairs
    tiles = -(-3 * pairs * held // (2 * total * TILE))
    return min(tiles * TILE, pairs)


def window_passes(top_e, held: int, total: int, first_held: int = 0):
    """The passes ``routed`` takes over ``top_e (T, K)``: the pairs that
    land on an expert held here over its window, rounded up — an int32
    scalar, 0 where none does."""
    local = top_e - first_held
    count = ((local >= 0) & (local < held)).sum().astype(jnp.int32)
    return -(-count // window_rows(*top_e.shape, held, total))


# What the prefill of a family whose expert layers are ``routed`` appends to
# its first id (``pass_report``), as the decode engine counts it:
# ``ai4e_decode_prefill_expert_passes_total{kind}``.
prefill_report_kinds = ("first", "extra")


def pass_report(passes):
    """Each expert layer's ``window_passes`` of one prefill → int32 ``(2,)``
    in ``prefill_report_kinds``' order: the layers that took a pass at all,
    and the passes beyond it — 0 while every layer's held pairs fit its
    window."""
    passes = jnp.stack(passes)
    first = jnp.minimum(passes, 1).sum()
    return jnp.stack([first, passes.sum() - first])


def routed(h, top_e, top_p, w_gate, w_up, w_down, total: int,
           first_held: int = 0, limit: float = 0.0):
    """The same sum with each held expert multiplying only the rows that
    chose it. ``h (T, D)``, ``top_e``, ``top_p (T, K)``, ``total`` the
    router's width. The ``T x K`` (row, pick) pairs are ranked by held
    expert, the pairs of experts held elsewhere last; a pass gathers,
    multiplies and combines a window of ``window_rows`` ranked pairs, each
    expert's group cut to the window, and there are as many passes as the
    held pairs need: one where the router spreads anywhere near evenly,
    none where nothing is held, ``T x K / window`` where every pick is.
    What ``ragged_dot`` leaves in the rows past the last group is not
    defined on every backend, so they are zeroed by hand. Each pair's
    output is weighted and added into its row of a ``(T, D)`` float32
    result, ``COMBINE_COLUMNS`` columns a scatter. ``limit``: ``swiglu``'s
    clamp. Returns ``(T, D)`` in ``h``'s dtype."""
    held = w_gate.shape[0]
    t, k = top_e.shape
    window = window_rows(t, k, held, total)
    with jax.named_scope("experts"):
        local = (top_e - first_held).reshape(-1)
        group = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.pad(jnp.argsort(group, stable=True).astype(jnp.int32),
                        (0, -(t * k) % window))
        sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
        ends = jnp.cumsum(sizes)
        begins = ends - sizes
        weight = top_p.reshape(-1)
        columns = range(0, w_down.shape[-1], COMBINE_COLUMNS)

        def one_pass(i, out):
            lo = i * window
            pairs = jax.lax.dynamic_slice_in_dim(order, lo, window)
            cut = (jnp.clip(ends, lo, lo + window)
                   - jnp.clip(begins, lo, lo + window))
            grouped = (lo + jnp.arange(window) < ends[-1])[:, None]
            row = pairs // k
            x = h[row]
            g = jax.lax.ragged_dot(x, w_gate, cut,
                                   preferred_element_type=jnp.float32)
            u = jax.lax.ragged_dot(x, w_up, cut,
                                   preferred_element_type=jnp.float32)
            a = jnp.where(grouped, swiglu(g, u, limit)
                          * weight[pairs][:, None], 0.0).astype(h.dtype)
            y = jnp.where(grouped, jax.lax.ragged_dot(
                a, w_down, cut, preferred_element_type=jnp.float32), 0.0)
            return tuple(
                block.at[row].add(y[:, at:at + COMBINE_COLUMNS])
                for at, block in zip(columns, out))

        out = jax.lax.fori_loop(
            0, window_passes(top_e, held, total, first_held), one_pass,
            tuple(jnp.zeros((t, min(COMBINE_COLUMNS, w_down.shape[-1] - at)),
                            jnp.float32) for at in columns))
        return jnp.concatenate(out, axis=1).astype(h.dtype)


def shared(h, gate_w, w_gate, w_up, w_down, limit: float = 0.0):
    """A shared expert every row passes through: ``W_down(silu(W_gate h) *
    W_up h)``, weights ``(D, F)`` and ``(F, D)`` — behind a sigmoid gate of
    its own, ``sigmoid(h w_s)``, where the family has one (``gate_w (D,
    1)``; None: ungated). ``limit``: ``swiglu``'s clamp."""
    with jax.named_scope("shared_expert"):
        a = swiglu(_dot("...d,df->...f", h, w_gate),
                   _dot("...d,df->...f", h, w_up), limit).astype(h.dtype)
        y = _dot("...f,fd->...d", a, w_down)
        if gate_w is None:
            return y.astype(h.dtype)
        return (jax.nn.sigmoid(_dot("...d,do->...o", h, gate_w))
                * y).astype(h.dtype)


# What ``load_report`` returns, as the decode engine exposes it (a model's
# ``step_report_series``): each name a histogram ``ai4e_decode_<name>``, with
# its help and buckets. One declaration for every routed family.
step_report_series = {
    "experts_touched": (
        "Experts with at least one LIVE token, a MoE layer a decode "
        "step (mean over the step's layers)",
        (*(2 ** i for i in range(11)), float("inf"))),
    "expert_peak_load": (
        "The fullest expert's live tokens over the mean load (live "
        "slots x experts a token / experts), a MoE layer a decode "
        "step: the straggler measure",
        (1.0, 1.25, 1.5, 1.75, 2.0, 2.5, 3.0, 4.0, 6.0, 8.0,
         float("inf"))),
    "held_picks_share": (
        "Live tokens' picks that land on an expert held here over all "
        "their picks, a decode step (held / total experts where the "
        "router spreads evenly)",
        (0.0, 0.05, 0.1, 0.15, 0.2, 0.25, 0.3, 0.35, 0.4, 0.5, 0.75,
         1.0, float("inf"))),
}


def load_report(picks: np.ndarray, total: int, held: int,
                first_held: int = 0) -> dict[str, float]:
    """A decode step's routing figures from its chosen experts, on the host:
    ``picks (layers, live slots, K)``, ids over ``total``. Over the experts
    HELD here: ``experts_touched`` — those with at least one live token —
    and ``expert_peak_load`` — the fullest one's tokens over the mean load
    (live × K ÷ ``total``) —, each the mean over the layers; and
    ``held_picks_share``, the share of the picks that land here (1 for a
    family that holds every expert)."""
    layers, live, k = picks.shape
    local = picks - first_held
    here = (local >= 0) & (local < held)
    layer = np.broadcast_to(np.arange(layers)[:, None, None], picks.shape)
    load = np.bincount((local + held * layer)[here],
                       minlength=layers * held).reshape(layers, held)
    return {"experts_touched": float((load > 0).sum(axis=1).mean()),
            "expert_peak_load": float(load.max(axis=1).mean()
                                      / (live * k / total)),
            "held_picks_share": float(here.mean())}
