"""On-device Pallas kernel validation (VERDICT r1 next-round #5).

The serving kernels (``flash_attention``, ``segmentation_argmax``,
``normalize_image``, ``decode_attention``, ``latent_attention``,
``prompt_attention``, ``index_scores``, ``select_top``, ``state_update``,
``kda_chunk``, ``mhc_pre`` / ``mhc_post``) default to interpret mode off-TPU, so CPU CI never
proves they compile to Mosaic and fit VMEM on real hardware. This module is
that proof: ``validate_kernels()`` runs each kernel with ``interpret=False``
(on TPU) against a pure-XLA oracle and asserts its working set fits the
per-core scoped-VMEM budget under double buffering. ``chip_smoke.py`` runs
``python -m ai4e_tpu.ops.pallas.validate`` as its kernel phase — a failed
kernel fails the run.

VMEM accounting mirrors each kernel's BlockSpecs (pallas_guide.md: Mosaic
double-buffers every in/out block; scratch is single-buffered).
"""

from __future__ import annotations

import jax
import numpy as np

# v4/v5e/v5p cores expose ~16 MiB of VMEM; stay under with headroom for
# Mosaic's own spills.
VMEM_BUDGET_BYTES = 16 * 1024 * 1024


def flash_attention_vmem_bytes(block_q: int, block_k: int, d: int,
                               dtype_bytes: int = 4) -> int:
    """Double-buffered q/k/v/out blocks + f32 scratch (acc, m, l)."""
    blocks = (block_q * d) + 2 * (block_k * d) + (block_q * d)
    scratch = (block_q * d + 2 * block_q) * 4
    return 2 * blocks * dtype_bytes + scratch


def segmentation_argmax_vmem_bytes(c: int, tile_h: int, w: int,
                                   dtype_bytes: int = 4) -> int:
    return 2 * ((c * tile_h * w) * dtype_bytes + tile_h * w * 1)


def normalize_image_vmem_bytes(tile_h: int, w: int, c: int) -> int:
    row = w * c
    return 2 * ((tile_h * row) * 1 + 2 * row * 4 + (tile_h * row) * 4)


def decode_attention_vmem_bytes(block: int, heads: int, row: int,
                                dtype_bytes: int) -> int:
    """Double-buffered K and V blocks, the new token's three rows and the
    output row; scratch (block-diagonal q, accumulator, max, sum) once."""
    blocks = (2 * block + 4) * row * dtype_bytes
    scratch = heads * row * (dtype_bytes + 4) + 2 * heads * 128 * 4
    return 2 * blocks + scratch


def state_update_vmem_bytes(block_bytes: int, small_bytes: int) -> int:
    """One slot's block of the state tensor double-buffered in and out, the
    slot's operands and read-out double-buffered, and the headroom the call
    asks Mosaic for beside them (``state_update.vmem_bytes``: the same
    number is the call's ``vmem_limit_bytes``)."""
    from .state_update import vmem_bytes
    return vmem_bytes(block_bytes, small_bytes)


def kda_chunk_vmem_bytes(head_block: int, d: int, dtype_bytes: int = 2,
                         taps: int = 4) -> int:
    """A chunk's blocks ``(CHUNK, head_block · d)`` — the convolution's
    input three times (q's, k's and v's lanes) with the ``HALO`` rows before
    each, in its dtype; g and o in float32 —, the taps' three blocks (a
    sublane tile each) and the head block's states ``(head_block, d, d)``,
    double-buffered all, β's block (a lane tile a row); and for the values
    of the heads in work — their q, k, v, the CHUNK-square matrices, the
    products — four times one chunk's float32 block."""
    from .kda_chunk import CHUNK, HALO
    lanes = head_block * d
    blocks = (3 * (CHUNK + HALO) * lanes * dtype_bytes
              + 3 * max(taps, 8) * lanes * 4 + 2 * CHUNK * lanes * 4
              + head_block * d * d * 4 + CHUNK * 128 * 4)
    return 2 * blocks + 4 * CHUNK * lanes * 4


def select_top_vmem_bytes(n: int) -> int:
    """A tile of rows at ``n`` columns: its float32 scores, its one-byte mask
    and its one-byte result double-buffered, the int32 key image once, and
    the headroom the call asks Mosaic for beside them (``select_top
    .vmem_bytes``: the same number is the call's ``vmem_limit_bytes``)."""
    from .select_top import vmem_bytes
    return vmem_bytes(n)


def mhc_rows_vmem_bytes(n: int, d: int, dtype_bytes: int = 2) -> int:
    """The larger of a prompt's two hyper-connection kernels at ``n``
    streams of ``d`` lanes: a block of rows double-buffered — in and out in
    ``mhc_post``, beside ``φ`` on a lane tile in ``mhc_pre`` — with the
    sublayer's row, the coefficients' lane tile and the headroom
    (``mhc_rows.pre_vmem_bytes`` / ``post_vmem_bytes``: the calls'
    ``vmem_limit_bytes``)."""
    from .mhc_rows import post_vmem_bytes, pre_vmem_bytes
    return max(pre_vmem_bytes(n, d, dtype_bytes),
               post_vmem_bytes(n, d, dtype_bytes))


# What the chip has of VMEM, which a call may ask for beyond the scoped
# default of ``VMEM_BUDGET_BYTES`` (``vmem_limit_bytes``).
VMEM_PHYSICAL_BYTES = 128 * 1024 * 1024


def validate_kernels(interpret: bool = False) -> dict:
    """Run each kernel against its XLA oracle; returns per-kernel
    {ok, max_err, vmem_bytes}. ``interpret=True`` runs the same checks in the
    pallas interpreter (CPU CI coverage of this module's own logic)."""
    from .. import kv_pool
    from .flash_attention import flash_attention
    from .image_preprocess import normalize_image
    from .seg_postprocess import segmentation_argmax

    results: dict[str, dict] = {}
    rng = np.random.default_rng(0)

    # flash attention vs naive softmax(QK^T)V — serving shape of the
    # long-context family (seqformer) at block 128.
    b, h, s, d = 2, 4, 512, 64
    q = rng.standard_normal((b, h, s, d)).astype(np.float32)
    k = rng.standard_normal((b, h, s, d)).astype(np.float32)
    v = rng.standard_normal((b, h, s, d)).astype(np.float32)
    got = np.asarray(jax.jit(
        lambda q, k, v: flash_attention(q, k, v, interpret=interpret)
    )(q, k, v))
    scores = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    want = np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), v)
    err = float(np.max(np.abs(got - want)))
    vmem = flash_attention_vmem_bytes(128, 128, d)
    assert vmem <= VMEM_BUDGET_BYTES, f"flash attention VMEM {vmem}"
    # Tolerance is set by the arithmetic of the executing backend, not the
    # kernel (or the interpret flag — interpret-mode jnp ops still run on
    # the default device): at DEFAULT precision the TPU MXU truncates f32
    # matmul operands to bf16 (~8 mantissa bits), so vs the f64-exact numpy
    # oracle the attention output carries ~4e-3 absolute error at these
    # scales (r2 measured 2.5e-3 on v5e, identically under interpret=True).
    # CPU runs true f32 (~1e-6) and keeps the tight bound so CPU CI still
    # catches sub-1e-2 kernel-logic regressions.
    tol = 1e-2 if jax.default_backend() == "tpu" else 1e-4
    results["flash_attention"] = {
        "ok": bool(err < tol), "max_err": round(err, 6), "vmem_bytes": vmem}

    # segmentation argmax vs jnp.argmax — the land-cover serving shape.
    bb, hh, ww, cc = 2, 256, 256, 4
    logits = rng.standard_normal((bb, hh, ww, cc)).astype(np.float32)
    got_map = np.asarray(jax.jit(
        lambda x: segmentation_argmax(x, interpret=interpret))(logits))
    want_map = np.argmax(logits, -1).astype(np.uint8)
    seg_ok = bool((got_map == want_map).mean() > 0.9999)  # fp ties tolerated
    vmem = segmentation_argmax_vmem_bytes(cc, 64, ww)
    assert vmem <= VMEM_BUDGET_BYTES, f"segmentation argmax VMEM {vmem}"
    results["segmentation_argmax"] = {
        "ok": seg_ok,
        "max_err": float((got_map != want_map).mean()),
        "vmem_bytes": vmem}

    # uint8 normalize vs XLA arithmetic — the tile ingestion shape.
    img = rng.integers(0, 256, (2, 256, 256, 3), dtype=np.uint8)
    mean, std = (0.45, 0.45, 0.4), (0.22, 0.22, 0.25)
    got_n = np.asarray(jax.jit(
        lambda x: normalize_image(x, mean=mean, std=std,
                                  interpret=interpret))(img))
    want_n = ((img.astype(np.float32) / 255.0 - np.asarray(mean))
              / np.asarray(std))
    err = float(np.max(np.abs(got_n - want_n)))
    vmem = normalize_image_vmem_bytes(64, 256, 3)
    assert vmem <= VMEM_BUDGET_BYTES, f"normalize VMEM {vmem}"
    results["normalize_image"] = {
        "ok": bool(err < 1e-5), "max_err": round(err, 7), "vmem_bytes": vmem}

    # decode attention over the K/V pool vs a plain float32 softmax — the
    # served rows (16 heads of 64 in float32, of 128 in bfloat16: 4 KB
    # either way, so blocks of 256 positions; 16 query heads on 2 K/V heads
    # of 256 in bfloat16: a 1 KB row, blocks of 1,024; 32 query heads on 8
    # K/V heads of 64 in bfloat16 — a group of 4, no whole sublane tile, a
    # K/V head on half a lane tile — with the scores multiplied by 1/64 as
    # that family hands it over), one layer of a pool of eight slots at
    # ragged positions: dead, a block's edges, the whole length.
    for name, dtype, heads, kv_heads, head_dim, length, scale, tol in (
            ("float32", "float32", 16, 16, 64, 1024, None, 1e-4),
            ("bfloat16", "bfloat16", 16, 16, 128, 1024, None, 0.04),
            ("grouped_bfloat16", "bfloat16", 16, 2, 256, 3072, None, 0.04),
            ("narrow_group_bfloat16", "bfloat16", 32, 8, 64, 1024, 1.0 / 64,
             0.04)):
        edge = kv_pool.read_block((1, 1, length, kv_heads * head_dim), dtype)
        position = np.asarray([0, 1, edge - 1, edge, edge + 1, 600,
                               length - 1, length], np.int32)
        shape = kv_pool.pool_shape(
            kv_pool.Rows("k", 1, kv_heads * head_dim, dtype), len(position),
            length)
        k_pool, v_pool = (jax.numpy.asarray(rng.standard_normal(shape), dtype)
                          for _ in range(2))
        q, k_new, v_new = (
            jax.numpy.asarray(
                rng.standard_normal((len(position), n, head_dim)), dtype)
            for n in (heads, kv_heads, kv_heads))
        got = np.asarray(jax.jit(
            lambda *a: kv_pool.decode_attention(
                *a, 0, position, interpret=interpret, scale=scale)
        )(q, k_new, v_new, k_pool, v_pool), np.float32)
        f32 = [np.asarray(x, np.float32) for x in (q, k_new, v_new)]
        cached = [np.asarray(x, np.float32)[0].reshape(
            len(position), length, kv_heads, head_dim)
            for x in (k_pool, v_pool)]
        err = 0.0
        for slot, p in enumerate(position):
            # each K/V head under its heads // kv_heads query heads
            keys, values = (np.repeat(
                np.concatenate([c[slot, :p], new[slot][None]]),
                heads // kv_heads, axis=1)
                for c, new in zip(cached, f32[1:]))
            scores = np.einsum("lhd,hd->hl", keys, f32[0][slot]) * (
                head_dim ** -0.5 if scale is None else scale)
            w = np.exp(scores - scores.max(-1, keepdims=True))
            want = np.einsum("hl,lhd->hd", w / w.sum(-1, keepdims=True),
                             values)
            err = max(err, float(np.max(np.abs(got[slot] - want))))
        block = kv_pool.read_block(shape, dtype)
        vmem = decode_attention_vmem_bytes(block, heads, shape[-1],
                                           np.dtype(dtype).itemsize)
        assert vmem <= VMEM_BUDGET_BYTES, f"decode attention VMEM {vmem}"
        results[f"decode_attention_{name}"] = {
            "ok": bool(err < tol), "max_err": round(err, 6),
            "vmem_bytes": vmem}

    # the latent read (rows every head shares, the value a row's own first
    # lanes) vs a plain float32 softmax, at the ``dots3`` cell's shapes: 128
    # heads on a 640-lane row (576 published + padding) of which 512 are the
    # value, a cache of 12,544 in blocks of 768 — the last one cut —, with
    # and without a selection's mask (whole blocks left out in one slot, the
    # new token's own term in another); a window's ring, 64 heads on 1,152
    # lanes, 512 rows; and the ``glm5`` cell's row of 512 lanes that is its
    # own value. Timed where compiled: the bytes are the rows of the blocks
    # fetched.
    import time
    for name, heads, row, value, length, masked in (
            ("latent", 128, 640, 512, 12544, False),
            ("latent_masked", 128, 640, 512, 12544, True),
            ("window", 64, 1152, 1024, 512, False),
            # ``glm5``: a row that is all value (no rotary lanes, nothing
            # padded), 64 heads, blocks of 1,024 of a cache of 17,408
            ("latent_all_value", 64, 512, 512, 17408, True)):
        dtype = "bfloat16"
        shape = (2, 8, length, row)
        edge = kv_pool.read_block(shape, dtype)
        position = np.asarray([0, 1, edge - 1, edge, edge + 1, 600 % length
                               or 7, length - 1, length], np.int32)
        pool = jax.numpy.asarray(rng.standard_normal(shape) * 0.3, dtype)
        q = jax.numpy.asarray(rng.standard_normal((8, heads, row)) * 0.2,
                              dtype)
        new = jax.numpy.asarray(rng.standard_normal((8, row)) * 0.3, dtype)
        keep = own = None
        if masked:
            keep = rng.random((8, length)) < 0.3
            keep[6, :4 * edge] = False      # whole blocks with nothing kept
            keep[7, 2 * edge:] = False
            own = np.asarray([1, 1, 1, 0, 1, 1, 1, 1], np.int32)
        run = jax.jit(lambda q, new, pool, position, keep, own:
                      kv_pool.latent_decode_attention(
                          q, new, pool, 1, position, value=value,
                          bound=length, scale=0.07, keep=keep, own=own,
                          interpret=interpret))
        args = (q, new, pool, jax.numpy.asarray(position), keep, own)
        got = np.asarray(run(*args), np.float32)
        rows = np.asarray(pool, np.float32)[1]
        f_q, f_new = np.asarray(q, np.float32), np.asarray(new, np.float32)
        err = 0.0
        for slot, p in enumerate(position):
            kept = (np.ones(p, bool) if keep is None else keep[slot, :p])
            keys = np.concatenate(
                [rows[slot, :p][kept], f_new[slot:slot + 1]
                 if own is None or own[slot] else f_new[:0]])
            if not len(keys) or not p:
                continue
            scores = f_q[slot] @ keys.T * 0.07
            w = np.exp(scores - scores.max(-1, keepdims=True))
            want = (w / w.sum(-1, keepdims=True)) @ keys[:, :value]
            err = max(err, float(np.max(np.abs(got[slot] - want))))
        entry = {"ok": bool(err < 0.04), "max_err": round(err, 6),
                 "vmem_bytes": 2 * (edge + heads + 1) * row * 2
                 + 2 * heads * value * 2 + heads * (value + 256) * 4}
        assert entry["vmem_bytes"] <= VMEM_BUDGET_BYTES, entry
        if not interpret:
            full = (q, new, pool, jax.numpy.full((8,), length, np.int32),
                    keep, own)
            run(*full).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(10):
                out = run(*full)
            out.block_until_ready()
            seconds = (time.perf_counter() - t0) / 10
            fetched = 8 * length * row * 2
            entry.update(ms=round(seconds * 1e3, 4),
                         gb_per_s=round(fetched / seconds / 1e9, 1))
        results[f"latent_attention_{name}"] = entry

    # a prompt's attention and its index scores vs plain float32 ``jax.numpy``
    # at ``highest``, at the ``dots3`` cell's longest bucket (12,288
    # positions; 1,536 under the interpreter, three blocks of 512): the full
    # layers' form — keys of 192 lanes, values of 128, a one-byte (P, P) mask
    # that keeps about a sixth of the pairs and leaves whole blocks of keys
    # out for some queries —, the sliding layers' — keys of 256, a window of
    # 513 —, and one block of 256 queries' 64 index heads against every key.
    # Four heads, one grid step's group, where the model hands over 32 a
    # call (eight groups of 4). The oracle holds (heads, 1024, P) scores at
    # a time.
    from .flash_attention import (PROMPT_VMEM_BYTES, _head_group,
                                  index_scores, prompt_attention)
    p = 1536 if interpret else 12288
    heads = 4
    hi = jax.lax.Precision.HIGHEST

    def plain_prompt(q, k, v, allowed, scale):
        out = []
        for at in range(0, p, 1024):
            s = jax.numpy.einsum(
                "hqd,hkd->hqk", q[:, at:at + 1024].astype("float32"),
                k.astype("float32"), precision=hi) * scale
            w = jax.nn.softmax(jax.numpy.where(
                allowed[None, at:at + 1024], s, -1e30), axis=-1)
            out.append(jax.numpy.einsum("hqk,hkd->hqd", w,
                                        v.astype("float32"), precision=hi))
        return np.asarray(jax.numpy.concatenate(out, axis=1))

    t_pos, s_pos = np.arange(p)[:, None], np.arange(p)[None, :]
    causal = s_pos <= t_pos
    selected = rng.random((p, p)) < 0.17
    selected[-700:, :1024] = False     # whole blocks of keys with none kept
    selected |= s_pos == t_pos         # every query keeps a key: its own
    for name, dqk, dv, mask, window in (
            ("selected", 192, 128, selected, None),
            ("window", 256, 128, None, 513),
            # ``glm5``: keys and values both 256 wide (no rotary part)
            ("selected_256", 256, 256, selected, None)):
        q, k = (jax.numpy.asarray(rng.standard_normal((heads, p, dqk)),
                                  "bfloat16") for _ in range(2))
        v = jax.numpy.asarray(rng.standard_normal((heads, p, dv)),
                              "bfloat16")
        scale = dqk ** -0.5
        allowed = causal & (mask if mask is not None
                            else s_pos > t_pos - window)
        run = jax.jit(lambda q, k, v, mask: prompt_attention(
            q, k, v, scale=scale, mask=mask, window=window,
            interpret=interpret))
        args = (q, k, v, None if mask is None
                else jax.numpy.asarray(mask, "int8"))
        got = np.asarray(run(*args), np.float32)
        err = float(np.max(np.abs(got - plain_prompt(
            q, k, v, jax.numpy.asarray(allowed), scale))))
        # the four heads are one grid step's group: their q, k (on whole
        # lane tiles: 192 lies on 256), v and out blocks double-buffered,
        # acc, m and l (a lane tile a row) a head; once for the group the
        # mask's block double-buffered, its float32 bias and one head's
        # scores, weights and their cast. The call asks Mosaic for
        # ``PROMPT_VMEM_BYTES`` (``vmem_limit_bytes``).
        block, lanes = 512, -(-dqk // 128) * 128
        assert _head_group(heads, block, dqk, dv, 2, mask is not None) == heads
        entry = {"ok": bool(err < 0.04), "max_err": round(err, 6),
                 "vmem_bytes": 2 * heads * block * (2 * lanes + 2 * dv) * 2
                 + heads * block * (dv + 2 * 128) * 4
                 + 2 * block * block * (mask is not None)
                 + 4 * block * block * 4}
        assert entry["vmem_bytes"] <= PROMPT_VMEM_BYTES, entry
        if not interpret:
            run(*args).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(5):
                out = run(*args)
            out.block_until_ready()
            entry["ms"] = round((time.perf_counter() - t0) / 5 * 1e3, 3)
        results[f"prompt_attention_{name}"] = entry

    j_heads, d, queries = 64, 128, 256
    first = p - 2 * queries - 128 if not interpret else p - queries
    first -= first % queries
    iq = jax.numpy.asarray(rng.standard_normal((j_heads, queries, d)),
                           "bfloat16")
    ik = jax.numpy.asarray(rng.standard_normal((p, d)), "bfloat16")
    w = jax.numpy.asarray(rng.standard_normal((queries, j_heads)) * 0.1,
                          "float32")
    got = np.asarray(jax.jit(lambda iq, ik, w, first: index_scores(
        iq, ik, w, first, interpret=interpret))(iq, ik, w,
                                                jax.numpy.int32(first)))
    want = np.asarray(jax.numpy.einsum(
        "jts,tj->ts", jax.nn.relu(jax.numpy.einsum(
            "jtd,sd->jts", iq.astype("float32"), ik.astype("float32"),
            precision=hi)), w, precision=hi))
    # keys in blocks wholly after the last query are not scored: read 0
    scored = np.arange(p) < -(-(first + queries) // 512) * 512
    err = float(np.max(np.abs(got - want)[:, scored]))
    results["index_scores"] = {
        "ok": bool(err < 0.02 and not got[:, ~scored].any()),
        "max_err": round(err, 6),
        "vmem_bytes": 2 * (j_heads * queries * d + 512 * d) * 2
        + 2 * queries * 512 * 4 + queries * j_heads * 4}

    # the selection of a block of a prompt's queries vs a stable sort: the
    # last 256 queries of the cache's own length against every key, 2,048
    # kept a row (under the interpreter 64 queries against 1,536 keys, 200
    # kept), once over scores as the indexer makes them — a sum of
    # rectified products, many exact zeros — and once rounded so that some
    # hundred keys tie at the 2,048th value. ``max_err`` counts the
    # positions that differ: none.
    from .select_top import select_top
    # ``pooled``: the ``glm5`` cell's selection of 511 of the 4,352 pooled
    # blocks of its cache (not a multiple of the kernel's 1,024-column chunk).
    sizes = {True: {"scores": (64, 1536, 200), "ties": (64, 1536, 200),
                    "pooled": (64, 384, 40)},
             False: {"scores": (256, 12544, 2048), "ties": (256, 12544, 2048),
                     "pooled": (256, 4352, 511)}}[bool(interpret)]
    for name, step in (("scores", 0.0), ("ties", 0.125), ("pooled", 0.125)):
        rows, keys, kept = sizes[name]
        select = jax.jit(lambda scores, valid: select_top(
            scores, valid, kept, interpret=interpret))
        valid = (np.arange(keys)[None, :]
                 <= (keys - rows + np.arange(rows))[:, None])
        vmem = select_top_vmem_bytes(keys)
        assert vmem <= VMEM_PHYSICAL_BYTES // 2, f"select_top VMEM {vmem}"
        scores = (np.maximum(rng.standard_normal((rows, keys)), 0)
                  * rng.standard_normal((rows, keys))).astype(np.float32)
        if step:
            scores = np.round(scores / step) * step
        args = (jax.numpy.asarray(scores), jax.numpy.asarray(valid, "int8"))
        got = np.asarray(select(*args))
        order = np.argsort(-np.where(valid, scores, -np.inf), axis=-1,
                           kind="stable")[:, :kept]
        want = np.zeros(scores.shape, bool)
        np.put_along_axis(want, order, True, axis=-1)
        wrong = int((got != (want & valid)).sum())
        results[f"select_top_{name}"] = {
            "ok": wrong == 0, "max_err": wrong, "vmem_bytes": vmem}

    # the state update at the live slots of the pool vs the families' own
    # jax.numpy recurrences — the two cells' blocks (Mamba-2: 64 heads of 64
    # by a state of 128, the pool's (128, 4096) a slot; the delta rule and
    # Kimi Delta Attention, whose decay is a column of the block and no
    # scalar: 32 heads of 128 x 128; 2 MB a slot all), eight slots of which
    # five are live: the first, the last and a dead one between; a dead
    # slot's state must come back bit for bit.
    from ...models import granite_hybrid, ling3, qwen3_next
    position = np.asarray([3, 0, 9, 1, 0, 0, 700, 12], np.int32)
    live = position > 0
    slots = len(position)

    def normal(*shape, scale=1.0):
        return jax.numpy.asarray(rng.standard_normal(shape) * scale,
                                 jax.numpy.float32)

    h, p, n = 64, 64, 128
    state = normal(slots, h, p, n)
    x, dt, a = normal(slots, h, p), abs(normal(slots, h, scale=0.1)), -abs(
        normal(h, scale=4.0))
    b, c = normal(slots, n), normal(slots, n)

    def ssd(state, *rest):   # the pool holds S (H, P, N) as (N, H · P)
        out, new = granite_hybrid.ssd_update(
            jax.numpy.moveaxis(state, 3, 1).reshape(slots, n, h * p), *rest,
            position, interpret=interpret)
        return out, jax.numpy.moveaxis(new.reshape(slots, n, h, p), 1, 3)

    hv, dk = 32, 128
    delta = normal(slots, hv, dk, dk)
    q, k = normal(slots, hv, dk, scale=0.1), normal(slots, hv, dk, scale=0.1)
    v, g = normal(slots, hv, dk), -abs(normal(slots, hv, scale=0.1))
    beta = abs(normal(slots, hv, scale=0.5))

    def delta_rule(*args):
        return qwen3_next.delta_rule_update(*args, position,
                                            interpret=interpret)

    # a head's channels from the gate's bound (-5) to nearly 0
    g_channel = -5.0 * jax.nn.sigmoid(normal(slots, hv, dk, scale=3.0))

    def kda(*args):
        return ling3.kda_update(*args, position, interpret=interpret)

    for name, run, oracle, args, flat in (
            ("ssd", ssd, granite_hybrid.ssd_step,
             (state, x, dt, a, b, c), (slots, h * p)),
            ("delta_rule", delta_rule, qwen3_next.delta_rule_step,
             (delta, q, k, v, g, beta), (slots, hv, dk)),
            ("kda", kda, ling3.kda_step,
             (delta, q, k, v, g_channel, beta), (slots, hv, dk))):
        out, new = (np.asarray(r) for r in jax.jit(run)(*args))
        want_out, want_new = (np.asarray(r) for r in oracle(*args))
        err = max(
            float(np.abs(out.reshape(flat)
                         - want_out.reshape(flat))[live].max()),
            float(np.abs(new.reshape(want_new.shape)
                         - want_new)[live].max()))
        untouched = bool(
            np.array_equal(new.reshape(want_new.shape)[~live],
                           np.asarray(args[0])[~live])
            and not out[~live].any())
        small = sum(int(np.prod(r.shape[1:])) * 4 for r in args[1:])
        vmem = state_update_vmem_bytes(int(np.prod(new.shape[1:])) * 4,
                                       small + out[0].size * 4)
        assert vmem <= VMEM_PHYSICAL_BYTES // 2, f"state update VMEM {vmem}"
        results[f"state_update_{name}"] = {
            "ok": bool(err < 1e-4 and untouched), "max_err": round(err, 7),
            "vmem_bytes": vmem}

    # the prefill's kernel — the convolution, q's and k's norms and the
    # chunked Kimi-Delta recurrence — vs ``jax.numpy`` and ``kda_step`` token
    # by token, at the ``ling3`` cell's shapes: 32 heads of 128 over a prompt
    # of 2,048 from a bfloat16 input (8 heads over 256 under the
    # interpreter), with the gate AT its bound on every channel — the
    # sub-block factors reach e^75 — and drawn from the bound to nearly 0
    # inside a head. Timed where compiled.
    from .kda_chunk import CHUNK, HEAD_BLOCK, kda_chunk
    hv, t = (8, 256) if interpret else (32, 2048)
    eps = qwen3_next.L2_EPS

    def recurrence(mixed, taps, g, beta):
        shifted = jax.numpy.pad(mixed.astype("float32"),
                                ((len(taps) - 1, 0), (0, 0)))
        out = jax.nn.silu(sum(shifted[j:j + t] * taps[j]
                              for j in range(len(taps))))
        q, k, v = (out[:, i * hv * dk:(i + 1) * hv * dk].reshape(t, hv, dk)
                   for i in range(3))

        def token(state, xs):
            out, state = ling3.kda_step(state, *xs)
            return state, out
        state, out = jax.lax.scan(
            token, jax.numpy.zeros((hv, dk, dk), jax.numpy.float32),
            (qwen3_next.l2_norm(q) * dk ** -0.5, qwen3_next.l2_norm(k), v, g,
             beta))
        return out, state

    run = jax.jit(lambda *a: kda_chunk(*a, eps=eps, interpret=interpret))
    vmem = kda_chunk_vmem_bytes(HEAD_BLOCK, dk)
    assert vmem <= VMEM_BUDGET_BYTES, f"kda chunk VMEM {vmem}"
    for name, g in (
            ("bound", jax.numpy.full((t, hv, dk), -5.0)),
            ("drawn", -5.0 * jax.nn.sigmoid(normal(t, hv, dk, scale=3.0)
                                            - 3.0))):
        args = (normal(t, 3 * hv * dk).astype("bfloat16"),
                normal(4, 3 * hv * dk, scale=0.35), g,
                jax.nn.sigmoid(normal(t, hv)))
        got = [np.asarray(r) for r in run(*args)]
        want = [np.asarray(r) for r in jax.jit(recurrence)(*args)]
        err = max(float(np.abs(a - b).max()) for a, b in zip(got, want))
        entry = {"ok": bool(err < 1e-4 and all(
            np.isfinite(a).all() for a in got)), "max_err": round(err, 9),
            "vmem_bytes": vmem}
        if not interpret:
            t0 = time.perf_counter()
            for _ in range(5):
                out = run(*args)
            jax.block_until_ready(out)
            seconds = (time.perf_counter() - t0) / 5
            entry.update(ms=round(seconds * 1e3, 3), us_a_chunk_a_head=round(
                seconds * 1e6 / (t // CHUNK * hv), 3))
        results[f"kda_chunk_{name}"] = entry

    # a prompt's hyper-connection halves vs ``ops/mhc.py``'s ``jax.numpy``
    # form at the two cells' widths — ``glm53.longctx``'s 4 x 4,096 over a
    # 4,096 bucket, ``xing4.reason``'s 4 x 3,584 over 2,048 tokens (4 x 256
    # over 200 tokens, no multiple of the block, under the interpreter) —
    # from bfloat16 rows: the coefficients to 2e-6, ``u`` and ``X'`` to one
    # bfloat16 unit in the last place (``max_err`` is the worst of the two
    # in such units: under 1). Timed where compiled.
    from .. import mhc
    from . import mhc_rows
    knobs = dict(iters=20, eps=1e-6, clamp=30.0, norm_eps=1e-6)
    for name, (t, n, d) in (
            {"glm53": (200, 4, 256)} if interpret else
            {"glm53": (4096, 4, 4096), "xing4": (2048, 4, 3584)}).items():
        columns = 2 * n + n * n
        hyper = {"phi": normal(n * d, columns,
                               scale=(n * d) ** -0.5).astype("bfloat16"),
                 "alpha": jax.numpy.asarray([1.0, 0.7, 2.0]),
                 "bias": normal(columns)}
        x = normal(t, n, d).astype("bfloat16")
        y = normal(t, d).astype("bfloat16")
        pre = jax.jit(lambda x: mhc_rows.pre(
            x, hyper["phi"], hyper["alpha"], hyper["bias"], **knobs,
            interpret=interpret))
        post = jax.jit(lambda x, y, coef: mhc_rows.post(
            x, y, coef, interpret=interpret))
        u, coef = pre(x.reshape(t, n * d))
        mixed = post(x.reshape(t, n * d), y, coef)
        want_u, h_post, h_res = jax.jit(
            lambda x: mhc.pre(x, hyper, **knobs))(x)
        want = jax.jit(mhc.post)(x, y, h_post, h_res)
        held = np.asarray(coef)[:, mhc_rows.coefficient_lanes(n)[n:]]
        coef_err = float(np.abs(held - np.concatenate(
            [h_post, h_res.reshape(t, n * n)], axis=1)).max())

        def ulps(got, want):
            got, want = (np.asarray(a, np.float32) for a in (got, want))
            # 2e-5: the float32 sums' own units, all there is where the
            # four or five terms cancel
            return float((np.abs(got - want) / (2.0 ** -7 * np.maximum(
                np.abs(want), np.abs(got)) + 2e-5)).max())

        err = max(ulps(u, want_u), ulps(mixed.reshape(t, n, d), want))
        vmem = mhc_rows_vmem_bytes(n, d)
        assert vmem <= VMEM_PHYSICAL_BYTES // 2, f"mhc rows VMEM {vmem}"
        entry = {"ok": bool(err < 1.0 and coef_err < 2e-6),
                 "max_err": round(err, 6), "coef_err": coef_err,
                 "vmem_bytes": vmem}
        if not interpret:
            for half, run, args in (("pre", pre, (x.reshape(t, n * d),)),
                                    ("post", post,
                                     (x.reshape(t, n * d), y, coef))):
                t0 = time.perf_counter()
                for _ in range(5):
                    out = run(*args)
                jax.block_until_ready(out)
                entry[f"{half}_ms"] = round(
                    (time.perf_counter() - t0) / 5 * 1e3, 3)
        results[f"mhc_rows_{name}"] = entry

    results["all_ok"] = all(r["ok"] for r in results.values()
                            if isinstance(r, dict))
    results["interpret"] = interpret
    return results


def main(argv=None) -> int:
    """``python -m ai4e_tpu.ops.pallas.validate [--interpret]``: one JSON
    line — the result plus the device it ran on — and exit 1 unless
    ``all_ok``. Compiled (Mosaic) by default, which only a TPU can run;
    ``--interpret`` is the CPU test mode."""
    import argparse
    import json

    from ...runtime.registry import device_report, enable_compilation_cache

    parser = argparse.ArgumentParser(prog="ai4e_tpu.ops.pallas.validate")
    parser.add_argument("--interpret", action="store_true")
    args = parser.parse_args(argv)
    enable_compilation_cache()
    result = validate_kernels(interpret=args.interpret)
    result["device"] = device_report()
    print(json.dumps(result), flush=True)
    return 0 if result["all_ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
