"""Plain reference for the ``glm5`` family (zai-org/GLM-5.3-Flash's block), the
comparison that decides ``correct`` for its cells, and the decode step's and
the prefill's operation and byte counts.

The forward pass is written from the configuration's equations, for a token's
``n`` streams ``X = (X_1 .. X_n)``, each ``(D,)``, ``n(x) = w ⊙ x / √(mean(x²)
+ eps)``:

    X_i = embedding, every i;  around every sublayer F (a mixer, an FFN, each
    with its input norm):  x' = vec(X) / √(mean(vec(X)²) + eps);  H_pre = σ(α_1
    x' φ_pre + b_pre);  H_post = 2 σ(α_2 x' φ_post + b_post);  H_res = SK(clip(
    α_3 mat(x' φ_res) + b_res, ±clamp)), SK = exp then `iters` times rows then
    columns divided by their sums + hc_eps;  u = Σ_i H_pre,i X_i;  y = F(n(u));
    X_i ← Σ_j H_res,ij X_j + H_post,i y.   logits = n_f(Σ_i X_i) W_head

    KDA (layer_types "kda"):  [q | k | v] = SiLU(causal depthwise convolution
          of [u W_q | u W_k | u W_v] over the last `conv` tokens);  a head: q ←
          q/‖q‖ / √d, k ← k/‖k‖;  β = σ(u W_β);  a = (u W_a1) W_a2;  g =
          gate_bound · σ(e^{A_h} (a + b)) a CHANNEL;  per head and token: S ←
          Diag(e^g) S;  δ = β (v − Sᵀ k);  S ← S + k ⊗ δ;  o = Sᵀ q;  o ← n_o(o)
          ⊙ σ((u W_z1) W_z2 + b_z);  W_o
    Sparse latent attention ("sparse"):  c_q = n_q(u W_dq);  q_h = c_q W_uq,h;
          c_s = n_kv(u_s W_dkv);  k_s,h = c_s W_uk,h;  v_s,h = c_s W_uv,h;  o_t,h
          = Σ_{s ∈ S_t} softmax_s(q_t,h · k_s,h / √qk_dim) v_s,h;  W_o.  No
          position enters it.
    Its indexer:  iq_t,j = c_q W_iq;  ik_s = LayerNorm(u_s W_ik);  the first
          index_rope lanes of both rotated on the lane pairs (2i, 2i + 1) at
          index_theta;  w_t,j = (u_t W_w)_j / √(J · index_dim);  block b = the
          positions P b .. P b + P − 1 (P = index_pool), ik̄_b the mean of its P
          rotated keys;  I_t,b = Σ_j w_t,j relu(iq_t,j · ik̄_b) for b < ⌊t/P⌋;
          S_t = {P ⌊t/P⌋ .. t} ∪ the positions of the index_topk / P − 1 blocks
          of largest I_t,b (all while there are no more; a tie to the lower
          block: a stable sort)
    FFN:  mlp_types "dense" a SwiGLU;  else s = σ(x W_r) over ALL experts, the K
          largest of s + b chosen (a tie to the lower index), weights s_e /
          Σ_picks s × route_scale, the terms of the experts HELD here, +
          Expert_shared(x);  every SwiGLU = silu(min(g, limit)) · clip(u, ±limit)

in plain ``jax.numpy``, float32, ``highest`` matmul precision: the streams a
Python list, Sinkhorn a loop, the recurrence a ``lax.scan`` over single
tokens, the convolution a sum of shifted products, every head's ``k`` and
``v`` built from ``c`` (nothing absorbed), the index scores of every (query,
block) pair from keys pooled by a reshape and a mean, a stable sort for the
chosen blocks, a mask for ``S_t``, the experts by a plain loop over the rows
that chose a held one — no cache, no chunk algebra, no kernel; queries and
heads a few at a time, which is only what memory needs; and no import from
``ai4e_tpu.models`` beyond ``create_glm5_lm`` for the parameter VALUES: the
same bfloat16 values the worker serves (the family's seeded init is integer
arithmetic on threefry bits, so the CPU draws them bit for bit). Departures
from the published model: seeded weights; the share of layers, experts and
vocabulary the configuration states; no multi-token-prediction layer, no
vision tower; nothing else.

The API returns greedy token ids only, and with random weights an argmax flips
on rounding. So the reference is teacher-forced on prompt + served tokens, and
each served token's reference logit must lie within LOGIT_MARGIN of that
position's reference maximum, all but SHARE_LIMIT of them (all but one, of a
stream so short that the share is less than one token) within SHARE_MARGIN.
"""

from __future__ import annotations

import numpy as np

# Reason for the two limits: see MARGIN_MEASURED and FAULTS_MEASURED. The
# worker computes in bfloat16 with float32 accumulation, reads its latent rows
# and pooled keys in bfloat16, decodes the sparse layer in the absorbed form
# and runs the prefill's recurrence in chunks: its logits differ from this
# float32 forward by rounding, and now and then rounding picks another eighth
# expert or another 511th block. A run is `not correct` by either limit.
LOGIT_MARGIN = 1.0
SHARE_MARGIN, SHARE_LIMIT = 0.05, 0.06
MARGIN_MEASURED = (
    "on the chip's served streams (my chip runs, PR 51: the knee sweep, the "
    "traced run and two sets of six, 20 runs, ONE stream a run - a prompt of "
    "3,072-3,840 and 160-768 served tokens, every checked token past "
    "index_topk positions, 7,046 checked tokens in all; sweeps/glm53.longctx"
    ".md has every run's line) the worst margin a run is 0.054-0.404 (two "
    "runs beyond 0.3: 0.352, 0.404), the share beyond 0.05 0.60-3.14 % (110 "
    "tokens, 1.56 % pooled; the largest 7 of 223), argmax agreement 88.0-95.1 "
    "%: a thin tail of expert and block flips. The margin limit 1.0 has 2.5 x "
    "over the one 0.404 and lies under float8's 1.08. The share limit 6 % "
    "has 1.9 x over the largest sound reading and 7 x under float8's 42.7 %; "
    "a run checks one stream of 223-523 tokens, so at 4 % (8 tokens of 223 "
    "allowed against ~3.5 expected, 7 read once) the count's own scatter "
    "would turn away a sound run in a hundred or fewer. No gain of the "
    "seeded init was re-scaled after a chip run: the first run read "
    "`correct`. Second session (after the review, PR 51): seven more runs "
    "of the committed, rotated cell read 0.044-0.219 and 0-2.83 % (streams "
    "of 232-538 tokens), and sweeps/longstream.py held a prompt of 8,192 "
    "and one of 16,384 to this reference, 256 tokens each: 0.225 | 5 (1.95 "
    "%) | 93.4 % and 0.206 | 11 (4.30 %) | 88.3 % - at 16 k more blocks lie "
    "within rounding of the 511th score, so the largest sound share is 4.30 "
    "% and the 6 % limit lies 1.4 x over it")
FAULTS_MEASURED = (
    "check(fault=...) on ONE served stream of the chip (sweeps/longstream.py "
    "serve glm53.longctx 2300000401 0:3072:192: a prompt of 3,072 and 192 "
    "greedy tokens, the sound system's own ids, the reference computed "
    "wrongly on the chip machine's CPU; PR 51), as worst margin | tokens "
    "beyond 0.05 of 192 (11 allowed) | argmax agreement. Sound: 0.295 | 5 "
    "(2.6 %) | 90.1 %. `ok` false by BOTH limits: float8 (the nearest "
    "precision below bfloat16) 1.08 | 82 (42.7 %) | 53.6 %; no_pool 1.39 | "
    "118 (61.5 %) | 32.3 %; rotary_latent 1.70 | 149 (77.6 %) | 21.4 %; "
    "scalar_gate 1.08 | 102 (53.1 %) | 40.6 %. `ok` false by the share "
    "alone: one_stream 0.53 | 57 (29.7 %) | 62.5 %; no_shared 0.29 | 25 "
    "(13.0 %) | 78.6 % - the mildest that is caught. `ok` TRUE: no_tail 0.27 "
    "| 9 (4.7 %) | 90.1 % - the query's own block is one to four of 2,048 "
    "kept positions; bf16_state 0.31 | 6 (3.1 %) | 88.0 % - as in qwen3-next, "
    "granite-hybrid and ling3; no_clamp is the sound reference itself (a "
    "seeded network never reaches 10). Tier-1 holds those three at a size "
    "where each bites (tests/test_glm5.py)")
FAULTS = ("float8", "no_pool", "no_tail", "rotary_latent", "scalar_gate",
          "one_stream", "no_shared", "bf16_state", "no_clamp")
ROW_PAD = 64       # an expert's rows are padded to a multiple: few shapes
HEAD_CHUNK = 8     # heads whose (T, T) scores are held at once, at most
SCORE_BYTES = 1 << 31   # ... and within this many bytes of float32 scores
QUERY_CHUNK = 512  # queries whose (J, T / P) index products are held at once
L2_EPS = 1e-6
LN_EPS = 1e-6


def _model_spec(config: dict) -> dict:
    return next(m for m in config["models"]["models"]
                if m["family"] == "glm5")


# The fields a models spec may leave to the program's defaults.
DEFAULTS = {"streams": 4, "sinkhorn_iters": 20, "hc_eps": 1e-6,
            "hc_clamp": 30.0, "conv": 4, "gate_bound": -5.0,
            "index_theta": 1e6, "index_pool": 4, "first_expert": 0,
            "route_scale": 2.5, "swiglu_limit": 10.0, "rms_eps": 1e-5}


def _get(spec: dict, key: str):
    return spec.get(key, DEFAULTS[key])


def _kinds(spec: dict) -> tuple[int, int]:
    """``(sparse layers, KDA layers)``."""
    sparse = sum(kind == "sparse" for kind in spec["layer_types"])
    return sparse, len(spec["layer_types"]) - sparse


# -- sizes ---------------------------------------------------------------------

def kda_params(spec: dict) -> int:
    """A KDA mixer: q, k, v and output projections, the decay's and the output
    gate's two low-rank factors each, β, the convolution, the decay's and the
    norm's parameters."""
    d, wide, r = spec["dim"], spec["heads"] * spec["head_dim"], spec["kda_lora"]
    return (4 * d * wide + 2 * (d * r + r * wide) + wide + d * spec["heads"]
            + _get(spec, "conv") * 3 * wide
            + spec["heads"] + wide + spec["head_dim"])


def sparse_params(spec: dict) -> int:
    """A sparse mixer with its indexer."""
    d, h, rq, r = (spec["dim"], spec["attn_heads"], spec["q_rank"],
                   spec["kv_rank"])
    index = (rq * spec["index_heads"] * spec["index_dim"]
             + d * spec["index_dim"] + 2 * spec["index_dim"]
             + d * spec["index_heads"])
    return (d * rq + rq + rq * h * spec["qk_dim"] + d * r + r
            + r * h * (spec["qk_dim"] + spec["v_dim"])
            + h * spec["v_dim"] * d + index)


def hyper_params(spec: dict) -> int:
    """One sublayer's hyper-connection maps (``alpha`` and ``bias`` are 27
    float32 numbers: left out)."""
    n = _get(spec, "streams")
    return n * spec["dim"] * (2 * n + n * n)


def ffn_params(spec: dict, dense: bool, experts: float | None = None) -> float:
    """A layer's FFN: a dense one whole; an expert layer's router at its
    published width, shared expert and ``experts`` routed ones (None: the
    held ones)."""
    d = spec["dim"]
    if dense:
        return 3 * d * spec["mlp_dim"]
    e = spec["experts_held"] if experts is None else experts
    return (d * spec["experts"] + 2 * spec["experts"]   # the bias is float32
            + 3 * e * d * spec["expert_dim"] + 3 * d * spec["shared_dim"])


def _layers(spec: dict, experts: float | None = None) -> float:
    """Parameters of every held layer: its mixer, its FFN (``experts`` routed
    ones where it has them), its two sublayers' hyper-connection maps and its
    two norms."""
    return sum((sparse_params(spec) if kind == "sparse" else kda_params(spec))
               + ffn_params(spec, mlp == "dense", experts)
               + 2 * hyper_params(spec) + 2 * spec["dim"]
               for kind, mlp in zip(spec["layer_types"], spec["mlp_types"]))


def weight_bytes(spec: dict) -> int:
    """What a decode step reads of the weights, bfloat16: every held layer
    (ALL the held experts: the step's ``dense`` product reads them), the head
    and the final norm. Not the embedding table: a step reads one row a
    slot."""
    d = spec["dim"]
    return int(2 * (_layers(spec) + d * spec["vocab_size"] + d))


def state_bytes_per_slot(spec: dict) -> tuple[int, int]:
    """A slot's fixed-size state: ``(the KDA layers' S in float32, the
    convolution's tails in bfloat16 + the sparse layers' open-block sums in
    float32)``."""
    sparse, linear = _kinds(spec)
    wide = spec["heads"] * spec["head_dim"]
    return (linear * wide * spec["head_dim"] * 4,
            linear * (_get(spec, "conv") - 1) * 3 * wide * 2
            + sparse * spec["index_dim"] * 4)


def _met(spec: dict) -> float:
    """Experts a token meets here where the router spreads evenly."""
    return spec["experts_per_token"] * spec["experts_held"] / spec["experts"]


def ops_and_bytes(config: dict, slots: int,
                  live_tokens: float) -> tuple[float, float]:
    """One decode step over the pool, by the published mathematics. Operations
    = 2 x (the mixers', the FFNs' — of the experts the K x held / total a token
    meets here —, the hyper-connections' weights + the head) per slot + 4 x the
    state's elements a slot a KDA layer + per live slot and sparse layer the
    index scores of its closed blocks (2 J d a block) and the absorbed
    attention over the positions it keeps (2 H (2 r) each). Least bytes =
    every held weight once + one embedding row a slot + the LIVE slots' KDA
    states once in and once out (``config["derived"]["live_slots"]``, which
    ``readers/step_roofline_live.py`` sets from the engine's own series; every
    slot's where nobody says) + every slot's convolution tails and open-block
    sums in and out + per live slot and sparse layer ``min(position + 1,
    index_topk)`` latent rows and ``position / index_pool`` pooled keys + the
    rows a slot writes. ``live_tokens``: the cached positions of the live
    slots, summed."""
    spec = _model_spec(config)
    d, pool = spec["dim"], _get(spec, "index_pool")
    sparse, _ = _kinds(spec)
    live = config["derived"].get("live_slots", slots)
    kept = live * min(live_tokens / max(live, 1e-9) + 1, spec["index_topk"])
    per_slot = _layers(spec, _met(spec)) + d * spec["vocab_size"]
    state, small = state_bytes_per_slot(spec)
    flops = (2.0 * per_slot * slots + 4.0 * (state // 4) * slots
             + sparse * (2.0 * spec["index_heads"] * spec["index_dim"]
                         * live_tokens / pool
                         + 2.0 * spec["attn_heads"] * 2 * spec["kv_rank"]
                         * kept))
    nbytes = (weight_bytes(spec) + 2 * d * slots + 2 * state * live
              + 2 * small * slots
              + sparse * 2 * (spec["kv_rank"] * (kept + slots)
                              + spec["index_dim"] * (live_tokens / pool
                                                     + slots)))
    return flops, float(nbytes)


def prefill_ops_and_bytes(config: dict, tokens: float, pairs: dict,
                          calls: float = 1.0) -> tuple[float, float]:
    """``calls`` prefills of ``tokens`` real tokens in all, by the PUBLISHED
    mathematics whatever form the program computes: 2 x (the mixers', the
    FFNs' and the hyper-connections' weights a token — of the experts the K x
    held / total it meets here) + the recurrence's 4 d² multiply-adds a head
    a token a KDA layer + a sparse layer's index scores over the (query,
    closed block) pairs (2 J d each; ``pairs["index"]``) and its attention
    over the pairs the selection keeps (2 H (qk + v) each;
    ``pairs["selected"]``); the head once a prefill. Least bytes: every held
    weight once a prefill + the streams of the real tokens read and written a
    sublayer."""
    spec = _model_spec(config)
    sparse, _ = _kinds(spec)
    state, _ = state_bytes_per_slot(spec)
    flops = (2.0 * _layers(spec, _met(spec)) * tokens
             + 2.0 * spec["dim"] * spec["vocab_size"] * calls
             + 2.0 * 4.0 * (state // 4) * tokens
             + sparse * (2.0 * spec["index_heads"] * spec["index_dim"]
                         * pairs.get("index", 0.0)
                         + 2.0 * spec["attn_heads"]
                         * (spec["qk_dim"] + spec["v_dim"])
                         * pairs.get("selected", 0.0)))
    streams = _get(spec, "streams") * spec["dim"]
    return flops, float(weight_bytes(spec) * calls
                        + 2 * len(spec["layer_types"]) * 2 * 2 * streams
                        * tokens)


# -- the forward pass ----------------------------------------------------------

def rms_norm(x, w, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def hyper(streams: list, layer: dict, name: str, spec: dict, w):
    """A sublayer's coefficients from the token's ``streams`` (a list of ``n``
    ``(T, D)``): ``H_pre (T, n)``, ``H_post (T, n)``, ``H_res (T, n, n)``."""
    import jax
    import jax.numpy as jnp
    n, t = len(streams), streams[0].shape[0]
    x = jnp.concatenate(streams, axis=-1)
    x = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                     + _get(spec, "rms_eps"))
    raw = x @ w(layer[name + "_phi"])
    alpha, bias = layer[name + "_alpha"], layer[name + "_bias"]
    h_pre = jax.nn.sigmoid(alpha[0] * raw[:, :n] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * raw[:, n:2 * n] + bias[n:2 * n])
    clamp, eps = _get(spec, "hc_clamp"), _get(spec, "hc_eps")
    m = jnp.exp(jnp.clip(alpha[2] * raw[:, 2 * n:] + bias[2 * n:], -clamp,
                         clamp)).reshape(t, n, n)
    for _ in range(_get(spec, "sinkhorn_iters")):
        m = m / (m.sum(axis=2, keepdims=True) + eps)
        m = m / (m.sum(axis=1, keepdims=True) + eps)
    return h_pre, h_post, m


def around(streams: list, layer: dict, name: str, spec: dict, w, fault, f):
    """``X' = H_res X + H_postᵀ F(H_pre X)`` for the sublayer ``f``
    (``one_stream``: a plain residual on one stream)."""
    if fault == "one_stream":
        return [streams[0] + f(streams[0])]
    n = len(streams)
    h_pre, h_post, h_res = hyper(streams, layer, name, spec, w)
    y = f(sum(h_pre[:, i:i + 1] * streams[i] for i in range(n)))
    return [sum(h_res[:, i, j:j + 1] * streams[j] for j in range(n))
            + h_post[:, i:i + 1] * y for i in range(n)]


def kda(u, layer: dict, spec: dict, w, fault):
    """Kimi Delta Attention over the whole sequence ``u (T, D)`` (after
    ``n_in``), one token at a time from a zero state."""
    import jax
    import jax.numpy as jnp
    t = u.shape[0]
    heads, d = spec["heads"], spec["head_dim"]
    conv, eps = _get(spec, "conv"), _get(spec, "rms_eps")
    mixed = u @ w(layer["in_qkv"])
    shifted = jnp.concatenate(
        [jnp.zeros((conv - 1, mixed.shape[1]), mixed.dtype), mixed])
    taps = w(layer["conv_w"])
    c = jax.nn.silu(sum(shifted[j:j + t] * taps[j] for j in range(conv)))

    def unit(a):
        return a / jnp.sqrt((a * a).sum(axis=-1, keepdims=True) + L2_EPS)

    q, k, v = (c[:, i * heads * d:(i + 1) * heads * d].reshape(t, heads, d)
               for i in range(3))
    q, k = unit(q) / np.sqrt(d), unit(k)
    beta = jax.nn.sigmoid(u @ w(layer["w_beta"]))
    a = ((u @ w(layer["w_a1"])) @ w(layer["w_a2"])).reshape(t, heads, d)
    g = _get(spec, "gate_bound") * jax.nn.sigmoid(
        jnp.exp(layer["a_log"])[None, :, None] * (a + layer["dt_bias"]))
    if fault == "scalar_gate":
        g = jnp.broadcast_to(g.mean(axis=-1, keepdims=True), g.shape)

    def token(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[:, :, None]
        delta = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * delta[:, None, :]
        if fault == "bf16_state":
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((heads, d, d), jnp.float32),
                        (q, k, v, g, beta))
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    z = ((u @ w(layer["w_z1"])) @ w(layer["w_z2"])
         + w(layer["b_z"])).reshape(t, heads, d)
    o = o * w(layer["norm_o"]) * jax.nn.sigmoid(z)
    return o.reshape(t, heads * d) @ w(layer["out_proj"])


def rotate(x, theta: float):
    """Rotary embedding of ``x (T, heads, width)`` over its whole width, the
    token's index as its position, the neighbours ``(2i, 2i + 1)`` a pair."""
    import jax.numpy as jnp
    t, width = x.shape[0], x.shape[-1]
    inv_freq = theta ** (-np.arange(0, width, 2, dtype=np.float64) / width)
    angle = (np.arange(t, dtype=np.float64)[:, None]
             * inv_freq[None, :]).astype(np.float32)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def selection(u, c_q, layer: dict, spec: dict, w, fault) -> np.ndarray:
    """``S_t`` of every query as a mask ``(T, T)`` bool, causal. ``no_pool``:
    every key scored by itself, the ``index_topk`` positions ``s ≤ t`` of
    largest score kept. ``no_tail``: the query's own block is not kept for
    being its own — of it the token itself alone — and the place goes to one
    more scored block."""
    import jax
    import jax.numpy as jnp
    t = u.shape[0]
    heads, width, topk = (spec["index_heads"], spec["index_dim"],
                          spec["index_topk"])
    pool, r, theta = (_get(spec, "index_pool"), spec["index_rope"],
                      _get(spec, "index_theta"))
    iq = (c_q @ w(layer["wi_q"])).reshape(t, heads, width)
    ik = u @ w(layer["wi_k"])
    ik = ik - ik.mean(axis=-1, keepdims=True)
    ik = (ik / jnp.sqrt(jnp.mean(ik * ik, axis=-1, keepdims=True) + LN_EPS)
          * w(layer["wi_norm"]) + w(layer["wi_bias"]))
    iq = jnp.concatenate([rotate(iq[..., :r], theta), iq[..., r:]], axis=-1)
    ik = jnp.concatenate([rotate(ik[:, None, :r], theta)[:, 0], ik[:, r:]],
                         axis=-1)
    weights = (u @ w(layer["wi_w"])) / np.sqrt(heads * width)
    position = np.arange(t)
    causal = position[None, :] <= position[:, None]
    if fault == "no_pool":
        keys, keep = ik, topk
        valid = causal
    else:
        blocks = -(-t // pool)
        keys = jnp.pad(ik, ((0, blocks * pool - t), (0, 0))).reshape(
            blocks, pool, width).mean(axis=1)
        keep = topk // pool - (0 if fault == "no_tail" else 1)
        valid = np.arange(blocks)[None, :] < (position // pool)[:, None]
    scores = np.empty(valid.shape, np.float32)
    for a in range(0, t, QUERY_CHUNK):
        part = jax.nn.relu(jnp.einsum("tjd,sd->tjs", iq[a:a + QUERY_CHUNK],
                                      keys))
        scores[a:a + QUERY_CHUNK] = np.asarray(jnp.einsum(
            "tjs,tj->ts", part, weights[a:a + QUERY_CHUNK]))
    order = np.argsort(-np.where(valid, scores, -np.inf), axis=-1,
                       kind="stable")[:, :keep]
    chosen = np.zeros(valid.shape, bool)
    np.put_along_axis(chosen, order, True, axis=-1)
    chosen &= valid
    if fault == "no_pool":
        return chosen
    chosen = np.repeat(chosen, pool, axis=1)[:, :t]
    own = ((position[None, :] == position[:, None]) if fault == "no_tail"
           else (position[None, :] // pool == position[:, None] // pool))
    return (chosen | own) & causal


def sparse(u, layer: dict, spec: dict, w, fault):
    """The selected latent attention over the whole sequence ``u (T, D)``
    (after ``n_in``), nothing absorbed and nothing cached. ``rotary_latent``:
    the first ``index_rope`` lanes of every head's query and key rotated — a
    rotary part the configuration says is not there."""
    import jax
    import jax.numpy as jnp
    t = u.shape[0]
    heads, eps = spec["attn_heads"], _get(spec, "rms_eps")
    c_q = rms_norm(u @ w(layer["w_dq"]), w(layer["norm_q"]), eps)
    c = rms_norm(u @ w(layer["w_dkv"]), w(layer["norm_kv"]), eps)
    allowed = jnp.asarray(selection(u, c_q, layer, spec, w, fault))
    w_uq, w_uk, w_uv = w(layer["w_uq"]), w(layer["w_uk"]), w(layer["w_uv"])
    out = []
    chunk = max(1, min(HEAD_CHUNK, SCORE_BYTES // (4 * t * t)))
    for a in range(0, heads, chunk):
        b = a + chunk
        q = jnp.einsum("tr,rhe->the", c_q, w_uq[:, a:b])
        k = jnp.einsum("tr,rhe->the", c, w_uk[:, a:b])
        v = jnp.einsum("tr,rhv->thv", c, w_uv[:, a:b])
        if fault == "rotary_latent":
            r, theta = spec["index_rope"], _get(spec, "index_theta")
            q, k = (jnp.concatenate([rotate(x[..., :r], theta), x[..., r:]],
                                    axis=-1) for x in (q, k))
        scores = jnp.einsum("the,she->hts", q, k) * spec["qk_dim"] ** -0.5
        p = jax.nn.softmax(jnp.where(allowed[None], scores, -jnp.inf),
                           axis=-1)
        out.append(jnp.einsum("hts,shv->thv", p, v))
    return jnp.concatenate(out, axis=1).reshape(t, -1) @ w(layer["w_o"])


def route(h, router, bias, k: int, scale: float):
    """``h (T, D)`` → the K experts of each row ``(T, K)`` — the largest of
    sigmoid score + bias, a tie to the lower index — and their weights ``(T,
    K)``: the scores without the bias, divided by their sum, times
    ``scale``."""
    import jax
    s = np.asarray(jax.nn.sigmoid(h @ router))
    experts = np.argsort(-(s + np.asarray(bias)[None]), axis=-1,
                         kind="stable")[:, :k]
    weights = np.take_along_axis(s, experts, axis=-1)
    return experts, weights / weights.sum(axis=-1, keepdims=True) * scale


def swiglu(x, gate, up, down, limit: float):
    """``W_down(silu(min(g, limit)) · clip(u, ±limit))``; ``limit`` 0: no
    clamp."""
    import jax
    import jax.numpy as jnp
    g, u = x @ gate, x @ up
    if limit:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return (jax.nn.silu(g) * u) @ down


def experts(h, layer: dict, spec: dict, w, limit: float, held=None):
    """The held experts' part of ``Σ_e w_e · Expert_e(h)``, every held expert
    in turn computing the rows that chose it. ``held = (first, count)``
    overrides the configuration's share (the share test)."""
    import jax.numpy as jnp
    base = _get(spec, "first_expert")     # the first expert of the weights
    first, count = held or (base, spec["experts_held"])
    chosen, weights = route(h, w(layer["router"]), layer["router_bias"],
                            spec["experts_per_token"],
                            _get(spec, "route_scale"))
    y = jnp.zeros_like(h)
    for e in range(count):
        rows, col = np.nonzero(chosen == first + e)
        if not rows.size:
            continue
        pad = -rows.size % ROW_PAD
        p = jnp.asarray(np.pad(weights[rows, col], (0, pad)))  # padding: 0
        rows = np.pad(rows, (0, pad))
        at = first - base + e
        out = swiglu(h[rows], w(layer["w_gate"][at]), w(layer["w_up"][at]),
                     w(layer["w_down"][at]), limit)
        y = y.at[rows].add(out * p[:, None])
    return y


def ffn(h, layer: dict, spec: dict, dense: bool, w, fault, held=None):
    limit = 0.0 if fault == "no_clamp" else _get(spec, "swiglu_limit")
    if dense:
        return swiglu(h, w(layer["m_gate"]), w(layer["m_up"]),
                      w(layer["m_down"]), limit)
    y = experts(h, layer, spec, w, limit, held)
    if fault != "no_shared" and (held is None or held[0] == 0):
        y = y + swiglu(h, w(layer["s_gate"]), w(layer["s_up"]),
                       w(layer["s_down"]), limit)
    return y


def forward(raw: dict, spec: dict, tokens, fault: str | None = None,
            first: int = 0, held=None, scale_embedding: float = 1.0):
    """Logits ``(T − first, V)`` of the positions from ``first`` of one
    sequence of token ids ``(T,)`` under the parameter tree ``raw``
    (``params["params"]`` of the family, any float dtype). ``fault`` computes
    a wrong model on purpose, to show what the limits catch: ``float8`` (every
    weight through float8_e4m3: the nearest precision below bfloat16),
    ``no_pool`` (every key scored by itself, 2,048 positions kept: DeepSeek
    sparse attention as ``dots3`` has it), ``no_tail`` (the query's own block
    not kept), ``rotary_latent`` (a rotary part on the latent heads),
    ``scalar_gate`` (a head's mean ``g`` on every channel), ``one_stream`` (a
    plain residual, no hyper-connection), ``no_shared`` (the shared expert
    left out), ``bf16_state`` (the recurrent state rounded to bfloat16 after
    every token), ``no_clamp`` (``swiglu_limit`` off). ``held = (first,
    count)``: another share of the experts, the shared expert counted with the
    share that starts at 0 (the share test); ``scale_embedding``: the
    embedding times that (the clamp's test)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    eps = _get(spec, "rms_eps")

    def w(a):
        if fault == "float8":
            a = a.astype(jnp.float8_e4m3fn)
        return a.astype(f32)

    with jax.default_matmul_precision("highest"):
        e = w(raw["embed"][jnp.asarray(tokens)]) * scale_embedding
        streams = [e] * (1 if fault == "one_stream"
                         else _get(spec, "streams"))
        for i, (kind, mlp) in enumerate(zip(spec["layer_types"],
                                            spec["mlp_types"])):
            layer = raw[f"layer{i}"]
            mixer = sparse if kind == "sparse" else kda
            streams = around(
                streams, layer, "hc_attn", spec, w, fault,
                lambda u: mixer(rms_norm(u, w(layer["norm_in"]), eps), layer,
                                spec, w, fault))
            streams = around(
                streams, layer, "hc_ffn", spec, w, fault,
                lambda u: ffn(rms_norm(u, w(layer["norm_post"]), eps), layer,
                              spec, mlp == "dense", w, fault, held))
        x = sum(streams)
        return np.asarray(rms_norm(x[first:], w(raw["norm_f"]), eps)
                          @ w(raw["lm_head"]))


# -- the comparison ------------------------------------------------------------

NOT_MODEL_KEYS = ("family", "name", "max_len", "maximum_concurrent_requests",
                  "async_path", "eos_id")


def prepare(config: dict, pre: dict) -> dict:
    from ai4e_tpu.models.glm5 import create_glm5_lm   # VALUES only
    spec = _model_spec(config)
    _, variables = create_glm5_lm(
        **{key: spec[key] for key in spec if key not in NOT_MODEL_KEYS})
    state = {"spec": spec, "raw": variables["params"], "payload": pre}
    forward(state["raw"], spec, [0] * ROW_PAD)   # compile the common shapes
    return state


def margins(state: dict, prompt: list[int], served: list[int],
            fault: str | None = None) -> np.ndarray:
    """For each served token: the reference maximum at its position minus the
    reference logit of the served id (0 where the reference agrees)."""
    seq = prompt + served
    rows = forward(state["raw"], state["spec"], seq[:-1], fault,
                   first=len(prompt) - 1)
    return rows.max(axis=-1) - rows[np.arange(len(served)), served]


def check(state: dict, jobs: list[dict], fault: str | None = None) -> dict:
    """``ok`` iff every served id lies within LOGIT_MARGIN of its position's
    reference maximum and at most SHARE_LIMIT of them (one, where that share
    of the tokens checked is less than one token) beyond SHARE_MARGIN.
    ``fault`` computes the reference wrongly on purpose (a control: it has to
    come out ``ok`` false on a sound system's streams)."""
    from benchmark.lib.payloads import PromptPayloads
    payloads = PromptPayloads(state["payload"]["seed"],
                              state["spec"]["vocab_size"])
    worst, exact, beyond, total, bad = 0.0, 0, 0, 0, []
    for job in jobs:
        prompt = payloads.prompt(job["counter"], job["prompt_len"])
        served = [int(t) for t in job["result"]["tokens"]]
        m = margins(state, prompt, served, fault)
        worst = max(worst, float(m.max()))
        exact += int((m == 0).sum())
        beyond += int((m > SHARE_MARGIN).sum())
        total += len(served)
        if float(m.max()) > LOGIT_MARGIN:
            bad.append({"counter": job["counter"],
                        "first_bad_index": int(np.argmax(m > LOGIT_MARGIN)),
                        "margin": float(m.max())})
    share = beyond / total if total else 0.0
    allowed = max(1, int(SHARE_LIMIT * total))
    return {"ok": not bad and beyond <= allowed and bool(jobs),
            "checked": len(jobs), "tokens_checked": total,
            "argmax_agreement": exact / total if total else 0.0,
            "worst_margin": worst, "limit_margin": LOGIT_MARGIN,
            "share_beyond": share, "share_margin": SHARE_MARGIN,
            "limit_share": SHARE_LIMIT, "beyond": beyond,
            "allowed_beyond": allowed, "bad": bad[:3]}
