"""Plain reference for the ``ling3`` family (inclusionAI/Ling-3.0-flash's
block), the comparison that decides ``correct`` for its cells, and the decode
step's and the prefill's operation and byte counts.

The forward pass is written from the configuration's equations, for hidden
``x``, ``n(x) = w ⊙ x / √(mean(x²) + eps)``:

    h = x + Mixer(n_in(x));  y = h + FFN(n_post(h))
    layer i mixes by latent attention iff (i + 1) % group == 0, else by KDA

    KDA:  u = n_in(x);  [q | k | v] = SiLU(causal depthwise convolution of
          [u W_q | u W_k | u W_v] over the last `conv` tokens);  a head: q ←
          q/‖q‖ / √d, k ← k/‖k‖ (eps 1e-6);  β = σ(u W_β);  g = gate_bound ·
          σ(e^{A_h} (u W_a + b)) a CHANNEL, in (gate_bound, 0);  per head and
          token: S ← Diag(e^g) S;  δ = β (v − Sᵀ k);  S ← S + k ⊗ δ;  o = Sᵀ q;
          then o ← n_o(o) ⊙ σ(u W_z);  W_o
    Latent attention:  [q_nope | q_rope]_h = u W_q;  [c_kv | k_r] = u W_dkv;
          c_kv ← n_kv(c_kv);  q_rope, k_r rotated on the lane pairs (2i, 2i +
          1), inv_freq = θ^(−2i/rope), k_r shared by every head;  k_nope,h =
          c_kv W_uk,h;  v_h = c_kv W_uv,h;  causal softmax of (q_nope·k_nope +
          q_rope·k_r) / √(nope + rope);  o_h ← o_h · σ(u w_g,h);  W_o
    FFN:  the first dense_layers a SwiGLU;  the others s = σ(x W_r) over ALL
          experts;  for the choice s + b: a group of experts scored by the sum
          of its two largest, the `keep` best of the `n` groups kept, the K
          largest of their experts chosen (a tie to the lower index);  weights
          s_e / Σ_picks s × route_scale;  the terms of the experts HELD here
          (the configuration's share: what the others would add is left out,
          as in the program);  + Expert_shared(x)
    logits = n_f(x) W_head, over the vocabulary's slice

in plain ``jax.numpy``, float32, ``highest`` matmul precision: the recurrence
a ``lax.scan`` over single tokens, the convolution a sum of shifted products,
every head's ``k_nope`` and ``v`` built from ``c_kv``, the groups by a loop,
the experts by a plain loop over the rows that chose a held one — no cache, no
chunk, no solve, no kernel, nothing absorbed; heads a few at a time, which is
only what memory needs; and no import from ``ai4e_tpu.models`` beyond
``create_ling3_lm`` for the parameter VALUES: the same bfloat16 values the
worker serves (the family's seeded init is integer arithmetic on threefry
bits, so the CPU draws them bit for bit). Departures from the published model:
seeded weights; the share of layers, experts and vocabulary the configuration
states; no multi-token-prediction module; nothing else.

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
# in bfloat16, decodes them in the absorbed form and runs the prefill's
# recurrence in chunks: its logits differ from this float32 forward by
# rounding, and now and then rounding picks another eighth expert or another
# fourth group (a sigmoid router's eight renormalised weights are nearly
# equal, so a flip swaps an eighth of an expert layer's routed output). A run
# is `not correct` by either limit. The SHARE is the limit that tells a lower
# precision and the milder faults from the sound system (1.0-1.7 % against
# rotate_half's 5.9-6.4 % and float8's 27 %); the MARGIN catches what rewrites
# the model (1.1-4.5) and no mild fault: the sound system's rare flips reach
# 0.49.
LOGIT_MARGIN = 1.0
SHARE_MARGIN, SHARE_LIMIT = 0.05, 0.035
MARGIN_MEASURED = (
    "on the chip's served streams (my chip runs, PR 48, the final init: the "
    "traced run and the first set of six at the cell's rate, two streams a "
    "run of prompts and answers within 3,072 tokens, 911-1,628 checked tokens "
    "a run, 9,594 in all) the worst margin a run is 0.295, 0.319, 0.277, "
    "0.366, 0.494, 0.390, 0.281, the share beyond 0.05 1.21, 1.25, 1.17, "
    "1.70, 1.25, 1.17, 1.66 %, argmax agreement 94.2-95.4 %. By stream "
    "(sweeps/longstream.py, one live at a time: prompts 650 / 230 / 900 + "
    "700 / 820 / 650 served tokens): worst 0.178 / 0.275 / 0.316, beyond "
    "0.05 1.57 / 1.34 / 1.54 %, agreement 96.1 / 96.2 / 95.5 %; and ONE "
    "stream of an 8,192-token prompt + 1,024 served tokens through the 8,192 "
    "bucket and the step's top rung: worst 0.435, 1.37 % beyond, 95.1 % - "
    "the tail a run's sample of two cannot hold reads as the short streams "
    "do. A thin tail of expert flips, no drift with the context. The share "
    "limit 3.5 % has 2.1 x over the largest sound reading (1.70 %) and 1.7 x "
    "under the mildest control it has to catch (rotate_half's 5.9 %); the "
    "margin limit 1.0 has 2.0 x over the one 0.494 and lies under every "
    "control it catches. The first six runs of the cell (the knee sweep) "
    "read 1.63-2.75 / 10.9-16.8 % / 79-85 % under an init whose FFNs added "
    "0.3-0.6 of the stream: the init was re-scaled (models/ling3.py "
    "create_ling3_lm), no limit was widened")
FAULTS_MEASURED = (
    "check(fault=...) on three SERVED streams of the chip (sweeps/longstream"
    ".py serve ling3.toolctx 2148000401: prompts 650 / 230 / 900 + 700 / 820 "
    "/ 650 served tokens; the sound system's own ids, the reference computed "
    "wrongly on the sandbox's CPU; PR 48), as worst margin | share beyond "
    "0.05 | argmax agreement, stream by stream. Sound: 0.178 0.275 0.316 | "
    "1.6 1.3 1.5 % | 96.1 96.2 95.5 %. Each `ok` false by BOTH limits on 3 of "
    "3: scalar_gate (a head's mean g on every channel) 1.15 1.25 1.19 | 42.6 "
    "39.6 40.8 % | 52-57 %; softplus_gate (the unbounded gate) 1.84 2.02 1.78 "
    "| 52.4 55.5 55.2 % | 41-44 %; no_delta 2.26 2.13 2.18 | 68.7 67.8 67.5 % "
    "| 29-30 %; silu_out_gate 4.15 3.87 4.17 | 88.7 87.7 88.9 % | 10-12 %; "
    "no_conv 4.17 4.49 4.01 | 90.4 87.6 89.8 % | 9-11 %; no_bias 1.68 1.40 "
    "1.50 | 47.9 48.4 47.2 % | 48-49 % (the best experts' sigmoid scores "
    "saturate near 1, so a bias of ~0.2 decides most of the order among "
    "them); softmax 3.49 3.30 2.68 | 68.3 66.6 66.5 % | 29-30 %; shared_out "
    "2.65 2.70 3.10 | 73.7 75.1 73.4 % | 23-24 %. `ok` false by the share "
    "alone on 3 of 3 (float8 also by the margin on 2 of 3): float8 (the "
    "nearest precision below bfloat16) 1.04 0.97 1.11 | 27.1 27.2 27.5 % | "
    "66-67 %; no_head_gate 0.89 0.85 0.88 | 29.0 32.1 32.1 % | 62-67 %; "
    "no_group_limit (the 8 largest of all 512) 0.71 0.62 0.66 | 23.3 20.6 "
    "24.8 % | 70-74 %; rotate_half (the other rotary layout, in ONE layer of "
    "seven) 0.39 0.26 0.36 | 6.4 6.0 5.9 % | 88-89 % - the mildest that is "
    "caught. bf16_state 0.183 0.260 0.226 | 1.0 1.3 1.4 % | 93.8-95.9 %: `ok` "
    "TRUE on 3 of 3 - a recurrent state rounded to bfloat16 after every token "
    "moves the logits no more than the system's own bfloat16 activations do, "
    "as in qwen3-next and granite-hybrid; tier-1 holds the dtype in float32 "
    "(tests/test_ling3.py: bf16_state moves the float32 pair's logits by more "
    "than 10 x their agreement)")
FAULTS = ("float8", "scalar_gate", "softplus_gate", "no_delta",
          "silu_out_gate", "no_conv", "rotate_half", "no_head_gate",
          "no_group_limit", "no_bias", "softmax", "shared_out", "bf16_state")
ROW_PAD = 64      # an expert's rows are padded to a multiple: few shapes
HEAD_CHUNK = 8    # heads whose (T, T) scores are held at once
L2_EPS = 1e-6


def _model_spec(config: dict) -> dict:
    return next(m for m in config["models"]["models"]
                if m["family"] == "ling3")


# The fields a models spec may leave to the program's defaults.
DEFAULTS = {"conv": 4, "gate_bound": -5.0, "dense_layers": 1,
            "first_expert": 0, "route_scale": 2.5, "rms_eps": 1e-6,
            "rope_theta": 6e6}


def _get(spec: dict, key: str):
    return spec.get(key, DEFAULTS[key])


def _latent_layers(spec: dict) -> list[bool]:
    """True for a latent-attention layer."""
    return [(i + 1) % spec["group"] == 0 for i in range(spec["depth"])]


# -- sizes ---------------------------------------------------------------------

def kda_params(spec: dict) -> int:
    """A KDA mixer's projections, convolution and the decay's parameters."""
    d, wide = spec["dim"], spec["heads"] * spec["head_dim"]
    return (5 * d * wide + d * spec["heads"] + wide * d
            + _get(spec, "conv") * 3 * wide
            + spec["heads"] + wide + spec["head_dim"])


def latent_params(spec: dict) -> int:
    d, h, r = spec["dim"], spec["heads"], spec["kv_rank"]
    return (d * h * (spec["nope"] + spec["rope_dim"])
            + d * (r + spec["rope_dim"]) + r
            + r * h * (spec["nope"] + spec["v_dim"]) + d * h
            + h * spec["v_dim"] * d)


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
    ones where it has them) and its two norms."""
    dense = _get(spec, "dense_layers")
    return sum((latent_params(spec) if latent else kda_params(spec))
               + ffn_params(spec, i < dense, experts) + 2 * spec["dim"]
               for i, latent in enumerate(_latent_layers(spec)))


def weight_bytes(spec: dict) -> int:
    """What a decode step reads of the weights, bfloat16: every held layer
    (ALL the held experts: the step's ``dense`` product reads them), the head
    and the final norm. Not the embedding table: a step reads one row a
    slot."""
    d = spec["dim"]
    return int(2 * (_layers(spec) + d * spec["vocab_size"] + d))


def state_bytes_per_slot(spec: dict) -> tuple[int, int]:
    """A slot's recurrent state over the KDA layers: ``(S in float32, the
    convolution's tails in bfloat16)``."""
    linear = spec["depth"] - sum(_latent_layers(spec))
    wide = spec["heads"] * spec["head_dim"]
    return (linear * wide * spec["head_dim"] * 4,
            linear * (_get(spec, "conv") - 1) * 3 * wide * 2)


def latent_row_bytes(spec: dict) -> int:
    """A cached position as published (no padding), every latent layer."""
    return (sum(_latent_layers(spec))
            * 2 * (spec["kv_rank"] + spec["rope_dim"]))


def _met(spec: dict) -> float:
    """Experts a token meets here where the router spreads evenly."""
    return spec["experts_per_token"] * spec["experts_held"] / spec["experts"]


def ops_and_bytes(config: dict, slots: int,
                  live_tokens: float) -> tuple[float, float]:
    """One decode step over the pool. Operations = 2 x (the mixers', the dense
    FFN's, the router's, the shared expert's weights + the K x held / total
    experts a token meets here + the head) per slot + 4 x the state's elements
    a slot a KDA layer (decay, two readings, update) + per live slot and
    latent layer the absorbed attention over its cached positions (2·H·(2·r +
    rope)). Least bytes = every held weight once + one embedding row a slot +
    the LIVE slots' KDA states once in and once out
    (``config["derived"]["live_slots"]``, which ``readers/step_roofline_live
    .py`` sets from the engine's own series; every slot's where nobody says)
    + every slot's convolution tails in and out + the live latent rows as
    published + one row a slot written. ``live_tokens``: the cached positions
    of the live slots, summed."""
    spec = _model_spec(config)
    d = spec["dim"]
    latent = sum(_latent_layers(spec))
    per_slot = _layers(spec, _met(spec)) + d * spec["vocab_size"]
    state, tails = state_bytes_per_slot(spec)
    flops = (2.0 * per_slot * slots + 4.0 * (state // 4) * slots
             + latent * 2.0 * spec["heads"]
             * (2 * spec["kv_rank"] + spec["rope_dim"]) * live_tokens)
    nbytes = (weight_bytes(spec) + 2 * d * slots
              + 2 * state * config["derived"].get("live_slots", slots)
              + 2 * tails * slots
              + latent_row_bytes(spec) * (live_tokens + slots))
    return flops, float(nbytes)


def prefill_ops_and_bytes(config: dict, tokens: float, pairs: dict,
                          calls: float = 1.0) -> tuple[float, float]:
    """``calls`` prefills of ``tokens`` real tokens in all, by the PUBLISHED
    mathematics whatever form the program computes: 2 x (the mixers' and the
    FFNs' weights a token — of the experts the K x held / total it meets
    here) + the recurrence's 4 d² multiply-adds a head a token a KDA layer
    (whatever chunk algebra implements it) + 2 x the causal pairs a latent
    layer x H x (nope + rope + v); the head once a prefill. Least bytes:
    every held weight once a prefill + the hidden state of the real tokens
    read and written a sublayer."""
    spec = _model_spec(config)
    latent = sum(_latent_layers(spec))
    state, _ = state_bytes_per_slot(spec)
    flops = (2.0 * _layers(spec, _met(spec)) * tokens
             + 2.0 * spec["dim"] * spec["vocab_size"] * calls
             + 2.0 * 4.0 * (state // 4) * tokens
             + 2.0 * latent * pairs.get("latent", 0.0) * spec["heads"]
             * (spec["nope"] + spec["rope_dim"] + spec["v_dim"]))
    return flops, float(weight_bytes(spec) * calls
                        + 2 * spec["depth"] * 2 * 2 * spec["dim"] * tokens)


# -- the forward pass ----------------------------------------------------------

def rms_norm(x, w, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def kda(u, layer: dict, spec: dict, w, fault):
    """Kimi Delta Attention over the whole sequence ``u (T, D)`` (after
    ``n_in``), one token at a time from a zero state."""
    import jax
    import jax.numpy as jnp
    t = u.shape[0]
    heads, d = spec["heads"], spec["head_dim"]
    conv, eps = _get(spec, "conv"), _get(spec, "rms_eps")
    mixed = u @ w(layer["in_qkv"])
    if fault == "no_conv":
        c = jax.nn.silu(mixed)
    else:
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
    a = (u @ w(layer["w_a"])).reshape(t, heads, d) + layer["dt_bias"]
    rate = jnp.exp(layer["a_log"])[None, :, None]
    if fault == "softplus_gate":
        g = -rate * jax.nn.softplus(a)
    else:
        g = _get(spec, "gate_bound") * jax.nn.sigmoid(rate * a)
    if fault == "scalar_gate":
        g = jnp.broadcast_to(g.mean(axis=-1, keepdims=True), g.shape)

    def token(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[:, :, None]
        read = 0.0 if fault == "no_delta" else jnp.einsum(
            "hkv,hk->hv", state, k_t)
        delta = beta_t[:, None] * (v_t - read)
        state = state + k_t[:, :, None] * delta[:, None, :]
        if fault == "bf16_state":
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((heads, d, d), jnp.float32),
                        (q, k, v, g, beta))
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    z = (u @ w(layer["w_z"])).reshape(t, heads, d)
    o = o * w(layer["norm_o"]) * (jax.nn.silu(z) if fault == "silu_out_gate"
                                  else jax.nn.sigmoid(z))
    return o.reshape(t, heads * d) @ w(layer["out_proj"])


def rotate(x, theta: float, fault=None):
    """Rotary embedding of ``x (T, heads, width)`` over its whole width, the
    token's index as its position: the neighbours ``(2i, 2i + 1)`` a pair
    (``rotate_half``: the lanes ``(i, i + width / 2)``, the other layout)."""
    import jax.numpy as jnp
    t, width = x.shape[0], x.shape[-1]
    inv_freq = theta ** (-np.arange(0, width, 2, dtype=np.float64) / width)
    angle = (np.arange(t, dtype=np.float64)[:, None]
             * inv_freq[None, :]).astype(np.float32)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    if fault == "rotate_half":
        a, b = x[..., :width // 2], x[..., width // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def latent(u, layer: dict, spec: dict, w, fault):
    """Latent attention over the whole sequence ``u (T, D)`` (after
    ``n_in``), nothing absorbed and nothing cached."""
    import jax
    import jax.numpy as jnp
    t = u.shape[0]
    heads, r, nope = spec["heads"], spec["kv_rank"], spec["nope"]
    theta = _get(spec, "rope_theta")
    q = (u @ w(layer["w_q"])).reshape(t, heads, -1)
    q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], theta, fault)
    kv = u @ w(layer["w_dkv"])
    c_kv = rms_norm(kv[:, :r], w(layer["norm_kv"]), _get(spec, "rms_eps"))
    k_r = rotate(kv[:, None, r:], theta, fault)[:, 0]
    causal = jnp.asarray(np.tril(np.ones((t, t), bool)))
    scale = (nope + spec["rope_dim"]) ** -0.5
    w_uk, w_uv = w(layer["w_uk"]), w(layer["w_uv"])
    out = []
    for a in range(0, heads, HEAD_CHUNK):
        b = a + HEAD_CHUNK
        k_nope = jnp.einsum("tr,rhn->thn", c_kv, w_uk[:, a:b])
        v = jnp.einsum("tr,rhv->thv", c_kv, w_uv[:, a:b])
        scores = (jnp.einsum("thn,shn->hts", q_nope[:, a:b], k_nope)
                  + jnp.einsum("thr,sr->hts", q_rope[:, a:b], k_r)) * scale
        p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hts,shv->thv", p, v))
    o = jnp.concatenate(out, axis=1)
    if fault != "no_head_gate":
        o = o * jax.nn.sigmoid(u @ w(layer["w_g"]))[..., None]
    return o.reshape(t, -1) @ w(layer["w_o"])


def route(h, router, bias, k: int, groups: tuple, scale: float, fault=None):
    """``h (T, D)`` → the K experts of each row ``(T, K)`` and their weights
    ``(T, K)``. The choice is by sigmoid score + bias: each of the ``n``
    groups of neighbouring experts scored by the sum of its two largest, the
    ``keep`` best groups kept (a tie to the lower group), the K largest of
    their experts chosen (a tie to the lower index). The weights are the
    scores without the bias, divided by their sum, times ``scale``."""
    import jax
    logits = h @ router
    s = np.asarray(jax.nn.softmax(logits, axis=-1) if fault == "softmax"
                   else jax.nn.sigmoid(logits))
    choice = s if fault == "no_bias" else s + np.asarray(bias)[None]
    if fault != "no_group_limit":
        n, keep = groups
        size = s.shape[1] // n
        score = np.stack([np.sort(choice[:, i * size:(i + 1) * size],
                                  axis=1)[:, -2:].sum(axis=1)
                          for i in range(n)], axis=1)
        kept = np.argsort(-score, axis=1, kind="stable")[:, :keep]
        allowed = np.zeros_like(choice, bool)
        for i in range(n):
            allowed[:, i * size:(i + 1) * size] = (kept == i).any(
                axis=1)[:, None]
        choice = np.where(allowed, choice, -np.inf)
    experts = np.argsort(-choice, axis=-1, kind="stable")[:, :k]
    weights = np.take_along_axis(s, experts, axis=-1)
    return experts, weights / weights.sum(axis=-1, keepdims=True) * scale


def swiglu(x, gate, up, down):
    import jax
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def experts(h, layer: dict, spec: dict, w, fault=None, held=None):
    """The held experts' part of ``Σ_e w_e · Expert_e(h)``, every held expert
    in turn computing the rows that chose it. ``held = (first, count)``
    overrides the configuration's share (the share test)."""
    import jax.numpy as jnp
    first, count = held or (_get(spec, "first_expert"), spec["experts_held"])
    chosen, weights = route(h, w(layer["router"]), layer["router_bias"],
                            spec["experts_per_token"],
                            tuple(spec["route_groups"]),
                            _get(spec, "route_scale"), fault)
    y = jnp.zeros_like(h)
    for e in range(count):
        rows, col = np.nonzero(chosen == first + e)
        if not rows.size:
            continue
        pad = -rows.size % ROW_PAD
        p = jnp.asarray(np.pad(weights[rows, col], (0, pad)))  # padding: 0
        rows = np.pad(rows, (0, pad))
        out = swiglu(h[rows], w(layer["w_gate"][e]), w(layer["w_up"][e]),
                     w(layer["w_down"][e]))
        y = y.at[rows].add(out * p[:, None])
    return y


def ffn(h, layer: dict, spec: dict, dense: bool, w, fault):
    if dense:
        return swiglu(h, w(layer["m_gate"]), w(layer["m_up"]),
                      w(layer["m_down"]))
    y = experts(h, layer, spec, w, fault)
    if fault != "shared_out":
        y = y + swiglu(h, w(layer["s_gate"]), w(layer["s_up"]),
                       w(layer["s_down"]))
    return y


def forward(raw: dict, spec: dict, tokens, fault: str | None = None,
            first: int = 0):
    """Logits ``(T − first, V)`` of the positions from ``first`` of one
    sequence of token ids ``(T,)`` under the parameter tree ``raw``
    (``params["params"]`` of the family, any float dtype). ``fault`` computes
    a wrong model on purpose, to show what the limits catch: ``float8`` (every
    weight through float8_e4m3: the nearest precision below bfloat16),
    ``scalar_gate`` (a head's mean ``g`` on every channel: the block
    ``qwen3-next`` has), ``softplus_gate`` (``g = −e^{A} softplus(a + b)``,
    the unbounded reading), ``no_delta`` (``δ = β v``: plain gated linear
    attention), ``silu_out_gate`` (``SiLU(z)`` for ``σ(z)``), ``no_conv``,
    ``rotate_half`` (the other rotary layout), ``no_head_gate``,
    ``no_group_limit`` (the K largest of all the experts), ``no_bias`` (the
    choice by the scores alone), ``softmax`` (routing), ``shared_out`` (the
    shared expert left out), ``bf16_state`` (the recurrent state rounded to
    bfloat16 after every token)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    eps, dense = _get(spec, "rms_eps"), _get(spec, "dense_layers")

    def w(a):
        if fault == "float8":
            a = a.astype(jnp.float8_e4m3fn)
        return a.astype(f32)

    with jax.default_matmul_precision("highest"):
        x = w(raw["embed"][jnp.asarray(tokens)])
        for i, is_latent in enumerate(_latent_layers(spec)):
            layer = raw[f"layer{i}"]
            u = rms_norm(x, w(layer["norm_in"]), eps)
            x = x + (latent if is_latent else kda)(u, layer, spec, w, fault)
            h = rms_norm(x, w(layer["norm_post"]), eps)
            x = x + ffn(h, layer, spec, i < dense, w, fault)
        return np.asarray(rms_norm(x[first:], w(raw["norm_f"]), eps)
                          @ w(raw["lm_head"]))


# -- the comparison ------------------------------------------------------------

NOT_MODEL_KEYS = ("family", "name", "max_len", "maximum_concurrent_requests",
                  "async_path", "eos_id")


def prepare(config: dict, pre: dict) -> dict:
    from ai4e_tpu.models.ling3 import create_ling3_lm   # VALUES only
    spec = _model_spec(config)
    _, variables = create_ling3_lm(
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
