"""Plain reference for the ``xing4`` family (XingChen-AGI/Xing4.0-29B-A4B's
block), the comparison that decides ``correct`` for its cells, and the decode
step's and the prefill's operation and byte counts.

The forward pass is written from the configuration's equations, for a token's
``n`` streams ``X_0 .. X_{n-1}`` (each ``D`` wide), ``n(x) = w ⊙ x /
√(mean(x²) + eps)``:

    X_i = e for every stream i (the token's embedding)
    a sublayer F (the mixer with n_in, or the FFN with n_post; 2 a layer,
          each with φ, α, b of its own):
          x = [X_0 | .. | X_{n-1}] (nD);  x' = x / √(mean(x²) + eps)
          H~_pre = α_pre x' φ_pre + b_pre;  H~_post = α_post x' φ_post + b_post
          H~_res = α_res mat(x' φ_res) + b_res  (n x n, row-major)
          H_pre = σ(H~_pre);  H_post = 2 σ(H~_post)
          M = exp(clip(H~_res, −clamp, clamp));  `sinkhorn_iters` times:
          M ← M / (rows' sums + hc_eps);  M ← M / (columns' sums + hc_eps)
          u = Σ_i H_pre,i X_i;  y = F(u);  X_i ← Σ_j M_ij X_j + H_post,i y
    Latent attention:  c_q = n_q(u_n W_dq);  [q_nope | q_rope]_h = c_q W_uq;
          [c_kv | k_r] = u_n W_dkv;  c_kv ← n_kv(c_kv);  q_rope, k_r rotated
          (rotate-half, YaRN's frequencies), k_r shared by every head;
          k_nope,h = c_kv W_uk,h;  v_h = c_kv W_uv,h;  causal softmax of
          (q_nope·k_nope + q_rope·k_r) · s;  W_o
    YaRN: f_i = θ^(−2i/d);  low = ⌊d ln(L/(2π β_fast)) / (2 ln θ)⌋,  high =
          ⌈d ln(L/(2π β_slow)) / (2 ln θ)⌉;  r_i = clip((i − low)/(high −
          low), 0, 1);  inv_freq_i = f_i (1 − r_i) + f_i r_i / factor;
          m(a) = 0.1 a ln(factor) + 1;  cos, sin × m(mscale)/m(mscale_all_dim);
          s = m(mscale_all_dim)² / √(nope + rope)
    FFN:  the first dense_layers a SwiGLU;  the others s = sigmoid(x W_r),
          the K largest of s + b (a tie to the lower index), weights s_e /
          Σ_picks s × route_scale, Σ_e w_e Expert_e(x) + Expert_shared(x)
    logits = n_f(Σ_i X_i) W_head

in plain ``jax.numpy``, float32, ``highest`` matmul precision: the streams a
Python list, Sinkhorn a loop, every head's ``k_nope`` and ``v`` built from
``c_kv`` (nothing absorbed), the experts by a plain loop over the rows that
chose each — no cache, no kernel; heads a few at a time, which is only what
memory needs; and no import from ``ai4e_tpu.models`` beyond
``create_xing4_lm`` for the parameter VALUES: the same bfloat16 values the
worker serves (the family's seeded init is integer arithmetic on threefry
bits, so the CPU draws them bit for bit). Departures from the published model:
seeded weights; the share of layers the configuration states; no
multi-token-prediction module; nothing else.

The API returns greedy token ids only, and with random weights an argmax flips
on rounding. So the reference is teacher-forced on prompt + served tokens, and
each served token's reference logit must lie within LOGIT_MARGIN of that
position's reference maximum, all but SHARE_LIMIT of them (all but one, of a
stream so short that the share is less than one token) within SHARE_MARGIN.
"""

from __future__ import annotations

import numpy as np

# Reason for the two limits: see MARGIN_MEASURED and FAULTS_MEASURED. The
# worker computes in bfloat16 with float32 accumulation, holds its streams and
# reads its cache in bfloat16 and decodes in the absorbed form: its logits
# differ from this float32 forward by rounding, and now and then rounding picks
# another fourth expert (a sigmoid router's four renormalised weights are
# nearly equal, so a flip swaps a quarter of an expert layer's routed output).
# A run is `not correct` by either limit. The SHARE is the limit that tells a
# lower precision from the sound system (1.0-2.1 % against float8's 25-32 %);
# the MARGIN catches what rewrites the model (1.0-2.3) and no mild fault: the
# sound system's rare flips reach 0.59.
LOGIT_MARGIN = 1.0
SHARE_MARGIN, SHARE_LIMIT = 0.05, 0.035
MARGIN_MEASURED = (
    "on the chip's served streams (my chip runs, PR 43: the knee sweep's five "
    "runs and the traced run, two streams of 624-1,536 served tokens each "
    "after prompts of 111-888; 1,276-2,377 tokens a run, 10,947 in all) the "
    "worst margin a run is 0.350, 0.278, 0.398, 0.589, 0.218, 0.190, the "
    "share beyond 0.05 1.35, 1.00, 1.17, 1.70, 1.05, 1.02 %, argmax "
    "agreement 95.3-96.8 %. By stream (three of them, 841 / 659 / 679 "
    "tokens): worst 0.350 / 0.278 / 0.338; beyond 0.05 12 / 8 / 13 (1.4 / "
    "1.2 / 1.9 %), beyond 0.1 7 / 4 / 8, beyond 0.2 3 / 1 / 3, beyond 0.3 1 / "
    "0 / 1: a thin tail, e-fold about 0.1, which is an expert flip now and "
    "then and not a drift with the context (the largest five of a stream lie "
    "anywhere in it). The share limit 3.5 % has 1.8 x over the largest sound "
    "stream (1.9 %; 2.1 x over the largest run, 1.70 %) and 1.8 x under the "
    "mildest control it has to catch (stream_0's 6.2 %); the margin limit "
    "1.0 has 1.7 x over the one 0.589 and lies under every control it "
    "catches. The two sets of six at the cell's rate (twelve seeds, "
    "1,074-3,052 tokens a run, 23,294 in all; every run `ok`): worst 0.233-"
    "0.453, share beyond 0.05 1.12-2.09 % (mean 1.51 %), argmax agreement "
    "94.4-96.2 %: the share limit has 1.7 x over the largest of eighteen runs")
FAULTS_MEASURED = (
    "check(fault=...) on three SERVED streams of the chip (seeds 4300101 / "
    "4300102 / 4300104 of the knee sweep: prompts 204 / 888 / 435 + 841 / "
    "659 / 679 served tokens; the sound system's own ids, the reference "
    "computed wrongly on the chip host's CPU; PR 43), as worst margin | share "
    "beyond 0.05 | argmax agreement, stream by stream. Sound: 0.350 0.278 "
    "0.338 | 1.4 1.2 1.9 % | 95.6 96.4 94.7 %. plain_residual 2.12 1.91 2.23 "
    "| 89.8 87.1 87.0 % | 8-12 %; post_unscaled 2.05 2.28 2.15 | 92.0 87.1 "
    "91.3 % | 7-10 %; softmax_routing 1.47 1.69 1.72 | 62.2 61.9 70.1 % | "
    "27-33 %; no_shared 1.20 1.01 1.18 | 57.2 54.0 59.8 % | 34-41 %: each "
    "`ok` false by both limits on 3 of 3. no_yarn 0.94 0.79 0.92 | 56.4 41.3 "
    "50.1 % | 37-53 %; float8 (the nearest precision below bfloat16) 0.57 "
    "0.57 0.59 | 31.2 25.2 31.7 % | 60-68 %; stream_0 0.34 0.27 0.34 | 9.3 "
    "6.2 7.5 % | 83-85 %: each `ok` false by the share alone on 3 of 3 "
    "(stream_0 is the mildest that is caught: after seven layers of mixing "
    "the four streams are nearly one another's copies up to scale, and the "
    "final norm takes the scale). sinkhorn_1 0.353 0.338 0.347 | 1.4 2.0 2.5 "
    "% | 94.2 95.1 93.2 %: `ok` TRUE on 3 of 3 - after ONE iteration the "
    "columns are exact and the rows 17 % off at the median, which rescales "
    "each stream by that much and moves the logits by less than bfloat16's "
    "rounding and its expert flips do; no limit on per-token margins can "
    "see it at these gains (a b_res spread wide enough to show leaves 1 % "
    "of the tokens unconverged after 20: models/xing4.py create_xing4_lm); "
    "tier-1 holds the iteration count in float32 at a small size "
    "(tests/test_xing4.py: sinkhorn_1 moves the logits by 0.14, 700 x the "
    "pair's agreement; ops/mhc.py's own test holds 20 against 1)")
FAULTS = ("float8", "sinkhorn_1", "plain_residual", "post_unscaled",
          "stream_0", "no_yarn", "softmax_routing", "no_shared")
ROW_PAD = 64      # an expert's rows are padded to a multiple: few shapes
HEAD_CHUNK = 8    # heads whose (T, T) scores are held at once


def _model_spec(config: dict) -> dict:
    return next(m for m in config["models"]["models"]
                if m["family"] == "xing4")


# The fields a models spec may leave to the program's defaults.
DEFAULTS = {"streams": 4, "sinkhorn_iters": 20, "hc_eps": 1e-6,
            "hc_clamp": 30.0, "rope_theta": 1e4, "rope_factor": 64.0,
            "rope_original": 4096, "beta_fast": 32.0, "beta_slow": 1.0,
            "mscale": 1.0, "mscale_all_dim": 1.0, "route_scale": 2.0,
            "rms_eps": 1e-6, "dense_layers": 1}


def _get(spec: dict, key: str):
    return spec.get(key, DEFAULTS[key])


# -- sizes ---------------------------------------------------------------------

def mixer_params(spec: dict) -> int:
    d, h = spec["dim"], spec["heads"]
    return (d * spec["q_rank"] + spec["q_rank"]
            + spec["q_rank"] * h * (spec["nope"] + spec["rope_dim"])
            + d * (spec["kv_rank"] + spec["rope_dim"]) + spec["kv_rank"]
            + spec["kv_rank"] * h * (spec["nope"] + spec["v_dim"])
            + h * spec["v_dim"] * d)


def hyper_params(spec: dict) -> int:
    """One sublayer's hyper-connection maps (``alpha`` and ``bias`` are 27
    float32 numbers: left out)."""
    n = _get(spec, "streams")
    return n * spec["dim"] * (2 * n + n * n)


def ffn_params(spec: dict, dense: bool, experts: float | None = None) -> float:
    """A layer's FFN: a dense one whole; an expert layer's router, shared
    expert and ``experts`` routed ones (None: all of them)."""
    d = spec["dim"]
    if dense:
        return 3 * d * spec["mlp_dim"]
    e = spec["experts"] if experts is None else experts
    return (d * spec["experts"] + 2 * spec["experts"]   # the bias is float32
            + 3 * e * d * spec["expert_dim"] + 3 * d * spec["shared_dim"])


def weight_bytes(spec: dict) -> int:
    """What a decode step reads of the weights, bfloat16: per layer the
    mixer, the FFN (ALL the experts: the step's ``dense`` product reads
    them), the two sublayers' hyper-connection maps and the two norms; the
    head and the final norm. Not the embedding table: a step reads one row a
    slot."""
    d = spec["dim"]
    dense = _get(spec, "dense_layers")
    n = sum(mixer_params(spec) + 2 * hyper_params(spec) + 2 * d
            + ffn_params(spec, i < dense) for i in range(spec["depth"]))
    return int(2 * (n + d * spec["vocab_size"] + d))


def stream_bytes(spec: dict) -> int:
    """What a token's streams cost a sublayer, bfloat16: ``n D`` read and
    ``n D`` written."""
    return 2 * 2 * _get(spec, "streams") * spec["dim"]


def ops_and_bytes(config: dict, slots: int,
                  live_tokens: float) -> tuple[float, float]:
    """One decode step over the pool. Operations = 2 x (the mixers', the
    hyper-connection maps' and the dense FFN's weights + router + the K
    experts a token meets + the shared expert + the head) per slot + per
    live slot and layer the absorbed attention over its cached positions
    (2·H·(2·r_kv + rope)). Least bytes = every weight once + one embedding
    row a slot + per live slot its cached rows as published (no padding),
    once a layer + one row a slot written + the streams (a sublayer reads
    ``n D`` and writes ``n D`` a slot). ``live_tokens``: the cached positions
    of the live slots, summed."""
    spec = _model_spec(config)
    d, depth = spec["dim"], spec["depth"]
    dense = _get(spec, "dense_layers")
    per_slot = sum(mixer_params(spec) + 2 * hyper_params(spec)
                   + ffn_params(spec, i < dense, spec["experts_per_token"])
                   for i in range(depth)) + d * spec["vocab_size"]
    flops = (2.0 * per_slot * slots + depth * 2.0 * spec["heads"]
             * (2 * spec["kv_rank"] + spec["rope_dim"]) * live_tokens)
    row = 2 * (spec["kv_rank"] + spec["rope_dim"])
    nbytes = (weight_bytes(spec) + 2 * d * slots
              + depth * row * (live_tokens + slots)
              + 2 * depth * stream_bytes(spec) * slots)
    return flops, float(nbytes)


def prefill_ops_and_bytes(config: dict, tokens: float, pairs: dict,
                          calls: float = 1.0) -> tuple[float, float]:
    """``calls`` prefills of ``tokens`` real tokens in all, by the PUBLISHED
    mathematics whatever form the program computes: 2 x (the mixers', the
    hyper-connection maps' and the FFN's weights a token — of the experts
    the K it meets) + 2 x the causal pairs a layer x H x (nope + rope + v);
    the head once a prefill. Least bytes: every weight once a prefill + the
    streams of the real tokens (a sublayer reads ``n D`` and writes ``n D``
    a token)."""
    spec = _model_spec(config)
    depth, dense = spec["depth"], _get(spec, "dense_layers")
    per_token = sum(mixer_params(spec) + 2 * hyper_params(spec)
                    + ffn_params(spec, i < dense, spec["experts_per_token"])
                    for i in range(depth))
    flops = 2.0 * (
        per_token * tokens + spec["dim"] * spec["vocab_size"] * calls
        + depth * pairs.get("latent", 0.0) * spec["heads"]
        * (spec["nope"] + spec["rope_dim"] + spec["v_dim"]))
    return flops, float(weight_bytes(spec) * calls
                        + 2 * depth * stream_bytes(spec) * tokens)


# -- the forward pass ----------------------------------------------------------

def rms_norm(x, w, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn(spec: dict, fault=None):
    """The rotary frequencies ``(rope / 2,)``, what multiplies cos and sin,
    and what multiplies the scores."""
    d, theta = spec["rope_dim"], _get(spec, "rope_theta")
    plain = (spec["nope"] + d) ** -0.5
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if fault == "no_yarn":
        return f.astype(np.float32), 1.0, plain
    factor, length = _get(spec, "rope_factor"), _get(spec, "rope_original")

    def pair(turns):
        return d * np.log(length / (2 * np.pi * turns)) / (2 * np.log(theta))

    low = max(np.floor(pair(_get(spec, "beta_fast"))), 0)
    high = min(np.ceil(pair(_get(spec, "beta_slow"))), d - 1)
    r = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)

    def m(a):
        return 0.1 * a * np.log(factor) + 1.0 if factor > 1 else 1.0

    return ((f * (1 - r) + f / factor * r).astype(np.float32),
            m(_get(spec, "mscale")) / m(_get(spec, "mscale_all_dim")),
            plain * m(_get(spec, "mscale_all_dim")) ** 2)


def rotate(x, inv_freq, factor):
    """Rotate-half rotary embedding of ``x (T, heads, width)`` over its whole
    width, the token's index as its position."""
    import jax.numpy as jnp
    t, width = x.shape[0], x.shape[-1]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    half = jnp.concatenate([-x[..., width // 2:], x[..., :width // 2]],
                           axis=-1)
    return (x * jnp.cos(angle) + half * jnp.sin(angle)) * factor


def hyper(streams: list, layer: dict, name: str, spec: dict, w, fault):
    """A sublayer's coefficients from the token's ``streams`` (a list of ``n``
    ``(T, D)``): ``H_pre (T, n)``, ``H_post (T, n)``, ``H_res (T, n, n)``."""
    import jax
    import jax.numpy as jnp
    n, t = len(streams), streams[0].shape[0]
    if fault == "plain_residual":
        first = jnp.zeros((t, n)).at[:, 0].set(1.0)
        return first, first, jnp.broadcast_to(jnp.eye(n), (t, n, n))
    x = jnp.concatenate(streams, axis=-1)
    x = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                     + _get(spec, "rms_eps"))
    raw = x @ w(layer[name + "_phi"])
    alpha, bias = layer[name + "_alpha"], layer[name + "_bias"]
    h_pre = jax.nn.sigmoid(alpha[0] * raw[:, :n] + bias[:n])
    h_post = jax.nn.sigmoid(alpha[1] * raw[:, n:2 * n] + bias[n:2 * n]) * (
        1.0 if fault == "post_unscaled" else 2.0)
    clamp, eps = _get(spec, "hc_clamp"), _get(spec, "hc_eps")
    m = jnp.exp(jnp.clip(alpha[2] * raw[:, 2 * n:] + bias[2 * n:], -clamp,
                         clamp)).reshape(t, n, n)
    for _ in range(1 if fault == "sinkhorn_1"
                   else _get(spec, "sinkhorn_iters")):
        m = m / (m.sum(axis=2, keepdims=True) + eps)
        m = m / (m.sum(axis=1, keepdims=True) + eps)
    return h_pre, h_post, m


def around(streams: list, layer: dict, name: str, spec: dict, w, fault, f):
    """``X' = H_res X + H_postᵀ F(H_pre X)`` for the sublayer ``f``."""
    n = len(streams)
    h_pre, h_post, h_res = hyper(streams, layer, name, spec, w, fault)
    y = f(sum(h_pre[:, i:i + 1] * streams[i] for i in range(n)))
    return [sum(h_res[:, i, j:j + 1] * streams[j] for j in range(n))
            + h_post[:, i:i + 1] * y for i in range(n)]


def mixer(u, layer: dict, spec: dict, w, fault):
    """Latent attention over the whole sequence ``u (T, D)``, nothing
    absorbed and nothing cached."""
    import jax
    import jax.numpy as jnp
    t = u.shape[0]
    heads, r, nope = spec["heads"], spec["kv_rank"], spec["nope"]
    eps = _get(spec, "rms_eps")
    inv_freq, factor, scale = yarn(spec, fault)
    h = rms_norm(u, w(layer["norm_in"]), eps)
    c_q = rms_norm(h @ w(layer["w_dq"]), w(layer["norm_q"]), eps)
    q = (c_q @ w(layer["w_uq"])).reshape(t, heads, -1)
    q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], inv_freq, factor)
    kv = h @ w(layer["w_dkv"])
    c_kv = rms_norm(kv[:, :r], w(layer["norm_kv"]), eps)
    k_r = rotate(kv[:, None, r:], inv_freq, factor)[:, 0]
    causal = jnp.asarray(np.tril(np.ones((t, t), bool)))
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
    return jnp.concatenate(out, axis=1).reshape(t, -1) @ w(layer["w_o"])


def route(h, router, bias, k: int, scale: float, fault=None):
    """``h (T, D)`` → the K experts of each row ``(T, K)`` — the largest of
    sigmoid score + bias, a tie to the lower index — and their weights ``(T,
    K)``: the scores without the bias, divided by their sum, times ``scale``."""
    import jax
    logits = h @ router
    s = np.asarray(jax.nn.softmax(logits, axis=-1) if fault
                   == "softmax_routing" else jax.nn.sigmoid(logits))
    experts = np.argsort(-(s + np.asarray(bias)[None]), axis=-1,
                         kind="stable")[:, :k]
    weights = np.take_along_axis(s, experts, axis=-1)
    return experts, weights / weights.sum(axis=-1, keepdims=True) * scale


def swiglu(x, gate, up, down):
    import jax
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def ffn(u, layer: dict, spec: dict, dense: bool, w, fault):
    import jax.numpy as jnp
    h = rms_norm(u, w(layer["norm_post"]), _get(spec, "rms_eps"))
    if dense:
        return swiglu(h, w(layer["m_gate"]), w(layer["m_up"]),
                      w(layer["m_down"]))
    experts, weights = route(h, w(layer["router"]), layer["router_bias"],
                             spec["experts_per_token"],
                             _get(spec, "route_scale"), fault)
    y = jnp.zeros_like(h)
    for e in range(spec["experts"]):
        rows, col = np.nonzero(experts == e)
        if not rows.size:
            continue
        pad = -rows.size % ROW_PAD
        p = jnp.asarray(np.pad(weights[rows, col], (0, pad)))  # padding: 0
        rows = np.pad(rows, (0, pad))
        out = swiglu(h[rows], w(layer["w_gate"][e]), w(layer["w_up"][e]),
                     w(layer["w_down"][e]))
        y = y.at[rows].add(out * p[:, None])
    if fault != "no_shared":
        y = y + swiglu(h, w(layer["s_gate"]), w(layer["s_up"]),
                       w(layer["s_down"]))
    return y


def forward(raw: dict, spec: dict, tokens, fault: str | None = None,
            first: int = 0):
    """Logits ``(T − first, V)`` of the positions from ``first`` of one
    sequence of token ids ``(T,)`` under the parameter tree ``raw``
    (``params["params"]`` of the family, any float dtype). ``fault`` computes a wrong model on purpose, to show what the
    limits catch: ``float8`` (every weight through float8_e4m3: the nearest
    precision below bfloat16), ``sinkhorn_1`` (one iteration), ``plain_residual``
    (``H_res`` = I, ``H_pre`` = ``H_post`` = e_0: the block every other family
    has), ``post_unscaled`` (``H_post`` without its factor 2), ``stream_0``
    (the final sum replaced by stream 0), ``no_yarn`` (plain θ and scale),
    ``softmax_routing``, ``no_shared`` (the shared expert left out)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32

    def w(a):
        if fault == "float8":
            a = a.astype(jnp.float8_e4m3fn)
        return a.astype(f32)

    with jax.default_matmul_precision("highest"):
        e = w(raw["embed"][jnp.asarray(tokens)])
        streams = [e] * _get(spec, "streams")
        for i in range(spec["depth"]):
            layer = raw[f"layer{i}"]
            streams = around(streams, layer, "hc_attn", spec, w, fault,
                             lambda u: mixer(u, layer, spec, w, fault))
            streams = around(
                streams, layer, "hc_ffn", spec, w, fault,
                lambda u: ffn(u, layer, spec, i < _get(spec, "dense_layers"),
                              w, fault))
        x = (streams[0] if fault == "stream_0" else sum(streams))[first:]
        return np.asarray(rms_norm(x, w(raw["norm_f"]), _get(spec, "rms_eps"))
                          @ w(raw["lm_head"]))


# -- the comparison ------------------------------------------------------------

NOT_MODEL_KEYS = ("family", "name", "max_len", "maximum_concurrent_requests",
                  "async_path", "eos_id")


def prepare(config: dict, pre: dict) -> dict:
    from ai4e_tpu.models.xing4 import create_xing4_lm   # VALUES only
    spec = _model_spec(config)
    _, variables = create_xing4_lm(
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
