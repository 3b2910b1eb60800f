"""Plain reference for the ``axk1`` family (skt/A.X-K1's block), the comparison
that decides ``correct`` for its cells, and the decode step's and the
prefill's operation and byte counts.

The forward pass is written from the configuration's equations, for a token's
hidden ``x`` (``D`` wide), ``n(x) = w ⊙ x / √(mean(x²) + eps)``:

    x = embedding of the token
    layer i:  x = x + Mixer(n_in(x));  x = x + FFN_i(n_post(x))
    Mixer (latent attention, h = n_in(x)):
          c_q = n_q(h W_dq);  [q_nope | q_rope]_head = c_q W_uq
          [c_kv | k_r] = h W_dkv;  c_kv ← n_kv(c_kv)
          q_rope and k_r rotated (rotate-half over their whole width, the
          frequencies below), k_r ONE head that every head shares
          k_nope,head = c_kv W_uk,head;  v_head = c_kv W_uv,head
          causal softmax of (q_nope·k_nope + q_rope·k_r) · s;  W_o
    YaRN: f_i = θ^(−2i/d);  low = ⌊d ln(L/(2π β_fast)) / (2 ln θ)⌋,  high =
          ⌈d ln(L/(2π β_slow)) / (2 ln θ)⌉;  r_i = clip((i − low)/(high −
          low), 0, 1);  inv_freq_i = f_i (1 − r_i) + f_i r_i / factor;
          m(a) = 0.1 a ln(factor) + 1;  cos, sin × m(mscale)/m(mscale_all_dim);
          s = m(mscale_all_dim)² / √(nope + rope)
    FFN:  the first dense_layers a SwiGLU;  the others s = sigmoid(h W_r)
          over ALL the experts, the K largest s (a tie to the lower index; no
          bias and, as the cell reads ``topk_method: "none"``, no group
          limit), weights s_e / Σ_picks s × route_scale, the terms of the
          experts HELD here Σ_e w_e Expert_e(h), + Expert_shared(h)
    logits = n_f(x) W_head

in plain ``jax.numpy``, float32, ``highest`` matmul precision: every head's
``k_nope`` and ``v`` built from ``c_kv`` (nothing absorbed), the experts by a
plain loop over the rows that chose each — no cache, no kernel; a head at a
time and queries a block at a time, and of the last layer only the rows whose
logits are asked for, which is only what the time and the memory of a stream
of thousands of positions need; and no import from ``ai4e_tpu.models``
beyond ``create_axk1_lm`` for the parameter VALUES: the same bfloat16 values
the worker serves (the family's seeded init is integer arithmetic on threefry
bits, so the CPU draws them bit for bit). Nothing is shared with
``references/xing4.py``: the latent mixer the two PROGRAMS share is written
here a second time, so an edit to one reference cannot move the other's
verdict. Departures from the published model: seeded weights; the share of
layers, experts and vocabulary the configuration states; nothing else.

The API returns greedy token ids only, and with random weights an argmax flips
on rounding. So the reference is teacher-forced on prompt + served tokens, and
each served token's reference logit must lie within LOGIT_MARGIN of that
position's reference maximum, all but SHARE_LIMIT of them (all but one, of a
stream so short that the share is less than one token) within SHARE_MARGIN.
"""

from __future__ import annotations

import functools

import numpy as np

# Reason for the two limits: see MARGIN_MEASURED and FAULTS_MEASURED. The
# worker computes in bfloat16 with float32 accumulation, holds its residual
# and reads its cache in bfloat16 and decodes in the absorbed form: its logits
# differ from this float32 forward by rounding, and now and then rounding
# picks another eighth expert (which matters here one time in eight: when the
# expert that came or the one that went is one of the twelve held). A run is
# `not correct` by either limit.
LOGIT_MARGIN = 1.0
SHARE_MARGIN, SHARE_LIMIT = 0.05, 0.04
MARGIN_MEASURED = (
    "on the chip's served streams (my chip runs, PR 56; benchmark/sweeps/"
    "axk1.history.md has every line): the two traced runs, the knee sweep's "
    "five runs, three sets of six at the cell's rate and four long streams "
    "served outside a run (704 + 512, 3,840 + 640, 5,632 + 384 and 13,824 + "
    "256 tokens) - 29 streams of 210-1,024 served tokens after prompts of "
    "540-13,824, 14,500 tokens in all: worst margin 0.000-0.347, share "
    "beyond 0.05 0-1.56 % (the largest 8 of 512), argmax agreement 93.2-100 "
    "%: a thin tail of expert flips, no drift with the context (the 14 k "
    "stream reads 0.087 and 0.39 %, the four checked streams of 5,011-5,702 "
    "tokens 0.001-0.261 and 0-0.75 %). The share limit 4 % has 2.6 x over "
    "the largest sound stream and 2.0 x under the mildest control "
    "(group_limited's 8.0 %); the margin limit 1.0 has 2.9 x over the "
    "largest 0.347 and lies under every fault that rewrites attention "
    "(1.00-1.64). No gain of the seeded init was re-scaled after a chip run "
    "and neither limit moved after a reading: the first run read `correct`")
FAULTS_MEASURED = (
    "check(fault=...) on two SERVED streams of the chip (sweeps/longstream.py "
    "serve axk1.history 2560000301 0:5632:384 1:704:512: the sound system's "
    "own ids, the reference computed wrongly; PR 56), as worst margin | "
    "tokens beyond 0.05 | argmax agreement, the 6,016-token stream (15 of 384 "
    "allowed) / the 1,216-token one (20 of 512). Sound: 0.225 | 3 (0.8 %) | "
    "97.1 % / 0.192 | 8 (1.6 %) | 94.7 %. `ok` false by BOTH limits on both: "
    "no_yarn 1.152 | 52 (13.5 %) | 86.5 % / 1.642 | 305 (59.6 %) | 38.7 %; "
    "absorbed_scale 1.003 | 52 (13.5 %) | 86.5 % / 1.634 | 321 (62.7 %) | "
    "30.5 %. By the share (and by the margin too on the short stream where "
    "it passes 1): float8 (the nearest precision below bfloat16) 0.472 | 131 "
    "(34.1 %) | 55.5 % / 1.352 | 249 (48.6 %) | 44.1 %; softmax_routing 0.376 "
    "| 61 (15.9 %) | 75.5 % / 1.200 | 78 (15.2 %) | 77.5 %; no_shared_expert "
    "0.897 | 48 (12.5 %) | 86.5 % / 0.698 | 192 (37.5 %) | 56.2 %; "
    "unscaled_routing 0.351 | 41 (10.7 %) | 84.6 % / 0.303 | 95 (18.6 %) | "
    "71.5 %; group_limited - the reading of n_group / topk_group the "
    "configuration does NOT take - 0.504 | 49 (12.8 %) | 78.9 % / 0.427 | 41 "
    "(8.0 %) | 84.4 %: the mildest that is caught, and caught on both. All "
    "seven read `ok` false on both streams")
FAULTS = ("no_yarn", "float8", "softmax_routing", "no_shared_expert",
          "unscaled_routing", "group_limited", "absorbed_scale")
# What ``group_limited`` reads the published ``n_group`` 8 / ``topk_group`` 4
# as (the reading the cell does NOT take): the choice inside the best 4 of 8
# groups of neighbours, a group scored by the sum of its two largest.
GROUP_LIMIT = (8, 4)
ROW_PAD = 64       # an expert's rows are padded to a multiple: few shapes
QUERY_BLOCK = 1024   # queries a call of ``_attend``


def _model_spec(config: dict) -> dict:
    return next(m for m in config["models"]["models"]
                if m["family"] == "axk1")


# The fields a models spec may leave to the program's defaults.
DEFAULTS = {"rope_theta": 1e4, "rope_factor": 32.0, "rope_original": 4096,
            "beta_fast": 32.0, "beta_slow": 1.0, "mscale": 1.0,
            "mscale_all_dim": 1.0, "route_scale": 2.5, "rms_eps": 1e-6,
            "dense_layers": 1, "first_expert": 0, "route_groups": None}


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


def ffn_params(spec: dict, dense: bool, experts: float | None = None) -> float:
    """A layer's FFN: a dense one whole; an expert layer's router (at its
    published width), shared expert and ``experts`` routed ones (None: the
    ``experts_held`` held here)."""
    d = spec["dim"]
    if dense:
        return 3 * d * spec["mlp_dim"]
    e = spec["experts_held"] if experts is None else experts
    return (d * spec["experts"] + 3 * e * d * spec["expert_dim"]
            + 3 * d * spec["shared_dim"])


def _layers(spec: dict, experts: float | None = None) -> float:
    """The layers' parameters: mixer, FFN and the two norms of each."""
    dense = _get(spec, "dense_layers")
    return sum(mixer_params(spec) + 2 * spec["dim"]
               + ffn_params(spec, i < dense, experts)
               for i in range(spec["depth"]))


def weight_bytes(spec: dict) -> int:
    """What a decode step reads of the weights, bfloat16: per layer the
    mixer, the FFN (ALL the held experts: the step's ``dense`` product reads
    them) and the two norms; the head and the final norm. Not the embedding
    table: a step reads one row a slot."""
    d = spec["dim"]
    return int(2 * (_layers(spec) + d * spec["vocab_size"] + d))


def _met(spec: dict) -> float:
    """Experts a token meets here where the router spreads evenly."""
    return spec["experts_per_token"] * spec["experts_held"] / spec["experts"]


def row_bytes(spec: dict) -> int:
    """A cached position of one layer as published: ``[c_kv | k_r]``,
    bfloat16, no padding."""
    return 2 * (spec["kv_rank"] + spec["rope_dim"])


def ops_and_bytes(config: dict, slots: int,
                  live_tokens: float) -> tuple[float, float]:
    """One decode step over the pool, by the published mathematics. Operations
    = 2 x (the mixers' and the FFNs' weights — of the experts the K x held /
    total a token meets here — + the head) per slot + per live slot and layer
    the absorbed attention over its cached positions (2·H·(2·r_kv + rope)).
    Least bytes = every held weight once + one embedding row a slot + per live
    slot its cached rows as published (576 lanes, no padding), once a layer +
    one row a slot written. ``live_tokens``: the cached positions of the live
    slots, summed."""
    spec = _model_spec(config)
    d, depth = spec["dim"], spec["depth"]
    per_slot = _layers(spec, _met(spec)) + d * spec["vocab_size"]
    flops = (2.0 * per_slot * slots + depth * 2.0 * spec["heads"]
             * (2 * spec["kv_rank"] + spec["rope_dim"]) * live_tokens)
    nbytes = (weight_bytes(spec) + 2 * d * slots
              + depth * row_bytes(spec) * (live_tokens + slots))
    return flops, float(nbytes)


def prefill_ops_and_bytes(config: dict, tokens: float, pairs: dict,
                          calls: float = 1.0) -> tuple[float, float]:
    """``calls`` prefills of ``tokens`` real tokens in all, by the PUBLISHED
    mathematics whatever form the program computes: 2 x (the mixers' and the
    FFNs' weights a token — of the experts the K x held / total it meets here)
    + 2 x the causal pairs a layer x H x (nope + rope + v); the head once a
    prefill. Least bytes: every held weight once a prefill + the residual of
    the real tokens read and written a sublayer + the rows they cache."""
    spec = _model_spec(config)
    depth, d = spec["depth"], spec["dim"]
    flops = 2.0 * (
        _layers(spec, _met(spec)) * tokens + d * spec["vocab_size"] * calls
        + depth * pairs.get("latent", 0.0) * spec["heads"]
        * (spec["nope"] + spec["rope_dim"] + spec["v_dim"]))
    return flops, float(weight_bytes(spec) * calls
                        + depth * (2 * 2 * 2 * d + row_bytes(spec)) * tokens)


# -- the forward pass ----------------------------------------------------------

def rms_norm(x, w, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn(spec: dict, fault=None):
    """The rotary frequencies ``(rope / 2,)``, what multiplies cos and sin,
    and what multiplies the scores. ``no_yarn``: plain θ and the plain scale;
    ``absorbed_scale``: YaRN's frequencies, the scores without ``m²``."""
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

    m_all = m(_get(spec, "mscale_all_dim"))
    return ((f * (1 - r) + f / factor * r).astype(np.float32),
            m(_get(spec, "mscale")) / m_all,
            plain if fault == "absorbed_scale" else plain * m_all ** 2)


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


def _attend(q, k, v, lo):
    """A block of one head's queries ``q (B, e)``, the first of them at
    position ``lo``, against the keys ``k (hi, e)`` and values ``v (hi, dv)``
    up to the block's end: the causal softmax of ``q kᵀ``, times ``v``."""
    import jax
    import jax.numpy as jnp
    rows = lo + jnp.arange(q.shape[0])[:, None]
    causal = jnp.arange(k.shape[0])[None, :] <= rows
    p = jax.nn.softmax(jnp.where(causal, q @ k.T, -jnp.inf), axis=-1)
    return p @ v


@functools.lru_cache(maxsize=None)
def _compiled_attend():
    """``_attend`` under ``jax.jit``: one program a block's shape, for every
    head, layer and stream of the process."""
    import jax
    return jax.jit(_attend)


def mixer(h, layer: dict, spec: dict, w, fault, first: int = 0):
    """Latent attention over the whole sequence ``h (T, D)`` (after
    ``n_in``), nothing absorbed and nothing cached, for the queries from
    ``first`` on ``(T − first, D)``: a head and a block of queries against
    every key up to the block's end at a time — ``_attend``, compiled once a
    block's shape so that the scores are not written out between its
    steps; that is only what time and memory need."""
    import jax.numpy as jnp
    t = h.shape[0]
    heads, r, nope = spec["heads"], spec["kv_rank"], spec["nope"]
    eps = _get(spec, "rms_eps")
    inv_freq, factor, scale = yarn(spec, fault)
    c_q = rms_norm(h @ w(layer["w_dq"]), w(layer["norm_q"]), eps)
    q = (c_q @ w(layer["w_uq"])).reshape(t, heads, -1)
    q = jnp.concatenate([q[..., :nope],
                         rotate(q[..., nope:], inv_freq, factor)], axis=-1)
    kv = h @ w(layer["w_dkv"])
    c_kv = rms_norm(kv[:, :r], w(layer["norm_kv"]), eps)
    k_r = rotate(kv[:, None, r:], inv_freq, factor)[:, 0]
    w_uk, w_uv = w(layer["w_uk"]), w(layer["w_uv"])
    attend = _compiled_attend()
    out = []
    for head in range(heads):
        k = jnp.concatenate([c_kv @ w_uk[:, head], k_r], axis=-1)
        v = c_kv @ w_uv[:, head]
        scaled = q[:, head] * scale
        out.append(jnp.concatenate([
            attend(scaled[lo:lo + QUERY_BLOCK], k[:lo + QUERY_BLOCK],
                   v[:lo + QUERY_BLOCK], lo)
            for lo in range(first, t, QUERY_BLOCK)], axis=0))
    return jnp.concatenate(out, axis=1) @ w(layer["w_o"])


def route(h, router, k: int, scale: float, groups=None, fault=None):
    """``h (T, D)`` → the K experts of each row ``(T, K)`` — the largest
    sigmoid scores, a tie to the lower index, inside the ``groups = (n,
    keep)`` limit where one is given — and their weights ``(T, K)``: the
    scores divided by their sum, times ``scale``."""
    import jax
    logits = h @ router
    s = np.asarray(jax.nn.softmax(logits, axis=-1) if fault
                   == "softmax_routing" else jax.nn.sigmoid(logits))
    choice = s
    if groups is not None:
        n, keep = groups
        per_group = s.reshape(s.shape[0], n, -1)
        best_two = np.sort(per_group, axis=-1)[..., -2:].sum(axis=-1)
        kept = np.argsort(-best_two, axis=-1, kind="stable")[:, :keep]
        allowed = np.zeros(best_two.shape, bool)
        np.put_along_axis(allowed, kept, True, axis=-1)
        choice = np.where(allowed[..., None], per_group,
                          -np.inf).reshape(s.shape)
    experts = np.argsort(-choice, axis=-1, kind="stable")[:, :k]
    weights = np.take_along_axis(s, experts, axis=-1)
    return experts, weights / weights.sum(axis=-1, keepdims=True) * scale


def swiglu(x, gate, up, down):
    import jax
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def ffn(h, layer: dict, spec: dict, dense: bool, w, fault, held=None):
    """``h (T, D)`` after ``n_post``. ``held = (first, count)`` overrides the
    configuration's share — the shared expert counted with the share that
    starts at 0 (the share test)."""
    import jax.numpy as jnp
    if dense:
        return swiglu(h, w(layer["m_gate"]), w(layer["m_up"]),
                      w(layer["m_down"]))
    base = _get(spec, "first_expert")     # the first expert of the weights
    first, count = held or (base, spec["experts_held"])
    chosen, weights = route(
        h, w(layer["router"]), spec["experts_per_token"],
        1.0 if fault == "unscaled_routing" else _get(spec, "route_scale"),
        GROUP_LIMIT if fault == "group_limited"
        else _get(spec, "route_groups"), fault)
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
                     w(layer["w_down"][at]))
        y = y.at[rows].add(out * p[:, None])
    if fault != "no_shared_expert" and (held is None or held[0] == 0):
        y = y + swiglu(h, w(layer["s_gate"]), w(layer["s_up"]),
                       w(layer["s_down"]))
    return y


def forward(raw: dict, spec: dict, tokens, fault: str | None = None,
            first: int = 0, held=None):
    """Logits ``(T − first, V)`` of the positions from ``first`` of one
    sequence of token ids ``(T,)`` under the parameter tree ``raw``
    (``params["params"]`` of the family, any float dtype). ``fault`` computes
    a wrong model on purpose, to show what the limits catch: ``no_yarn``
    (plain θ and scale), ``float8`` (every weight through float8_e4m3: the
    nearest precision below bfloat16), ``softmax_routing``,
    ``no_shared_expert``, ``unscaled_routing`` (the routed weights × 1 for ×
    ``route_scale``), ``group_limited`` (the choice inside the best 4 of 8
    groups: the reading of ``n_group`` / ``topk_group`` the configuration
    does not take), ``absorbed_scale`` (YaRN's frequencies, the scores
    without ``m²``). ``held = (first, count)``: another share of the experts
    (the share test)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    eps = _get(spec, "rms_eps")

    def w(a):
        if fault == "float8":
            a = a.astype(jnp.float8_e4m3fn)
        return a.astype(f32)

    with jax.default_matmul_precision("highest"):
        x = w(raw["embed"][jnp.asarray(tokens)])
        last = spec["depth"] - 1
        for i in range(spec["depth"]):
            layer = raw[f"layer{i}"]
            # nothing reads the last layer's rows before ``first``
            at = first if i == last else 0
            x = x[at:] + mixer(rms_norm(x, w(layer["norm_in"]), eps), layer,
                               spec, w, fault, at)
            x = x + ffn(rms_norm(x, w(layer["norm_post"]), eps), layer, spec,
                        i < _get(spec, "dense_layers"), w, fault, held)
        return np.asarray(rms_norm(x, w(raw["norm_f"]), eps)
                          @ w(raw["lm_head"]))


# -- the comparison ------------------------------------------------------------

NOT_MODEL_KEYS = ("family", "name", "max_len", "maximum_concurrent_requests",
                  "async_path", "eos_id")


def prepare(config: dict, pre: dict) -> dict:
    from ai4e_tpu.models.axk1 import create_axk1_lm   # VALUES only
    spec = _model_spec(config)
    _, variables = create_axk1_lm(
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
