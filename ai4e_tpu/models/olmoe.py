"""OLMoE — a sparse-expert decoder LM for the decode engine.

The block of allenai/OLMoE-1B-7B (``transformers``' ``modeling_olmoe.py``),
written from its equations. For hidden ``x``:

- ``RMSNorm(x) = x · rsqrt(mean(x²) + eps) · g``, in float32, cast back;
- layer: ``x = x + Attn(RMSNorm_in(x))``; ``x = x + MoE(RMSNorm_post(x))``;
- ``Attn``: ``q = RMSNorm_q(x W_q)``, ``k = RMSNorm_k(x W_k)`` — each norm
  over the WHOLE projection, before the split into heads — ``v = x W_v``; no
  bias; rotary embedding on q and k (rotate-half, ``inv_freq = θ^(−2i/hd)``,
  position = the token's index); causal softmax attention, scale
  ``1/√hd``, softmax in float32; ``W_o``. The cache holds k AFTER norm and
  rotation;
- ``MoE``: ``p = softmax(x W_r)`` over the experts in float32; the
  ``experts_per_token`` largest ``p`` and their experts, the weights NOT
  renormalised; ``y = Σ_e p_e · W_down,e(silu(W_gate,e x) ⊙ W_up,e x)``;
- final ``RMSNorm``, an untied ``lm_head``, greedy argmax on the device.

Dispatch (``models/experts.py``, its ``dense`` form, which holds all the
experts here), one form for both programs: every expert computes every row and
the rows' un-chosen experts are multiplied by zero before the down
projection, so ``y = (silu(x W_gate) ⊙ x W_up ⊙ P) W_down`` with ``P`` the
(rows, experts) matrix that holds a row's ``experts_per_token`` weights and
zeros. No capacity, no drop, no gather or scatter of rows. At the decode
step it is the least work there is: with 32 slots × 8 picks over 64 experts
nearly every expert is touched (an expert is idle with probability
0.875^32 ≈ 1.4 %), each expert's weights are read exactly once a layer and
the step is bound by that read, not by the 8 × more multiplies. At prefill
it spends 8 × the multiplies a sorted, grouped form would (``ROADMAP.md``
Speed 12 is that form); nothing is dropped either way.

The entry points of an LM family (``runtime/kvcache.py`` ``LMServable``
calls them by name): ``prefill``, ``decode_step``, ``cache_spec`` (K/V
only: the ``state`` it is handed and hands back is empty);
``step_report`` reads what ``decode_step`` appends to its ids. The K/V
pool, the attention over it and its writes are ``ops/kv_pool.py``'s.
Weights and cache are ``dtype`` (bfloat16 as served), accumulation float32.
"""

from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops import kv_pool
from . import experts as expert_layer


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _seeded(key, shape, dtype, exponent, center):
    """``center + t · 2^exponent`` with ``t`` the sum of two random bytes
    less 255 (triangular on [-255, 255], standard deviation 104.5).

    Integer arithmetic on threefry bits, one exact conversion and one
    rounding: every backend draws these values bit for bit, so the plain
    reference on the CPU holds the weights the chip serves. (A normal draw
    goes through ``erf_inv``, which backends round differently.)"""
    half = (*shape[:-1], (shape[-1] + 1) // 2)   # two values a 32-bit word
    words = jax.random.bits(key, half, jnp.uint32)

    def byte(shift):
        return ((words >> shift) & 0xFF).astype(jnp.int32)

    t = jnp.concatenate([byte(0) + byte(8), byte(16) + byte(24)],
                        axis=-1)[..., :shape[-1]] - 255
    return (center + t.astype(jnp.float32) * 2.0 ** exponent).astype(dtype)


def seeded(gain: float, center: float = 0.0, fan_in_axis: int | None = -2):
    """An initializer of standard deviation about ``gain / sqrt(fan_in)``
    around ``center``: the power of two nearest that, times ``_seeded``'s
    triangular draw. ``fan_in_axis=None``: no fan-in (scales, embeddings)."""
    def init(key, shape, dtype=jnp.float32):
        fan_in = 1 if fan_in_axis is None else shape[fan_in_axis]
        exponent = round(float(np.log2(gain / (104.5 * np.sqrt(fan_in)))))
        return _seeded(key, tuple(shape), jnp.dtype(dtype), exponent, center)
    return init


def norm_scale(center: float):
    """A norm's scales: ``center`` ± 0.25, so that none is 1."""
    return seeded(0.1, center, fan_in_axis=None)


# The seeded init's gains (``create_olmoe_lm`` says why these): the q/k
# norm scales' centre, and the deviation of ``wo``'s, the router's and
# ``w_down``'s outputs per unit of input.
INIT_GAINS = {"qk_scale": 1.5, "wo": 0.25, "router": 2.0, "w_down": 0.4}


def rms_norm(x, scale, eps):
    h = x.astype(jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return (h * scale.astype(jnp.float32)).astype(x.dtype)


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's rotary frequencies of a rotated width ``dim``, ``(dim / 2,)``
    float32: ``f_i = θ^(−2i/dim)`` kept where a pair turns more than
    ``beta_fast`` times over the ``original`` positions, divided by
    ``factor`` where it turns fewer than ``beta_slow`` times, blended
    linearly between (``r_i`` from 0 at pair ``low`` to 1 at ``high``)."""
    def pair(turns):   # the pair that turns ``turns`` times over ``original``
        return (dim * np.log(original / (2 * np.pi * turns))
                / (2 * np.log(theta)))

    low = max(int(np.floor(pair(beta_fast))), 0)
    high = min(int(np.ceil(pair(beta_slow))), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / dim)
    r = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f * (1.0 - r) + f / factor * r).astype(np.float32)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's ``m(a) = 0.1 a ln(factor) + 1`` (1 where nothing is scaled)."""
    return 0.1 * mscale * float(np.log(factor)) + 1.0 if factor > 1 else 1.0


def rope(x, position, theta, inv_freq=None, interleave: bool = False):
    """Rotary embedding of ``x (..., heads, head_dim)`` at ``position
    (...)``, in float32, cast back: pair ``i`` is the lanes ``(i, i + half)``
    (rotate-half) or, where ``interleave``, the neighbours ``(2i, 2i + 1)``.
    ``inv_freq (head_dim / 2,)``, where a family scales its frequencies
    (``yarn_inv_freq``), stands in for ``theta``'s own."""
    half = x.shape[-1] // 2
    if inv_freq is None:
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = position.astype(jnp.float32)[..., None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    h = x.astype(jnp.float32)
    if interleave:
        a, b = h[..., 0::2], h[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape).astype(x.dtype)
    a, b = h[..., :half], h[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _dot(eq, a, b):
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


class _OlmoeLayer(nn.Module):
    dim: int
    heads: int
    experts: int
    experts_per_token: int
    expert_dim: int
    eps: float
    theta: float
    dtype: jnp.dtype

    def setup(self):
        d, e, f = self.dim, self.experts, self.expert_dim

        def p(name, init, *shape):
            return self.param(name, init, shape, self.dtype)

        g = INIT_GAINS
        scale = norm_scale
        self.norm_in = p("norm_in", scale(1.0), d)
        self.norm_post = p("norm_post", scale(1.0), d)
        self.norm_q = p("norm_q", scale(g["qk_scale"]), d)
        self.norm_k = p("norm_k", scale(g["qk_scale"]), d)
        self.wq = p("wq", seeded(1.0), d, d)
        self.wk = p("wk", seeded(1.0), d, d)
        self.wv = p("wv", seeded(1.0), d, d)
        self.wo = p("wo", seeded(g["wo"]), d, d)
        self.router = p("router", seeded(g["router"]), d, e)
        self.w_gate = p("w_gate", seeded(1.0), e, d, f)
        self.w_up = p("w_up", seeded(1.0), e, d, f)
        self.w_down = p("w_down", seeded(g["w_down"]), e, f, d)

    def _qkv(self, x, position):
        """``x (..., D)`` at ``position (...)`` → q, k, v ``(..., H, hd)``,
        q and k normed then rotated."""
        h = rms_norm(x, self.norm_in, self.eps)
        with jax.named_scope("qk_norm"):
            q = rms_norm(_dot("...d,de->...e", h, self.wq).astype(self.dtype),
                         self.norm_q, self.eps)
            k = rms_norm(_dot("...d,de->...e", h, self.wk).astype(self.dtype),
                         self.norm_k, self.eps)
        v = _dot("...d,de->...e", h, self.wv).astype(self.dtype)
        split = (*x.shape[:-1], self.heads, self.dim // self.heads)
        with jax.named_scope("rope"):
            q = rope(q.reshape(split), position, self.theta)
            k = rope(k.reshape(split), position, self.theta)
        return q, k, v.reshape(split)

    def route(self, h):
        """``h (..., D)`` (after ``norm_post``) → the chosen experts
        ``(..., K)`` and ``P (..., E)``: each row's K un-normalised weights
        at its experts' columns, zero elsewhere (``models/experts.py``)."""
        top_e, top_p = expert_layer.route(h, self.router, self.experts_per_token)
        return top_e, expert_layer.gate_matrix(top_e, top_p, self.experts)

    def _moe(self, x):
        h = rms_norm(x, self.norm_post, self.eps)
        top_e, gate = self.route(h)
        return x + expert_layer.dense(h, gate, self.w_gate, self.w_up,
                                      self.w_down), top_e

    def prefill(self, x, mask):
        """x: (B, P, D); mask: (B, P) valid-token mask. Returns
        ``(y, k, v)`` with k/v of shape (B, P, H, hd)."""
        b, p, _ = x.shape
        q, k, v = self._qkv(x, jnp.broadcast_to(jnp.arange(p), (b, p)))
        o = kv_pool.prefill_attention(q, k, v, mask)
        x = x + _dot("...d,de->...e", o.reshape(x.shape),
                     self.wo).astype(self.dtype)
        x, _ = self._moe(x)
        return x, k, v

    def step(self, x, k_pool, v_pool, layer, position, bound):
        """One token per slot against the pool: x (S, D), attending this
        block's ``layer`` of it as ``kv_pool.decode_attention`` says.
        Returns ``(y, k_new, v_new, experts)`` with k_new/v_new (S, H, hd)
        — the rows ``OlmoeLM.decode_step`` stores — and ``experts`` (S, K)
        the slot's chosen experts."""
        q, k_new, v_new = self._qkv(x, position)
        o = kv_pool.decode_attention(q, k_new, v_new, k_pool, v_pool, layer,
                                     position, bound)
        x = x + _dot("sd,de->se", o.reshape(x.shape),
                     self.wo).astype(self.dtype)
        x, experts = self._moe(x)
        return x, k_new, v_new, experts


class OlmoeLM(nn.Module):
    """Causal LM over the OLMoE block stack, with the two serving entry
    points of an LM family (``prefill`` returns ``kv_pool.prompt_block``s,
    ``decode_step`` takes and returns the pool). ``decode_step`` returns
    its ids followed by every layer's chosen experts, in one int32 vector,
    so the routing counters ride the fetch the step makes anyway
    (``step_report``)."""

    vocab_size: int
    dim: int = 64
    depth: int = 2
    heads: int = 4
    experts: int = 8
    experts_per_token: int = 2
    expert_dim: int = 32
    rms_eps: float = 1e-5
    rope_theta: float = 10000.0
    dtype: jnp.dtype = jnp.bfloat16

    def setup(self):
        self.embed = self.param("embed", seeded(1.0, fan_in_axis=None),
                                (self.vocab_size, self.dim), self.dtype)
        self.layers = [_OlmoeLayer(
            self.dim, self.heads, self.experts, self.experts_per_token,
            self.expert_dim, self.rms_eps, self.rope_theta, self.dtype,
            name=f"layer{i}") for i in range(self.depth)]
        self.norm_f = self.param("norm_f", norm_scale(1.0), (self.dim,),
                                 self.dtype)
        self.lm_head = self.param("lm_head", seeded(1.0),
                                  (self.dim, self.vocab_size), self.dtype)

    @nn.nowrap
    def cache_spec(self):
        """What a slot holds (``kv_pool.SlotSpec``): K/V of every layer."""
        return kv_pool.kv_slot(
            self.depth, self.heads, self.dim // self.heads, self.dtype)

    def _logits(self, h):
        with jax.named_scope("head"):
            return _dot("...d,dv->...v",
                        rms_norm(h, self.norm_f, self.rms_eps), self.lm_head)

    def _prefill(self, tokens, length):
        with jax.named_scope("embedding"):
            h = self.embed[tokens]
        mask = jnp.arange(tokens.shape[1])[None, :] < length[:, None]
        ks, vs = [], []
        for layer in self.layers:
            h, k, v = layer.prefill(h, mask)
            ks.append(k)
            vs.append(v)
        return h, kv_pool.prompt_block(ks), kv_pool.prompt_block(vs)

    def _step(self, tokens, k_cache, v_cache, position, bound):
        with jax.named_scope("embedding"):
            h = self.embed[tokens]
        k_rows, v_rows, experts = [], [], []
        for i, layer in enumerate(self.layers):
            h, k, v, e = layer.step(h, k_cache, v_cache, i, position, bound)
            k_rows.append(k)
            v_rows.append(v)
            experts.append(e)
        k_cache, v_cache = kv_pool.write_rows(
            (k_cache, v_cache), (k_rows, v_rows), position)
        return h, k_cache, v_cache, jnp.stack(experts)

    def prefill(self, tokens, length):
        h, k, v = self._prefill(tokens, length)
        last = jnp.take_along_axis(
            h, (length - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        return (jnp.argmax(self._logits(last), axis=-1).astype(jnp.int32),
                k, v, {})

    def decode_step(self, tokens, k_cache, v_cache, state, position,
                    bound=None):
        """One token for every slot of the pool. Attention reads the cached
        positions ``< bound`` (a Python int, static under jit; default the
        whole cache), which must be ``>=`` the largest position of a slot
        whose output is read (``kv_pool.decode_attention``)."""
        h, k_cache, v_cache, experts = self._step(tokens, k_cache, v_cache,
                                                  position, bound)
        ids = jnp.argmax(self._logits(h), axis=-1).astype(jnp.int32)
        return (jnp.concatenate([ids, experts.astype(jnp.int32).reshape(-1)]),
                k_cache, v_cache, state)

    # Logits, for tests only: the serving programs ship ids.

    def prefill_logits(self, tokens, length):
        h, k, v = self._prefill(tokens, length)
        return self._logits(h), k, v

    def decode_logits(self, tokens, k_cache, v_cache, position, bound=None):
        h, k_cache, v_cache, _ = self._step(tokens, k_cache, v_cache,
                                            position, bound)
        return self._logits(h), k_cache, v_cache

    # What ``step_report`` returns (``experts.step_report_series``): every
    # expert is held here, so no share of the picks.
    step_report_series = {
        name: expert_layer.step_report_series[name]
        for name in ("experts_touched", "expert_peak_load")}

    @nn.nowrap
    def step_report(self, extra: np.ndarray, active) -> dict[str, float]:
        """What ``decode_step`` appended to its ids, over the LIVE slots:
        a MoE layer's experts with at least one live token, and its fullest
        expert's tokens over the mean load (live × K ÷ E), each the mean
        over the layers of this step."""
        live = np.flatnonzero(active)
        if not live.size:
            return {}
        picks = extra.reshape(self.depth, -1, self.experts_per_token)[:, live]
        report = expert_layer.load_report(picks, self.experts, self.experts)
        return {name: report[name] for name in self.step_report_series}


def create_olmoe_lm(rng=None, vocab_size: int = 512, dim: int = 64,
                    depth: int = 2, heads: int = 4, experts: int = 8,
                    experts_per_token: int = 2, expert_dim: int = 32,
                    rms_eps: float = 1e-5, rope_theta: float = 10000.0,
                    dtype=jnp.bfloat16):
    """Build the LM and its seeded params (``seeded``: the same values on
    every backend). Every norm scale is drawn away from 1, so a scale left
    out shows. The gains make random weights behave as trained ones do
    where it matters to a comparison with a float32 reference: q/k scales
    near 1.5 give scores that deviate by ~2, so attention picks tokens;
    router logits deviate by ~2, so a token's K weights run from ~0.3 down
    to ~0.02 and sum to ~0.8 (renormalising them shows, and which expert is
    the K-th — the one choice rounding can flip — matters little); each
    attention and expert block adds about a fifth of the residual stream's
    size, as in a trained network. At the published widths and eight layers
    that keeps the model in the regime where an error grows in proportion
    to its cause: bfloat16 rounding moves the logits by ~0.07 of their
    deviation of 1.2, float8 weights by ~0.75, a missing norm or an
    un-rotated key by 1.5-3 (``benchmark/references/olmoe.py``). With
    blocks as large as the stream (the first gains tried) the same rounding
    moved them by 1.3: eight layers of a random network that rewrites its
    stream amplify anything, and no margin separates a fault from
    rounding."""
    if dim % heads or (dim // heads) % 2:
        raise ValueError(f"dim {dim} must split into {heads} even heads")
    if not 0 < experts_per_token <= experts:
        raise ValueError(f"{experts_per_token} experts a token of {experts}")
    model = OlmoeLM(vocab_size=vocab_size, dim=dim, depth=depth, heads=heads,
                    experts=experts, experts_per_token=experts_per_token,
                    expert_dim=expert_dim, rms_eps=rms_eps,
                    rope_theta=rope_theta, dtype=jnp.dtype(dtype))
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    # One program: the forward pass that names the parameters is dead code
    # in it, and 3.6 G parameters are not drawn one small program each.
    params = jax.jit(partial(model.init, method="prefill"))(
        rng, np.zeros((1, 8), np.int32), np.ones((1,), np.int32))
    return model, params
