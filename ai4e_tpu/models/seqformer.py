"""SeqFormer — long-context transformer encoder served with sequence
parallelism.

The reference has no sequence dimension anywhere (SURVEY.md §5 long-context:
its unit of work is one image tile); this model family fills the long-context
slot the TPU framework treats as first-class. Inputs are long feature
sequences — e.g. embedded acoustic-monitoring or satellite time series — of
shape ``(S, input_dim)`` with S in the tens of thousands; attention over them
is computed with **ring attention** (K/V blocks rotating over the mesh's
``sp`` axis via ``ppermute``) or **Ulysses all-to-all**
(``parallel/ring_attention.py``), so a sequence's O(S²) attention is sharded
S/n-per-device and the activations never materialise full S×S scores.

The attention strategy is injected as a plain callable: ``create_seqformer``
picks ring/Ulysses over the given mesh when its ``sp`` axis is >1 and plain
full attention otherwise, so the same module serves single-chip and
sequence-parallel deployments.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops import kv_pool


class SeqAttention(nn.Module):
    dim: int
    heads: int
    attn_fn: Callable  # (q, k, v) -> o, all (B, H, S, D)
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        head_dim = self.dim // self.heads
        qkv = nn.Dense(3 * self.dim, use_bias=False, dtype=self.dtype,
                       name="qkv")(x)
        qkv = qkv.reshape(b, s, 3, self.heads, head_dim)
        q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
        o = self.attn_fn(q, k, v)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, self.dim)
        return nn.Dense(d, use_bias=False, dtype=self.dtype, name="out")(o)


class SeqBlock(nn.Module):
    dim: int
    heads: int
    attn_fn: Callable
    mlp_ratio: int = 4
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        x = x + SeqAttention(self.dim, self.heads, self.attn_fn,
                             dtype=self.dtype, name="attn")(nn.LayerNorm()(x))
        h = nn.LayerNorm()(x)
        h = nn.Dense(self.dim * self.mlp_ratio, dtype=self.dtype,
                     name="mlp_up")(h)
        h = nn.gelu(h)
        h = nn.Dense(self.dim, dtype=self.dtype, name="mlp_down")(h)
        return x + h


class SeqFormer(nn.Module):
    """Encoder over (B, S, input_dim) float features — or, with
    ``vocab_size`` set, over (B, S) integer token ids — → (B, num_classes).

    Token mode is the production long-context wire: clients ship ids
    (2 bytes/token) and the embedding lookup happens on-device, instead of
    shipping pre-embedded S×D float features (128 bytes/token at D=64 f16
    — 524 kB/request at S=4096 against 8 kB of ids)."""

    seq_len: int
    input_dim: int
    dim: int = 128
    depth: int = 2
    heads: int = 8
    num_classes: int = 16
    attn_fn: Callable = None  # injected; None → full attention
    dtype: jnp.dtype = jnp.bfloat16
    vocab_size: int | None = None  # None → float features, else token ids

    @nn.compact
    def __call__(self, x):
        from ..parallel.ring_attention import reference_attention
        attn_fn = self.attn_fn or reference_attention
        if self.vocab_size is not None:
            h = nn.Embed(self.vocab_size, self.dim, dtype=self.dtype,
                         name="embed")(x)
        else:
            h = nn.Dense(self.dim, dtype=self.dtype, name="embed")(x)
        pos = self.param("pos_emb", nn.initializers.normal(0.02),
                         (1, self.seq_len, self.dim))
        h = h + pos.astype(self.dtype)
        for i in range(self.depth):
            h = SeqBlock(self.dim, self.heads, attn_fn, dtype=self.dtype,
                         name=f"block{i}")(h)
        h = nn.LayerNorm()(h.mean(axis=1))  # pool over the sequence
        return nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(h)


class _LMBlock(nn.Module):
    """One causal decoder block with the two attention entry points the
    serving runtime needs: ``prefill`` (full causal attention over the
    prompt, returning the K/V it computed) and ``step`` (one token per
    sequence against the K/V pool, returning the new token's K/V). Both
    run through the SAME parameters — ``setup`` instead of ``nn.compact``
    so the two methods share the module tree. The attention itself and
    everything about the pool are ``ops/kv_pool.py``'s."""

    dim: int
    heads: int
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        self.ln1 = nn.LayerNorm(name="ln1")
        self.qkv = nn.Dense(3 * self.dim, use_bias=False, dtype=self.dtype,
                            name="qkv")
        self.proj = nn.Dense(self.dim, use_bias=False, dtype=self.dtype,
                             name="proj")
        self.ln2 = nn.LayerNorm(name="ln2")
        self.mlp_up = nn.Dense(self.dim * 4, dtype=self.dtype, name="mlp_up")
        self.mlp_down = nn.Dense(self.dim, dtype=self.dtype, name="mlp_down")

    def _qkv(self, x):
        """``x (..., D)`` → q, k, v ``(..., H, hd)``."""
        qkv = self.qkv(self.ln1(x)).reshape(
            *x.shape[:-1], 3, self.heads, self.dim // self.heads)
        return qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]

    def prefill(self, x, mask):
        """x: (B, S, D); mask: (B, S) True on real tokens. Returns
        ``(y, k, v)`` with k/v of shape (B, S, H, hd) — the block's
        contribution to the sequence's KV cache."""
        q, k, v = self._qkv(x)
        o = kv_pool.prefill_attention(q, k, v, mask)
        x = x + self.proj(o.reshape(x.shape))
        x = x + self.mlp_down(nn.gelu(self.mlp_up(self.ln2(x))))
        return x, k, v

    def step(self, x, k_pool, v_pool, layer, position, bound):
        """One decode step over the slot pool. x: (S, D) — one new token
        per slot, attending this block's ``layer`` of the pool as
        ``kv_pool.decode_attention`` says. Returns ``(y, k_new, v_new)``
        with k_new/v_new of shape (S, H, hd) — the rows
        ``SeqFormerLM.decode_step`` stores."""
        q, k_new, v_new = self._qkv(x)
        o = kv_pool.decode_attention(q, k_new, v_new, k_pool, v_pool, layer,
                                     position, bound)
        x = x + self.proj(o.reshape(x.shape))
        with jax.named_scope("mlp"):
            x = x + self.mlp_down(nn.gelu(self.mlp_up(self.ln2(x))))
        return x, k_new, v_new


class SeqFormerLM(nn.Module):
    """Causal token LM over the SeqFormer block stack — the
    autoregressive serving shape (``runtime/decode.py``). Two entry
    points, applied via ``method=``:

    - ``prefill(tokens (B, P), length (B,))`` → ``(next-token ids (B,),
      k, v, state)`` — k/v the prompt's blocks (``kv_pool.prompt_block``),
      which the decode runtime (``runtime/kvcache.py``) inserts into a slot
      of the pool; ``state`` what else a slot holds: nothing here, ``{}``;
    - ``decode_step(tokens (S,), k, v, state, position (S,), bound=None)``
      → ``(next-token ids (S,), k, v, state)`` — k/v the pool
      (``ops/kv_pool.py``):
      ONE token for every slot of it per call, inactive slots riding along
      masked (their cache rows are garbage a later prefill overwrites).
      Every layer reads a slot as far as it has written
      (``kv_pool.decode_attention``); ``bound`` trims that read's grid to
      the cached positions ``< bound``. The row writes take the whole pool
      either way.

    Greedy decoding is computed on-device (argmax over the tied-embedding
    logits) so each step ships S int32s back to the host, not S×V logits.
    """

    vocab_size: int
    max_len: int
    dim: int = 64
    depth: int = 2
    heads: int = 4
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        self.embed = nn.Embed(self.vocab_size, self.dim, dtype=self.dtype,
                              name="embed")
        self.pos_emb = self.param("pos_emb", nn.initializers.normal(0.02),
                                  (self.max_len, self.dim))
        self.blocks = [_LMBlock(self.dim, self.heads, dtype=self.dtype,
                                name=f"block{i}") for i in range(self.depth)]
        self.ln_f = nn.LayerNorm(name="ln_f")

    @nn.nowrap
    def cache_spec(self):
        """What a slot holds (``kv_pool.SlotSpec``): K/V of every layer."""
        return kv_pool.kv_slot(
            self.depth, self.heads, self.dim // self.heads, jnp.float32)

    def _logits(self, h):
        # Tied embedding head: attend() reuses the embedding matrix, so
        # the LM head adds no parameters beyond the encoder families'.
        return self.embed.attend(self.ln_f(h).astype(jnp.float32)
                                 .astype(self.dtype))

    def prefill(self, tokens, length):
        b, p = tokens.shape
        with jax.named_scope("embedding"):
            h = (self.embed(tokens)
                 + self.pos_emb[None, :p].astype(self.dtype))
        mask = jnp.arange(p)[None, :] < length[:, None]
        ks, vs = [], []
        for blk in self.blocks:
            h, k, v = blk.prefill(h, mask)
            ks.append(k)
            vs.append(v)
        with jax.named_scope("head"):
            last = jnp.take_along_axis(
                h, (length - 1)[:, None, None].astype(jnp.int32),
                axis=1)[:, 0]
            next_token = jnp.argmax(self._logits(last),
                                    axis=-1).astype(jnp.int32)
        return (next_token, kv_pool.prompt_block(ks),
                kv_pool.prompt_block(vs), {})

    def _step(self, tokens, k_cache, v_cache, position, bound):
        with jax.named_scope("embedding"):
            h = (self.embed(tokens)
                 + self.pos_emb[position].astype(self.dtype))  # (S, D)
        k_rows, v_rows = [], []
        for i, blk in enumerate(self.blocks):
            h, k, v = blk.step(h, k_cache, v_cache, i, position, bound)
            k_rows.append(k)
            v_rows.append(v)
        k_cache, v_cache = kv_pool.write_rows(
            (k_cache, v_cache), (k_rows, v_rows), position)
        return h, k_cache, v_cache

    def decode_step(self, tokens, k_cache, v_cache, state, position,
                    bound=None):
        h, k_cache, v_cache = self._step(tokens, k_cache, v_cache, position,
                                         bound)
        with jax.named_scope("head"):
            next_token = jnp.argmax(self._logits(h),
                                    axis=-1).astype(jnp.int32)
        return next_token, k_cache, v_cache, state

    def decode_logits(self, tokens, k_cache, v_cache, position, bound=None):
        """``decode_step`` with the logits in place of their argmax: for
        tests only, the serving program ships ids."""
        h, k_cache, v_cache = self._step(tokens, k_cache, v_cache, position,
                                         bound)
        return self._logits(h), k_cache, v_cache


def create_seqformer_lm(rng=None, vocab_size: int = 512, max_len: int = 256,
                        dim: int = 64, depth: int = 2, heads: int = 4):
    """Build the causal LM + params for the continuous-batching decode
    path. ``max_len`` is the KV-cache depth per slot — prompt plus
    generated tokens must fit under it (``docs/streaming.md`` has the
    memory math)."""
    if dim % heads:
        raise ValueError(f"dim {dim} not divisible by heads {heads}")
    model = SeqFormerLM(vocab_size=vocab_size, max_len=max_len, dim=dim,
                        depth=depth, heads=heads)
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    init_p = min(8, max_len)
    params = model.init(rng, np.zeros((1, init_p), np.int32),
                        np.ones((1,), np.int32), method=SeqFormerLM.prefill)
    return model, params


def attention_for(mesh=None, strategy: str = "auto", causal: bool = False,
                  batch_axes=("dp", "fsdp")) -> Callable:
    """Pick the attention implementation for a mesh.

    ``auto`` → ring when the mesh's sp axis is >1, else the fused flash
    kernel; ``ring`` / ``ulysses`` force the parallel paths; ``flash``
    forces the single-device Pallas kernel (``ops/pallas/flash_attention``);
    ``full`` forces plain materialised attention (the correctness oracle).
    """
    from ..ops.pallas import flash_attention
    from ..parallel.ring_attention import (
        reference_attention,
        ring_attention,
        ulysses_attention,
    )
    valid = ("auto", "ring", "ulysses", "flash", "full")
    if strategy not in valid:
        raise ValueError(f"unknown attention strategy {strategy!r}; "
                         f"valid: {valid}")
    sp = mesh.shape.get("sp", 1) if mesh is not None else 1
    if strategy == "auto":
        strategy = "ring" if sp > 1 else "flash"
    if strategy == "full":
        return partial(reference_attention, causal=causal)
    if strategy == "flash":
        return partial(flash_attention, causal=causal, mesh=mesh)
    if mesh is None or sp <= 1:
        raise ValueError(f"{strategy} attention needs a mesh with sp > 1")
    fn = {"ring": ring_attention, "ulysses": ulysses_attention}[strategy]
    return partial(fn, mesh=mesh, causal=causal, batch_axes=batch_axes)


def create_seqformer(rng=None, seq_len: int = 4096, input_dim: int = 64,
                     dim: int = 128, depth: int = 2, heads: int = 8,
                     num_classes: int = 16, mesh=None,
                     attention: str = "auto", causal: bool = False,
                     vocab_size: int | None = None):
    """Build model + params. With a sequence-parallel mesh the sequence must
    divide the sp axis size (static shapes — SPMD). ``vocab_size`` switches
    the input contract to (B, S) token ids with on-device embedding."""
    if mesh is not None:
        sp = mesh.shape.get("sp", 1)
        if seq_len % max(sp, 1):
            raise ValueError(f"seq_len {seq_len} not divisible by sp={sp}")
    model = SeqFormer(seq_len=seq_len, input_dim=input_dim, dim=dim,
                      depth=depth, heads=heads, num_classes=num_classes,
                      attn_fn=attention_for(mesh, attention, causal),
                      vocab_size=vocab_size)
    # Init with a param-free stub attention (identity on q — same output
    # shape): the strategy carries no params, so the tree is identical, and
    # init neither materialises O(S²) scores for long sequences nor gets
    # constrained to the mesh's dp size by the batch-1 forward.
    init_model = model.clone(attn_fn=lambda q, k, v: q)
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    init_x = (np.zeros((1, seq_len), np.int32) if vocab_size is not None
              else np.zeros((1, seq_len, input_dim), np.float32))
    params = init_model.init(rng, init_x)
    return model, params
