"""Plain reference for the ``seqformer-lm`` family (GPT-2-shaped causal LM),
the comparison that decides ``correct`` for its cells, and the decode step's
operation and byte counts.

The forward pass is written from the GPT-2 block as the repo's family runs
it: token + learned position embeddings; per layer pre-LayerNorm → fused qkv
→ causal softmax attention → projection → residual, pre-LayerNorm → 4x MLP
with tanh-GELU → residual; final LayerNorm; logits against the tied embedding.
Departures from openai-community/gpt2-medium, as the family has them: no bias
on ``qkv``/``proj``, LayerNorm eps 1e-6, seeded weights. Plain ``jax.numpy``,
float32, ``highest`` matmul precision, full causal attention over the whole
sequence — no cache, no batching, no import from ``ai4e_tpu.models`` beyond
``create_seqformer_lm`` for the parameter VALUES (key 0, as the worker's).

The API returns greedy token ids only, and with random weights an argmax
flips on rounding. So the reference is teacher-forced on prompt + served
tokens, and each served token's reference logit must lie within LOGIT_MARGIN
of that position's reference maximum.
"""

from __future__ import annotations

import numpy as np

# Reason for the margin: the worker's float32 matmuls run at the TPU's
# default precision (bfloat16 passes) and its decode path reads K/V through a
# cache, so logits differ from the float32 reference by rounding. Logits of
# this init have a standard deviation of ~1 over 50,257 ids and the runner-up
# sits ~0.2 under the maximum; a wrong position, a stale cache row or a
# dropped layer puts the served id ~4 under it. Measured on the chip: see
# MARGIN_MEASURED.
LOGIT_MARGIN = 0.25
MARGIN_MEASURED = ("worst 0.022 over about 6,000 tokens of 52 streams, argmax agreement 98.5-100 % "
                   "(my chip runs, PR 23)")
PAD_LEN = 512          # sampled streams have prompt + output <= PAD_LEN


def _model_spec(config: dict) -> dict:
    return next(m for m in config["models"]["models"]
                if m["family"] == "seqformer-lm")


def weight_bytes(spec: dict) -> int:
    d, v, n, length = (spec["dim"], spec["vocab_size"], spec["depth"],
                       spec["max_len"])
    per_block = 3 * d * d + d * d + 2 * 4 * d * d + 4 * d + d + 4 * d
    return 4 * (v * d + length * d + n * per_block + 2 * d)


def kv_bytes_per_token(spec: dict) -> int:
    return 2 * spec["depth"] * spec["dim"] * 4     # K and V, float32


def ops_and_bytes(config: dict, slots: int,
                  live_tokens: float) -> tuple[float, float]:
    """One decode step over the pool: operations = 2 x matmul parameters per
    slot + 4·dim per live cached token; least bytes = the weights once + one
    read of the live K/V + one row written per slot."""
    spec = _model_spec(config)
    d, v, n = spec["dim"], spec["vocab_size"], spec["depth"]
    matmul_params = n * 12 * d * d + v * d
    flops = 2.0 * matmul_params * slots + 4.0 * d * n * live_tokens
    nbytes = (weight_bytes(spec) + kv_bytes_per_token(spec)
              * (live_tokens + slots))
    return flops, float(nbytes)


def _forward(p: dict, tokens, heads: int):
    """Logits (T, V) of one sequence of token ids (T,)."""
    import jax
    import jax.numpy as jnp

    def layer_norm(x, q):
        mean = x.mean(-1, keepdims=True)
        var = ((x - mean) ** 2).mean(-1, keepdims=True)
        return (x - mean) / jnp.sqrt(var + 1e-6) * q["scale"] + q["bias"]

    def gelu(x):
        return 0.5 * x * (1.0 + jnp.tanh(
            np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))

    t = tokens.shape[0]
    x = p["embed"]["embedding"][tokens] + p["pos_emb"][:t]
    d = x.shape[-1]
    hd = d // heads
    causal = jnp.tril(jnp.ones((t, t), bool))

    def block(x, b):
        qkv = (layer_norm(x, b["ln1"]) @ b["qkv"]["kernel"]).reshape(
            t, 3, heads, hd)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
        scores = jnp.where(causal[None], scores, -1e30)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
        x = x + o.reshape(t, d) @ b["proj"]["kernel"]
        h = gelu(layer_norm(x, b["ln2"]) @ b["mlp_up"]["kernel"]
                 + b["mlp_up"]["bias"])
        return x + h @ b["mlp_down"]["kernel"] + b["mlp_down"]["bias"], None

    x, _ = jax.lax.scan(block, x, p["blocks"])
    return layer_norm(x, p["ln_f"]) @ p["embed"]["embedding"].T


def prepare(config: dict, pre: dict) -> dict:
    import jax
    import jax.numpy as jnp
    from ai4e_tpu.models.seqformer import create_seqformer_lm  # VALUES only
    spec = _model_spec(config)
    _, variables = create_seqformer_lm(
        vocab_size=spec["vocab_size"], max_len=spec["max_len"],
        dim=spec["dim"], depth=spec["depth"], heads=spec["heads"])
    raw = variables["params"]
    params = {k: raw[k] for k in ("embed", "pos_emb", "ln_f")}
    params["blocks"] = jax.tree.map(
        lambda *leaves: jnp.stack(leaves),
        *[raw[f"block{i}"] for i in range(spec["depth"])])
    pad = min(PAD_LEN, spec["max_len"])

    def logits_of(p, tokens):
        with jax.default_matmul_precision("highest"):
            return _forward(p, tokens, spec["heads"])

    fn = jax.jit(logits_of)
    fn(params, jnp.zeros((pad,), jnp.int32)).block_until_ready()  # compile now
    return {"spec": spec, "params": params, "fn": fn, "pad": pad,
            "payload": pre}


def margins(state: dict, prompt: list[int], served: list[int]) -> np.ndarray:
    """For each served token: the reference maximum at its position minus the
    reference logit of the served id (0 where the reference agrees)."""
    import jax.numpy as jnp
    seq = np.asarray(prompt + served, np.int32)
    padded = np.zeros((state["pad"],), np.int32)
    padded[:len(seq) - 1] = seq[:-1]   # causal: padding cannot reach back
    logits = np.asarray(state["fn"](state["params"], jnp.asarray(padded)))
    rows = logits[len(prompt) - 1:len(seq) - 1]
    return rows.max(axis=-1) - rows[np.arange(len(served)), served]


def check(state: dict, jobs: list[dict]) -> dict:
    from benchmark.lib.payloads import PromptPayloads
    payloads = PromptPayloads(state["payload"]["seed"],
                              state["spec"]["vocab_size"])
    worst, exact, total, bad = 0.0, 0, 0, []
    for job in jobs:
        prompt = payloads.prompt(job["counter"], job["prompt_len"])
        served = [int(t) for t in job["result"]["tokens"]]
        m = margins(state, prompt, served)
        worst = max(worst, float(m.max()))
        exact += int((m == 0).sum())
        total += len(served)
        if float(m.max()) > LOGIT_MARGIN:
            bad.append({"counter": job["counter"],
                        "first_bad_index": int(np.argmax(m > LOGIT_MARGIN)),
                        "margin": float(m.max())})
    return {"ok": not bad and bool(jobs), "checked": len(jobs),
            "tokens_checked": total, "argmax_agreement": (
                exact / total if total else 0.0),
            "worst_margin": worst, "limit_margin": LOGIT_MARGIN,
            "bad": bad[:3]}
