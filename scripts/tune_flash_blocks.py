"""On-device flash-attention block sweep — one command for a chip run.

r3's retune (128/128 → 512/1024 at S=4096 D=128) bought 1.9× from block
shapes alone; r4 made the defaults head_dim-aware (`default_blocks`). This
script measures the remaining headroom on REAL hardware so the next retune
is a lookup, not a guess: sweeps (block_q, block_k) for the serving
geometries, times each with a readout fetch (the timed region ends with
the result on the host), and prints one JSON line per geometry plus a
final summary line.

Usage (time-boxed; safe to ^C — partial lines are valid JSON):
    timeout 600 python scripts/tune_flash_blocks.py
"""

from __future__ import annotations

import json
import sys
import time

sys.path.insert(0, ".")

import numpy as np  # noqa: E402


def sweep(s: int, d: int, heads: int, batch: int, iters: int = 8,
          interpret: bool = False) -> dict:
    import jax
    import jax.numpy as jnp

    from ai4e_tpu.ops.pallas.flash_attention import (default_blocks,
                                                     flash_attention)
    from ai4e_tpu.ops.pallas.validate import flash_attention_vmem_bytes

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((batch, heads, s, d)),
                    jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((batch, heads, s, d)),
                    jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((batch, heads, s, d)),
                    jnp.bfloat16)
    results = {}
    candidates = [(bq, bk)
                  for bq in (128, 256, 512, 1024)
                  for bk in (128, 256, 512, 1024, 2048)
                  if bq <= s and bk <= s]
    # VMEM guard: skip only shapes that genuinely can't fit — the sweep's
    # q/k/v tiles are bf16 (2 B), and validate.py's 16 MiB budget already
    # carries spill headroom. A stricter fp32 cutoff would silently drop
    # the largest (often winning) tiles at D>=256.
    from ai4e_tpu.ops.pallas.validate import VMEM_BUDGET_BYTES
    candidates = [c for c in candidates
                  if flash_attention_vmem_bytes(c[0], c[1], d,
                                                dtype_bytes=2)
                  < VMEM_BUDGET_BYTES]
    for bq, bk in candidates:
        fn = jax.jit(lambda q, k, v, _bq=bq, _bk=bk: flash_attention(
            q, k, v, block_q=_bq, block_k=_bk, interpret=interpret))
        try:
            out = fn(q, k, v)
            float(jnp.sum(out))  # force + fetch (real timing baseline)
            t0 = time.perf_counter()
            acc = 0.0
            for _ in range(iters):
                acc += float(jnp.sum(fn(q, k, v)))  # fetch every iter
            dt = (time.perf_counter() - t0) / iters
        except Exception as exc:  # noqa: BLE001 — record and keep sweeping
            results[f"{bq}/{bk}"] = {"error": str(exc)[:120]}
            continue
        results[f"{bq}/{bk}"] = {"ms": round(dt * 1000, 2)}
    ok = {k: v["ms"] for k, v in results.items() if "ms" in v}
    best = min(ok, key=ok.get) if ok else None
    default = "%d/%d" % default_blocks(d)
    rec = {"geometry": {"s": s, "d": d, "heads": heads, "batch": batch},
           "results": results, "best": best,
           "default": default,
           "default_ms": ok.get(default),
           "best_ms": ok.get(best) if best else None}
    print(json.dumps(rec), flush=True)
    return rec


def main() -> None:
    import jax
    assert jax.devices()[0].platform == "tpu", (
        "tune on the real chip — CPU timings would mislead the defaults")
    # Serving geometries: longcontext (S=4096, D=128 via heads=2 dim=256),
    # plus the larger-D case the head_dim-aware defaults protect.
    summary = []
    for s, d, heads, batch in ((4096, 128, 2, 16),
                               (4096, 256, 2, 8),
                               (8192, 128, 2, 8)):
        rec = sweep(s, d, heads, batch)
        summary.append({k: rec[k] for k in ("geometry", "best", "best_ms",
                                            "default", "default_ms")})
    print(json.dumps({"summary": summary}), flush=True)


if __name__ == "__main__":
    main()
