"""Pallas kernel correctness vs plain-XLA oracles (interpreter mode on CPU;
the same code compiles to Mosaic on TPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from ai4e_tpu.ops.pallas import (
    class_histogram,
    fused_seg_postprocess,
    normalize_image,
    segmentation_argmax,
)


class TestSegArgmax:
    def test_matches_jnp_argmax(self):
        rng = np.random.default_rng(0)
        logits = jnp.asarray(rng.standard_normal((2, 64, 128, 4)), jnp.float32)
        got = segmentation_argmax(logits, tile_h=32)
        expected = jnp.argmax(logits, axis=-1).astype(jnp.uint8)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(expected))

    def test_bfloat16_logits(self):
        rng = np.random.default_rng(1)
        logits = jnp.asarray(rng.standard_normal((1, 32, 128, 7)),
                             jnp.bfloat16)
        got = segmentation_argmax(logits, tile_h=32)
        expected = jnp.argmax(logits, axis=-1).astype(jnp.uint8)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(expected))

    def test_rejects_bad_tiling(self):
        with pytest.raises(ValueError):
            segmentation_argmax(jnp.zeros((1, 100, 128, 4)), tile_h=64)

    def test_full_postprocess_counts(self):
        rng = np.random.default_rng(2)
        logits = jnp.asarray(rng.standard_normal((2, 64, 128, 4)), jnp.float32)
        out = fused_seg_postprocess(logits)
        assert out["classmap"].shape == (2, 64, 128)
        assert out["counts"].shape == (2, 4)
        assert np.asarray(out["counts"]).sum() == 2 * 64 * 128

    def test_postprocess_counts_only(self):
        """with_classmap=False keeps the map on-device: counts must still
        match the full variant's, and the map key must be absent (nothing
        for run_batch's device_get to fetch)."""
        rng = np.random.default_rng(4)
        logits = jnp.asarray(rng.standard_normal((2, 64, 128, 4)), jnp.float32)
        full = fused_seg_postprocess(logits)
        slim = fused_seg_postprocess(logits, with_classmap=False)
        assert set(slim) == {"counts"}
        np.testing.assert_array_equal(np.asarray(slim["counts"]),
                                      np.asarray(full["counts"]))

    def test_unet_family_classmap_png_roundtrip(self):
        """return_classmap=True responses carry the classified tile as a
        lossless PNG whose pixels reproduce the histogram (the reference's
        land-cover APIs return classified tiles, not just statistics)."""
        import base64
        import io

        from PIL import Image

        from ai4e_tpu.runtime import build_servable

        servable = build_servable("unet", name="lc-png", tile=32,
                                  widths=[8, 16], buckets=(2,),
                                  return_classmap=True)
        batch = np.random.default_rng(5).integers(
            0, 256, (2, 32, 32, 3), np.uint8)
        out = servable.apply_fn(servable.params, jnp.asarray(batch))
        result = servable.postprocess(
            {k: np.asarray(v)[0] for k, v in out.items()})
        png = base64.b64decode(result["classmap_png"])
        decoded = np.asarray(Image.open(io.BytesIO(png)))
        assert decoded.shape == (32, 32)
        values, counts = np.unique(decoded, return_counts=True)
        assert {int(v): int(c) for v, c in zip(values, counts)} == \
            result["class_histogram"]

    def test_unet_family_default_keeps_map_on_device(self):
        from ai4e_tpu.runtime import build_servable

        servable = build_servable("unet", name="lc-slim", tile=32,
                                  widths=[8, 16], buckets=(2,))
        batch = np.zeros((2, 32, 32, 3), np.uint8)
        out = servable.apply_fn(servable.params, jnp.asarray(batch))
        assert set(out) == {"counts"}
        result = servable.postprocess(
            {k: np.asarray(v)[0] for k, v in out.items()})
        assert "classmap_png" not in result
        assert sum(result["class_histogram"].values()) == 32 * 32


class TestClassHistogram:
    def test_counts(self):
        cm = jnp.asarray([[[0, 1], [1, 3]]], jnp.uint8)
        counts = class_histogram(cm, 4)
        np.testing.assert_array_equal(np.asarray(counts), [[1, 2, 0, 1]])


class TestNormalizeImage:
    def test_matches_numpy(self):
        rng = np.random.default_rng(3)
        img = rng.integers(0, 256, (2, 64, 128, 3), np.uint8)
        mean = [0.485, 0.456, 0.406]
        std = [0.229, 0.224, 0.225]
        got = normalize_image(jnp.asarray(img), mean, std, tile_h=32)
        expected = (img.astype(np.float32) / 255.0 - mean) / std
        np.testing.assert_allclose(np.asarray(got), expected, rtol=1e-5,
                                   atol=1e-6)

    def test_default_identity_normalization(self):
        img = np.full((1, 32, 128, 3), 255, np.uint8)
        got = normalize_image(jnp.asarray(img))
        np.testing.assert_allclose(np.asarray(got), 1.0, rtol=1e-6)

    def test_rejects_float_input(self):
        with pytest.raises(ValueError):
            normalize_image(jnp.zeros((1, 32, 128, 3), jnp.float32))


class TestFlashAttention:
    def _qkv(self, b=2, h=3, s=256, d=32, seed=0):
        import numpy as _np
        rng = _np.random.default_rng(seed)
        mk = lambda: rng.standard_normal((b, h, s, d)).astype(_np.float32)
        return mk(), mk(), mk()

    def test_matches_reference(self):
        import numpy as _np

        from ai4e_tpu.ops.pallas import flash_attention
        from ai4e_tpu.parallel.ring_attention import reference_attention

        q, k, v = self._qkv()
        got = flash_attention(q, k, v, block_q=64, block_k=64)
        expected = reference_attention(q, k, v)
        _np.testing.assert_allclose(_np.asarray(got), _np.asarray(expected),
                                    rtol=2e-4, atol=2e-5)

    def test_causal_matches_reference(self):
        import numpy as _np

        from ai4e_tpu.ops.pallas import flash_attention
        from ai4e_tpu.parallel.ring_attention import reference_attention

        q, k, v = self._qkv(seed=1)
        got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        expected = reference_attention(q, k, v, causal=True)
        _np.testing.assert_allclose(_np.asarray(got), _np.asarray(expected),
                                    rtol=2e-4, atol=2e-5)

    def test_cross_attention_shapes(self):
        # S_q != S_k (non-causal): decoder-style cross attention.
        import numpy as _np

        from ai4e_tpu.ops.pallas import flash_attention
        from ai4e_tpu.parallel.ring_attention import reference_attention

        rng = _np.random.default_rng(2)
        q = rng.standard_normal((1, 2, 64, 16)).astype(_np.float32)
        k = rng.standard_normal((1, 2, 192, 16)).astype(_np.float32)
        v = rng.standard_normal((1, 2, 192, 16)).astype(_np.float32)
        got = flash_attention(q, k, v, block_q=32, block_k=64)
        _np.testing.assert_allclose(
            _np.asarray(got), _np.asarray(reference_attention(q, k, v)),
            rtol=2e-4, atol=2e-5)

    def test_gradients_match_reference(self):
        # The custom_vjp's pallas backward (FlashAttention-2 recurrence:
        # P recomputed from the saved logsumexp) must match autodiff
        # through the materialized reference — both causal and not, and
        # with uneven block counts so the accumulator carry is exercised.
        import jax as _jax
        import jax.numpy as _jnp
        import numpy as _np

        from ai4e_tpu.ops.pallas import flash_attention
        from ai4e_tpu.parallel.ring_attention import reference_attention

        q, k, v = self._qkv(b=1, h=2, s=256, d=32, seed=4)
        for causal in (False, True):
            def loss_f(q, k, v, _c=causal):
                return _jnp.sum(_jnp.sin(flash_attention(
                    q, k, v, causal=_c, block_q=64, block_k=128)))

            def loss_r(q, k, v, _c=causal):
                return _jnp.sum(_jnp.sin(reference_attention(
                    q, k, v, causal=_c)))

            gf = _jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
            gr = _jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
            for name, a, b in zip("qkv", gf, gr):
                _np.testing.assert_allclose(
                    _np.asarray(a), _np.asarray(b), rtol=2e-3, atol=2e-4,
                    err_msg=f"d{name} causal={causal}")

    def test_seqformer_trains_with_flash_attention(self):
        # The training plane now matches the serving plane: a seqformer
        # built with the flash strategy optimizes end to end (loss drops),
        # with no S×S score matrix in either pass.
        import jax as _jax
        import numpy as _np

        from ai4e_tpu.models import create_seqformer
        from ai4e_tpu.parallel import MeshSpec, make_mesh
        from ai4e_tpu.train import Trainer, cross_entropy_loss

        model, params = create_seqformer(
            seq_len=256, input_dim=16, dim=32, depth=1, heads=2,
            num_classes=4, attention="flash")
        mesh = make_mesh(MeshSpec(), devices=_jax.devices()[:1])
        tr = Trainer(model.apply, params, mesh, loss_fn=cross_entropy_loss)
        rng = _np.random.default_rng(5)
        x = rng.standard_normal((8, 256, 16)).astype(_np.float32)
        y = (rng.integers(0, 4, 8)).astype(_np.int32)
        first = float(tr.train_step(x, y))
        for _ in range(12):
            last = float(tr.train_step(x, y))
        assert last < first * 0.85, (first, last)

    def test_seqformer_flash_strategy_matches_full(self):
        import numpy as _np

        from ai4e_tpu.models import create_seqformer

        model_flash, params = create_seqformer(
            seq_len=256, input_dim=16, dim=32, depth=1, heads=4,
            num_classes=8, attention="flash")
        model_full, _ = create_seqformer(
            seq_len=256, input_dim=16, dim=32, depth=1, heads=4,
            num_classes=8, attention="full")
        x = _np.random.default_rng(3).standard_normal(
            (2, 256, 16)).astype(_np.float32)
        _np.testing.assert_allclose(
            _np.asarray(model_flash.apply(params, x)),
            _np.asarray(model_full.apply(params, x)), rtol=2e-2, atol=2e-2)


class TestValidationHarness:
    def test_validate_kernels_in_interpreter(self):
        """The on-device validation harness (ops/pallas/validate.py — run by
        chip_smoke.py on real TPU) must itself be correct: same checks under the
        pallas interpreter pass, and the VMEM accounting stays in budget."""
        from ai4e_tpu.ops.pallas.validate import (
            VMEM_BUDGET_BYTES,
            flash_attention_vmem_bytes,
            validate_kernels,
        )

        results = validate_kernels(interpret=True)
        assert results["all_ok"], results
        for name in ("flash_attention", "segmentation_argmax",
                     "normalize_image", "decode_attention_float32",
                     "decode_attention_bfloat16"):
            assert results[name]["vmem_bytes"] <= VMEM_BUDGET_BYTES
        # The flash kernel's footprint depends only on block sizes and head
        # dim — never sequence length (the k-axis is a grid axis) — so even
        # the largest serving config (d=128) fits comfortably.
        assert flash_attention_vmem_bytes(128, 128, 128) <= VMEM_BUDGET_BYTES

    def test_main_fails_and_names_the_kernel_that_is_not_ok(
            self, monkeypatch, capsys):
        """``chip_smoke.py``'s kernel phase is ``validate.main``: a kernel
        that is not ok is a non-zero exit and is named on the result line."""
        import json

        from ai4e_tpu.ops.pallas import validate
        from ai4e_tpu.runtime import registry
        monkeypatch.setattr(registry, "enable_compilation_cache",
                            lambda: None)  # jax.config is process-global
        monkeypatch.setattr(validate, "validate_kernels", lambda interpret: {
            "flash_attention": {"ok": False, "max_err": 1.0},
            "all_ok": False})
        assert validate.main(["--interpret"]) != 0
        result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert result["flash_attention"]["ok"] is False
        assert result["device"]["platform"] == "cpu"


class TestShardOverBatch:
    """``lowering.shard_over_batch`` — how a compiled kernel runs under a
    batch sharded over the mesh's data axes (GSPMD cannot partition a Mosaic
    call). The Mosaic side is pinned by the deviceless compile in
    ``test_tpu_aot_compile.py``; here, on the virtual CPU mesh, the
    plumbing: each device gets its slice of the batch and the replicated
    arguments whole."""

    @staticmethod
    def _kernel(x, scale, bias):
        return x * scale + bias

    def test_per_shard_result_equals_the_unsharded_one(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ai4e_tpu.ops.pallas.lowering import shard_over_batch
        from ai4e_tpu.parallel import make_mesh
        mesh = make_mesh()
        assert mesh.shape["dp"] == 8
        x = jnp.arange(16 * 4, dtype=jnp.float32).reshape(16, 4)
        scale, bias = jnp.full((1, 4), 2.0), jnp.full((1, 4), -1.0)
        sharded = shard_over_batch(self._kernel, mesh, interpret=False,
                                   replicated_args=2)
        assert sharded is not self._kernel
        got = jax.jit(sharded, in_shardings=(
            NamedSharding(mesh, P(("dp", "fsdp"))), None, None))(
                x, scale, bias)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(self._kernel(x, scale, bias)))
        assert len(got.sharding.device_set) == 8

    def test_left_alone_where_nothing_needs_partitioning(self):
        from ai4e_tpu.ops.pallas.lowering import shard_over_batch
        from ai4e_tpu.parallel import MeshSpec, make_mesh
        import jax
        k = self._kernel
        assert shard_over_batch(k, None, interpret=False) is k
        assert shard_over_batch(k, make_mesh(), interpret=True) is k
        one_shard = make_mesh(MeshSpec(dp=1, tp=8), devices=jax.devices())
        assert shard_over_batch(k, one_shard, interpret=False) is k
