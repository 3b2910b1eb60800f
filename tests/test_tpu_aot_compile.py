"""Deviceless TPU compile pre-flight — slow, outside tier-1.

``libtpu`` hands out a v5e topology description without a chip, and
``jit(...).lower(...).compile()`` against it runs the real Mosaic/XLA:TPU
compiler. Nothing executes, so this says nothing about numerics or time — it
says whether a kernel or the deployed servable still *compiles* for the chip,
which is the cheapest thing to know before spending chip budget on a change:

    JAX_PLATFORMS=cpu python -m pytest tests/test_tpu_aot_compile.py -m slow

Skipped where libtpu cannot describe the topology.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def v5e_devices():
    from jax.experimental import topologies
    try:
        topology = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no libtpu / no such topology
        pytest.skip(f"no deviceless v5e topology here: {exc}")
    assert topology.devices[0].device_kind == "TPU v5 lite"
    return list(topology.devices)


@pytest.fixture(scope="module")
def v5e_sharding(v5e_devices):
    return jax.sharding.SingleDeviceSharding(v5e_devices[0])


@pytest.fixture(scope="module")
def v5e_host_mesh(v5e_devices):
    """The four chips of one host as the worker lays them out by default:
    all on ``dp``."""
    from ai4e_tpu.parallel.sharding import make_mesh
    return make_mesh(devices=v5e_devices)


def _on(sharding, spec):
    """``spec`` — a ``(shape, dtype)`` pair or a pytree of arrays — as
    abstract arguments placed with ``sharding``."""
    def struct(leaf):
        shape, dtype = ((leaf.shape, leaf.dtype) if hasattr(leaf, "shape")
                        else leaf)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return jax.tree.map(struct, spec, is_leaf=lambda x: isinstance(x, tuple))


def _compile(fn, *args):
    """AOT-compile ``fn`` for the topology the arguments are placed on."""
    return jax.jit(fn).lower(*args).compile()


class TestServingKernelsCompileForV5e:
    def test_normalize_image(self, v5e_sharding):
        from ai4e_tpu.ops.pallas import normalize_image
        _compile(lambda x: normalize_image(x, interpret=False),
                 _on(v5e_sharding, ((16, 256, 256, 3), jnp.uint8)))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_segmentation_argmax(self, v5e_sharding, dtype):
        """Both dtypes the docstring promises: bfloat16 logits used to die
        in Mosaic ("Invalid relayout ... xi1") until the kernel compared
        in float32."""
        from ai4e_tpu.ops.pallas import segmentation_argmax
        _compile(lambda x: segmentation_argmax(x, interpret=False),
                 _on(v5e_sharding, ((16, 256, 256, 4), dtype)))

    @pytest.mark.parametrize("head_dim", [64, 128])
    def test_flash_attention_forward_and_grad(self, v5e_sharding, head_dim):
        from ai4e_tpu.ops.pallas import flash_attention
        qkv = _on(v5e_sharding, ((1, 2, 4096, head_dim), jnp.bfloat16))

        def loss(q, k, v):
            return flash_attention(q, k, v, interpret=False).astype(
                jnp.float32).sum()

        _compile(lambda q, k, v: flash_attention(q, k, v, interpret=False),
                 qkv, qkv, qkv)
        _compile(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)


def _deployed_landcover(mesh=None):
    from ai4e_tpu.runtime import build_servable
    with open(os.path.join(REPO, "deploy", "specs", "models.json")) as f:
        spec = dict(next(m for m in json.load(f)["models"]
                         if m["name"] == "landcover"))
    family = spec.pop("family")
    for key in ("checkpoint", "sync_path", "async_path"):
        spec.pop(key)
    servable = build_servable(family, mesh=mesh, **spec)
    assert servable.input_shape == (256, 256, 3)
    return servable


def test_deployed_landcover_servable_compiles_at_bucket_16(
        v5e_sharding, monkeypatch):
    """The servable ``deploy/specs/models.json`` names, at its deployed
    widths, through the same ``apply_fn`` the worker jits — with the
    kernels lowered to Mosaic, as they are when the default backend is a
    TPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    servable = _deployed_landcover()
    compiled = _compile(
        servable.apply_fn, _on(v5e_sharding, servable.params),
        _on(v5e_sharding, ((16, *servable.input_shape),
                           np.dtype(servable.input_dtype))))
    assert "tpu_custom_call" in compiled.as_text()


class TestFourChipHost:
    """The batch sharded over a 2x2 host's four chips, as ``ModelRuntime``
    shards it. GSPMD cannot partition a Mosaic kernel: without the
    per-shard ``shard_map`` (``ops/pallas/lowering.shard_over_batch``) these
    fail to lower with "Mosaic kernels cannot be automatically partitioned"
    — what the deployed landcover worker died of on a four-chip v5e."""

    def test_deployed_landcover_servable(self, v5e_host_mesh, monkeypatch):
        from jax.sharding import NamedSharding, PartitionSpec as P
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        servable = _deployed_landcover(mesh=v5e_host_mesh)
        batch = NamedSharding(v5e_host_mesh, P(("dp", "fsdp")))
        compiled = _compile(
            servable.apply_fn,
            _on(NamedSharding(v5e_host_mesh, P()), servable.params),
            _on(batch, ((16, *servable.input_shape),
                        np.dtype(servable.input_dtype))))
        assert "tpu_custom_call" in compiled.as_text()

    def test_flash_attention(self, v5e_host_mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ai4e_tpu.ops.pallas import flash_attention
        batch = NamedSharding(v5e_host_mesh, P(("dp", "fsdp")))
        qkv = _on(batch, ((4, 2, 4096, 128), jnp.bfloat16))
        _compile(lambda q, k, v: flash_attention(
            q, k, v, interpret=False, mesh=v5e_host_mesh), qkv, qkv, qkv)


def test_decode_step_at_the_benchmark_cell_writes_rows_in_place(v5e_sharding):
    """The ``step`` program ``PagedDecodeRuntime`` builds, donated, at the
    shape of the benchmark's ``gpt2m.chat`` cell (GPT-2-medium, 32 slots):
    the new token's K/V go into the pool as one ``dynamic-update-slice``
    per slot and tensor, on the donated parameters. No whole-pool ``copy``
    (XLA's answer to a scatter: it re-lays the pool out and back, 6.5 GB of
    temporaries), no fusion that rewrites a pool (the one-hot blend: 3.3 GB).
    About 8 s."""
    import re
    from ai4e_tpu.models.seqformer import SeqFormerLM, create_seqformer_lm
    from ai4e_tpu.runtime.kvcache import LMServable, PagedDecodeRuntime
    with open(os.path.join(REPO, "benchmark", "configs",
                           "gpt2-medium.json")) as f:
        config = json.load(f)
    spec = config["models"]["models"][0]
    slots = int(config["worker_env"]["AI4E_RUNTIME_KV_SLOTS"])
    dims = {key: spec[key]
            for key in ("vocab_size", "max_len", "dim", "depth", "heads")}
    params = jax.eval_shape(lambda: create_seqformer_lm(**dims)[1])
    runtime = PagedDecodeRuntime(
        LMServable(name="lm", model=SeqFormerLM(**dims), params=params,
                   vocab_size=spec["vocab_size"], max_len=spec["max_len"]),
        slots=slots, donate=True)
    runtime._build_programs()
    pool_shape = (spec["depth"], slots, spec["heads"], spec["max_len"],
                  spec["dim"] // spec["heads"])
    pool = _on(v5e_sharding, (pool_shape, jnp.float32))
    ints = _on(v5e_sharding, ((slots,), jnp.int32))
    compiled = runtime._programs["step"].lower(
        _on(v5e_sharding, params), ints, pool, pool, ints).compile()

    pool_type = "f32[" + ",".join(map(str, pool_shape)) + "]"
    entry = re.search(r"ENTRY [^\n]*\{\n(.*?)\n\}", compiled.as_text(),
                      re.S).group(1)
    makers = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?\S+ = (\S+) ([\w\-]+)\(", line)
        if m and m.group(1).startswith(pool_type):
            makers.append(m.group(2))
    assert sorted(set(makers)) == ["dynamic-update-slice", "parameter"], (
        sorted(set(makers)))
    assert makers.count("dynamic-update-slice") == 2 * slots
    assert makers.count("parameter") == 2
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 0.5e9, memory.temp_size_in_bytes
    # Both pool tensors are aliased input to output: the pool exists once.
    assert memory.alias_size_in_bytes >= 2 * 4 * np.prod(pool_shape)


def test_olmoe_step_at_the_benchmark_cell_writes_rows_in_place(v5e_sharding):
    """The same, for the ``olmoe.decode`` cell, read from
    ``benchmark/configs/olmoe-1b-7b.json``: a bfloat16 pool of
    ``[8,32,16,2048,128]`` is another layout (bf16 tiles, head dimension
    128), and the step must still make it only by row writes — 2 x slots
    ``dynamic-update-slice``, no pool-shaped copy or fusion — with the pool
    aliased input to output. Then the whole worker's memory: weights + pool
    + the largest program's temporaries (the top prefill bucket) stay under
    the 15 GB line the configuration states. About 25 s."""
    import re
    from ai4e_tpu.models.olmoe import OlmoeLM, create_olmoe_lm
    from ai4e_tpu.runtime.kvcache import LMServable, PagedDecodeRuntime
    with open(os.path.join(REPO, "benchmark", "configs",
                           "olmoe-1b-7b.json")) as f:
        config = json.load(f)
    spec = config["models"]["models"][0]
    slots = int(config["worker_env"]["AI4E_RUNTIME_KV_SLOTS"])
    dims = {key: spec[key] for key in (
        "vocab_size", "dim", "depth", "heads", "experts",
        "experts_per_token", "expert_dim", "rms_eps", "rope_theta")}
    params = jax.eval_shape(lambda: create_olmoe_lm(**dims)[1])
    runtime = PagedDecodeRuntime(
        LMServable(name="lm", model=OlmoeLM(**dims), params=params,
                   vocab_size=spec["vocab_size"], max_len=spec["max_len"]),
        slots=slots, donate=True)
    runtime._build_programs()
    params = _on(v5e_sharding, params)
    pool_shape, pool_dtype = runtime.cache_spec()
    assert pool_shape == (8, 32, 16, 2048, 128)
    assert pool_dtype == jnp.bfloat16
    pool = _on(v5e_sharding, (pool_shape, pool_dtype))
    ints = _on(v5e_sharding, ((slots,), jnp.int32))
    step = runtime._programs["step"].lower(
        params, ints, pool, pool, ints).compile()

    pool_type = "bf16[" + ",".join(map(str, pool_shape)) + "]"
    entry = re.search(r"ENTRY [^\n]*\{\n(.*?)\n\}", step.as_text(),
                      re.S).group(1)
    makers = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?\S+ = (\S+) ([\w\-]+)\(", line)
        if m and m.group(1).startswith(pool_type):
            makers.append(m.group(2))
    assert sorted(set(makers)) == ["dynamic-update-slice", "parameter"], (
        sorted(set(makers)))
    assert makers.count("dynamic-update-slice") == 2 * slots
    assert makers.count("parameter") == 2
    memory = step.memory_analysis()
    assert memory.temp_size_in_bytes < 0.1e9, memory.temp_size_in_bytes
    pool_bytes = 2 * 2 * int(np.prod(pool_shape))
    assert memory.alias_size_in_bytes >= pool_bytes
    assert runtime.cache_nbytes() == pool_bytes

    top = runtime.prompt_buckets[-1]
    assert top == spec["max_len"] == 2048
    prefill = runtime._programs["prefill"].lower(
        params, _on(v5e_sharding, ((1, top), jnp.int32)),
        _on(v5e_sharding, ((1,), jnp.int32))).compile().memory_analysis()
    resident = memory.argument_size_in_bytes      # weights + pool (+ ints)
    assert 11.3e9 < resident < 11.5e9, resident
    peak = resident + max(memory.temp_size_in_bytes,
                          prefill.temp_size_in_bytes
                          + prefill.output_size_in_bytes)
    assert peak < 15e9, peak
