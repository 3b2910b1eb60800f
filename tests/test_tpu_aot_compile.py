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


def _benchmark_cell(config_file, create, model_cls, keys):
    """``(runtime, spec)`` of a decode cell as ``benchmark/configs`` states
    it: ``model_cls(**spec[keys])`` at its widths, with the parameters
    ``create`` would draw as shapes, the cell's slots, the programs built
    donated."""
    from ai4e_tpu.runtime.kvcache import LMServable, PagedDecodeRuntime
    with open(os.path.join(REPO, "benchmark", "configs", config_file)) as f:
        config = json.load(f)
    spec = config["models"]["models"][0]
    dims = {key: spec[key] for key in keys}
    params = jax.eval_shape(lambda: create(**dims)[1])
    runtime = PagedDecodeRuntime(
        LMServable(name="lm", model=model_cls(**dims), params=params,
                   vocab_size=spec["vocab_size"], max_len=spec["max_len"]),
        slots=int(config["worker_env"]["AI4E_RUNTIME_KV_SLOTS"]),
        donate=True)
    runtime._build_programs()
    return runtime, spec


def _compile_step(runtime, sharding, bound):
    """The step program of one rung, compiled for the chip — its attention
    and state-update kernels by Mosaic, as on the chip: the program asks the
    default backend (``lowering.resolve_interpret``), which here is the CPU,
    so the test answers for it."""
    from ai4e_tpu.ops.pallas import decode_attention, state_update
    from ai4e_tpu.runtime import kvcache
    pools = tuple(_on(sharding, spec) for spec in runtime.cache_spec())
    # What a launch hands it: the host's three rows a slot, and the last
    # step's ids, which stayed on the device (and after them, a slot a kind,
    # what the model's prefill reports beside its first id).
    host = _on(sharding, ((3, runtime.slots), jnp.int32))
    previous = _on(sharding, (
        ((1 + len(runtime.report_kinds)) * runtime.slots,), jnp.int32))
    with pytest.MonkeyPatch.context() as patch:
        for kernel in (decode_attention, state_update):
            patch.setattr(kernel, "resolve_interpret",
                          lambda kernel, interpret: False)
        state = {name: _on(sharding, ((runtime.slots, *shape), dtype))
                 for name, shape, dtype in runtime.state_spec()}
        # The default backend here is the CPU: the chip's options by hand.
        return runtime._programs["step"].lower(
            _on(sharding, runtime.servable.params), host, previous, pools,
            state, bound).compile(
                compiler_options=kvcache.STEP_COMPILER_OPTIONS["tpu"])


def _entry(compiled):
    """The text of the entry computation's body."""
    import re
    return re.search(r"ENTRY [^\n]*\{\n(.*?)\n\}", compiled.as_text(),
                     re.S).group(1)


def _entry_results(compiled):
    """``[(result type, operation)]`` of every instruction of the entry
    computation: what the program makes outside its fusions."""
    import re
    entry = _entry(compiled)
    out = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?\S+ = (\S+) ([\w\-]+)\(", line)
        if m:
            out.append((m.group(1), m.group(2)))
    return out


def _mosaic_calls(compiled, kernel):
    """``[(name, [operand names], line)]`` of the entry computation's Mosaic
    calls of the kernel named ``kernel`` (``pallas_call``'s ``name``)."""
    import re
    entry = _entry(compiled)
    calls = []
    for line in entry.splitlines():
        m = re.match(r"\s*(%" + kernel + r"[\w.\-]*) = .*? custom-call\("
                     r"([^)]*)\), custom_call_target=\"tpu_custom_call\"",
                     line)
        if m:
            calls.append((m.group(1), re.findall(r"%[\w.\-]+", m.group(2)),
                          line))
    return calls


def _assert_state_steps_in_place(compiled, state_type, tensors):
    """Each of the ``tensors`` state tensors of ``state_type`` is advanced
    by ONE Mosaic call (``state_update``) whose operand is the donated
    parameter itself and whose result is aliased onto it: a state tensor is
    a parameter or that call's own result, nothing copies or re-lays one,
    and no fusion makes one."""
    import re
    results = _entry_results(compiled)
    makers = sorted(op for kind, op in results if kind.startswith(state_type))
    # the call's result is a tuple (read-out, successor): the successor is
    # taken from it, and nothing else has the tensor's type
    assert makers == (["get-tuple-element"] * tensors
                      + ["parameter"] * tensors), makers
    entry = _entry(compiled)
    parameters = set(re.findall(
        r"(%\S+) = " + re.escape(state_type) + r"\S* parameter\(", entry))
    assert len(parameters) == tensors
    calls = _mosaic_calls(compiled, "state_update")
    assert len(calls) == tensors, len(calls)
    stepped = set()
    for name, operands, line in calls:
        # result 1, the successor, lies on the operand it was made from
        aliased = re.search(
            r"output_to_operand_aliasing=\{\{1\}: \((\d+), \{\}\)\}", line)
        assert aliased, line[:400]
        tensor = operands[int(aliased.group(1))]
        assert tensor in parameters, (tensor, line[:400])
        stepped.add(tensor)
        # the successor is read off this call and nothing else
        assert re.search(re.escape(state_type) + r"\S* get-tuple-element\("
                         + re.escape(name) + r"\), index=1", entry), name
    assert stepped == parameters


def _hlo_type(shape, dtype):
    name = {"float32": "f32", "bfloat16": "bf16"}[jnp.dtype(dtype).name]
    return name + "[" + ",".join(map(str, shape)) + "]"


# ``bytes accessed`` of each cell's step program on the tree before the
# token select (commit 35ac25d, the same at both rungs), compiled with the
# options the chip's compile is given now (``STEP_COMPILER_OPTIONS``: XLA's
# count reads 5,367,156,224 / 8,007,884,800 / 18,796,433,408 with sliced
# prefetches, on both trees alike). The select, the three host rows and the
# ids kept for the next launch may add tens of KB (padded tiles of a few
# hundred bytes of ints), nothing of the pool's or the weights' size.
# ``qnext``: PR 35's program — each ``delta<j>`` stepped by a Mosaic call
# (XLA counts the call's operands and results whole) where 35ac25d's fusions
# read 20,230,184,960.
STEP_BYTES_BEFORE_THE_SELECT = {
    "gpt2m": 6_516_489_728, "olmoe": 8_252_233_728, "qnext": 15_237_463_040}


def _assert_step_reads_in_place(runtime, compiled, bound, temp_limit,
                                cell=None):
    """The pool is made only by 2 x slots row writes on the two donated
    parameters — no whole-pool ``copy`` (XLA's answer to a scatter: it
    re-lays the pool out and back, 6.5 GB of temporaries), no fusion that
    rewrites a pool (the one-hot blend: 3.3 GB) — and read only by the
    attention kernel, one Mosaic call a layer whose K and V operands are
    the pool parameters themselves: nothing outside a fusion, and no
    fusion's result, holds one layer's K or V, whole or cut (a slice that
    reached the kernel would be such a copy, every layer, every step)."""
    import re
    pool_shape, pool_dtype = runtime.cache_spec()[0]
    layers, slots, max_len, row = pool_shape
    pool_type = _hlo_type(pool_shape, pool_dtype)
    results = _entry_results(compiled)
    makers = [op for kind, op in results if kind.startswith(pool_type)]
    assert sorted(set(makers)) == ["dynamic-update-slice", "parameter"], (
        sorted(set(makers)))
    assert makers.count("dynamic-update-slice") == 2 * slots
    assert makers.count("parameter") == 2
    for length in {bound, max_len}:
        for view in ((slots, length, row), (1, slots, length, row)):
            layer_type = _hlo_type(view, pool_dtype)
            assert not [r for r in results if r[0].startswith(layer_type)], (
                [r for r in results if r[0].startswith(layer_type)])
    entry = _entry(compiled)
    pools = re.findall(r"(%\S+) = " + re.escape(pool_type)
                       + r"\S* parameter\(", entry)
    assert len(pools) == 2, pools
    kernels = _mosaic_calls(compiled, "decode_attention")
    assert len(kernels) == layers, len(kernels)
    for _, operands, _ in kernels:
        assert all(pool in operands for pool in pools), operands
    # every Mosaic call of the program is one of the two kernels it names
    assert len(kernels) + len(_mosaic_calls(compiled, "state_update")) == (
        compiled.as_text().count('custom_call_target="tpu_custom_call"'))
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < temp_limit, memory.temp_size_in_bytes
    # Both pool tensors are aliased input to output: the pool exists once.
    assert memory.alias_size_in_bytes >= runtime.cache_nbytes()
    if cell is not None:
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, list) else cost
        added = cost["bytes accessed"] - STEP_BYTES_BEFORE_THE_SELECT[cell]
        assert 0 <= added < 65_536, added
    return memory


@pytest.fixture(scope="module")
def gpt2m_cell():
    from ai4e_tpu.models.seqformer import SeqFormerLM, create_seqformer_lm
    return _benchmark_cell(
        "gpt2-medium.json", create_seqformer_lm, SeqFormerLM,
        ("vocab_size", "max_len", "dim", "depth", "heads"))


@pytest.fixture(scope="module")
def olmoe_cell():
    from ai4e_tpu.models.olmoe import OlmoeLM, create_olmoe_lm
    return _benchmark_cell(
        "olmoe-1b-7b.json", create_olmoe_lm, OlmoeLM,
        ("vocab_size", "dim", "depth", "heads", "experts",
         "experts_per_token", "expert_dim", "rms_eps", "rope_theta"))


@pytest.mark.parametrize("rung", [0, 1])
def test_decode_step_at_the_benchmark_cell_writes_rows_in_place(
        v5e_sharding, gpt2m_cell, rung):
    """The ``step`` programs ``PagedDecodeRuntime`` builds, donated, at the
    shape of the benchmark's ``gpt2m.chat`` cell (GPT-2-medium, 32 slots,
    a float32 pool), one case a rung of its ladder (768 / 1,024 attended
    positions): ``_assert_step_reads_in_place``. About 6 s each."""
    runtime, spec = gpt2m_cell
    assert runtime.step_bounds == (768, 1024)
    assert runtime.cache_spec() == 2 * (((24, 32, 1024, 1024), jnp.float32),)
    bound = runtime.step_bounds[rung]
    _assert_step_reads_in_place(
        runtime, _compile_step(runtime, v5e_sharding, bound), bound, 0.5e9,
        cell="gpt2m")


@pytest.mark.parametrize("rung", [0, 1])
def test_olmoe_step_at_the_benchmark_cell_writes_rows_in_place(
        v5e_sharding, olmoe_cell, rung):
    """The same, for the ``olmoe.decode`` cell, read from
    ``benchmark/configs/olmoe-1b-7b.json``: a bfloat16 pool of
    ``[8,32,16,2048,128]`` is another layout (bf16 tiles, head dimension
    128), and every rung (1,536 / 2,048) must still make it only by
    row writes, with the pool aliased input to output. At the top rung,
    the whole worker's memory too: weights + pool + the largest program's
    temporaries (the top prefill bucket) stay under the 15 GB line the
    configuration states. About 4 s and 10 s."""
    runtime, spec = olmoe_cell
    assert runtime.step_bounds == (1536, 2048)
    assert runtime.cache_spec() == 2 * (((8, 32, 2048, 2048), jnp.bfloat16),)
    bound = runtime.step_bounds[rung]
    memory = _assert_step_reads_in_place(
        runtime, _compile_step(runtime, v5e_sharding, bound), bound, 0.1e9,
        cell="olmoe")
    if bound < runtime.max_len:
        return

    top = runtime.prompt_buckets[-1]
    assert top == spec["max_len"] == 2048
    prefill = runtime._programs["prefill"].lower(
        _on(v5e_sharding, runtime.servable.params),
        _on(v5e_sharding, ((1, top), jnp.int32)),
        _on(v5e_sharding, ((1,), jnp.int32))).compile().memory_analysis()
    resident = memory.argument_size_in_bytes      # weights + pool (+ ints)
    assert 11.3e9 < resident < 11.5e9, resident
    peak = resident + max(memory.temp_size_in_bytes,
                          prefill.temp_size_in_bytes
                          + prefill.output_size_in_bytes)
    assert peak < 15e9, peak


@pytest.fixture(scope="module")
def qnext_cell():
    from ai4e_tpu.models.qwen3_next import Qwen3NextLM, create_qwen3_next_lm
    from benchmark.references.qwen3_next import MODEL_KEYS
    return _benchmark_cell("qwen3-next-80b-a3b.json", create_qwen3_next_lm,
                           Qwen3NextLM, MODEL_KEYS)


@pytest.mark.parametrize("rung", [0, 1])
def test_qnext_step_at_the_benchmark_cell_moves_no_pool(
        v5e_sharding, qnext_cell, rung):
    """The same, for the ``qnext.docqa`` cell
    (``benchmark/configs/qwen3-next-80b-a3b.json``): a K/V pool of the three
    full-attention layers only, rows of 1 KB under sixteen query heads (one
    Mosaic kernel a K/V layer, the grouped-head form), made only by row
    writes; and beside it the state pool — nine ``f32[32,32,128,128]``
    tensors, each advanced by one ``state_update`` Mosaic call on the donated
    parameter itself, and nine convolution tails — every tensor aliased input
    to output, with temporaries smaller than ONE state tensor: no copy of a
    state tensor, of the state pool or of the K/V pool exists while the step
    runs. At the top rung, the whole worker's memory: weights + both pools +
    the widest prefill's (2,048, and the cache length 3,072 the runtime adds)
    temporaries and outputs stay under the 15 GB line. About 8 s and 60 s."""
    from ai4e_tpu.ops import state_pool
    runtime, spec = qnext_cell
    assert runtime.step_bounds == (2304, 3072)
    assert runtime.cache_spec() == 2 * (((3, 32, 3072, 512), jnp.bfloat16),)
    state = runtime.state_spec()
    assert len(state) == 18
    assert state[0] == ("delta0", (32, 128, 128), jnp.float32)
    one_state = 32 * 32 * 128 * 128 * 4
    bound = runtime.step_bounds[rung]
    compiled = _compile_step(runtime, v5e_sharding, bound)
    memory = _assert_step_reads_in_place(runtime, compiled, bound, one_state,
                                         cell="qnext")
    pools = runtime.cache_nbytes()
    assert pools == 2 * 3 * 32 * 3072 * 512 * 2 + state_pool.nbytes(state, 32)
    assert memory.alias_size_in_bytes >= pools
    # nothing makes a state tensor by a plain copy (a re-layout): each is
    # stepped where it lies by its own Mosaic call
    _assert_state_steps_in_place(
        compiled, _hlo_type((32, 32, 128, 128), jnp.float32), 9)
    if bound < runtime.max_len:
        return

    resident = memory.argument_size_in_bytes   # weights + pools (+ ints)
    assert 12.0e9 < resident < 12.2e9, resident
    # the cell's widest bucket, and the cache length the runtime adds
    assert runtime.max_len == spec["max_len"] == 3072
    for top in (2048, runtime.max_len):
        prefill = runtime._programs["prefill"].lower(
            _on(v5e_sharding, runtime.servable.params),
            _on(v5e_sharding, ((1, top), jnp.int32)),
            _on(v5e_sharding, ((1,), jnp.int32))).compile().memory_analysis()
        peak = resident + max(memory.temp_size_in_bytes,
                              prefill.temp_size_in_bytes
                              + prefill.output_size_in_bytes)
        assert peak < 15e9, (top, peak)


@pytest.fixture(scope="module")
def granite_cell():
    from ai4e_tpu.models.granite_hybrid import (GraniteHybridLM,
                                                create_granite_hybrid_lm)
    from benchmark.references.granite_hybrid import MODEL_KEYS

    def model(**dims):   # the spec's JSON list as the module's tuple
        return GraniteHybridLM(**dict(dims, attention_layers=tuple(
            dims["attention_layers"])))

    return _benchmark_cell("granite-4.0-h-micro.json",
                           create_granite_hybrid_lm, model, MODEL_KEYS)


@pytest.mark.parametrize("rung", [0, 1])
def test_granite_step_at_the_benchmark_cell_moves_no_pool(
        v5e_sharding, granite_cell, rung):
    """The same, for the ``granite.burstchat`` cell
    (``benchmark/configs/granite-4.0-h-micro.json``), the whole model: a K/V
    pool of the four attention layers, rows of 1 KB under 32 query heads on 8
    K/V heads of 64 (one Mosaic kernel a K/V layer, the narrow-group form),
    made only by row writes; and beside it the state pool — 36
    ``f32[64,128,4096]`` tensors (4.83 GB: a slot's state as ``(N, H · P)``)
    and 36 convolution tails — every tensor aliased input to output, each
    state advanced by ONE ``state_update`` Mosaic call on the donated
    parameter itself, which reads a live slot's ``S`` once, writes its
    successor and takes ``S C`` from the same pass, with temporaries smaller
    than one state tensor. At the top
    rung, the whole worker's memory: weights (the tied table once) + both
    pools + the widest prefill's (512, and the cache length 1,024 the runtime
    adds) temporaries and outputs stay under the 15 GB line. About 8 s and
    50 s."""
    from ai4e_tpu.ops import state_pool
    runtime, spec = granite_cell
    assert runtime.step_bounds == (768, 1024)
    assert runtime.cache_spec() == 2 * (((4, 64, 1024, 512), jnp.bfloat16),)
    state = runtime.state_spec()
    assert len(state) == 72
    assert state[0] == ("ssm0", (128, 64 * 64), jnp.float32)
    assert state[1] == ("conv0", (3, 4352), jnp.bfloat16)
    one_state = 64 * 64 * 64 * 128 * 4
    bound = runtime.step_bounds[rung]
    compiled = _compile_step(runtime, v5e_sharding, bound)
    memory = _assert_step_reads_in_place(runtime, compiled, bound, one_state)
    pools = runtime.cache_nbytes()
    assert pools == 2 * 4 * 64 * 1024 * 512 * 2 + state_pool.nbytes(state, 64)
    assert memory.alias_size_in_bytes >= pools
    # a state tensor is a parameter or the result of the Mosaic call that
    # steps it where it lies: nothing copies or re-lays one
    _assert_state_steps_in_place(
        compiled, _hlo_type((64, 128, 4096), jnp.float32), 36)
    # the table is one parameter: embedding and head read the same array
    table = _hlo_type((spec["vocab_size"], spec["dim"]), jnp.bfloat16)
    assert [op for kind, op in _entry_results(compiled)
            if kind.startswith(table)] == ["parameter"]
    if bound < runtime.max_len:
        return

    resident = memory.argument_size_in_bytes   # weights + pools (+ ints)
    assert 11.7e9 < resident < 11.9e9, resident
    assert runtime.max_len == spec["max_len"] == 1024
    for top in (512, runtime.max_len):
        prefill = runtime._programs["prefill"].lower(
            _on(v5e_sharding, runtime.servable.params),
            _on(v5e_sharding, ((1, top), jnp.int32)),
            _on(v5e_sharding, ((1,), jnp.int32))).compile().memory_analysis()
        peak = resident + max(memory.temp_size_in_bytes,
                              prefill.temp_size_in_bytes
                              + prefill.output_size_in_bytes)
        assert peak < 15e9, (top, peak)


def test_decode_kernel_with_a_narrow_group_compiles(v5e_sharding):
    """The decode-attention kernel at the narrow-group shape alone: 32 query
    heads on 8 K/V heads of 64 — a group of 4, no whole sublane tile, a K/V
    head on half a lane tile — one block of the 1,024 positions of a 1 KB
    row, with a scale handed over, by Mosaic."""
    from ai4e_tpu.ops import kv_pool
    pool = _on(v5e_sharding, ((4, 64, 1024, 512), jnp.bfloat16))
    q = _on(v5e_sharding, ((64, 32, 64), jnp.bfloat16))
    new = _on(v5e_sharding, ((64, 8, 64), jnp.bfloat16))
    ints = _on(v5e_sharding, ((64,), jnp.int32))
    compiled = _compile(
        lambda q, k_new, v_new, k, v, position: kv_pool.decode_attention(
            q, k_new, v_new, k, v, 1, position, 768, interpret=False,
            scale=1.0 / 64),
        q, new, new, pool, pool, ints)
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_kernel_with_grouped_heads_compiles(v5e_sharding):
    """The decode-attention kernel at the grouped shape alone: sixteen
    query heads on two K/V heads of 256, blocks of 1,024 positions of a 1 KB
    row, by Mosaic."""
    from ai4e_tpu.ops import kv_pool
    pool = _on(v5e_sharding, ((3, 32, 3072, 512), jnp.bfloat16))
    q = _on(v5e_sharding, ((32, 16, 256), jnp.bfloat16))
    new = _on(v5e_sharding, ((32, 2, 256), jnp.bfloat16))
    ints = _on(v5e_sharding, ((32,), jnp.int32))
    compiled = _compile(
        lambda q, k_new, v_new, k, v, position: kv_pool.decode_attention(
            q, k_new, v_new, k, v, 1, position, 2304, interpret=False),
        q, new, new, pool, pool, ints)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("pool,heads,value,bound", [
    ((3, 16, 12544, 640), 128, 512, 9472),
    ((3, 16, 12544, 640), 128, 512, 12544),
    ((3, 16, 512, 1152), 64, 1024, 512)])
def test_latent_kernel_compiles(v5e_sharding, pool, heads, value, bound,
                                masked):
    """The latent read at the ``dots3.longdoc`` cell's shapes alone, by
    Mosaic: 128 heads on one 640-lane row whose first 512 lanes are the
    value, blocks of 768 positions (the last one of the cache cut), with and
    without the selection's mask; and the window's ring, 64 heads on 1,152
    lanes."""
    from ai4e_tpu.ops import kv_pool
    slots = pool[1]
    rows = _on(v5e_sharding, (pool, jnp.bfloat16))
    q = _on(v5e_sharding, ((slots, heads, pool[3]), jnp.bfloat16))
    new = _on(v5e_sharding, ((slots, pool[3]), jnp.bfloat16))
    ints = _on(v5e_sharding, ((slots,), jnp.int32))
    keep = _on(v5e_sharding, ((slots, bound), jnp.bool_))

    def read(q, new, rows, position, keep, own):
        return kv_pool.latent_decode_attention(
            q, new, rows, 1, position, value=value, bound=bound, scale=0.07,
            keep=keep if masked else None, own=own if masked else None,
            interpret=False)

    compiled = _compile(read, q, new, rows, ints, keep, ints)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("p", [3072, 12288, 12544])
@pytest.mark.parametrize("heads,dqk,window,masked", [
    (32, 192, None, True), (32, 256, 513, False), (32, 192, None, False)])
def test_prompt_kernels_compile(v5e_sharding, p, heads, dqk, window, masked):
    """The prefill's two kernels at the ``dots3.longdoc`` cell's shapes
    alone, by Mosaic: a group of 32 heads, keys 192 (256 on the sliding
    layers) wide against values of 128, under the selection's one-byte mask
    or over the window's band, at the shortest and the longest buckets
    (blocks of 512) and the cache's own length (blocks of 256), 4 heads a
    grid step; ``xing4``'s form of the same call, 32 heads with
    neither mask nor window; the indexer's 64 heads of 128 for a block
    of 256 queries, and the selection of 2,048 of that block's scores. A
    call's blocks fit the VMEM it asks Mosaic for, by the kernel's own count
    and by the compile."""
    import importlib
    from ai4e_tpu.ops import kv_pool
    from ai4e_tpu.ops.pallas.validate import VMEM_PHYSICAL_BYTES
    flash = importlib.import_module("ai4e_tpu.ops.pallas.flash_attention")
    q, k = (_on(v5e_sharding, ((p, heads, dqk), jnp.bfloat16))
            for _ in range(2))
    v = _on(v5e_sharding, ((p, heads, 128), jnp.bfloat16))
    mask = _on(v5e_sharding, ((p, p), jnp.int8))

    def attend(q, k, v, mask):
        return kv_pool.prompt_attention(
            q, k, v, 0.07, mask=mask if masked else None, window=window,
            interpret=False)

    assert "tpu_custom_call" in _compile(attend, q, k, v, mask).as_text()
    block = flash._prompt_block(p)
    group = flash._head_group(heads, block, dqk, 128, 2, masked)
    assert group == 4
    assert (flash.prompt_vmem_bytes(group, block, dqk, 128, 2, masked)
            <= flash.PROMPT_VMEM_BYTES <= VMEM_PHYSICAL_BYTES // 2)
    if not masked:
        return
    scores = _compile(
        lambda iq, ik, w, first: flash.index_scores(iq, ik, w, first,
                                                    interpret=False),
        _on(v5e_sharding, ((64, 256, 128), jnp.bfloat16)),
        _on(v5e_sharding, ((p, 128), jnp.bfloat16)),
        _on(v5e_sharding, ((256, 64), jnp.float32)),
        _on(v5e_sharding, ((), jnp.int32)))
    assert "tpu_custom_call" in scores.as_text()
    from ai4e_tpu.ops.pallas import select_top
    selection = _compile(
        lambda scores, valid: select_top.select_top(scores, valid, 2048,
                                                    interpret=False),
        _on(v5e_sharding, ((256, p), jnp.float32)),
        _on(v5e_sharding, ((256, p), jnp.int8)))
    assert "tpu_custom_call" in selection.as_text()
    assert select_top.vmem_bytes(p) <= VMEM_PHYSICAL_BYTES // 2


@pytest.fixture(scope="module")
def dots3_cell():
    from ai4e_tpu.models.dots3 import Dots3LM, create_dots3_lm
    from benchmark.references.dots3 import NOT_MODEL_KEYS
    with open(os.path.join(REPO, "benchmark", "configs",
                           "dots3-note-prev.json")) as f:
        spec = json.load(f)["models"]["models"][0]

    def model(**dims):   # the spec's JSON list as the module's tuple
        return Dots3LM(**dict(dims, layer_types=tuple(dims["layer_types"])))

    return _benchmark_cell(
        "dots3-note-prev.json", create_dots3_lm, model,
        [key for key in spec if key not in NOT_MODEL_KEYS])


@pytest.mark.parametrize("rung", [0, 1])
def test_dots3_step_at_the_benchmark_cell_moves_no_pool(
        v5e_sharding, dots3_cell, rung):
    """The ``dots3.longdoc`` cell (``benchmark/configs/dots3-note-prev.json``):
    three tensors of rows — latent ``(3, 16, 12544, 640)``, index ``(3, 16,
    12544, 128)`` and the window's ring ``(3, 16, 512, 1152)`` — each made
    only by row writes on its donated parameter (no copy, no fusion that
    rewrites one), one ``latent_attention`` Mosaic call a layer whose pool
    operand is the parameter itself, every tensor aliased input to output. At
    the top rung, the whole worker's memory: weights + pools + the widest
    prefill's (12,288, and the cache length 12,544 the runtime adds)
    temporaries and outputs stay under 15.5 GB."""
    import re
    runtime, spec = dots3_cell
    assert runtime.step_bounds == (9472, 12544)
    shapes = ((3, 16, 12544, 640), (3, 16, 12544, 128), (3, 16, 512, 1152))
    assert runtime.cache_spec() == tuple((s, jnp.bfloat16) for s in shapes)
    bound = runtime.step_bounds[rung]
    compiled = _compile_step(runtime, v5e_sharding, bound)
    results, entry = _entry_results(compiled), _entry(compiled)
    kernels = _mosaic_calls(compiled, "latent_attention")
    assert len(kernels) == 6, len(kernels)
    for shape in shapes:
        pool_type = _hlo_type(shape, jnp.bfloat16)
        makers = [op for kind, op in results if kind.startswith(pool_type)]
        assert sorted(set(makers)) == ["dynamic-update-slice",
                                       "parameter"], (shape, set(makers))
        assert makers.count("dynamic-update-slice") == 16
        (pool,) = re.findall(r"(%\S+) = " + re.escape(pool_type)
                             + r"\S* parameter\(", entry)
        if shape[-1] != 128:   # the index keys are read by XLA's product
            assert sum(pool in ops for _, ops, _ in kernels) == 3
    assert len(kernels) == compiled.as_text().count(
        'custom_call_target="tpu_custom_call"')
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= runtime.cache_nbytes()
    assert runtime.cache_nbytes() == 2 * 3 * 16 * (
        12544 * 640 + 12544 * 128 + 512 * 1152)
    assert memory.temp_size_in_bytes < 1.0e9, memory.temp_size_in_bytes
    if bound < runtime.max_len:
        return

    resident = memory.argument_size_in_bytes   # weights + pools (+ ints)
    assert 10.9e9 < resident < 11.2e9, resident
    assert runtime.max_len == spec["max_len"] == 12544
    import importlib
    flash = importlib.import_module("ai4e_tpu.ops.pallas.flash_attention")
    for top in (12288, runtime.max_len):
        with pytest.MonkeyPatch.context() as patch:
            # the prefill's kernels by Mosaic, as on the chip (the default
            # backend here is the CPU)
            patch.setattr(flash, "resolve_interpret",
                          lambda kernel, interpret: False)
            prefill = runtime._programs["prefill"].lower(
                _on(v5e_sharding, runtime.servable.params),
                _on(v5e_sharding, ((1, top), jnp.int32)),
                _on(v5e_sharding, ((1,), jnp.int32))).compile()
        assert len(_mosaic_calls(prefill, "prompt_attention")) == 3 * 4 + 3 * 2
        prefill = prefill.memory_analysis()
        peak = resident + max(memory.temp_size_in_bytes,
                              prefill.temp_size_in_bytes
                              + prefill.output_size_in_bytes)
        print(f"dots3 cell: resident {resident}, step temporaries "
              f"{memory.temp_size_in_bytes}, prefill {top}: temporaries "
              f"{prefill.temp_size_in_bytes} + outputs "
              f"{prefill.output_size_in_bytes}, peak {peak}")
        assert peak < 15.5e9, (top, peak, prefill.temp_size_in_bytes)


@pytest.fixture(scope="module")
def xing4_cell():
    from ai4e_tpu.models.xing4 import Xing4LM, create_xing4_lm
    from benchmark.references.xing4 import NOT_MODEL_KEYS
    with open(os.path.join(REPO, "benchmark", "configs",
                           "xing4.0-29b-a4b.json")) as f:
        spec = json.load(f)["models"]["models"][0]
    return _benchmark_cell(
        "xing4.0-29b-a4b.json", create_xing4_lm, Xing4LM,
        [key for key in spec if key not in NOT_MODEL_KEYS])


@pytest.mark.parametrize("rung", [0, 1])
def test_xing4_step_at_the_benchmark_cell_moves_no_pool(
        v5e_sharding, xing4_cell, rung):
    """The ``xing4.reason`` cell (``benchmark/configs/xing4.0-29b-a4b.json``):
    one tensor of latent rows ``(7, 32, 4096, 640)`` made only by row writes
    on its donated parameter, one ``latent_attention`` Mosaic call a layer
    whose pool operand is the parameter itself, aliased input to output. At
    the top rung, the whole worker's memory: weights + pool + the widest
    prefill's (2,048, and the cache length 4,096 the runtime adds)
    temporaries and outputs stay under 15 GB."""
    import importlib
    import re
    from ai4e_tpu.ops.pallas import mhc_rows, validate
    runtime, spec = xing4_cell
    assert runtime.step_bounds == (3072, 4096)
    shape = (7, 32, 4096, 640)
    assert runtime.cache_spec() == ((shape, jnp.bfloat16),)
    bound = runtime.step_bounds[rung]
    compiled = _compile_step(runtime, v5e_sharding, bound)
    results, entry = _entry_results(compiled), _entry(compiled)
    kernels = _mosaic_calls(compiled, "latent_attention")
    assert len(kernels) == 7, len(kernels)
    pool_type = _hlo_type(shape, jnp.bfloat16)
    makers = [op for kind, op in results if kind.startswith(pool_type)]
    assert sorted(set(makers)) == ["dynamic-update-slice", "parameter"]
    assert makers.count("dynamic-update-slice") == 32
    (pool,) = re.findall(r"(%\S+) = " + re.escape(pool_type)
                         + r"\S* parameter\(", entry)
    assert sum(pool in ops for _, ops, _ in kernels) == 7
    # no other Mosaic call: the step's 32 slots of four streams (0.9 MB)
    # keep the ``jax.numpy`` hyper-connections (``mhc.ROWS_KERNEL_BYTES``)
    assert len(kernels) == compiled.as_text().count(
        'custom_call_target="tpu_custom_call"')
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= runtime.cache_nbytes()
    assert runtime.cache_nbytes() == 2 * 7 * 32 * 4096 * 640
    assert memory.temp_size_in_bytes < 0.5e9, memory.temp_size_in_bytes
    if bound < runtime.max_len:
        return

    resident = memory.argument_size_in_bytes   # weights + pool (+ ints)
    assert 12.2e9 < resident < 12.3e9, resident
    flash = importlib.import_module("ai4e_tpu.ops.pallas.flash_attention")
    assert (validate.mhc_rows_vmem_bytes(4, 3584)
            <= validate.VMEM_PHYSICAL_BYTES // 2)
    for top in (2048, runtime.max_len):
        with pytest.MonkeyPatch.context() as patch:
            for kernel in (flash, mhc_rows):
                patch.setattr(kernel, "resolve_interpret",
                              lambda kernel, interpret: False)
            prefill = runtime._programs["prefill"].lower(
                _on(v5e_sharding, runtime.servable.params),
                _on(v5e_sharding, ((1, top), jnp.int32)),
                _on(v5e_sharding, ((1,), jnp.int32))).compile()
        assert len(_mosaic_calls(prefill, "prompt_attention")) == 7
        # a prompt's fourteen sublayers: each half one read of its rows
        assert len(_mosaic_calls(prefill, "mhc_pre")) == 14
        assert len(_mosaic_calls(prefill, "mhc_post")) == 14
        prefill = prefill.memory_analysis()
        peak = resident + max(memory.temp_size_in_bytes,
                              prefill.temp_size_in_bytes
                              + prefill.output_size_in_bytes)
        print(f"xing4 cell: resident {resident}, step temporaries "
              f"{memory.temp_size_in_bytes}, prefill {top}: temporaries "
              f"{prefill.temp_size_in_bytes} + outputs "
              f"{prefill.output_size_in_bytes}, peak {peak}")
        assert peak < 15.0e9, (top, peak, prefill.temp_size_in_bytes)


@pytest.fixture(scope="module")
def ling3_cell():
    from ai4e_tpu.models.ling3 import Ling3LM, create_ling3_lm
    from benchmark.references.ling3 import NOT_MODEL_KEYS
    with open(os.path.join(REPO, "benchmark", "configs",
                           "ling-3.0-flash.json")) as f:
        spec = json.load(f)["models"]["models"][0]
    limits = ("expert_swiglu_limits", "shared_swiglu_limits")
    keys = [key for key in spec if key not in NOT_MODEL_KEYS + limits]
    spec["route_groups"] = tuple(spec["route_groups"])
    return _benchmark_cell("ling-3.0-flash.json", create_ling3_lm, Ling3LM,
                           keys)


@pytest.mark.parametrize("rung", [0, 1])
def test_ling3_step_at_the_benchmark_cell_moves_no_pool(
        v5e_sharding, ling3_cell, rung):
    """The ``ling3.toolctx`` cell (``benchmark/configs/ling-3.0-flash.json``):
    one tensor of latent rows ``(1, 96, 9216, 640)`` made only by row writes
    on its donated parameter, read by ONE ``latent_attention`` Mosaic call;
    six ``f32[96,32,128,128]`` KDA states, each advanced by one
    ``state_update`` Mosaic call on the donated parameter itself, and six
    convolution tails, every tensor aliased input to output with temporaries
    smaller than ONE state tensor. At the top rung, the whole worker's
    memory: weights + pools + the temporaries and outputs of the 2,048 and
    8,192 prefills and of the cache length 9,216 the runtime adds stay under
    15 GB, each prefill with its ``prompt_attention`` and its six
    ``kda_chunk`` calls by Mosaic."""
    import importlib
    from ai4e_tpu.ops import state_pool
    from ai4e_tpu.ops.pallas import kda_chunk
    runtime, spec = ling3_cell
    assert runtime.step_bounds == (6912, 9216)
    shape = (1, 96, 9216, 640)
    assert runtime.cache_spec() == ((shape, jnp.bfloat16),)
    state = runtime.state_spec()
    assert len(state) == 12
    assert state[0] == ("kda0", (32, 128, 128), jnp.float32)
    assert state[1] == ("conv0", (3, 12288), jnp.bfloat16)
    one_state = 96 * 32 * 128 * 128 * 4
    bound = runtime.step_bounds[rung]
    compiled = _compile_step(runtime, v5e_sharding, bound)
    assert len(_mosaic_calls(compiled, "latent_attention")) == 1
    _assert_state_steps_in_place(
        compiled, _hlo_type((96, 32, 128, 128), jnp.float32), 6)
    memory = compiled.memory_analysis()
    pools = runtime.cache_nbytes()
    assert pools == 2 * 96 * 9216 * 640 + state_pool.nbytes(state, 96)
    assert memory.alias_size_in_bytes >= pools
    assert memory.temp_size_in_bytes < one_state, memory.temp_size_in_bytes
    if bound < runtime.max_len:
        return

    resident = memory.argument_size_in_bytes   # weights + pools (+ ints)
    assert 12.7e9 < resident < 13.0e9, resident
    flash = importlib.import_module("ai4e_tpu.ops.pallas.flash_attention")
    for top in (2048, 8192, runtime.max_len):
        with pytest.MonkeyPatch.context() as patch:
            for kernel in (flash, kda_chunk):
                patch.setattr(kernel, "resolve_interpret",
                              lambda kernel, interpret: False)
            prefill = runtime._programs["prefill"].lower(
                _on(v5e_sharding, runtime.servable.params),
                _on(v5e_sharding, ((1, top), jnp.int32)),
                _on(v5e_sharding, ((1,), jnp.int32))).compile()
        assert len(_mosaic_calls(prefill, "prompt_attention")) == 1
        assert len(_mosaic_calls(prefill, "kda_chunk")) == 6
        prefill = prefill.memory_analysis()
        peak = resident + max(memory.temp_size_in_bytes,
                              prefill.temp_size_in_bytes
                              + prefill.output_size_in_bytes)
        print(f"ling3 cell: resident {resident}, step temporaries "
              f"{memory.temp_size_in_bytes}, prefill {top}: temporaries "
              f"{prefill.temp_size_in_bytes} + outputs "
              f"{prefill.output_size_in_bytes}, peak {peak}")
        assert peak < 15.0e9, (top, peak, prefill.temp_size_in_bytes)


@pytest.mark.parametrize("bound", [13056, 17408])
def test_latent_kernel_compiles_on_a_row_that_is_all_value(v5e_sharding,
                                                           bound):
    """The latent read at the ``glm53.longctx`` cell's shapes alone, by
    Mosaic: 64 heads on one 512-lane row that is its own value (``value ==
    row``: no rotary lanes and nothing padded), blocks of 1,024 positions,
    under the selection's mask."""
    from ai4e_tpu.ops import kv_pool
    pool = (1, 64, 17408, 512)
    rows = _on(v5e_sharding, (pool, jnp.bfloat16))
    q = _on(v5e_sharding, ((64, 64, 512), jnp.bfloat16))
    new = _on(v5e_sharding, ((64, 512), jnp.bfloat16))
    ints = _on(v5e_sharding, ((64,), jnp.int32))
    keep = _on(v5e_sharding, ((64, bound), jnp.bool_))
    assert kv_pool.read_block(pool, jnp.bfloat16) == 1024

    def read(q, new, rows, position, keep):
        return kv_pool.latent_decode_attention(
            q, new, rows, 0, position, value=512, bound=bound, scale=1 / 16,
            keep=keep, interpret=False)

    compiled = _compile(read, q, new, rows, ints, keep)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("p", [4096, 16384, 17408])
def test_prompt_kernels_compile_at_keys_and_values_256_wide(v5e_sharding, p):
    """The sparse layer's prefill kernels at the ``glm53.longctx`` cell's
    shapes alone, by Mosaic: a group of 16 heads with keys and values both
    256 wide under the selection's one-byte mask, at the shortest and the
    longest buckets and the cache's own length; the indexer's 32 heads of 128
    for a block of 256 queries against ``p / 4`` pooled keys; and the
    selection of 511 of that block's scores. A call's blocks fit the VMEM it
    asks Mosaic for, by the kernel's own count and by the compile."""
    import importlib
    from ai4e_tpu.ops import kv_pool
    from ai4e_tpu.ops.pallas import select_top
    from ai4e_tpu.ops.pallas.validate import VMEM_PHYSICAL_BYTES
    flash = importlib.import_module("ai4e_tpu.ops.pallas.flash_attention")
    q, k, v = (_on(v5e_sharding, ((p, 16, 256), jnp.bfloat16))
               for _ in range(3))
    mask = _on(v5e_sharding, ((p, p), jnp.int8))

    def attend(q, k, v, mask):
        return kv_pool.prompt_attention(q, k, v, 1 / 16, mask=mask,
                                        interpret=False)

    assert "tpu_custom_call" in _compile(attend, q, k, v, mask).as_text()
    block = flash._prompt_block(p)
    group = flash._head_group(16, block, 256, 256, 2, True)
    assert (flash.prompt_vmem_bytes(group, block, 256, 256, 2, True)
            <= flash.PROMPT_VMEM_BYTES <= VMEM_PHYSICAL_BYTES // 2)
    scores = _compile(
        lambda iq, ik, w, first: flash.index_scores(iq, ik, w, first,
                                                    interpret=False),
        _on(v5e_sharding, ((32, 256, 128), jnp.bfloat16)),
        _on(v5e_sharding, ((p // 4, 128), jnp.bfloat16)),
        _on(v5e_sharding, ((256, 32), jnp.float32)),
        _on(v5e_sharding, ((), jnp.int32)))
    assert "tpu_custom_call" in scores.as_text()
    selection = _compile(
        lambda scores, valid: select_top.select_top(scores, valid, 511,
                                                    interpret=False),
        _on(v5e_sharding, ((256, p // 4), jnp.float32)),
        _on(v5e_sharding, ((256, p // 4), jnp.int8)))
    assert "tpu_custom_call" in selection.as_text()
    assert select_top.vmem_bytes(p // 4) <= VMEM_PHYSICAL_BYTES // 2


@pytest.fixture(scope="module")
def glm53_cell():
    from ai4e_tpu.models.glm5 import Glm5LM, create_glm5_lm
    from benchmark.references.glm5 import NOT_MODEL_KEYS
    with open(os.path.join(REPO, "benchmark", "configs",
                           "glm-5.3-flash.json")) as f:
        spec = json.load(f)["models"]["models"][0]

    def model(**dims):   # the spec's JSON lists as the module's tuples
        return Glm5LM(**dict(dims, layer_types=tuple(dims["layer_types"]),
                             mlp_types=tuple(dims["mlp_types"])))

    return _benchmark_cell(
        "glm-5.3-flash.json", create_glm5_lm, model,
        [key for key in spec if key not in NOT_MODEL_KEYS])


@pytest.mark.parametrize("rung", [0, 1])
def test_glm53_step_at_the_benchmark_cell_moves_no_pool(
        v5e_sharding, glm53_cell, rung):
    """The ``glm53.longctx`` cell (``benchmark/configs/glm-5.3-flash.json``):
    a tensor of latent rows ``(1, 64, 17408, 512)`` — a row that is all
    value — and one of pooled index keys ``(1, 64, 4352, 128)``, a row every
    four positions, each made only by row writes on its donated parameter;
    ONE ``latent_attention`` Mosaic call; four ``f32[64,64,128,128]`` KDA
    states, each advanced by one ``state_update`` Mosaic call on the donated
    parameter itself; four convolution tails and the open block's sum; every
    tensor aliased input to output with temporaries smaller than ONE state
    tensor. At the top rung, the whole worker's memory: weights + pools (at
    least 11.5 GB) + the temporaries and outputs of the 8,192 and 16,384
    prefills and of the cache length 17,408 the runtime adds stay within the
    chip's 16.9 GB (4.07 GB of temporaries at 16,384 by the compiler's count:
    15.9 GB; the chip's own allocator read a peak of 12.1 GB over a whole
    run, ``PERF.md`` section 4), each prefill with its sixteen-head
    ``prompt_attention`` calls and its four ``kda_chunk`` calls by Mosaic."""
    import importlib
    from ai4e_tpu.ops import state_pool
    from ai4e_tpu.ops.pallas import kda_chunk, mhc_rows, select_top, validate
    runtime, spec = glm53_cell
    assert runtime.step_bounds == (13056, 17408)
    shapes = ((1, 64, 17408, 512), (1, 64, 4352, 128))
    assert runtime.cache_spec() == tuple((s, jnp.bfloat16) for s in shapes)
    state = runtime.state_spec()
    assert len(state) == 9
    assert state[0] == ("kda0", (64, 128, 128), jnp.float32)
    assert state[1] == ("conv0", (3, 24576), jnp.bfloat16)
    assert state[8] == ("isum0", (128,), jnp.float32)
    one_state = 64 * 64 * 128 * 128 * 4
    bound = runtime.step_bounds[rung]
    with pytest.MonkeyPatch.context() as patch:
        # the step's selection of 511 of 4,352 blocks a slot is the kernel's
        # at the top rung (``kv_pool.SELECT_KERNEL_BYTES``)
        patch.setattr(select_top, "resolve_interpret",
                      lambda kernel, interpret: False)
        compiled = _compile_step(runtime, v5e_sharding, bound)
    assert len(_mosaic_calls(compiled, "latent_attention")) == 1
    assert len(_mosaic_calls(compiled, "select_top")) == rung
    # the step's 64 slots of four streams (2 MB) keep the ``jax.numpy``
    # hyper-connections (``mhc.ROWS_KERNEL_BYTES``)
    assert not _mosaic_calls(compiled, "mhc_")
    _assert_state_steps_in_place(
        compiled, _hlo_type((64, 64, 128, 128), jnp.float32), 4)
    results = _entry_results(compiled)
    for shape in shapes:
        pool_type = _hlo_type(shape, jnp.bfloat16)
        makers = [op for kind, op in results if kind.startswith(pool_type)]
        # The pooled keys are scored WHOLE (71 MB of a step's 10 GB): at the
        # top rung, where the scores read the tensor's every row, XLA hands
        # them one prefetched copy of it (at the lower rung a slice, of
        # another type); the latent rows, 17 times that, are never copied.
        allowed = {"dynamic-update-slice", "parameter"} | (
            {"copy-done"} if shape[3] == 128 and rung else set())
        assert set(makers) <= allowed, (shape, set(makers))
        assert makers.count("dynamic-update-slice") == 64
        assert makers.count("copy-done") <= 1
    memory = compiled.memory_analysis()
    pools = runtime.cache_nbytes()
    assert pools == (2 * 64 * (17408 * 512 + 4352 * 128)
                     + state_pool.nbytes(state, 64))
    assert memory.alias_size_in_bytes >= pools
    assert memory.temp_size_in_bytes < one_state, memory.temp_size_in_bytes
    if bound < runtime.max_len:
        return

    resident = memory.argument_size_in_bytes   # weights + pools (+ ints)
    assert 11.5e9 < resident < 12.0e9, resident
    flash = importlib.import_module("ai4e_tpu.ops.pallas.flash_attention")
    assert (validate.mhc_rows_vmem_bytes(4, 4096)
            <= validate.VMEM_PHYSICAL_BYTES // 2)
    for top in (8192, 16384, runtime.max_len):
        with pytest.MonkeyPatch.context() as patch:
            for kernel in (flash, kda_chunk, select_top, mhc_rows):
                patch.setattr(kernel, "resolve_interpret",
                              lambda kernel, interpret: False)
            prefill = runtime._programs["prefill"].lower(
                _on(v5e_sharding, runtime.servable.params),
                _on(v5e_sharding, ((1, top), jnp.int32)),
                _on(v5e_sharding, ((1,), jnp.int32))).compile()
        assert len(_mosaic_calls(prefill, "prompt_attention")) == 4
        assert len(_mosaic_calls(prefill, "kda_chunk")) == 4
        # a prompt's ten sublayers: each half one read of its rows, and no
        # float32 or re-laid copy of the rows between them
        assert len(_mosaic_calls(prefill, "mhc_pre")) == 10
        assert len(_mosaic_calls(prefill, "mhc_post")) == 10
        rows = [op for kind, op in _entry_results(prefill)
                if kind.startswith((f"f32[{top},16384]",
                                    f"bf16[{top},4,4096]",
                                    f"f32[{top},4,4096]"))]
        assert not rows, rows
        prefill = prefill.memory_analysis()
        peak = resident + max(memory.temp_size_in_bytes,
                              prefill.temp_size_in_bytes
                              + prefill.output_size_in_bytes)
        print(f"glm53 cell: resident {resident}, step temporaries "
              f"{memory.temp_size_in_bytes}, prefill {top}: temporaries "
              f"{prefill.temp_size_in_bytes} + outputs "
              f"{prefill.output_size_in_bytes}, peak {peak}")
        assert peak < 16.3e9, (top, peak, prefill.temp_size_in_bytes)


@pytest.mark.parametrize("bound", [12288, 16384])
def test_latent_kernel_compiles_at_64_heads_on_a_640_lane_row(v5e_sharding,
                                                              bound):
    """The latent read at the ``axk1.history`` cell's shapes alone, by
    Mosaic: 64 heads (``xing4``'s 32 doubled) on one 640-lane row whose first
    512 lanes are the value, every block under a slot's position, no mask,
    at both rungs of a 16,384-position cache."""
    from ai4e_tpu.ops import kv_pool
    pool = (7, 16, 16384, 640)
    rows = _on(v5e_sharding, (pool, jnp.bfloat16))
    q = _on(v5e_sharding, ((16, 64, 640), jnp.bfloat16))
    new = _on(v5e_sharding, ((16, 640), jnp.bfloat16))
    ints = _on(v5e_sharding, ((16,), jnp.int32))

    def read(q, new, rows, position):
        return kv_pool.latent_decode_attention(
            q, new, rows, 3, position, value=512, bound=bound,
            scale=192 ** -0.5 * 1.8133, interpret=False)

    compiled = _compile(read, q, new, rows, ints)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("p", [1024, 6144, 14336, 16384])
def test_prompt_kernel_compiles_at_64_heads(v5e_sharding, p):
    """The prefill's attention at the ``axk1.history`` cell's shapes alone,
    by Mosaic: 64 heads with keys 192 wide against values of 128, neither
    mask nor window, at its shortest bucket, at 6,144, at the traffic's cap
    14,336 (28 blocks of 512) and at the cache's own length; 4 heads a grid
    step, and the call's blocks fit the VMEM it asks Mosaic for."""
    import importlib
    from ai4e_tpu.ops import kv_pool
    from ai4e_tpu.ops.pallas.validate import VMEM_PHYSICAL_BYTES
    flash = importlib.import_module("ai4e_tpu.ops.pallas.flash_attention")
    q, k = (_on(v5e_sharding, ((p, 64, 192), jnp.bfloat16)) for _ in range(2))
    v = _on(v5e_sharding, ((p, 64, 128), jnp.bfloat16))

    def attend(q, k, v):
        return kv_pool.prompt_attention(q, k, v, 192 ** -0.5 * 1.8133,
                                        interpret=False)

    assert "tpu_custom_call" in _compile(attend, q, k, v).as_text()
    block = flash._prompt_block(p)
    assert block == 512
    group = flash._head_group(64, block, 192, 128, 2, False)
    assert group == 4
    assert (flash.prompt_vmem_bytes(group, block, 192, 128, 2, False)
            <= flash.PROMPT_VMEM_BYTES <= VMEM_PHYSICAL_BYTES // 2)


@pytest.fixture(scope="module")
def axk1_cell():
    from ai4e_tpu.models.axk1 import Axk1LM, create_axk1_lm
    from benchmark.references.axk1 import NOT_MODEL_KEYS
    with open(os.path.join(REPO, "benchmark", "configs",
                           "a.x-k1.json")) as f:
        spec = json.load(f)["models"]["models"][0]
    return _benchmark_cell(
        "a.x-k1.json", create_axk1_lm, Axk1LM,
        [key for key in spec if key not in NOT_MODEL_KEYS])


@pytest.mark.parametrize("rung", [0, 1])
def test_axk1_step_at_the_benchmark_cell_moves_no_pool(
        v5e_sharding, axk1_cell, rung):
    """The ``axk1.history`` cell (``benchmark/configs/a.x-k1.json``): one
    tensor of latent rows ``(7, 16, 16384, 640)`` made only by row writes on
    its donated parameter, one ``latent_attention`` Mosaic call a layer — 64
    heads on the row — whose pool operand is the parameter itself, aliased
    input to output, and no other Mosaic call. At the top rung, the whole
    worker's memory: weights + pool (at least 11 GB) + the temporaries and
    outputs of the 8,192 and 14,336 prefills and of the cache length 16,384
    the runtime adds stay within the chip's 16.9 GB, each prefill with one
    ``prompt_attention`` Mosaic call a layer."""
    import importlib
    import re
    runtime, spec = axk1_cell
    assert runtime.step_bounds == (12288, 16384)
    shape = (7, 16, 16384, 640)
    assert runtime.cache_spec() == ((shape, jnp.bfloat16),)
    assert runtime.state_spec() == ()
    bound = runtime.step_bounds[rung]
    compiled = _compile_step(runtime, v5e_sharding, bound)
    results, entry = _entry_results(compiled), _entry(compiled)
    kernels = _mosaic_calls(compiled, "latent_attention")
    assert len(kernels) == 7, len(kernels)
    pool_type = _hlo_type(shape, jnp.bfloat16)
    makers = [op for kind, op in results if kind.startswith(pool_type)]
    assert sorted(set(makers)) == ["dynamic-update-slice", "parameter"]
    assert makers.count("dynamic-update-slice") == 16
    (pool,) = re.findall(r"(%\S+) = " + re.escape(pool_type)
                         + r"\S* parameter\(", entry)
    assert sum(pool in ops for _, ops, _ in kernels) == 7
    assert len(kernels) == compiled.as_text().count(
        'custom_call_target="tpu_custom_call"')
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= runtime.cache_nbytes()
    assert runtime.cache_nbytes() == 2 * 7 * 16 * 16384 * 640
    assert memory.temp_size_in_bytes < 0.5e9, memory.temp_size_in_bytes
    if bound < runtime.max_len:
        return

    resident = memory.argument_size_in_bytes   # weights + pool (+ ints)
    assert 11.9e9 < resident < 12.2e9, resident
    flash = importlib.import_module("ai4e_tpu.ops.pallas.flash_attention")
    for top in (8192, 14336, runtime.max_len):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(flash, "resolve_interpret",
                          lambda kernel, interpret: False)
            prefill = runtime._programs["prefill"].lower(
                _on(v5e_sharding, runtime.servable.params),
                _on(v5e_sharding, ((1, top), jnp.int32)),
                _on(v5e_sharding, ((1,), jnp.int32))).compile()
        assert len(_mosaic_calls(prefill, "prompt_attention")) == 7
        prefill = prefill.memory_analysis()
        peak = resident + max(memory.temp_size_in_bytes,
                              prefill.temp_size_in_bytes
                              + prefill.output_size_in_bytes)
        print(f"axk1 cell: resident {resident}, step temporaries "
              f"{memory.temp_size_in_bytes}, prefill {top}: temporaries "
              f"{prefill.temp_size_in_bytes} + outputs "
              f"{prefill.output_size_in_bytes}, peak {peak}")
        assert peak < 16.3e9, (top, peak, prefill.temp_size_in_bytes)
