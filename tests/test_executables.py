"""The store of serialised executables (``runtime/executables.py``) under
the decode runtime (``runtime/kvcache.py`` ``_run`` / ``_obtain``):

- a second runtime on the same store loads every program the first built,
  JAX reports no trace or lowering under its ``boot.warm.program`` spans,
  and its prefill and step give exactly the first one's ids and rows;
- the key changes with a byte of any source file, with each of the cache's
  geometry, the pool's dtype, the model, the program's jit and arguments,
  and with the installation and the device as the store reads them;
- a truncated, empty, foreign or unreadable entry is a miss that rebuilds
  and overwrites; two writers of one key leave one whole file; other source
  trees' entries go at the first write, but for the newest;
- a size nobody warmed is built at serving, stored, and booked as
  ``compile``; arguments that span devices bypass the store.
"""

from __future__ import annotations

import logging
import os
import pickle
import shutil
import threading
import time

import jax
import numpy as np
import pytest

from ai4e_tpu.metrics import MetricsRegistry
from ai4e_tpu.observability import boot
from ai4e_tpu.runtime import executables
from ai4e_tpu.runtime.executables import ExecutableStore, source_fingerprint
from ai4e_tpu.runtime.kvcache import PagedDecodeRuntime, build_lm_servable

# Every kind of pool the runtime holds: K/V in float32 and in bfloat16, K/V
# beside a recurrent state with a convolution's tail, Mamba-2 state.
FAMILIES = {
    "seqformer-lm": dict(vocab_size=64, max_len=24, dim=32, depth=2, heads=4),
    "olmoe": dict(vocab_size=64, max_len=24, dim=32, depth=2, heads=4,
                  experts=8, experts_per_token=2, expert_dim=32),
    "qwen3-next": dict(
        vocab_size=97, max_len=24, dim=64, depth=4, heads=4, kv_heads=2,
        head_dim=32, rotary_dim=8, lin_k_heads=2, lin_v_heads=4, lin_dim=16,
        experts=16, experts_held=8, experts_per_token=3, expert_dim=32,
        shared_dim=32),
    "granite-hybrid": dict(
        vocab_size=97, max_len=24, dim=64, depth=4, attention_layers=[2],
        heads=8, kv_heads=2, head_dim=16, mlp_dim=96, ssm_heads=4,
        ssm_head_dim=16, ssm_state=16, chunk=8),
}
SLOTS, BUCKETS = 3, (4, 8)
PROMPT = [3, 7, 11]


def build(store, family="seqformer-lm", slots=SLOTS, buckets=BUCKETS,
          donate=None, **spec):
    servable = build_lm_servable(family=family, name="lm",
                                 **{**FAMILIES[family], **spec})
    return PagedDecodeRuntime(servable, slots=slots, prompt_buckets=buckets,
                              donate=donate, store=store)


def decode(runtime, steps=4):
    """A prompt's first id, ``steps`` ids after it, and the pool they left."""
    ids = [runtime.prefill_into(1, PROMPT)]
    for position in range(len(PROMPT), len(PROMPT) + steps):
        ids.append(runtime.step([0, ids[-1], 0], [0, position, 0],
                                [False, True, False])[1])
    pool = jax.tree.leaves((runtime._rows, runtime._state))
    return ids, [np.asarray(leaf) for leaf in pool]


def booted(runtime, registry=None):
    """``runtime.warm()`` as a worker's boot books it: the ledger, closed,
    its series on ``registry``."""
    ledger = boot.begin("test-worker", start_epoch=time.time() - 0.1)
    try:
        ledger.enter("warm", model="lm")
        runtime.warm()
        ledger.enter("serve")
        ledger.serving(registry or MetricsRegistry())
    finally:
        boot._ACTIVE = None
    return ledger


def entries(store):
    return sorted(os.listdir(store.directory))


@pytest.fixture(autouse=True)
def compile_cache(tmp_path):
    """JAX's persistent compile cache, off — another test's worker may have
    switched it on for the process, and on the CPU what it retrieves is not
    stored. ``compile_cache(True)`` switches it on, at a directory of the
    test's own."""
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    before = {name: getattr(jax.config, name) for name in names}

    def switch(on: bool) -> None:
        jax.config.update("jax_enable_compilation_cache", on)
        jax.config.update("jax_compilation_cache_dir",
                          str(tmp_path / "jax_cache") if on else None)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        compilation_cache.reset_cache()

    switch(False)
    yield switch
    for name, value in before.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


@pytest.fixture
def store(tmp_path):
    return ExecutableStore(str(tmp_path / "executables"))


@pytest.fixture
def filled(store):
    """A store one warmed runtime has filled, and that runtime."""
    runtime = build(store)
    runtime.warm()
    return store, runtime


# -- a second start loads what the first built ---------------------------------


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_second_start_loads_every_program_and_traces_nothing(store, family):
    first, second = build(store, family), build(store, family)
    programs = 2 * len(first.prompt_buckets) + len(first.step_bounds)
    built = booted(first)
    assert built.programs == {"loaded": 0, "built": programs}
    assert len(entries(store)) == programs and store.nbytes() > 0
    loaded = booted(second)
    assert loaded.programs == {"loaded": programs, "built": 0}
    assert loaded.load_s > 0
    spans = [s.attrs for s in loaded.spans if s.name == "boot.warm.program"]
    assert len(spans) == len(first.prompt_buckets) + 2 * len(first.step_bounds)
    for attrs in spans:
        # JAX reported no trace and no lowering while the span was open.
        assert "trace_s" not in attrs and "lower_s" not in attrs, attrs
        assert attrs.get("outcome", "loaded") == "loaded"
    assert sum(a.get("loaded", 0) for a in spans) == programs
    assert all(a["load_s"] > 0 for a in spans if "loaded" in a)
    assert any(a.get("trace_s", 0) > 0 and a["outcome"] == "built"
               for a in (s.attrs for s in built.spans
                         if s.name == "boot.warm.program"))
    assert "%d programs loaded" % programs in loaded.summary()
    assert ", %d built" % programs in built.summary()
    # The loaded programs ARE the built ones: the same ids, the same pool.
    (ids, pool), (ids_loaded, pool_loaded) = decode(first), decode(second)
    assert ids == ids_loaded
    for built_leaf, loaded_leaf in zip(pool, pool_loaded, strict=True):
        np.testing.assert_array_equal(built_leaf, loaded_leaf)


def test_the_boot_publishes_how_it_came_by_its_programs(filled):
    store, _ = filled
    registry = MetricsRegistry()
    ledger = booted(build(store), registry)
    programs = registry.counter("ai4e_boot_programs_total")
    assert programs.value(outcome="loaded") == 7
    assert programs.value(outcome="built") == 0   # there, and 0
    load_s = registry.counter("ai4e_boot_program_load_seconds_total").value()
    assert load_s == ledger.load_s > 0
    text = registry.render_prometheus()
    assert 'ai4e_boot_programs_total{outcome="loaded"} 7' in text
    assert "ai4e_boot_program_load_seconds_total" in text


def test_a_loaded_step_donates_its_pool_as_the_built_one_does(store):
    for runtime in (build(store, donate=True), build(store, donate=True)):
        runtime.warm()
        rows = runtime._rows
        runtime.step([1] * SLOTS, [1] * SLOTS, [True] * SLOTS)
        assert all(row.is_deleted() for row in rows)
    assert booted(build(store, donate=True)).programs["built"] == 0


# -- the key -------------------------------------------------------------------


def _changed_step_options(runtime):
    runtime._jitted_with["step"]["compiler_options"] = {"xla_cpu_x": 1}


def _changed_donation(runtime):
    runtime._jitted_with["step"]["donate_argnums"] = (3, 4)


def _changed_bounds(runtime):
    runtime.step_bounds = (16, 24)


def _context(**changes):
    def change(runtime):
        for name, value in changes.items():
            if name in runtime._store.context["versions"]:
                runtime._store.context["versions"][name] = value
            else:
                assert name in runtime._store.context
                runtime._store.context[name] = value
    return change


KEY_CHANGES = {
    "a source file's byte": _context(source="0" * 64),
    "jax": _context(jax="0.0.1"),
    "jaxlib": _context(jaxlib="0.0.1"),
    "libtpu": _context(libtpu="0.0.1"),
    "the backend's version": _context(platform_version="another build"),
    "the platform": _context(platform="tpu"),
    "the device kind": _context(device_kind="TPU v9"),
    "the device count": _context(device_count=4),
    "the process count": _context(process_count=2),
    "a compiler flag in the environment": _context(
        environment={"XLA_FLAGS": "--xla_something", "LIBTPU_INIT_ARGS": None}),
    "the slots": dict(slots=4),
    "the cache's length": dict(max_len=32),
    "the prompt buckets": dict(buckets=(4, 16)),
    "the step bounds": _changed_bounds,
    "the pool's dtype": dict(family="olmoe"),
    "the model": dict(depth=3),
    "the step's compiler options": _changed_step_options,
    "the step's donation": _changed_donation,
}


@pytest.mark.parametrize("what", list(KEY_CHANGES))
def test_the_key_changes_with(store, what):
    def keys(runtime):
        runtime._ensure()
        return {(program, size): runtime._key(program, size, ["same"])
                for program, size in (("prefill", 8), ("insert", 8),
                                      ("step", 24))}

    base = keys(build(store))
    assert base == keys(build(store))   # the same start: the same keys
    change = KEY_CHANGES[what]
    if callable(change):
        changed = build(store)
        changed._ensure()
        change(changed)
    else:
        changed = build(store, **change)
    changed = keys(changed)
    moved = {k for k in base if base[k] != changed[k]}
    # What is the step's alone moves the step's key; the rest moves all.
    assert moved == ({("step", 24)} if "the step's" in what else set(base))


def test_the_key_holds_the_program_its_size_and_its_arguments(filled):
    _, runtime = filled
    key = runtime._key("prefill", 8, ["same"])
    assert key != runtime._key("insert", 8, ["same"])
    assert key != runtime._key("prefill", 4, ["same"])
    assert key != runtime._key("prefill", 8, ["other"])
    args = (runtime.servable.params, np.zeros((1, 8), np.int32))
    described, device = executables.signature(args)
    assert device in jax.devices()
    for other in ((runtime.servable.params, np.zeros((1, 8), np.float32)),
                  (runtime.servable.params, np.zeros((1, 4), np.int32)),
                  (runtime.servable.params, 8),      # a weak type
                  ({"params": {}}, np.zeros((1, 8), np.int32))):
        assert executables.signature(other)[0] != described


def test_the_store_reads_the_installation_and_the_package_it_runs(store):
    from importlib import metadata
    context = store.context
    assert context["source"] == source_fingerprint()
    assert context["versions"]["jax"] == jax.__version__
    assert context["versions"]["jaxlib"] == metadata.version("jaxlib")
    assert set(context["versions"]) == {"jax", "jaxlib", "libtpu", "flax"}
    device = jax.devices()[0]
    assert (context["platform"], context["device_kind"]) == (
        device.platform, device.device_kind)
    assert context["device_count"] == len(jax.devices())
    assert context["platform_version"] == device.client.platform_version
    assert store.directory == os.path.join(store.root,
                                           context["source"][:16])


@pytest.mark.parametrize("change, same", [
    ("a byte of a file", False), ("a file more", False),
    ("a file's name", False), ("a file that is not source", True),
    ("nothing", True)])
def test_the_source_fingerprint_changes_with(tmp_path, change, same):
    root = tmp_path / "package"
    (root / "ops").mkdir(parents=True)
    (root / "model.py").write_bytes(b"x = 1\n")
    (root / "ops" / "kernel.py").write_bytes(b'SCOPE = "attention"\n')
    before = source_fingerprint(str(root))
    if change == "a byte of a file":
        (root / "ops" / "kernel.py").write_bytes(b'SCOPE = "attentioN"\n')
    elif change == "a file more":
        (root / "ops" / "other.py").write_bytes(b"")
    elif change == "a file's name":
        (root / "model.py").rename(root / "models.py")
    elif change == "a file that is not source":
        (root / "ops" / "notes.txt").write_bytes(b"x")
    assert (source_fingerprint(str(root)) == before) is same


# -- every failure is a miss ---------------------------------------------------


def _truncate(path, others):
    with open(path, "rb") as f:
        whole = f.read()
    with open(path, "wb") as f:
        f.write(whole[:len(whole) // 2])


def _empty(path, others):
    open(path, "wb").close()


def _foreign(path, others):
    shutil.copyfile(others[0], path)   # another key's entry, whole


def _not_an_entry(path, others):
    with open(path, "wb") as f:
        pickle.dump({"not": "an entry"}, f)


def _another_topology(path, others):
    with open(path, "rb") as f:
        key, payload, in_tree, out_tree = pickle.load(f)
    with open(path, "wb") as f:   # the trees of another program
        pickle.dump((key, payload, out_tree, in_tree), f)


@pytest.mark.parametrize("damage", [_truncate, _empty, _foreign,
                                    _not_an_entry, _another_topology],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_bad_entry_is_a_miss_that_rebuilds_and_overwrites(filled, damage,
                                                            caplog):
    store, first = filled
    expected = decode(first)[0]
    runtime = build(store)
    runtime._ensure()
    runtime._ensure_ids()
    args = (runtime.servable.params, np.zeros((1, 8), np.int32),
            np.asarray([3], np.int32))
    key = runtime._key("prefill", 8, executables.signature(args)[0])
    path = os.path.join(store.directory, key)
    size = os.path.getsize(path)
    damage(path, [os.path.join(store.directory, name)
                  for name in entries(store) if name != key])
    with caplog.at_level(logging.INFO, logger="ai4e_tpu.executables"):
        ledger = booted(runtime)
        assert store.load(key, jax.devices()[0]) is not None   # overwritten
    assert ledger.programs == {"loaded": 6, "built": 1}
    told = [r for r in caplog.records if "cannot be loaded" in r.getMessage()]
    assert len(told) == 1 and told[0].levelno == logging.INFO
    assert os.path.getsize(path) > 0.9 * size   # whole again
    assert decode(runtime)[0] == expected
    assert booted(build(store)).programs == {"loaded": 7, "built": 0}


def test_a_stale_tree_s_entries_are_never_loaded_and_go_at_a_write(filled):
    store, _ = filled
    older = os.path.join(store.root, "0" * 16)
    os.makedirs(older)
    os.utime(older, (1.0, 1.0))
    moved = ExecutableStore(store.root)
    moved.context["source"] = "f" * 64   # the same checkout, edited
    assert moved.directory != store.directory
    ledger = booted(build(moved))
    assert ledger.programs == {"loaded": 0, "built": 7}
    # The edited tree's and the newest other's stay; the third's is gone.
    assert sorted(os.listdir(store.root)) == sorted(
        os.path.basename(d) for d in (store.directory, moved.directory))
    assert len(entries(moved)) == len(entries(store)) == 7


def test_two_writers_of_one_key_leave_one_whole_file(filled):
    store, runtime = filled
    compiled = runtime._executables["prefill", 8]
    device = jax.devices()[0]
    key = store.key(program="written twice")
    failures = []

    def write():
        try:
            for _ in range(5):
                store.save(key, compiled)
                if store.load(key, device) is None:
                    failures.append("a reader found no whole entry")
        except Exception as exc:  # noqa: BLE001 — reported below
            failures.append(exc)

    threads = [threading.Thread(target=write) for _ in range(4)]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join(timeout=120)
    assert not any(thread.is_alive() for thread in threads)
    assert not failures
    assert [n for n in entries(store) if n.startswith(key)] == [key]
    assert store.load(key, device) is not None


def test_a_store_that_cannot_be_written_costs_the_start_nothing(tmp_path,
                                                                caplog):
    blocked = tmp_path / "executables"
    blocked.write_bytes(b"a file where the directory would be")
    store = ExecutableStore(str(blocked))
    with caplog.at_level(logging.INFO, logger="ai4e_tpu.executables"):
        ledger = booted(build(store))
    assert ledger.programs == {"loaded": 0, "built": 7}
    # One line an entry, however often it fails: the load's, here.
    assert len(caplog.records) == 7
    assert all("cannot be loaded" in r.getMessage() for r in caplog.records)
    assert store.nbytes() == 0


def test_on_the_cpu_what_jax_s_cache_retrieved_is_served_and_not_stored(
        store, compile_cache, caplog):
    """XLA:CPU serialises an executable it loaded from its own cache entry
    without its functions; the store keeps none of those, and a start after
    one builds again and answers right."""
    compile_cache(True)
    first = build(store)
    first.warm()                              # compiled, cached, stored
    expected = decode(first)[0]
    assert len(entries(store)) == 7
    shutil.rmtree(store.directory)
    with caplog.at_level(logging.INFO, logger="ai4e_tpu.executables"):
        retrieved = build(ExecutableStore(store.root))
        assert booted(retrieved).programs == {"loaded": 0, "built": 7}
    assert sum("not stored" in r.getMessage() for r in caplog.records) == 7
    assert not os.path.isdir(store.directory)
    assert decode(retrieved)[0] == expected
    assert decode(build(ExecutableStore(store.root)))[0] == expected


# -- serving -------------------------------------------------------------------


def test_an_unwarmed_size_at_serving_is_built_stored_and_a_compile(store):
    runtime = build(store, buckets=(4,))
    runtime.warm()
    assert len(entries(store)) == 5
    # A bucket nobody warmed (a ladder changed under a live worker).
    runtime.prompt_buckets = (4, 8, 24)
    phases = []
    runtime.phase_hook = lambda phase, seconds: phases.append(phase)
    first = runtime.prefill_into(1, [3, 7, 11, 2, 5])
    assert phases.count("compile") == 2   # the prefill and its insert
    assert len(entries(store)) == 7
    phases.clear()
    assert runtime.prefill_into(2, [3, 7, 11, 2, 5]) == first
    assert "compile" not in phases
    # The next start finds them: loaded at serving, and still a compile.
    again = build(store, buckets=(4,))
    assert booted(again).programs == {"loaded": 5, "built": 0}
    again.prompt_buckets = (4, 8, 24)
    again.phase_hook = lambda phase, seconds: phases.append(phase)
    assert again.prefill_into(1, [3, 7, 11, 2, 5]) == first
    assert phases.count("compile") == 2 and len(entries(store)) == 7


def test_arguments_that_span_devices_bypass_the_store(store):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("x",))
    spread = jax.device_put(np.zeros((2, 4), np.float32),
                            NamedSharding(mesh, PartitionSpec("x")))
    assert executables.signature((spread,)) is None
    assert executables.signature((np.zeros(3), jax.device_put(
        np.zeros(3), jax.devices()[1])))[1] == jax.devices()[1]
    runtime = build(store)
    runtime._ensure()
    double = jax.jit(lambda x: x * 2)
    runtime._programs["double"], runtime._jitted_with["double"] = double, {}
    np.testing.assert_array_equal(runtime._run("double", 2, spread), spread)
    assert not os.path.isdir(store.directory)   # built, and kept nowhere


def test_a_runtime_without_a_store_builds_and_keeps_nothing(tmp_path):
    runtime = build(None)
    assert booted(runtime).programs == {"loaded": 0, "built": 7}
    assert decode(runtime)[0] == decode(build(None))[0]
