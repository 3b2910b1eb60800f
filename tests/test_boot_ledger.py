"""The worker's boot ledger (``observability/boot.py``): contiguous phases
that add up to the root, one trace with named parents, JAX's own trace /
lower / compile / cache events booked to the span open on their thread and
to ``when="boot"`` or ``"serving"``, the decode runtime's warm-up as one
span a program call, and the series a scrape reads afterwards."""

from __future__ import annotations

import io
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai4e_tpu.metrics import MetricsRegistry
from ai4e_tpu.observability import (InMemoryExporter, boot, configure_tracer,
                                    tracing, vitals)
from ai4e_tpu.observability.traceview import render_trace

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
RETRIEVE = "/jax/compilation_cache/cache_retrieval_time_sec"


@pytest.fixture
def ledger():
    """A boot that began half a second ago, the process's ledger until the
    test ends."""
    led = boot.begin("test-worker", start_epoch=time.time() - 0.5)
    yield led
    boot._ACTIVE = None


@pytest.fixture
def exported():
    sink = InMemoryExporter()
    configure_tracer(exporter=sink, sample_rate=1.0)
    yield sink
    configure_tracer(exporter=None, sample_rate=None)


def _fresh_jit(scale: float):
    """A function no other test has traced, so its first call compiles."""
    def boot_ledger_probe(x):
        return jnp.tanh(x * scale).sum()
    return jax.jit(boot_ledger_probe)


def _walk(led: boot.BootLedger) -> None:
    for phase in ("backend", "build", "pools", "batch_warmup", "warm",
                  "serve"):
        led.enter(phase)
        time.sleep(0.01)


def test_phases_are_contiguous_and_add_up_to_the_total(ledger):
    _walk(ledger)
    ledger.serving(MetricsRegistry())
    seconds = ledger.phase_seconds()
    assert set(seconds) == {*boot.PHASES, "total"}
    total = seconds.pop("total")[0]
    assert total >= 0.5
    assert seconds["import"][0] >= 0.5   # open since the process's start
    assert sum(wall for wall, _ in seconds.values()) == pytest.approx(
        total, abs=0.02)
    tops = sorted((s for s in ledger.spans
                   if s.parent_id == ledger.root.span_id),
                  key=lambda s: s.start)
    for before, after in zip(tops, tops[1:]):
        assert after.start - (before.start + before.duration) < 0.01


def test_every_span_shares_the_trace_and_names_its_parent(ledger, exported):
    ledger.enter("warm", model="lm")
    with ledger.program("prefill", bucket=8):
        pass
    ledger.enter("serve")
    ledger.serving(MetricsRegistry())
    spans = exported.spans
    assert [s.name for s in spans][:2] == ["boot", "boot.import"]
    assert {s.trace_id for s in spans} == {ledger.root.trace_id}
    by_id = {s.span_id: s for s in spans}
    for span in spans:
        if span.name == "boot":
            assert span.parent_id is None
        elif span.name == "boot.warm.program":
            assert by_id[span.parent_id].name == "boot.warm"
        else:
            assert by_id[span.parent_id].name == "boot"
        assert "cpu_s" in span.attrs
    tree = render_trace([s.to_dict() for s in spans])
    assert "boot.warm.program" in tree and "program=prefill" in tree


def test_nothing_is_exported_without_an_exporter_but_the_series_are(ledger):
    ledger.enter("serve")
    reg = MetricsRegistry()
    ledger.serving(reg)   # configure_tracer's default: no exporter
    text = reg.render_prometheus()
    for phase in (*boot.PHASES, "total"):
        assert f'ai4e_boot_seconds{{phase="{phase}"}}' in text
        assert f'ai4e_boot_cpu_seconds{{phase="{phase}"}}' in text
    for stage in ("trace", "lower", "backend", "retrieve"):
        assert (f'ai4e_jax_compile_seconds_total{{stage="{stage}",'
                f'when="boot"}}') in text
    assert 'when="serving"' not in text


def test_a_first_call_books_its_parts_to_the_open_program_span(ledger):
    ledger.enter("warm", model="lm")
    with ledger.program("step", bound=128) as span:
        _fresh_jit(1.25)(jnp.ones((8, 8))).block_until_ready()
    assert span.attrs["trace_s"] > 0 and span.attrs["lower_s"] > 0
    assert span.attrs["compile_s"] + span.attrs.get("retrieve_s", 0) > 0
    parts = sum(span.attrs.get(p, 0.0) for p in boot._PARTS)
    assert parts <= span.duration + 1e-3   # each second booked once
    assert span.attrs["run_s"] == pytest.approx(span.duration - parts,
                                                abs=1e-3)
    warm = ledger._phase
    assert "trace_s" not in warm.attrs   # the innermost span alone
    reg = MetricsRegistry()
    ledger.serving(reg)
    counter = reg.counter("ai4e_jax_compile_seconds_total")
    for stage, attr in (("trace", "trace_s"), ("lower", "lower_s")):
        assert counter.value(stage=stage, when="boot") == pytest.approx(
            span.attrs[attr], abs=1e-3)
    assert counter.value(stage="trace", when="serving") == 0.0


def test_nested_events_are_booked_once(ledger, monkeypatch):
    """JAX reports an event when it ends, an enclosed one before the one
    around it: a trace of 0.2 s that held one of 0.05 s is 0.2 s of tracing,
    and a backend compile that was a retrieval but for 0.01 s leaves 0.01 s
    to the backend."""
    clock = [100.0]
    monkeypatch.setattr(boot, "_now", lambda: clock[0])

    def at(end, event, seconds, **kw):
        clock[0] = end
        boot._on_duration(event, seconds, **kw)

    span = ledger.enter("build")
    at(100.10, TRACE, 0.05, fun_name="inner")
    at(100.20, TRACE, 0.2, fun_name="outer")       # 100.0-100.2: holds inner
    at(100.25, TRACE, 0.04, fun_name="sibling")    # began after outer ended
    at(100.30, LOWER, 0.03, fun_name="jit_outer")
    at(100.61, RETRIEVE, 0.3)
    at(100.62, BACKEND, 0.31, fun_name="jit_outer")   # holds the retrieval
    at(100.70, "/jax/compilation_cache/compile_time_saved_sec", 9.0)
    assert span.attrs["trace_s"] == pytest.approx(0.24)
    assert span.attrs["lower_s"] == pytest.approx(0.03)
    assert span.attrs["retrieve_s"] == pytest.approx(0.3)
    assert span.attrs["compile_s"] == pytest.approx(0.01)


def test_a_pool_thread_books_to_the_open_phase(ledger):
    from concurrent.futures import ThreadPoolExecutor
    span = ledger.enter("batch_warmup")
    with ThreadPoolExecutor(1) as pool:
        pool.submit(boot._on_duration, LOWER, 0.25,
                    fun_name="jit_bucket").result()
    assert span.attrs["lower_s"] == pytest.approx(0.25)


@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent compile cache in a directory of the test's own,
    every program written whatever its size or compile time."""
    from jax._src import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    old = [getattr(jax.config, name) for name in names]
    for name, value in zip(names, (str(tmp_path), 0.0, -1)):
        jax.config.update(name, value)
    compilation_cache.reset_cache()
    yield compilation_cache.reset_cache
    for name, value in zip(names, old):
        jax.config.update(name, value)
    compilation_cache.reset_cache()
    boot._ACTIVE = None


def _boot_once():
    x = jnp.ones((16, 16)).block_until_ready()   # no part of the boot
    led = boot.begin("test-worker", start_epoch=time.time())
    led.enter("warm", model="lm")
    with led.program("prefill", bucket=16) as span:
        _fresh_jit(2.5)(x).block_until_ready()
    reg = MetricsRegistry()
    led.serving(reg)
    return span.attrs, reg.counter("ai4e_jax_compile_cache_total")


@pytest.mark.parametrize("boots", [1, 2], ids=["first-misses", "second-hits"])
def test_the_cache_counts_misses_then_hits(persistent_cache, boots):
    """Boots over one cache directory: the first writes what it compiles
    (misses); the second — a new function object, the in-memory caches
    dropped: a new process in all the cache sees — retrieves it."""
    attrs, total = _boot_once()
    if boots == 2:
        jax.clear_caches()
        persistent_cache()
        attrs, total = _boot_once()
        assert attrs["cache_hits"] >= 1 and attrs["retrieve_s"] > 0
        assert "cache_misses" not in attrs
        assert total.value(result="hit", when="boot") >= 1
        assert total.value(result="miss", when="boot") == 0
    else:
        assert attrs["cache_misses"] >= 1 and "retrieve_s" not in attrs
        assert total.value(result="miss", when="boot") >= 1
        assert total.value(result="hit", when="boot") == 0


def test_an_event_after_the_boot_is_serving_s_and_names_the_function(
        ledger, caplog):
    ledger.enter("serve")
    reg = MetricsRegistry()
    ledger.serving(reg)
    assert boot.active() is None
    with caplog.at_level(logging.WARNING, logger="ai4e_tpu.boot"):
        _fresh_jit(3.75)(jnp.ones((4, 4))).block_until_ready()
    counter = reg.counter("ai4e_jax_compile_seconds_total")
    assert counter.value(stage="trace", when="serving") > 0
    assert counter.value(stage="backend", when="serving") \
        + counter.value(stage="retrieve", when="serving") > 0
    assert any("boot_ledger_probe" in r.getMessage()
               and r.levelno == logging.WARNING for r in caplog.records)
    # The boot's own numbers are closed.
    assert reg.gauge("ai4e_boot_seconds").value(phase="total") \
        == ledger.root.duration


def test_the_decode_warm_up_is_one_span_a_bucket_and_two_a_rung(ledger):
    from ai4e_tpu.runtime.kvcache import PagedDecodeRuntime, build_lm_servable
    servable = build_lm_servable(family="seqformer-lm", name="lm",
                                 vocab_size=64, max_len=24, dim=32, depth=1,
                                 heads=2)
    runtime = PagedDecodeRuntime(servable, slots=2, prompt_buckets=(4, 8))
    warm = ledger.enter("warm", model="lm")
    runtime.warm()
    assert ledger._phase is warm   # the runtime marks no phase of its own
    ledger.enter("serve")
    ledger.serving(MetricsRegistry())
    programs = [s for s in ledger.spans if s.name == "boot.warm.program"]
    assert {s.parent_id for s in programs} == {warm.span_id}
    programs = [s.attrs for s in programs]
    assert [(a["program"], a["bucket"]) for a in programs
            if a["program"] == "prefill"] == [
        ("prefill", b) for b in runtime.prompt_buckets]
    assert [(a["bound"], a["feed"]) for a in programs
            if a["program"] == "step"] == [
        (bound, feed) for bound in runtime.step_bounds
        for feed in ("host", "device")]
    first_step = next(a for a in programs if a["program"] == "step")
    assert first_step["trace_s"] > 0 and "run_s" in first_step
    assert "prefill" in ledger.summary() and "bound=" in ledger.summary()


def test_warm_records_nothing_outside_a_boot():
    from ai4e_tpu.runtime.kvcache import PagedDecodeRuntime, build_lm_servable
    assert boot.active() is None
    servable = build_lm_servable(family="seqformer-lm", name="lm",
                                 vocab_size=64, max_len=16, dim=32, depth=1,
                                 heads=2)
    runtime = PagedDecodeRuntime(servable, slots=2, prompt_buckets=(4,))
    assert runtime.warm() is None
    assert boot.program("step", bound=16) is boot._NO_SPAN


def test_the_gauges_are_in_the_worker_s_rendered_metrics(ledger):
    """``serving()`` publishes on the registry it is given — the one the
    worker's ``/metrics`` renders — and a second call changes nothing."""
    _walk(ledger)
    reg = MetricsRegistry()
    ledger.serving(reg)
    text = reg.render_prometheus()
    total = reg.gauge("ai4e_boot_seconds").value(phase="total")
    assert f'ai4e_boot_seconds{{phase="total"}} {total!r}' in text
    ledger.serving(MetricsRegistry())
    assert reg.gauge("ai4e_boot_seconds").value(phase="total") == total
    parts = sum(reg.gauge("ai4e_boot_seconds").value(phase=p)
                for p in boot.PHASES)
    assert parts == pytest.approx(total, abs=0.02)


def test_the_process_start_is_read_from_proc(tmp_path):
    started = vitals.read_start_epoch()
    assert started is not None and 0 < time.time() - started < 3600
    assert vitals.read_start_epoch(proc_root=str(tmp_path)) is None
    # A start /proc cannot give falls back to the module's import instant.
    led = boot.BootLedger("w", start_epoch=time.time() + 60)
    assert led.root.start == boot._IMPORTED


def test_no_span_of_a_boot_is_the_context_s_current_span(ledger):
    """A boot runs on the event loop's own context: a span left current
    while tasks and handles are made would be every later request's parent."""
    import asyncio
    seen = []

    async def main():
        ledger.enter("warm", model="lm")
        with ledger.program("step", bound=8):
            seen.append(tracing._CURRENT.get())
        ledger.enter("serve")
        seen.append(tracing._CURRENT.get())
        # Made while ``boot.serve`` is open, run after the boot closed — as
        # the listening socket's reader and every connection's task are.
        task = asyncio.create_task(later())
        ledger.serving(MetricsRegistry())
        await task

    async def later():
        seen.append(tracing._CURRENT.get())
        with tracing.get_tracer().span("service.request", headers={}) as span:
            seen.append((span.trace_id != ledger.root.trace_id,
                         span.parent_id))

    asyncio.run(main())
    assert seen == [None, None, None, (True, None)]


def test_a_request_after_a_booted_worker_starts_a_trace_of_its_own(exported):
    """``cli.build_worker`` under a ledger, the server started while
    ``boot.serve`` is open as ``run_worker`` starts it: the phases the shell
    marks, and a request without x-b3 headers that is no part of the boot."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from ai4e_tpu.cli import build_worker
    from ai4e_tpu.config import FrameworkConfig
    config = FrameworkConfig()
    config.runtime.decode_enable = True
    config.runtime.kv_slots = 2
    config.runtime.decode_prompt_buckets = (4,)
    models = {"service_name": "w", "prefix": "v1/lm", "models": [
        {"family": "echo", "name": "echo", "size": 4, "buckets": [2]},
        {"family": "seqformer-lm", "name": "lm", "vocab_size": 32,
         "max_len": 32, "dim": 16, "depth": 1, "heads": 2, "eos_id": 2}]}

    async def main():
        led = boot.begin("w", start_epoch=time.time())
        try:
            worker, batcher, _tm = build_worker(config, models)
            await batcher.start()
            client = TestClient(TestServer(worker.service.app))
            await client.start_server()
            boot.serving(worker.service.metrics)
            body = io.BytesIO()
            np.save(body, np.ones(4, np.float32))
            resp = await client.post("/v1/lm/echo", data=body.getvalue())
            assert resp.status == 200
            text = await (await client.get("/metrics")).text()
            await client.close()
            await batcher.stop()
        finally:
            boot._ACTIVE = None
        return led, worker, text

    led, worker, text = asyncio.run(main())
    names = [s.name for s in led.spans]
    for phase in ("build", "pools", "batch_warmup", "warm", "serve"):
        assert "boot." + phase in names
    assert names.count("boot.pools") == names.count("boot.warm") == 1
    pools = next(s for s in led.spans if s.name == "boot.pools")
    assert pools.attrs["bytes"] \
        == worker.decode_engines[0].backend.cache_nbytes()
    assert 'ai4e_boot_seconds{phase="total"}' in text
    of_the_boot = exported.by_trace(led.root.trace_id)
    assert len(of_the_boot) == len(led.spans)
    assert all(s.name.startswith("boot") for s in of_the_boot)
    request = next(s for s in exported.spans if s.name == "/echo")
    assert request.trace_id != led.root.trace_id
    assert request.parent_id is None
