#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A run is a new process. It reads the cell from ``BENCHMARK.json``, writes the
cell's specs, starts the deployed roles as separate processes (control plane;
ONE worker that holds the chip, through ``lib/worker_launcher.py``), waits
until the worker's own device report names the platform and chip count the
cell asks for (else exits non-zero and prints no result: there is no CPU
fallback), offers the cell's traffic for ``--seconds`` through the gateway,
stops both processes, holds a seeded sample of outputs to the plain reference
and prints one JSON object as the last line of standard output.

``--trace 0`` reports the cell's end-to-end metrics, taken by this process as
a client of the gateway with every observability switch off. ``--trace 1``
turns on the hop ledger and records a few seconds of the window with the JAX
profiler inside the worker process, and reports the cell's per-layer metrics.

Everything that belongs to one configuration, traffic mix or metric is a file
found by its name in the manifest: ``configs/<config>.json``,
``traffic/<mix>.json`` → ``generators/<generator>.py``,
``end_to_end/<metric>.json``, ``layer_metrics/<metric>.json`` →
``readers/<reader>.py``, ``references/<family>.py``. See ``README.md``.
"""

from __future__ import annotations

import time

T_START = time.monotonic()   # set-up is counted from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark.lib import e2e, prom, stats  # noqa: E402
from benchmark.lib.payloads import KINDS as PAYLOAD_KINDS  # noqa: E402
from benchmark.lib.stack import LIB, Stack, StartError  # noqa: E402
from benchmark.lib.tracing import TRACE_SECONDS, trace_instant  # noqa: E402

HELPER_TIMEOUT_S = 240.0


def log(msg: str) -> None:
    print(msg, flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def write_json_atomic(path: str, obj) -> None:
    with open(path + ".tmp", "w") as f:
        json.dump(obj, f)
    os.replace(path + ".tmp", path)


class Cell:
    """One entry of ``workloads`` with the files its names lead to."""

    def __init__(self, manifest_path: str, workload: str):
        self.manifest = load_json(manifest_path)
        try:
            self.entry = next(w for w in self.manifest["workloads"]
                              if w["name"] == workload)
        except StopIteration:
            raise SystemExit(f"no workload {workload!r} in {manifest_path}")
        config_entry = next(c for c in self.manifest["configs"]
                            if c["name"] == self.entry["config"])
        self.config_path = os.path.join(ROOT, config_entry["file"])
        self.config = load_json(self.config_path)
        self.dir = HERE
        self.traffic = load_json(os.path.join(
            HERE, "traffic", self.entry["traffic"] + ".json"))
        self.chips = self.entry["chips"]
        self.name = workload

    def metrics(self, group: str) -> list[dict]:
        return [m for m in self.manifest[group]
                if self.name in m.get("workloads", [self.name])]

    def definition(self, kind_dir: str, name: str) -> dict:
        return dict(load_json(os.path.join(self.dir, kind_dir,
                                           name + ".json")), name=name)


class RunContext:
    """What a generator sees of the run."""

    def __init__(self, cell: Cell, stack: Stack, seed: int, seconds: float,
                 trace: bool, work: str):
        self.cell, self.stack = cell, stack
        self.config, self.traffic = cell.config, cell.traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.work = work
        self.cp_base = stack.cp_base
        self.now = time.monotonic
        self.reference_sample = cell.config["reference"]["sample"]
        self.setup_s = None
        self.prom_before = self.prom_after = None
        self.gauge_samples: list[dict] = []
        self.trace_instant_s = self.trace_written_epoch = None
        self._sampler = None
        self._payloads = None

    def payloads(self):
        if self._payloads is None:
            spec = dict(self.config["derived"], **self.traffic["payload"])
            self._payloads = PAYLOAD_KINDS[spec.pop("kind")](self.seed, **spec)
        return self._payloads

    def valid(self, result, arrival=None) -> bool:
        return self.payloads().valid(result, arrival)

    def reference_eligible(self, record: dict) -> bool:
        return self.payloads().eligible(record)

    async def _scrape(self) -> dict:
        return prom.parse(await asyncio.to_thread(self.stack.worker_metrics))

    async def _sample_gauges(self) -> None:
        while True:
            await asyncio.sleep(1.0)
            self.gauge_samples.append(await self._scrape())

    async def _trigger_trace(self, dues) -> None:
        self.trace_instant_s = trace_instant(self.seconds, dues)
        await asyncio.sleep(self.trace_instant_s)
        open(os.path.join(self.work, "trace.start"), "w").close()
        self.trace_written_epoch = time.time()

    async def window_start(self, dues=None) -> float:
        """The first request of the window is about to be offered. ``dues``:
        the instants, in seconds from now, at which the generator's schedule
        has the window's arrivals due (``lib/tracing.py`` places the traced
        seconds on one of them); a generator that has no schedule gives
        none."""
        if self.trace:
            self.prom_before = await self._scrape()
            self._sampler = [asyncio.ensure_future(self._sample_gauges()),
                             asyncio.ensure_future(self._trigger_trace(dues))]
        t0 = self.now()
        self.setup_s = t0 - T_START
        log(f"window open after {self.setup_s:.1f}s of set-up")
        return t0

    async def window_end(self) -> None:
        if self.trace:
            for task in self._sampler:
                task.cancel()
            await asyncio.gather(*self._sampler, return_exceptions=True)
            self.prom_after = await self._scrape()

    async def ledgers(self, sess, task_ids: list[str]) -> list[list[dict]]:
        if not self.trace:
            return []
        from benchmark.lib import client
        out = []
        for i in range(0, len(task_ids), 32):
            out += await asyncio.gather(*[
                client.task_ledger(sess, self.cp_base, t)
                for t in task_ids[i:i + 32]])
        return [events for events in out if events]


def trim_logs(work: str, keep: int = 100_000) -> None:
    """The roles log every request at INFO, as deployed: tens of MB a run.
    Keep each log's head and tail."""
    for name in os.listdir(work):
        path = os.path.join(work, name)
        if name.endswith(".log") and os.path.getsize(path) > 2 * keep:
            with open(path, "rb") as f:
                head = f.read(keep)
                f.seek(-keep, os.SEEK_END)
                tail = f.read()
            with open(path, "wb") as f:
                f.write(head + b"\n... [trimmed] ...\n" + tail)


def wait_for(path: str, proc, timeout: float) -> bool:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if os.path.exists(path):
            return True
        if proc is not None and proc.poll() is not None:
            return os.path.exists(path)
        time.sleep(0.1)
    return False


def label_gaps(trace: dict, trace_done: dict, ledgers) -> list[list]:
    """The longest idle gaps, each with the nearest hop-ledger stamp by wall
    clock. The program has no annotations on the profiler's clock yet, so
    the attribution is by nearness only."""
    stamps = sorted((ev["t"], f"{ev['e']}@{ev.get('h', '?')}")
                    for events in ledgers for ev in events if "t" in ev)
    # The trace counts nanoseconds from its own start.
    origin = trace_done.get("trace_begin_epoch", 0.0)
    out = []
    for gap in trace["idle_gaps"]:
        label = f"+{gap['at_s']:.3f}s"
        if stamps:
            at = origin + gap["at_ns"] / 1e9
            near = min(stamps, key=lambda s: abs(s[0] - at))
            if abs(near[0] - at) < 1.0:
                label += f" near {near[1]}"
        out.append([label, gap["seconds"]])
    return out


# (number, its limit) as the references' verdicts name them. A family whose
# verdict counts the tokens beyond the share margin is held to that count,
# not to the share it also reports.
COMPARED = (("worst_margin", "limit_margin"), ("beyond", "allowed_beyond"),
            ("share_beyond", "limit_share"),
            ("worst_pixels_moved", "limit_pixels"))


def compared(verdict: dict, gen: dict, compiles) -> dict:
    """Every number ``correct`` rests on, beside its limit."""
    out = {"failed": [gen["failed"], 0]}
    if compiles is not None:
        out["compiles_in_window"] = [compiles, 0]
    for value, limit in COMPARED:
        if value in verdict and limit in verdict and not (
                value == "share_beyond" and "beyond" in out):
            out[value] = [verdict[value], verdict[limit]]
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("--manifest",
                        default=os.path.join(ROOT, "BENCHMARK.json"),
                        help="another manifest (tests, sweeps); default: "
                             "BENCHMARK.json at the root")
    parser.add_argument("--set", action="append", default=[],
                        metavar="KEY=JSON",
                        help="override one traffic parameter (knee sweeps "
                             "only; the result is then marked overridden)")
    parser.add_argument("--keep-trace", action="store_true",
                        help="keep the raw .xplane.pb in the work directory")
    args = parser.parse_args()

    cell = Cell(args.manifest, args.workload)
    for item in args.set:
        key, value = item.split("=", 1)
        cell.traffic[key] = json.loads(value)
    trace = bool(args.trace)
    work = os.path.join(ROOT, "chiprun_out", "benchmark",
                        f"{cell.name}.{args.seed}.t{args.trace}")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    for env_group in ("control_plane_env", "worker_env"):
        cell.config[env_group] = {
            k: str(v).replace("{work}", work)
            for k, v in cell.config.get(env_group, {}).items()}

    stack = Stack(cell.config, work, trace, TRACE_SECONDS)
    ctx = RunContext(cell, stack, args.seed, args.seconds, trace, work)
    family = cell.config["reference"]["family"]
    generator = importlib.import_module(
        "benchmark.generators." + cell.traffic["generator"])
    try:
        stack.start()
        write_json_atomic(os.path.join(work, "ref_pre.json"), dict(
            cell.config["derived"], seed=args.seed))
        stack.start_helper("reference", [
            os.path.join(LIB, "refcheck.py"), family, cell.config_path, work])
        device = stack.wait_serving(cell.chips)
        log(f"worker reports: platform {device['platform']}, device_kind "
            f"{device['device_kind']!r}, device_count "
            f"{device['device_count']}, mesh {device.get('mesh')}, versions "
            f"{device.get('versions')}")
        # The reference helper shares this host's cores with the roles: the
        # generator offers nothing until it has prepared and gone to sleep.
        if not wait_for(os.path.join(work, "ref_ready"),
                        stack.procs["reference"], HELPER_TIMEOUT_S):
            raise StartError("the reference helper did not prepare:\n"
                             + stack.log_tail("reference"))
        gen = asyncio.run(generator.run(ctx))
        trace_done = {}
        if trace:
            if not wait_for(os.path.join(work, "trace.done"),
                            stack.procs["worker"], 120.0):
                raise StartError("the worker's trace thread never finished")
            trace_done = load_json(os.path.join(work, "trace.done"))
            if trace_done.get("error"):
                raise StartError(f"tracing failed: {trace_done['error']}")
        memory_peak = stack.memory_peak_bytes()
        for name in ("worker", "control-plane"):
            rc = stack.stop(name)
            if rc != 0:
                log(f"warning: {name} exited {rc} on SIGTERM")
        write_json_atomic(os.path.join(work, "ref_jobs.json"),
                          gen["check_jobs"])
        trace_summary = None
        if trace:
            summary_path = os.path.join(work, "trace_summary.json")
            begin = trace_done["trace_begin_epoch"]
            stack.start_helper("xplane", [
                os.path.join(LIB, "xplane.py"),
                os.path.join(work, "trace"), summary_path,
                *(f"{t - begin:.6f}" for t in trace_done["interval_epoch"])])
            if stack.procs["xplane"].wait(timeout=HELPER_TIMEOUT_S) != 0:
                raise StartError("trace reduction failed:\n"
                                 + stack.log_tail("xplane"))
            trace_summary = load_json(summary_path)
        verdict_path = os.path.join(work, "ref_verdict.json")
        if not wait_for(verdict_path, stack.procs["reference"],
                        HELPER_TIMEOUT_S):
            raise StartError("the reference helper gave no verdict:\n"
                             + stack.log_tail("reference"))
        verdict = load_json(verdict_path)
        stack.procs["reference"].wait(timeout=30)
    except StartError as exc:
        print(f"benchmark: {exc}", file=sys.stderr, flush=True)
        return 3
    finally:
        stack.kill_all()
        for leftover in ("tasks.jsonl", "results"):
            path = os.path.join(work, leftover)
            if os.path.isdir(path):
                shutil.rmtree(path, ignore_errors=True)
            elif os.path.exists(path):
                os.remove(path)
        if not args.keep_trace:
            shutil.rmtree(os.path.join(work, "trace"), ignore_errors=True)
        trim_logs(work)

    # -- what the client saw ---------------------------------------------------
    errors: dict[str, int] = {}
    for r in gen["requests"]:
        if not r["ok"]:
            errors[r["error"]] = errors.get(r["error"], 0) + 1
    # For diagnosis, in the work directory only: each request of the window,
    # and how many ended in each second of it.
    t_open = min((r["end"] - r["latency_s"] for r in gen["requests"]),
                 default=0.0)
    with open(os.path.join(work, "requests.jsonl"), "w") as f:
        for r in gen["requests"]:
            f.write(json.dumps({k: r.get(k) for k in (
                "counter", "ok", "latency_s", "prompt_len", "max_new_tokens",
                "late_s", "error")} | {"end_s": r["end"] - t_open}) + "\n")
    ends = sorted(r["end"] for r in gen["requests"])
    if ends:
        per_second = [0] * (int(ends[-1] - ends[0]) + 1)
        for e in ends:
            per_second[int(e - ends[0])] += 1
        log(f"requests ended in each second: {per_second}")
    late = gen["lateness_s"]
    log(f"requests: attempted {gen['attempted']}, failed {gen['failed']}"
        + (f", errors {errors}" if errors else ""))
    if late:
        log(f"load generator: p95 lateness of sends against their due "
            f"instants {stats.percentile(late, 95) * 1000:.3f} ms "
            f"(median {stats.median(late) * 1000:.3f} ms, n={len(late)})")
    if "in_flight" in gen:
        log(f"requests in flight when the window opened / closed: "
            f"{gen['in_flight']['at_open']} / {gen['in_flight']['at_close']}")
    log(f"reference ({family}): {json.dumps(verdict)}")

    metrics: dict[str, dict] = {}
    notes: dict = {}
    compiles = None
    if not trace:
        for m in cell.metrics("end_to_end"):
            if m["name"] == "setup_s":
                value = ctx.setup_s
            else:
                definition = cell.definition("end_to_end", m["name"])
                value = e2e.KINDS[definition["kind"]](definition, gen)
                log(f"{m['name']}: {e2e.describe(definition, gen)}")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        peaks = load_json(os.path.join(cell.dir, "peaks.json"))
        kind = stack.device["device_kind"]
        if kind not in peaks and stack.device["platform"] != "cpu":
            print(f"benchmark: no peaks for device kind {kind!r} in "
                  "benchmark/peaks.json", file=sys.stderr)
            return 3
        reader_ctx = {
            "config": cell.config, "traffic": cell.traffic, "gen": gen,
            "prom_before": ctx.prom_before, "prom_after": ctx.prom_after,
            "trace_prom_before": prom.parse(
                trace_done.get("metrics_before", "")),
            "trace_prom_after": prom.parse(
                trace_done.get("metrics_after", "")),
            "gauge_samples": ctx.gauge_samples, "ledgers": gen["ledgers"],
            "trace": trace_summary, "peaks": peaks.get(kind), "notes": notes}
        for m in cell.metrics("per_layer"):
            definition = cell.definition("layer_metrics", m["name"])
            reader = importlib.import_module(
                "benchmark.readers." + definition["reader"])
            value = reader.read(definition, reader_ctx)
            if value is not None:   # a reader that finds nothing says nothing
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        compiles = prom.delta(ctx.prom_before, ctx.prom_after,
                              "ai4e_device_phase_seconds_count",
                              {"phase": "compile"})
        log(f"compile phases inside the window: {compiles:.0f}")
        if compiles:
            verdict["ok"] = False
            log("a program compiled inside the measured window")

    result = {
        "correct": bool(verdict["ok"]) and gen["failed"] == 0
        and gen["attempted"] > 0,
        "attempted": gen["attempted"], "failed": gen["failed"],
        "metrics": metrics,
        "device": {"platform": stack.device["platform"],
                   "kind": stack.device["device_kind"],
                   "count": stack.device["device_count"],
                   "memory_peak_bytes": memory_peak},
        "workload": cell.name, "seed": args.seed, "seconds": args.seconds,
        "reference": {k: verdict[k] for k in verdict if k != "bad"},
    }
    if trace and trace_summary is not None:
        result["device"]["busy_s"] = trace_summary["busy_s"]
        result["device"]["window_s"] = trace_summary["window_s"]
        result["breakdown"] = {
            "device_ops": trace_summary["device_ops"],
            "idle_gaps": label_gaps(trace_summary, trace_done,
                                    gen["ledgers"])}
        if ctx.trace_written_epoch is not None:
            # How long the launcher took from ``trace.start`` to the interval
            # it keeps: what ``TRACE_LEAD_S`` has to cover.
            notes["trace_placement"] = {
                "instant_s": ctx.trace_instant_s,
                "lead_measured_s": (trace_done["interval_epoch"][0]
                                    - ctx.trace_written_epoch)}
        result["notes"] = notes
    if args.set:
        result["overridden"] = args.set
    if stack.device["platform"] == "cpu":
        # A rehearsal of the plumbing: its numbers are not device numbers and
        # are never printed under a device metric's name.
        result["rehearsal"] = True
        result["rehearsal_metrics"] = result.pop("metrics")
        result["metrics"] = {}
    # Last in the line and last on standard error: what was compared.
    result["compared"] = compared(verdict, gen, compiles)
    write_json_atomic(os.path.join(work, "result.json"), result)
    for name, (value, limit) in result["compared"].items():
        print(f"compared: {name} {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
