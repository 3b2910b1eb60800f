"""The deployed process layout, started for one run and stopped after it.

    run.py (this process: load generator; never imports JAX)
      ├─ python -m ai4e_tpu control-plane      gateway + store + broker + dispatchers
      ├─ python benchmark/lib/worker_launcher.py   the ONE process that holds the chip(s):
      │     the program's own ``ai4e_tpu.cli`` worker entry, called in-process
      └─ python benchmark/lib/refcheck.py      plain reference, pinned to the host CPU

The layout is ``chip_smoke.py``'s (control plane and worker as separate
processes, as the charts deploy them); the file is not imported, because a
later PR may change it and may not change the yardstick.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
LIB = os.path.dirname(os.path.abspath(__file__))
START_TIMEOUT_S = 1100.0   # a cold first run compiles; the contract allows 1200 s


class StartError(RuntimeError):
    """The stack could not be brought up as the cell asks (no chip, fewer
    chips, a process died). The run exits non-zero and prints no result."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def get_text(url: str, timeout: float = 30.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode()


def get_json(url: str, timeout: float = 30.0):
    return json.loads(get_text(url, timeout))


class Stack:
    """Control plane + worker (+ reference helper) of one run."""

    def __init__(self, config: dict, work: str, trace: bool,
                 trace_seconds: float):
        self.config = config
        self.work = work
        self.trace = trace
        self.trace_seconds = trace_seconds
        self.procs: dict[str, subprocess.Popen] = {}
        self.cp_base = f"http://127.0.0.1:{free_port()}"
        self.wk_base = f"http://127.0.0.1:{free_port()}"
        self.prefix = config["models"]["prefix"]
        self.device: dict = {}

    # -- files ---------------------------------------------------------------

    def _write_specs(self) -> tuple[str, str]:
        models = dict(self.config["models"], taskstore=self.cp_base)
        routes = {"apis": [
            {k: v for k, v in dict(
                api, backend=f"{self.wk_base}/{api['backend_path']}").items()
             if k != "backend_path"} for api in self.config["routes"]]}
        paths = []
        for name, spec in (("routes.json", routes), ("models.json", models)):
            paths.append(os.path.join(self.work, name))
            with open(paths[-1], "w") as f:
                json.dump(spec, f, indent=1)
        return paths[0], paths[1]

    def log_path(self, name: str) -> str:
        return os.path.join(self.work, f"{name}.log")

    def log_tail(self, name: str, n: int = 3000) -> str:
        try:
            with open(self.log_path(name), errors="replace") as f:
                return f.read()[-n:]
        except OSError:
            return ""

    # -- processes -------------------------------------------------------------

    def _spawn(self, name: str, argv: list[str], env: dict) -> None:
        with open(self.log_path(name), "w") as out:
            self.procs[name] = subprocess.Popen(
                [sys.executable, *argv], env=env, cwd=ROOT,
                stdout=out, stderr=subprocess.STDOUT)

    def start(self) -> None:
        routes_path, models_path = self._write_specs()
        base_env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
        base_env["PYTHONPATH"] = ROOT + os.pathsep + base_env.get(
            "PYTHONPATH", "")
        # One fixed cache directory inside the checkout (the path is part of
        # the cache key); an operator's own setting wins, as in the program.
        base_env.setdefault("JAX_COMPILATION_CACHE_DIR",
                            os.path.join(ROOT, ".jax_cache"))
        platform = self.config["platform"]
        cp_env = dict(base_env, AI4E_RUNTIME_PLATFORM="cpu",
                      **self.config.get("control_plane_env", {}))
        wk_env = dict(base_env, AI4E_RUNTIME_PLATFORM=platform,
                      **self.config.get("worker_env", {}))
        if self.trace:
            # The hop ledger (and with it the device-phase histograms) is the
            # per-layer source; it is off in the end-to-end runs.
            cp_env["AI4E_PLATFORM_OBSERVABILITY"] = "1"
            wk_env["AI4E_OBSERVABILITY_HOP_LEDGER"] = "1"
        self._spawn("control-plane",
                    ["-m", "ai4e_tpu", "control-plane", "--routes",
                     routes_path, "--port", self.cp_base.rsplit(":", 1)[1]],
                    cp_env)
        launcher = [os.path.join(LIB, "worker_launcher.py"),
                    "--models", models_path,
                    "--port", self.wk_base.rsplit(":", 1)[1],
                    "--control", self.work]
        if self.trace:
            launcher += ["--trace-seconds", str(self.trace_seconds)]
        self._spawn("worker", launcher, wk_env)

    def start_helper(self, name: str, argv: list[str]) -> None:
        """A child pinned to the host CPU (reference, trace reduction): it
        may import JAX without ever reaching for the chip."""
        env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
        env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT + os.pathsep
                   + env.get("PYTHONPATH", ""))
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        self._spawn(name, argv, env)

    def wait_http(self, name: str, url: str, timeout: float) -> None:
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            rc = self.procs[name].poll()
            if rc is not None:
                raise StartError(f"{name} exited with {rc} before serving; "
                                 f"its log ends:\n{self.log_tail(name)}")
            try:
                with urllib.request.urlopen(url, timeout=2):
                    return
            except (OSError, urllib.error.URLError):
                time.sleep(0.2)
        raise StartError(f"{name} did not answer {url} within {timeout:.0f}s;"
                         f" its log ends:\n{self.log_tail(name)}")

    def wait_serving(self, chips: int) -> dict:
        """Block until both roles serve; the worker's OWN device report must
        name the platform and the chip count the cell asks for."""
        self.wait_http("control-plane", f"{self.cp_base}/healthz", 120.0)
        self.wait_http("worker", self.models_url, START_TIMEOUT_S)
        self.device = get_json(self.models_url)["device"]
        want = self.config["platform"]
        if (self.device["platform"] != want
                or self.device["device_count"] != chips):
            raise StartError(
                f"the worker reports {self.device['device_count']} x "
                f"{self.device['device_kind']!r} on platform "
                f"{self.device['platform']!r}; the cell asks for {chips} "
                f"on {want!r}")
        return self.device

    @property
    def models_url(self) -> str:
        return f"{self.wk_base}/{self.prefix}/models"

    def worker_metrics(self) -> str:
        return get_text(f"{self.wk_base}/metrics")

    def memory_peak_bytes(self) -> int | None:
        """Peak device memory on the fullest chip: the allocator's
        ``peak_bytes_in_use`` as the worker's own device report has it.
        None where the backend keeps no figures (XLA:CPU)."""
        memory = get_json(self.models_url)["device"].get("memory")
        if not memory:
            return None
        return max(int(m["peak_bytes_in_use"]) for m in memory)

    def stop(self, name: str, timeout: float = 90.0) -> int | None:
        proc = self.procs.get(name)
        if proc is None:
            return None
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                return proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                proc.kill()
        return proc.wait(timeout=30)

    def kill_all(self) -> None:
        for proc in self.procs.values():
            if proc.poll() is None:
                proc.kill()
        for proc in self.procs.values():
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass
