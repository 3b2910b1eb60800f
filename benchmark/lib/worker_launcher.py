"""Start the program's worker role in this process, and — only when asked —
trace the device from a side thread.

Only the process that holds the chip can trace it, and the worker has no
profiler switch. So the benchmark starts the worker through this launcher,
which calls the same ``ai4e_tpu.cli`` worker entry ``python -m ai4e_tpu worker``
calls. Without ``--trace-seconds`` nothing else happens here and no profiler
is imported. With it, a thread waits for ``<control>/trace.start``, records
``--trace-seconds`` of the steady window with ``jax.profiler``, scrapes the
worker's own ``/metrics`` at both edges of the trace (so counters line up with
the trace), and writes ``<control>/trace.done``.
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time
import urllib.request


def _scrape(port: int) -> str:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                                timeout=30) as resp:
        return resp.read().decode()


def _trace_thread(control: str, port: int, seconds: float) -> None:
    start = os.path.join(control, "trace.start")
    while not os.path.exists(start):
        time.sleep(0.05)
    import jax
    out = {"error": None}
    try:
        out["trace_begin_epoch"] = time.time()   # the trace's own zero, to ms
        # Device planes only. The Python and host tracers hook every call of
        # the worker's event loop: with them on, a flood's tasks piled up
        # behind the slowed loop and were cut as 51-row batches where the
        # untraced worker cuts 3-row ones (my chip run, PR 23).
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        jax.profiler.start_trace(os.path.join(control, "trace"),
                                 profiler_options=options)
        # The scrapes bound the interval the reduction keeps: the profiler
        # goes on recording for seconds while stop_trace collects.
        out["metrics_before"] = _scrape(port)
        out["interval_epoch"] = [time.time()]
        time.sleep(seconds)
        out["interval_epoch"].append(time.time())
        out["metrics_after"] = _scrape(port)
        jax.profiler.stop_trace()
        out["memory_stats"] = [d.memory_stats() for d in jax.local_devices()]
    except Exception as exc:  # noqa: BLE001 — reported to the parent, which fails the run
        out["error"] = repr(exc)
    tmp = os.path.join(control, "trace.done.tmp")
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.replace(tmp, os.path.join(control, "trace.done"))


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--models", required=True)
    parser.add_argument("--port", type=int, required=True)
    parser.add_argument("--control", required=True,
                        help="the run's work directory")
    parser.add_argument("--trace-seconds", type=float, default=0.0)
    args = parser.parse_args()
    if args.trace_seconds > 0:
        threading.Thread(target=_trace_thread, daemon=True,
                         args=(args.control, args.port,
                               args.trace_seconds)).start()
    from ai4e_tpu import cli
    cli.main(["worker", "--models", args.models, "--port", str(args.port)])


if __name__ == "__main__":
    main()
