"""Rules at the device boundary: a process says which device it holds,
refuses one it was not asked for, takes its compile cache from where the
environment says, and a measurement or smoke run that is not on the chip
fails instead of producing a result. The start-up cases run a fresh
interpreter each — ``jax.config`` is process-global."""

import json
import os
import socket
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**overrides) -> dict:
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    for name in ("JAX_COMPILATION_CACHE_DIR", "AI4E_RUNTIME_PLATFORM"):
        env.pop(name, None)
    env.update(overrides)
    return env


_RESOLVE = """
import json, jax
set_in_code = []
real_update = jax.config.update
def recording_update(name, value):
    set_in_code.append(name)
    real_update(name, value)
jax.config.update = recording_update
from ai4e_tpu.runtime import enable_compilation_cache
path = enable_compilation_cache()
print(json.dumps({"returned": path, "set_in_code": set_in_code,
                  "in_force": jax.config.jax_compilation_cache_dir}))
"""


def _resolve(env: dict) -> dict:
    out = subprocess.run([sys.executable, "-c", _RESOLVE], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True, cwd="/")
    return json.loads(out.stdout.strip().splitlines()[-1])


class TestCompileCachePlacedFromOutside:
    def test_env_set_means_code_sets_no_directory(self, tmp_path):
        got = _resolve(_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
        assert got["returned"] == got["in_force"] == str(tmp_path)
        assert "jax_compilation_cache_dir" not in got["set_in_code"]

    def test_env_unset_is_one_fixed_path_in_the_checkout(self):
        first, second = _resolve(_env()), _resolve(_env())
        assert first == second
        assert first["returned"] == first["in_force"] == os.path.join(
            REPO, ".jax_cache")


def _models_file(tmp_path) -> str:
    path = tmp_path / "models.json"
    path.write_text(json.dumps({
        "service_name": "w", "prefix": "v1/echo",
        "models": [{"family": "echo", "name": "echo", "size": 8,
                    "buckets": [1], "sync_path": "/run"}]}))
    return str(path)


def _run_worker_until_exit(tmp_path, env: dict) -> tuple[int, str, bool]:
    """``(returncode, output, port_ever_bound)`` of a worker expected to
    refuse at start-up."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    proc = subprocess.Popen(
        [sys.executable, "-m", "ai4e_tpu", "worker", "--models",
         _models_file(tmp_path), "--port", str(port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    bound = False
    deadline = time.monotonic() + 120
    try:
        while proc.poll() is None and time.monotonic() < deadline:
            with socket.socket() as probe:
                probe.settimeout(0.2)
                bound |= probe.connect_ex(("127.0.0.1", port)) == 0
            time.sleep(0.05)
    finally:
        if proc.poll() is None:
            proc.kill()
    output, _ = proc.communicate(timeout=30)
    assert time.monotonic() < deadline, "worker neither served nor exited"
    return proc.returncode, output, bound


class TestWorkerRefusesADeviceItWasNotAskedFor:
    def test_asked_for_tpu_where_there_is_none(self, tmp_path):
        rc, output, bound = _run_worker_until_exit(
            tmp_path, _env(AI4E_RUNTIME_PLATFORM="tpu"))
        assert rc != 0
        assert not bound
        assert "Unable to initialize backend 'tpu'" in output

    def test_unasked_cpu_is_a_start_up_error_naming_what_was_found(
            self, tmp_path):
        rc, output, bound = _run_worker_until_exit(
            tmp_path, _env(JAX_PLATFORMS="cpu"))
        assert rc != 0
        assert not bound
        assert "platform 'cpu'" in output
        assert "AI4E_RUNTIME_PLATFORM=cpu" in output


# -- benchmark/peaks.json: a peak is keyed by the exact device_kind ----------

def test_peaks_are_keyed_by_exact_device_kind_with_a_source():
    with open(os.path.join(REPO, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["TPU v5 lite"]["source"]
    # "TPU v5" once prefix-matched a guessed peak: no key may shadow another.
    assert not [(a, b) for a in peaks for b in peaks
                if a != b and b.startswith(a)]


# -- kernels: which lowering, said once -------------------------------------

def test_kernel_lowering_is_logged_once_per_kernel_and_mode(caplog):
    import logging

    from ai4e_tpu.ops.pallas import lowering
    lowering._log_once.cache_clear()
    with caplog.at_level(logging.INFO, logger="ai4e_tpu.pallas"):
        assert lowering.resolve_interpret("k", None) is True  # CPU backend
        assert lowering.resolve_interpret("k", None) is True
        assert lowering.resolve_interpret("k", False) is False
    assert [r.getMessage().split(" (")[0] for r in caplog.records] == [
        "pallas k: interpreter", "pallas k: Mosaic"]


# -- chip_smoke.py: the contract's failure modes -----------------------------

def _smoke(cwd: str, script: str, *flags: str):
    return subprocess.run([sys.executable, script, *flags], cwd=cwd,
                          env=_env(JAX_PLATFORMS="cpu"), capture_output=True,
                          text=True, timeout=600)


def test_chip_smoke_without_a_chip_fails_before_serving_and_prints_no_result():
    res = _smoke(REPO, os.path.join(REPO, "chip_smoke.py"))
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "Unable to initialize backend 'tpu'" in res.stderr


def test_chip_smoke_alone_without_the_program_fails(tmp_path):
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    res = _smoke(str(tmp_path), str(tmp_path / "chip_smoke.py"))
    assert res.returncode != 0
    assert res.stdout.strip() == ""


@pytest.mark.slow
def test_chip_smoke_cpu_cut_passes_end_to_end():
    res = _smoke(REPO, os.path.join(REPO, "chip_smoke.py"), "--cpu-cut")
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    # The suite's XLA_FLAGS reach the worker: it runs on the virtual
    # 8-device mesh, so this is also the smoke's multi-device pass.
    last = json.loads(res.stdout.strip().splitlines()[-1])
    assert last["ok"] is True and last["device"]["platform"] == "cpu"
