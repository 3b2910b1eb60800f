"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; all sharding tests run against
``--xla_force_host_platform_device_count=8`` (the cluster-simulator gap
SURVEY.md §4 flags in the reference, fixed here). ``JAX_PLATFORMS=cpu`` in
the environment does the same; the config update below makes the suite hold
to the CPU — and never claim a chip another process is serving from —
whatever the shell carries.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

try:
    import jax  # noqa: E402
except ImportError:
    # The race-smoke CI job runs the interleaving suite with no JAX
    # toolchain installed (like the stdlib-only analysis job). Tests that
    # need JAX fail at their own module imports; the race/analysis files
    # import none of it.
    jax = None

if jax is not None:
    jax.config.update("jax_platforms", "cpu")


if os.environ.get("AI4E_OBSERVABILITY_TRACE_EXPORT_PATH"):
    # CI debugging hook (observability PR): when the env names a span
    # log, install the configured exporters on the process tracer —
    # every platform component's tracer follows it live, so a red
    # chaos/race run's spans land in a JSONL the workflow uploads as an
    # artifact beside the invariant checker's flight-recorder dump.
    # No-op locally (the variable is unset).
    from ai4e_tpu.config import ObservabilitySection
    ObservabilitySection.from_env().apply()


def pytest_configure(config):
    # Registered here (no pytest.ini): `slow` gates tier-1's wall clock
    # (`-m 'not slow'`), `chaos` marks the seeded fault-injection
    # scenarios CI's chaos-smoke job runs explicitly (`-m chaos`),
    # `race` marks the deterministic interleaving suite CI's race-smoke
    # job runs without JAX (`-m race`).
    config.addinivalue_line("markers", "slow: excluded from tier-1 CI")
    config.addinivalue_line(
        "markers", "chaos: seeded fault-injection scenario "
        "(AI4E_CHAOS_SEED overrides the seed)")
    config.addinivalue_line(
        "markers", "race: deterministic interleaving-exploration suite "
        "(ai4e_tpu.analysis.race; runs JAX-free in race-smoke)")
    config.addinivalue_line(
        "markers", "durability: crash-point sweep + disk-fault chaos "
        "(docs/durability.md; runs JAX-free in durability-smoke)")
