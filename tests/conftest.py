"""Test bootstrap: run the whole suite on a virtual 8-device CPU mesh.

Tests never ask for a chip: sharding correctness is validated on XLA's
host platform with 8 virtual devices (the same mechanism
``__graft_entry__.dryrun_multichip`` uses), and a chip is exercised by
``chip_smoke.py`` through the chip tool.
"""

import os

FORCED_HOST_DEVICES = 8


def _force_host_devices(n: int = FORCED_HOST_DEVICES) -> None:
    """Force ``n`` virtual CPU devices BEFORE jax initializes a backend.

    Both settings are environment variables JAX reads at backend
    initialization, so every child process (spawn-pool segment builders)
    inherits them. Idempotent: a device-count flag already present, ours or
    the caller's, is left alone. ``JAX_PLATFORMS=cpu`` is what the tier-1
    command already sets; setting it here keeps a bare ``pytest`` off any
    accelerator too.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"


_force_host_devices()

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "lint: graftlint static-analysis gate (pytest -m lint runs just "
        "the invariant checkers)")
    config.addinivalue_line(
        "markers",
        "startree: star-tree pre-aggregation rung (pytest -m startree "
        "exercises build/plan/device-exec in isolation; part of tier-1)")
    config.addinivalue_line(
        "markers",
        "residency_tier: tiered residency (host-RAM spill tier, "
        "restage-cost-aware eviction, budget-sliced sharded combine; "
        "pytest -m residency_tier runs it in isolation; part of tier-1)")
    config.addinivalue_line(
        "markers",
        "trace: query lifecycle tracing (span trees, decision ledger, "
        "slow-query log; pytest -m trace runs it in isolation; part of "
        "tier-1)")
    config.addinivalue_line(
        "markers",
        "telemetry: continuous telemetry (windowed histograms, SLO burn "
        "tracking, flight recorder; pytest -m telemetry runs it in "
        "isolation; part of tier-1)")
    config.addinivalue_line(
        "markers",
        "pallas: fused Pallas scan kernel (interpret-mode parity, SSB-13 "
        "eligibility, group-range probe narrowing; pytest -m pallas runs "
        "it in isolation; part of tier-1)")
    config.addinivalue_line(
        "markers",
        "cluster_routing: partition-aware scatter routing + replica "
        "groups + partial-result gather + the sharded combine on the "
        "forced multi-device mesh (pytest -m cluster_routing runs it in "
        "isolation; part of tier-1)")
    config.addinivalue_line(
        "markers",
        "reduce: array-native broker reduce (columnar DataTables, "
        "vectorized merge parity vs the row-path oracle, "
        "reduce-as-arrivals; pytest -m reduce runs it in isolation; "
        "part of tier-1)")
    config.addinivalue_line(
        "markers",
        "pallas_preflight: kernel preflight (static lowering model over "
        "the SSB plan space + fuzz grid, interpret-mode cross-check, "
        "blocklist seeding/persistence; pytest -m pallas_preflight runs "
        "it in isolation; part of tier-1)")
    config.addinivalue_line(
        "markers",
        "reduce_device: device-resident broker reduce (group-by merge "
        "over the forced 8-virtual-device mesh, SSB parity vs the "
        "vectorized host path and the row oracle, decline-shape "
        "fixtures; pytest -m reduce_device runs it in isolation; part "
        "of tier-1)")
    config.addinivalue_line(
        "markers",
        "realtime_tier: realtime serving tier (device-queryable "
        "consuming segments, watermark-snapshot parity, seal-under-query "
        "hammer, hybrid time-boundary routing, freshness SLO; pytest "
        "-m realtime_tier runs it in isolation; part of tier-1)")
    config.addinivalue_line(
        "markers",
        "index_rung: index-accelerated selective filters (host docId "
        "resolution over inverted/sorted/range indexes, device gather "
        "kernel parity vs scan and host oracle, residency pinning, "
        "decision-ledger exactness; pytest -m index_rung runs it in "
        "isolation; part of tier-1)")


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {devs}"
    return devs


@pytest.fixture(scope="session")
def forced_mesh_devices(eight_devices):
    """The conftest-forced virtual device set the multi-device mesh tests
    build their ``Mesh`` from (see ``_force_host_devices``: env-flag based,
    so spawn subprocesses — segment builders — inherit the same device
    count)."""
    return eight_devices
