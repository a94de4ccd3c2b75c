"""chip_smoke.py on the forced CPU mesh (tier-1, no chip).

The smoke's phases take the platform they expect, so this drives the
same code the chip run drives — EmbeddedCluster + REST + client, every
answer against the pandas oracle, the ledger / residency / launch
evidence — at toy size with ``cpu`` expected. ``main`` itself always
expects ``tpu`` and must refuse this sandbox before it builds anything.
"""

import os
import subprocess
import sys

import jax
import pytest

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_phases_at_toy_size_on_forced_cpu_mesh(tmp_path):
    n_dev = len(jax.devices())
    report = chip_smoke.run("cpu", 50_000, chip_smoke.NUM_SEGMENTS, 7,
                            str(tmp_path))
    assert report["device"] == {"platform": "cpu", "kind": "cpu",
                                "count": n_dev}
    queries = report["queries"]
    assert len(queries) == 13 + len(chip_smoke.FORCED_SCAN) + 1
    for qid in chip_smoke.FORCED_SCAN:
        # _run_sharded reached through EmbeddedCluster + REST
        assert queries[f"{qid}/scan"]["rung"] == "sharded_combine"
        assert queries[f"{qid}/scan"]["mesh"] == f"{n_dev}x1"
    # every device holds part of the sharded batch
    assert len(report["sharded_batch_bytes_per_device"]) == n_dev
    # every burst request passed the dispatcher once: launched, or riding
    # an identical rider's launch
    last = report["burst"]["last_round"]
    assert last["requests"] == len(chip_smoke.BURST_QUANTITIES)
    assert last["launches"] + last["launchesSaved"] == last["requests"]
    assert report["residency"]["counters"]["spills"] == 0


@pytest.mark.parametrize("after, sent, fails", [
    # eight distinct literals, one launch each
    ({"requests": 108, "launches": 58, "launchesSaved": 7}, 8, None),
    # three of the eight shared a rider's launch
    ({"requests": 108, "launches": 55, "launchesSaved": 10}, 8, None),
    # a request that never reached the dispatcher
    ({"requests": 107, "launches": 57, "launchesSaved": 7}, 8,
     "8 burst requests sent"),
    # a rider neither launched nor counted as saved
    ({"requests": 108, "launches": 57, "launchesSaved": 7}, 8,
     "launches \\+ launchesSaved != requests"),
])
def test_burst_conservation_on_hand_made_counters(after, sent, fails):
    before = {"requests": 100, "launches": 50, "launchesSaved": 7,
              "queued": 0}
    if fails is None:
        delta = chip_smoke.burst_conservation(before, after, sent)
        assert delta["requests"] == sent
    else:
        with pytest.raises(chip_smoke.SmokeFailure, match=fails):
            chip_smoke.burst_conservation(before, after, sent)


def test_wrong_platform_fails_at_the_device_line(tmp_path):
    with pytest.raises(chip_smoke.SmokeFailure, match="expected platform"):
        chip_smoke.run("tpu", 50_000, chip_smoke.NUM_SEGMENTS, 7,
                       str(tmp_path))
    assert os.listdir(tmp_path) == []      # before any data was built


def test_main_exits_nonzero_without_a_result_on_cpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "expected platform 'tpu'" in proc.stderr


def test_segment_builder_children_initialise_no_backend(tmp_path):
    """The spawn-pool builders run while the parent holds the chip: they
    may import jax but must never initialise a backend."""
    code = (
        "from pinot_tpu.tools import ssb\n"
        f"ssb._build_one(0, 2, 4000, 7, {str(tmp_path)!r})\n"
        "import jax._src.xla_bridge as xb\n"
        "print(xb.backends_are_initialized())\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "False"


def test_compile_cache_follows_the_environment(monkeypatch):
    from pinot_tpu.engine import ensure_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        ensure_compile_cache()
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        ensure_compile_cache()
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            ROOT, ".jax_cache")
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
