"""Rehearsals of chip_smoke.py and the compile-cache helper on the CPU.

The smoke's phases run here at a tiny size on the conftest CPU mesh, with
the Pallas kernels in interpret mode; the auto modes that pick the CPU's
own branches (host seek, host density, no exact device descriptor) are
steered to the chip's branches from the test.
"""

import os
import shutil
import subprocess
import sys

import jax
import pytest

import chip_smoke
from geomesa_tpu.parallel import default_mesh
from geomesa_tpu.parallel.mesh import enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def chip_branches(monkeypatch):
    for k, v in {
        "GEOMESA_DEVBATCH": "1",
        "GEOMESA_EXACT_DEVICE": "1",
        "GEOMESA_DENSITY_DEVICE": "1",
    }.items():
        monkeypatch.setenv(k, v)


TINY = dict(rows=40_000, queries=4)


def test_one_chip_phases_pass(chip_branches, monkeypatch):
    monkeypatch.setenv("GEOMESA_PALLAS", "1")
    chip_smoke.run_one_chip(
        default_mesh(jax.devices()[:1]), 13, density_rows=20_000, grid=64, **TINY
    )


def test_four_chip_phase_passes_on_virtual_devices(chip_branches, monkeypatch):
    monkeypatch.setenv("GEOMESA_PALLAS", "spmd")
    chip_smoke.run_four_chips(default_mesh(jax.devices()[:4]), 13, **TINY)


def test_stream_parity_mismatch_fails(chip_branches, monkeypatch):
    import bench

    monkeypatch.setenv("GEOMESA_PALLAS", "1")
    real = bench.brute_force
    monkeypatch.setattr(bench, "brute_force", lambda *a: real(*a)[1:])
    with pytest.raises(chip_smoke.SmokeFailure, match="brute force"):
        chip_smoke.run_one_chip(
        default_mesh(jax.devices()[:1]), 13, density_rows=20_000, grid=64, **TINY
    )


def test_device_leg_answered_off_the_device_fails(chip_branches, monkeypatch):
    """The device leg is checked by its results' scan paths: a stream the
    host answered fails the smoke even when every answer is right."""
    import bench
    from geomesa_tpu.parallel import TpuScanExecutor
    from geomesa_tpu.store.datastore import TpuDataStore

    monkeypatch.setenv("GEOMESA_PALLAS", "1")
    monkeypatch.setattr(TpuScanExecutor, "supports", lambda self, table, plan: False)
    store = TpuDataStore(executor=TpuScanExecutor(default_mesh(jax.devices()[:1])))
    x, y, t = bench.synthesize(TINY["rows"], 13)
    fids = chip_smoke.ingest(store, "gdelt", x, y, t)
    with pytest.raises(chip_smoke.SmokeFailure, match="device leg answered by"):
        chip_smoke.phase_stream(store, "gdelt", x, y, t, fids, TINY["queries"],
                                legs=("device",))


def test_host_degradation_fails_the_smoke():
    from geomesa_tpu.parallel import TpuScanExecutor
    from geomesa_tpu.store.datastore import TpuDataStore
    from geomesa_tpu.utils.audit import robustness_metrics

    store = TpuDataStore(executor=TpuScanExecutor(default_mesh(jax.devices()[:1])))
    before = chip_smoke.degraded()
    robustness_metrics().inc("degrade.device_to_host")
    with pytest.raises(chip_smoke.SmokeFailure, match="degrade.device_to_host = 1"):
        chip_smoke.check_device_answered(store, "xla", before)


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, script], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=120,
    )


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py", "bench_suite.py"])
def test_no_tpu_exits_nonzero_without_result(script):
    proc = _run(script, REPO)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert '"value"' not in proc.stdout


def test_chip_smoke_alone_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run("chip_smoke.py", tmp_path)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_honours_env(cache_config, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing else set


def test_compile_cache_defaults_to_repo(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
