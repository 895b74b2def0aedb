"""Cost gates consult the measured device link latency: a high-latency
(remote) accelerator link makes per-query device dispatch lose to
the host kernels, so the kNN/density autos must decline there."""

import numpy as np
import pytest

from geomesa_tpu.parallel import mesh as pmesh
from geomesa_tpu.process.knn import _device_knn_wanted


@pytest.fixture(autouse=True)
def _reset_cache(monkeypatch):
    monkeypatch.setattr(pmesh, "_LINK_LATENCY_MS", None)
    yield
    pmesh._LINK_LATENCY_MS = None


def test_cpu_backend_latency_is_zero():
    assert pmesh.link_latency_ms() == 0.0


def test_env_override_wins(monkeypatch):
    monkeypatch.setenv("GEOMESA_LINK_LATENCY_MS", "83.5")
    assert pmesh.link_latency_ms() == 83.5


def test_knn_auto_declines_on_high_latency_link(monkeypatch):
    # pretend the backend is an accelerator behind a slow link
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("GEOMESA_LINK_LATENCY_MS", "80")
    assert _device_knn_wanted() is False
    monkeypatch.setenv("GEOMESA_LINK_LATENCY_MS", "0.3")
    assert _device_knn_wanted() is True
    # explicit force beats the cost gate both ways
    monkeypatch.setenv("GEOMESA_LINK_LATENCY_MS", "80")
    monkeypatch.setenv("GEOMESA_KNN_DEVICE", "1")
    assert _device_knn_wanted() is True
    monkeypatch.setenv("GEOMESA_KNN_DEVICE", "0")
    assert _device_knn_wanted() is False
