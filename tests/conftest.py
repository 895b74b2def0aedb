"""Test config: force an 8-device virtual CPU mesh before JAX initializes.

Mirrors the reference's mock-cluster test pattern (SURVEY.md section 4):
distributed behavior is exercised in-process, here via
``xla_force_host_platform_device_count`` instead of Accumulo MockInstance.

Tests never claim an accelerator (a chip belongs to one process at a
time); the single shared pinning recipe lives in
``geomesa_tpu.parallel.mesh.force_cpu_platform`` (env + jax config + XLA
flags, inherited by subprocesses).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

try:
    from geomesa_tpu.parallel.mesh import force_cpu_platform

    force_cpu_platform(min_devices=8)
except ImportError:  # jax missing entirely -> host-only tests still run
    pass


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "chaos: randomized fault-injection soak (bounded; scripts/chaos_smoke.sh "
        "runs just these)",
    )
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 run (-m 'not slow')"
    )
