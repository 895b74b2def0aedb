"""Device mesh construction and columnar sharding helpers.

Blocks are sharded along their row axis (the analog of tablet splits,
api/GeoMesaFeatureIndex.scala:116 getSplits); query descriptors are
replicated. Multi-host meshes ride DCN automatically through jax's global
device set — the layout code here is identical single-chip and pod-scale.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from geomesa_tpu.utils import deadline, faults, trace
from geomesa_tpu.utils.devstats import count_h2d, instrumented_jit

DATA_AXIS = "shards"

# per-device-set dispatch gates (see dispatch_gate): one entry per
# distinct multi-device set, shared across every Mesh built over it
_DISPATCH_GATES: Dict[tuple, threading.RLock] = {}
_DISPATCH_GATES_LOCK = threading.Lock()


def dispatch_gate(mesh) -> Optional[threading.RLock]:
    """The per-mesh dispatch gate: at most ONE collective-bearing XLA
    program in flight per device set.

    XLA's collective rendezvous assumes programs reach every
    participating device in one global order; two host threads each
    launching a program with collectives (the all-gather of
    ``executor._gathered``, a cross-shard ``jnp.sum`` reduction) onto
    the SAME multi-device mesh can interleave their launches and
    deadlock the rendezvous — the hazard PR 9's concurrency tests
    surfaced with concurrent SOLO queries. The fence: callers hold this
    gate from launch until the program's outputs are READY, so no
    collective of one program can still be pending when the next
    launches. Keyed by the underlying device set (not the Mesh object),
    so every Mesh built over the same chips shares one gate; re-entrant
    so a gated kernel may compose gated helpers.

    Returns None — no gating — for single-device meshes (nothing to
    rendezvous) and under ``GEOMESA_SPMD_GATE=0`` (A/B escape hatch;
    shipping code must treat the gate as always on). Collective-free
    kernels (the shard_map shard-extract and stacked-mask editions,
    whose bodies contain no cross-shard communication) never consult
    the gate at all — that layout is the other half of the
    rendezvous-safety contract."""
    import os

    if mesh is None or getattr(mesh, "devices", np.empty(0)).size <= 1:
        return None
    if os.environ.get("GEOMESA_SPMD_GATE", "1") == "0":
        return None
    key = tuple(
        (getattr(d, "platform", "?"), getattr(d, "id", id(d)))
        for d in mesh.devices.flat
    )
    with _DISPATCH_GATES_LOCK:
        gate = _DISPATCH_GATES.get(key)
        if gate is None:
            gate = _DISPATCH_GATES[key] = threading.RLock()
    return gate


def gated(fn, mesh):
    """Wrap a jitted multi-device execution in the mesh's dispatch gate
    (see ``dispatch_gate``): the call holds the gate until its outputs
    are READY, so no collective of this program can still be pending
    when another thread launches the next one. Single-device meshes
    (and ``GEOMESA_SPMD_GATE=0``) return ``fn`` unchanged — zero
    overhead exactly where there is nothing to rendezvous."""
    gate = dispatch_gate(mesh)
    if gate is None:
        return fn

    def call(*args, **kwargs):
        with gate:
            out = fn(*args, **kwargs)
            jax.block_until_ready(out)
            return out

    call.__name__ = f"mesh_gated[{getattr(fn, '__name__', 'fn')}]"
    for shared in ("_jitted", "_devstats"):
        if hasattr(fn, shared):
            setattr(call, shared, getattr(fn, shared))
    return call


def force_cpu_platform(min_devices: int = 0):
    """Pin jax to the cpu platform and return its devices, never touching
    an accelerator backend (a chip belongs to one process at a time).

    Both the env var (inherited by children) and jax's config are forced
    before any backend initializes. If ``min_devices`` > 1 and the cpu
    backend is not yet initialized, the
    ``xla_force_host_platform_device_count`` flag is added so a virtual
    multi-device mesh exists even when the caller's env forgot it.
    """
    import os
    import re

    os.environ["JAX_PLATFORMS"] = "cpu"
    if min_devices > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
        if m is None:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={min_devices}"
            ).strip()
        elif int(m.group(1)) < min_devices:
            os.environ["XLA_FLAGS"] = flags.replace(
                m.group(0), f"--xla_force_host_platform_device_count={min_devices}"
            )
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass  # backends already initialized; explicit "cpu" lookup below
    devices = jax.devices("cpu")
    if min_devices and len(devices) < min_devices:
        raise RuntimeError(
            f"cpu backend has {len(devices)} device(s), need {min_devices}; "
            "set XLA_FLAGS=--xla_force_host_platform_device_count before jax "
            "initializes"
        )
    return devices


def shard_map_fn(f, mesh: Mesh, in_specs, out_specs, check: bool = True):
    """``jax.shard_map``; check=False disables the vma checker (required
    when the per-shard body is a pallas_call, whose out_shape carries no
    vma annotation)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check
    )


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (jax reads it
    itself; nothing else is set). Otherwise the cache lives at the fixed
    path ``<repo>/.jax_cache``: the path is part of the cache key, so a
    directory that moved between runs would never hit. Call before the
    first compile."""
    import os

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        ".jax_cache",
    )
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def default_mesh(devices: Optional[Sequence] = None, axis: str = DATA_AXIS) -> Mesh:
    """1D mesh over all (or the given) devices; rows shard over ``axis``."""
    devices = list(devices) if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), (axis,))


def multihost_mesh(
    coordinator: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    axis: str = DATA_AXIS,
) -> Mesh:
    """Mesh spanning every chip of every host (the multi-host DCN path).

    The reference scales batch compute by adding Spark executors over the
    database's RPC fabric (AccumuloSpatialRDDProvider); here the fabric is
    jax's distributed runtime: each host calls this with the same
    coordinator address, ``jax.distributed.initialize`` wires DCN, and
    ``jax.devices()`` becomes the GLOBAL device set. Collectives inserted
    by shard_map/pjit ride ICI within a host and DCN across hosts — the
    executor's scan/merge code is unchanged at any scale.

    With no arguments this is a no-op wrapper around the local device set
    (single-controller dev mode and tests).
    """
    if coordinator is not None:
        # the CPU backend only runs multi-process collectives over gloo,
        # and the default is "none" — without this, the first
        # cross-process psum dies with "Multiprocess computations aren't
        # implemented on the CPU backend". Harmless on TPU/GPU pods (the
        # flag only configures the cpu backend) and must be set BEFORE
        # any backend initializes.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
    devices = jax.devices()
    # hosts first: keeps each host's chips contiguous along the data axis so
    # block shards stay host-local and cross-host traffic is merge-only
    devices = sorted(devices, key=lambda d: (d.process_index, d.id))
    return Mesh(np.asarray(devices), (axis,))


def pad_to_multiple(arr: np.ndarray, multiple: int, fill) -> np.ndarray:
    """Pad axis 0 to a multiple so rows divide evenly across shards."""
    n = arr.shape[0]
    rem = (-n) % multiple
    if rem == 0:
        return arr
    pad_width = [(0, rem)] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, pad_width, constant_values=fill)


def shard_array(mesh: Mesh, arr: np.ndarray, axis: str = DATA_AXIS):
    """Place a host array on the mesh, sharded along axis 0.

    ``device.dispatch`` fault point: every H2D placement (mirror uploads
    and query descriptors) passes here or through ``replicate``, so an
    injected dispatch fault exercises the executor's device->host
    degradation exactly where a lost device would surface. The span of
    the same name is the tracing half of that contract: every H2D
    boundary crossing lands on the owning query's span tree."""
    with trace.span("device.dispatch", bytes=int(getattr(arr, "nbytes", 0))):
        deadline.check("device.dispatch")
        faults.fault_point("device.dispatch")
        out = jax.device_put(arr, NamedSharding(mesh, P(axis)))
        # counted AFTER the put: a faulted/failed dispatch moved nothing,
        # and the degradation path must not inflate the link counters
        count_h2d(int(getattr(arr, "nbytes", 0)))
        return out


def replicate(mesh: Mesh, arr: np.ndarray):
    """Place a host array on the mesh fully replicated (query descriptors)."""
    with trace.span("device.dispatch", bytes=int(getattr(arr, "nbytes", 0))):
        deadline.check("device.dispatch")
        faults.fault_point("device.dispatch")
        out = jax.device_put(arr, NamedSharding(mesh, P()))
        count_h2d(int(getattr(arr, "nbytes", 0)))
        return out


_LINK_LATENCY_MS: Optional[float] = None


def link_latency_ms() -> float:
    """Measured host<->device round-trip latency (ms), cached per process.

    The per-query cost floor of any device dispatch. A PCIe-attached chip
    measures well under 1 ms; a remote or networked accelerator can cost
    tens of ms per execution. Cost-based executor choices
    (device kNN/density autos) consult this so a high-latency link prefers
    host kernels while a local accelerator keeps the device paths — the
    same per-deployment cost asymmetry the reference handles by moving
    compute to the data (SURVEY.md section 2.6). CPU backend: 0 (device
    compute IS host compute). GEOMESA_LINK_LATENCY_MS overrides (tests,
    known deployments)."""
    global _LINK_LATENCY_MS
    import os

    env = os.environ.get("GEOMESA_LINK_LATENCY_MS")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    if _LINK_LATENCY_MS is None:
        if jax.default_backend() == "cpu":
            _LINK_LATENCY_MS = 0.0
        else:
            import time
            import numpy as _np

            f = instrumented_jit("link_probe", lambda x: x + 1)
            x = jax.device_put(_np.zeros(8, _np.float32))
            _np.asarray(f(x))  # compile + first transfer
            samples = []
            for _ in range(5):
                t0 = time.perf_counter()
                _np.asarray(f(x))
                samples.append((time.perf_counter() - t0) * 1000.0)
            # MIN, not median: the cost model wants the link's FLOOR, and
            # host-side contention only ever inflates samples (a loaded
            # box once measured >10 ms on a 0.2 ms link and parked the
            # density auto on the host path for the whole process)
            _LINK_LATENCY_MS = float(min(samples))
    return _LINK_LATENCY_MS


def device_auto_declines(env_var: str, link_cap_ms: float = 10.0) -> bool:
    """The shared auto-mode cost gate for scalar/aggregate device
    push-downs (count/stats/density): True when the path should decline
    to the host — forced off ("0"), or in auto mode on the CPU backend
    (where "device" compute IS host compute) or over a high-latency
    link (the per-execution floor loses to the host seek's sub-ms
    answer). An explicit "1" always passes."""
    import os

    import jax

    env = os.environ.get(env_var, "auto")
    if env == "0":
        return True
    if env == "1":
        return False
    if jax.default_backend() == "cpu":
        return True
    return link_latency_ms() > link_cap_ms


def device_tripped(executor, env_var: str) -> bool:
    """True when a device path already failed this session AND the
    operator has not forced THIS path on (env_var != "1"): auto-mode
    queries stick to the host after one device/backend failure instead
    of paying the failure latency per query; an explicit =1 keeps
    retrying. One home for the gate check the device kNN and density
    autos share."""
    import os

    if os.environ.get(env_var, "auto") == "1":
        return False
    return bool(getattr(executor, "_device_tripped", False))


def trip_device(executor, env_var: str, tag: str, exc: BaseException) -> None:
    """Record a device-path failure: one stderr line, and set the
    executor's session trip flag — UNLESS the operator forced this path
    on (a deterministic kernel-specific failure under a forced =1 must
    not poison the OTHER auto-mode device paths on a healthy device)."""
    import os
    import sys

    if os.environ.get(env_var, "auto") != "1":
        executor._device_tripped = True
    sys.stderr.write(
        f"[{tag}] device path failed ({type(exc).__name__}); "
        "host path answers\n"
    )
