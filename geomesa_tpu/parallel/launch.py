"""Pluggable worker launchers: the process-lifecycle SPI of the fleet.

``FleetSupervisor`` (parallel/fleet.py) used to hard-code
``subprocess.Popen`` + a shared-filesystem portfile handshake, which
bound the whole fleet to one box. This module inverts that: every
process-lifecycle action — the first launch, the restart ladder after a
death, a standby takeover's adoption, the chaos harness's hard kill —
routes through one ``WorkerLauncher``, selected by the
``geomesa.fleet.launcher`` knob.

The CONTRACT is the endpoint handshake, not the portfile:

* ``launch(i)`` starts worker ``i`` by whatever means the launcher
  knows and returns a :class:`WorkerHandle` whose ``addr`` is a
  dialable ``(host, port)`` endpoint, within the spawn timeout. How the
  endpoint travels back is the launcher's private business — the local
  launcher polls the worker's atomically-published portfile, the ssh
  launcher reads the worker's ``ENDPOINT host:port`` announcement from
  the remote stdout (``--announce stdout``). A launch that cannot
  produce a live endpoint raises the crisp :class:`WorkerLaunchFailed`
  (an OSError: the supervisor's restart ladder classifies it as
  transient and backs off).
* ``adopt(i)`` attaches to a worker an earlier (dead) coordinator left
  behind: it reads the coordinator-side endpoint record every launch
  publishes under ``<base>/w<i>.endpoint``, probes it with a raw ping,
  and returns a handle WITHOUT starting anything — takeover must never
  double-spawn over a healthy worker's partition roots.
* ``poll(handle)`` answers "is this process observably dead?" from the
  launcher's local evidence (a reaped child, a dead pid). A remote
  worker whose transport is gone but whose death cannot be observed
  locally answers False — the heartbeat machine owns that verdict.
* ``kill(handle)`` / ``shutdown(handle)`` are the hard and graceful
  teardown levers.

Every launch runs under the ``fleet.launch`` fault point with a
``fleet.launch`` span and the handshake bounded by
``geomesa.fleet.spawn.timeout`` — the standing invariant: a new process
boundary is injectable, attributable, and deadline-bounded.
"""

from __future__ import annotations

import json
import os
import select
import signal
import socket
import subprocess
import sys
import time
from dataclasses import dataclass
from typing import Callable, Optional, Tuple

from geomesa_tpu.stream.netlog import recv_frame, request_envelope, send_frame
from geomesa_tpu.utils import deadline, faults, trace
from geomesa_tpu.utils.audit import robustness_metrics


class WorkerLaunchFailed(OSError):
    """Crisp launch failure: the worker process could not be started,
    exited before the handshake, or never announced a live endpoint
    inside ``geomesa.fleet.spawn.timeout``. Deliberately an OSError so
    the supervisor's restart ladder (``RetryPolicy`` over
    ``(OSError, TimeoutError)``) treats it exactly like any other
    transient infrastructure failure: bounded backoff, then OUT."""


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except OSError:
        return False
    return True


def _repo_pythonpath() -> str:
    import geomesa_tpu

    pkg_parent = os.path.dirname(
        os.path.dirname(os.path.abspath(geomesa_tpu.__file__))
    )
    existing = os.environ.get("PYTHONPATH", "")
    return pkg_parent + (os.pathsep + existing if existing else "")


def _worker_env(i: int) -> dict:
    """The environment every launched worker runs under (shared by the
    launchers so a loopback ssh template behaves like a local spawn)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _repo_pythonpath()
    # workers are host-scan processes and a chip belongs to one process:
    # they run on the CPU whatever platform the coordinator holds (a
    # launcher command template may still set its own JAX_PLATFORMS)
    env["JAX_PLATFORMS"] = "cpu"
    env["GEOMESA_FLEET_WORKER_ID"] = str(i)
    return env


def probe_endpoint(addr: Tuple[str, int]) -> Optional[int]:
    """Raw ping against a candidate endpoint: the serving worker's pid
    on success, None for anything dead/foreign (bounded at 1s —
    adoption probes must not serialize a takeover on a wedged corpse)."""
    try:
        s = socket.create_connection(addr, timeout=1.0)
    except OSError:
        return None
    try:
        s.settimeout(1.0)
        send_frame(s, json.dumps(request_envelope("ping", frames=0)).encode())
        resp = json.loads(recv_frame(s).decode())
        for _ in range(int(resp.get("frames", 0))):
            recv_frame(s)
        if resp.get("ok") != 1:
            return None
        return int(resp.get("pid") or 0) or None
    except (OSError, ValueError):
        return None
    finally:
        s.close()


@dataclass
class WorkerHandle:
    """One launched-or-adopted worker process as a launcher sees it.
    ``proc`` is the local child Popen when the launcher owns one (the
    local spawn, or the ssh CLIENT process); ``pid`` is the worker's
    pid as reported over the handshake — for a remote worker that pid
    lives on another host (``remote=True``) and must never be signalled
    locally."""

    worker_id: int
    addr: Tuple[str, int]
    pid: Optional[int] = None
    proc: Optional[subprocess.Popen] = None
    adopted: bool = False
    remote: bool = False
    launcher: str = "local"
    handshake_ms: float = 0.0


class WorkerLauncher:
    """The SPI. Subclasses implement ``_start``; ``launch`` wraps it in
    the fault point + span + deadline pairing and publishes the
    endpoint record adoption reads back."""

    kind = "abstract"

    def __init__(self, base_dir: str, worker_root: Callable[[int], str],
                 auths=None):
        self.base_dir = base_dir
        self.worker_root = worker_root
        self.auths = auths

    # -- the handshake contract ----------------------------------------------

    def endpoint_path(self, i: int) -> str:
        """Coordinator-side endpoint record: the generalized handshake
        artifact ``adopt`` trusts (after a probe). The portfile under
        the same directory is the LOCAL launcher's private mechanism."""
        return os.path.join(self.base_dir, f"w{i}.endpoint")

    def _publish_endpoint(self, i: int, addr: Tuple[str, int]) -> None:
        tmp = self.endpoint_path(i) + ".tmp"
        with open(tmp, "w") as fh:
            fh.write(f"{addr[0]}:{addr[1]}\n")
        os.replace(tmp, self.endpoint_path(i))

    def _read_endpoint(self, i: int) -> Optional[Tuple[str, int]]:
        try:
            text = open(self.endpoint_path(i)).read().strip()
        except OSError:
            return None
        if not text:
            return None
        host, _, port = text.partition(":")
        try:
            return (host, int(port))
        except ValueError:
            return None

    # -- SPI -----------------------------------------------------------------

    def launch(self, i: int, timeout_s: float,
               stop: Optional[Callable[[], bool]] = None) -> WorkerHandle:
        """Start worker ``i`` and complete the endpoint handshake within
        ``timeout_s``. Raises ``WorkerLaunchFailed`` on any failure to
        produce a live endpoint, ``RuntimeError("supervisor stopping")``
        when ``stop()`` turned true mid-handshake."""
        t0 = time.monotonic()
        with trace.span("fleet.launch", worker=i, launcher=self.kind):
            # a launch inside a bounded repair (or a bounded takeover)
            # must not outlive the caller's budget: cooperative check
            # first, then the injectable boundary itself
            deadline.check("fleet.launch")
            faults.fault_point("fleet.launch")
            try:
                handle = self._start(i, timeout_s, stop or (lambda: False))
            except (WorkerLaunchFailed, RuntimeError):
                robustness_metrics().inc("fleet.launch.failed")
                raise
            except (OSError, ValueError, subprocess.SubprocessError) as e:
                robustness_metrics().inc("fleet.launch.failed")
                raise WorkerLaunchFailed(
                    f"fleet worker {i} launch via {self.kind!r} failed: {e}"
                ) from e
            handle.launcher = self.kind
            handle.handshake_ms = (time.monotonic() - t0) * 1000.0
            self._publish_endpoint(i, handle.addr)
            robustness_metrics().inc("fleet.worker.launched")
            trace.event(
                "fleet.worker.launched", worker=i, launcher=self.kind,
                handshake_ms=round(handle.handshake_ms, 1),
            )
            return handle

    def _start(self, i: int, timeout_s: float,
               stop: Callable[[], bool]) -> WorkerHandle:
        raise NotImplementedError

    def adopt(self, i: int) -> Optional[WorkerHandle]:
        """Attach to an already-running worker — one a dead coordinator
        left behind — via the published endpoint record + a raw probe.
        None when there is nothing live to adopt."""
        addr = self._read_endpoint(i)
        if addr is None:
            return None
        pid = probe_endpoint(addr)
        if pid is None:
            return None
        return WorkerHandle(
            worker_id=i, addr=addr, pid=pid, proc=None, adopted=True,
            remote=self._pid_is_remote(), launcher=self.kind,
        )

    def _pid_is_remote(self) -> bool:
        return False

    def poll(self, handle: WorkerHandle) -> bool:
        """True when the process is OBSERVABLY dead from here (reaped
        child / dead local pid). A remote worker with no local evidence
        answers False — missed heartbeats carry that verdict."""
        if handle.proc is not None:
            return handle.proc.poll() is not None
        if handle.pid is not None and not handle.remote:
            return not _pid_alive(handle.pid)
        return False

    def kill(self, handle: WorkerHandle, wait_s: float = 5.0) -> None:
        """Hard-kill (SIGKILL) — the chaos harness's and the respawn
        ladder's lever. Waits up to ``wait_s`` for the death to be
        locally observable so a respawn never races its predecessor."""
        if handle.proc is not None and handle.proc.poll() is None:
            handle.proc.kill()
            try:
                handle.proc.wait(timeout=wait_s)
            except subprocess.TimeoutExpired:
                pass
            return
        if handle.pid is None or handle.remote:
            return
        if _pid_alive(handle.pid):
            try:
                os.kill(handle.pid, signal.SIGKILL)
            except OSError:
                return
            t_end = time.monotonic() + wait_s
            while time.monotonic() < t_end and _pid_alive(handle.pid):
                time.sleep(0.02)

    def shutdown(self, handle: WorkerHandle, timeout_s: float = 2.0) -> None:
        """Graceful teardown: SIGTERM, bounded wait, then SIGKILL."""
        if handle.proc is not None:
            if handle.proc.poll() is not None:
                return
            handle.proc.terminate()
            try:
                handle.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                handle.proc.kill()
                try:
                    handle.proc.wait(timeout=timeout_s)
                except subprocess.TimeoutExpired:
                    pass
            return
        if handle.pid is None or handle.remote:
            return
        try:
            os.kill(handle.pid, signal.SIGTERM)
        except OSError:
            return
        t_end = time.monotonic() + timeout_s
        while time.monotonic() < t_end and _pid_alive(handle.pid):
            time.sleep(0.05)
        if _pid_alive(handle.pid):
            try:
                os.kill(handle.pid, signal.SIGKILL)
            except OSError:
                pass


class LocalSpawnLauncher(WorkerLauncher):
    """Today's behavior, now behind the SPI: ``subprocess.Popen`` of
    ``python -m geomesa_tpu.parallel.fleet --worker`` with the bound
    port published through an atomically-replaced portfile the launcher
    polls. The portfile is PRIVATE to this launcher; adoption still
    falls back to it so roots written before the endpoint record
    existed stay adoptable."""

    kind = "local"

    def portfile(self, i: int) -> str:
        return os.path.join(self.base_dir, f"w{i}.port")

    def _worker_cmd(self, i: int) -> list:
        cmd = [
            sys.executable,
            "-m",
            "geomesa_tpu.parallel.fleet",
            "--worker",
            "--id",
            str(i),
            "--root",
            self.worker_root(i),
            "--portfile",
            self.portfile(i),
        ]
        # list-shaped auths travel to the worker stores (visibility rows
        # must filter identically on both sides of the wire); provider
        # OBJECTS cannot cross a process boundary — workers then run
        # auth-less and visibility-bearing scans under-serve (documented)
        auths = self.auths
        if isinstance(auths, str):
            auths = [auths]
        if isinstance(auths, (list, tuple)) and all(
            isinstance(a, str) for a in auths
        ) and auths:
            cmd += ["--auths", ",".join(auths)]
        return cmd

    def _start(self, i: int, timeout_s: float,
               stop: Callable[[], bool]) -> WorkerHandle:
        portfile = self.portfile(i)
        try:
            os.remove(portfile)
        except FileNotFoundError:
            pass
        log = open(os.path.join(self.base_dir, f"w{i}.log"), "ab")
        try:
            proc = subprocess.Popen(
                self._worker_cmd(i), env=_worker_env(i), stdout=log,
                stderr=log,
            )
        finally:
            log.close()
        t_end = time.monotonic() + timeout_s
        addr: Optional[Tuple[str, int]] = None
        while time.monotonic() < t_end:
            if stop():
                # the supervisor's stop() is waiting on this repair:
                # abort promptly instead of making close()/atexit wait
                # out the handshake timeout
                proc.kill()
                raise RuntimeError("supervisor stopping")
            if proc.poll() is not None:
                raise WorkerLaunchFailed(
                    f"fleet worker {i} exited rc={proc.returncode} "
                    "during spawn"
                )
            try:
                text = open(portfile).read().strip()
            except FileNotFoundError:
                time.sleep(0.02)
                continue
            if text:
                host, _, port = text.partition(":")
                addr = (host, int(port))
                break
            time.sleep(0.02)
        if addr is None:
            proc.kill()
            raise WorkerLaunchFailed(
                f"fleet worker {i} never published its port"
            )
        return WorkerHandle(worker_id=i, addr=addr, pid=proc.pid, proc=proc)

    def adopt(self, i: int) -> Optional[WorkerHandle]:
        handle = super().adopt(i)
        if handle is not None:
            return handle
        # pre-endpoint-record roots: the worker-published portfile is
        # still a valid (local-only) handshake artifact
        try:
            text = open(self.portfile(i)).read().strip()
        except OSError:
            return None
        if not text:
            return None
        host, _, port = text.partition(":")
        try:
            addr = (host, int(port))
        except ValueError:
            return None
        pid = probe_endpoint(addr)
        if pid is None:
            return None
        return WorkerHandle(
            worker_id=i, addr=addr, pid=pid, proc=None, adopted=True,
            launcher=self.kind,
        )


class SshLauncher(WorkerLauncher):
    """A command-template launcher: ``geomesa.fleet.ssh.command`` is a
    shell template with ``{python}``/``{id}``/``{root}``/``{host}``
    placeholders, rendered per worker and run as the launch command
    (typically ``ssh <host> ...``; the tests drive it with a local
    loopback template — no ssh binary — which exercises the identical
    template + stdout-handshake path). The launched worker must run
    with ``--announce stdout`` so its ``ENDPOINT host:port`` line
    travels back over the command's stdout: no shared filesystem in the
    contract.

    Lifecycle caveats, by design: ``poll``/``kill``/``shutdown`` act on
    the LOCAL command process (for real ssh, killing the client tears
    the session; ``ssh -tt`` propagates the hangup to the remote
    worker), and an adopted remote worker's pid is never signalled
    locally — a takeover that must retire one goes through the worker's
    own drain RPC or the remote host's supervisor. The rendered command
    runs ``shell=True`` in its OWN session, and every local signal goes
    to the process GROUP: signalling only the shell would reap it while
    orphaning whatever it spawned (the loopback template's worker, a
    wrapper script's ssh client) — the leak that poisons every test and
    bench that runs after a fleet teardown."""

    kind = "ssh"

    def __init__(self, base_dir: str, worker_root: Callable[[int], str],
                 auths=None, command_template: Optional[str] = None):
        super().__init__(base_dir, worker_root, auths=auths)
        if command_template is None:
            from geomesa_tpu.utils.config import FLEET_SSH_COMMAND

            command_template = FLEET_SSH_COMMAND.get()
        if not command_template:
            raise ValueError(
                "geomesa.fleet.launcher=ssh needs geomesa.fleet.ssh.command "
                "(a shell template with {python} {id} {root} {host} "
                "placeholders)"
            )
        self.command_template = str(command_template)

    def _pid_is_remote(self) -> bool:
        return True

    @staticmethod
    def _signal_command(proc: subprocess.Popen, sig: int) -> None:
        try:
            os.killpg(proc.pid, sig)
        except OSError:
            try:
                proc.send_signal(sig)
            except OSError:
                pass

    def kill(self, handle: WorkerHandle, wait_s: float = 5.0) -> None:
        proc = handle.proc
        if proc is None or proc.poll() is not None:
            return  # adopted remote pid: never signalled locally
        self._signal_command(proc, signal.SIGKILL)
        try:
            proc.wait(timeout=wait_s)
        except subprocess.TimeoutExpired:
            pass

    def shutdown(self, handle: WorkerHandle, timeout_s: float = 2.0) -> None:
        proc = handle.proc
        if proc is None or proc.poll() is not None:
            return
        self._signal_command(proc, signal.SIGTERM)
        try:
            proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            self._signal_command(proc, signal.SIGKILL)
            try:
                proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                pass

    def _render(self, i: int) -> str:
        return self.command_template.format(
            python=sys.executable,
            id=i,
            root=self.worker_root(i),
            host="127.0.0.1",
        )

    def _start(self, i: int, timeout_s: float,
               stop: Callable[[], bool]) -> WorkerHandle:
        cmd = self._render(i)
        log = open(os.path.join(self.base_dir, f"w{i}.log"), "ab")
        try:
            proc = subprocess.Popen(
                cmd, shell=True, env=_worker_env(i),
                stdout=subprocess.PIPE, stderr=log,
                start_new_session=True,
            )
        finally:
            log.close()
        assert proc.stdout is not None
        fd = proc.stdout.fileno()
        buf = b""
        t_end = time.monotonic() + timeout_s
        addr: Optional[Tuple[str, int]] = None
        pid: Optional[int] = None
        while time.monotonic() < t_end and addr is None:
            if stop():
                self._signal_command(proc, signal.SIGKILL)
                raise RuntimeError("supervisor stopping")
            ready, _, _ = select.select([fd], [], [], 0.05)
            if not ready:
                if proc.poll() is not None:
                    raise WorkerLaunchFailed(
                        f"fleet worker {i} launch command exited "
                        f"rc={proc.returncode} before announcing an endpoint"
                    )
                continue
            data = os.read(fd, 4096)
            if not data:
                if proc.poll() is not None:
                    raise WorkerLaunchFailed(
                        f"fleet worker {i} launch command exited "
                        f"rc={proc.returncode} before announcing an endpoint"
                    )
                time.sleep(0.02)
                continue
            buf += data
            while b"\n" in buf and addr is None:
                line, _, buf = buf.partition(b"\n")
                parts = line.decode("utf-8", "replace").strip().split()
                # "ENDPOINT host:port [pid]" — the worker's stdout
                # announcement (--announce stdout, worker_main)
                if len(parts) >= 2 and parts[0] == "ENDPOINT":
                    host, _, port = parts[1].partition(":")
                    try:
                        addr = (host, int(port))
                    except ValueError:
                        self._signal_command(proc, signal.SIGKILL)
                        raise WorkerLaunchFailed(
                            f"fleet worker {i} announced a malformed "
                            f"endpoint {parts[1]!r}"
                        ) from None
                    if len(parts) >= 3 and parts[2].isdigit():
                        pid = int(parts[2])
        if addr is None:
            self._signal_command(proc, signal.SIGKILL)
            raise WorkerLaunchFailed(
                f"fleet worker {i} never announced its endpoint"
            )
        return WorkerHandle(
            worker_id=i, addr=addr, pid=pid, proc=proc, remote=True,
        )


def make_launcher(base_dir: str, worker_root: Callable[[int], str],
                  auths=None, kind: Optional[str] = None) -> WorkerLauncher:
    """The ``geomesa.fleet.launcher`` knob -> a launcher instance."""
    if kind is None:
        from geomesa_tpu.utils.config import FLEET_LAUNCHER

        kind = (FLEET_LAUNCHER.get() or "local").strip().lower()
    if kind == "local":
        return LocalSpawnLauncher(base_dir, worker_root, auths=auths)
    if kind == "ssh":
        return SshLauncher(base_dir, worker_root, auths=auths)
    raise ValueError(
        f"unknown geomesa.fleet.launcher {kind!r} (known: local, ssh)"
    )
