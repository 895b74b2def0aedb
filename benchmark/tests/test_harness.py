"""The harness's own checks, on the CPU at test sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

A clean run of every cell comes out correct; the control (the float32
reference in the program's place) and every fault planted in the timed
path come out not correct; a machine without a TPU gets no result. Beside
the cells of ``BENCHMARK.json`` the same checks run the ``features``
request kind (``traffic/gdelt.features.json``), kept for the host-path
cells of PERF.md's Open questions.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark.lib import spec
from benchmark.lib.cell import run_cell

ROOT = spec.ROOT
ROWS = 120_000
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
# a cell of the features kind, not in BENCHMARK.json (see PERF.md)
EXTRA = {"gdelt.features": ("gdelt-events", "gdelt.features")}


def _cell(name):
    if name not in EXTRA:
        return spec.load(name)
    config, traffic = EXTRA[name]
    conf = next(c for c in BENCH["configs"] if c["name"] == config)
    cfg = json.load(open(os.path.join(ROOT, conf["file"])))
    return spec.Cell(name, 1, cfg, spec.traffic(traffic), [], [])


@pytest.fixture(autouse=True)
def _cpu_device_paths(monkeypatch):
    # the CPU backend declines the density push-down in auto mode; steer it
    # to the branch the chip takes (the benchmark itself sets no knob)
    monkeypatch.setenv("GEOMESA_DENSITY_DEVICE", "1")


def _run(name, seed=1234567890123, hook=None, control=False, rows=ROWS):
    import jax

    return run_cell(_cell(name), seed, 1.0, False, jax.devices()[:1],
                    time.perf_counter(), rows=rows, store_hook=hook,
                    control=control, log=lambda m: None)


def test_benchmark_json_pieces_exist():
    for c in BENCH["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert set(c["reduced"]) <= set(cfg["reduced"])
    for w in BENCH["workloads"]:
        assert os.path.exists(os.path.join(ROOT, "benchmark", "traffic", w["traffic"] + ".json"))
    for m in BENCH["per_layer"]:
        assert callable(spec.reader(m["name"]))
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s"}


@pytest.mark.parametrize("name", CELLS + list(EXTRA))
def test_clean_run_is_correct(name):
    out = _run(name)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {m["name"] for m in _cell(name).end_to_end}


@pytest.mark.parametrize("name", CELLS + list(EXTRA))
def test_control_fails(name):
    """The control, the float32 reference in the program's place, goes
    through the window and the check and comes out not correct."""
    out = _run(name, control=True, rows=400_000)
    assert not out["correct"], out["checks"]


class _Broken:
    """A store whose query answers are altered where they are produced."""

    def __init__(self, store, fault):
        self._store = store
        self._fault = fault

    def __getattr__(self, k):
        return getattr(self._store, k)

    def query(self, name, q):
        res = self._store.query(name, q)
        return self._fault(res)


def _alter_one(res):
    if res.aggregate and "density" in res.aggregate:
        g = np.array(res.aggregate["density"], copy=True)
        g.flat[int(np.argmax(g))] += 1
        res.aggregate = dict(res.aggregate, density=g)
        return res
    cols = {k: np.asarray(res.columns[k]).copy() for k in list(res.columns.keys())}
    if len(cols["__fid__"]):
        cols["geom__x"][0] += 1e-9
    res.columns = cols
    return res


def _half_left_out(res):
    if res.aggregate and "density" in res.aggregate:
        g = np.asarray(res.aggregate["density"])
        res.aggregate = dict(res.aggregate, density=np.floor(g / 2))
        return res
    cols = {k: np.asarray(res.columns[k]) for k in list(res.columns.keys())}
    n = len(cols["__fid__"])
    res.columns = {k: v[: n // 2] for k, v in cols.items()}
    return res


@pytest.mark.parametrize("fault", [_alter_one, _half_left_out],
                         ids=["answer_altered", "half_left_out"])
@pytest.mark.parametrize("name", CELLS + list(EXTRA))
def test_fault_in_timed_path_fails(name, fault):
    out = _run(name, hook=lambda store: _Broken(store, fault))
    assert not out["correct"], out["checks"]


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
