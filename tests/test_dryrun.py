"""Regression tests for the driver entry points (__graft_entry__.py).

These tests exercise the exact functions the driver calls, on the conftest
8-device CPU mesh, so any backend-selection regression fails the suite
instead of the driver run.
"""

import jax

import __graft_entry__ as graft


def test_entry_compiles_and_runs():
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    mask, count, checksum = out
    assert int(count) == int(mask.sum())
    assert int(count) > 0


def test_dryrun_multichip_8():
    # self-validating: raises AssertionError on mask/count mismatch
    graft.dryrun_multichip(8)


def test_dryrun_multichip_odd_counts():
    # 1 = degenerate single-device mesh; 3 = genuinely odd count (ragged
    # (3,1) mesh shape — non-pow2 shard math). light: these exercise
    # MESH-SHAPE stitching; the full kernel families (attr member/range,
    # poly attr, count) compile per mesh and are covered at 8 devices
    for n in (1, 3):
        graft.dryrun_multichip(n, light=True)
