"""What decides ``correct``: the plain reference agrees with the program
exactly, and each fault a cell can have, and the lower-precision control,
come out as not correct.  CPU, small lattices, the jnp kernel oracles."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ea3d
import harness

SEED = 2 ** 33 + 17          # wider than 32 signed bits, as --seed may be
PEAKS = json.load(open(os.path.join(harness.BENCH, "peaks.json")))[
    "TPU v5 lite"]


def small_run(workload, seconds=2.0, L=8):
    """One run of a cell through the harness (its look for a chip
    skipped) at a size a test can hold; the window is long enough for
    some anneals to finish on a loaded CPU."""
    spec, cell, cfg, traffic = harness.load_cell(workload)
    cfg = dict(cfg, L=L)
    traffic = dict(traffic, schedule=dict(traffic["schedule"], sweeps=16))
    if "record_points" in traffic:
        traffic["record_points"] = [8, 16]
    devs = jax.devices()[:cell["chips"]]
    return harness.run_cell(spec, cell, cfg, traffic, SEED, seconds, False,
                            PEAKS, devs)


def test_instance_is_the_programs_own():
    """The benchmark's generator gives the couplings and colors that the
    program's own builder makes from (L, seed)."""
    from repro.core.lattice import build_ea3d_lattice
    for L in (4, 6):
        p = build_ea3d_lattice(L, seed=SEED)
        w6 = ea3d.six_planes(*ea3d.couplings(L, SEED))
        for a, b in zip(p.w6, w6):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(p.masks),
                                      ea3d.color_masks(L))


@pytest.mark.parametrize("workload", ["ea3d_1m.anneal", "ea3d_1m.sample"])
def test_sound_run_is_correct(workload):
    out = small_run(workload)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def test_control_fails():
    """The reference at the precision below the machine's (16-bit draws
    and thresholds) in the program's place is not correct."""
    L, S = 16, 4
    j = tuple(jnp.asarray(a) for a in ea3d.couplings(L, SEED))
    seeds = ea3d.replica_seeds(SEED, 0, 2)
    betas = ea3d.staircase("ea", 64)
    ref = ea3d.run(L, seeds, j, betas, S)
    ctl = ea3d.run(L, seeds, j, betas, S, draw_bits=16)
    assert int((ref[0] != ctl[0]).sum()) > 0
    assert int(np.abs(ref[2].sum(0) - ctl[2].sum(0)).max()) > 0


def _patch_runner(monkeypatch, fault):
    """Break the lattice engine's chunk runner underneath the harness."""
    from repro.core.lattice_dsim import LatticeDSIM
    orig = LatticeDSIM._run_chunk

    def broken(self, iters, S, per_rep=False):
        run = orig(self, iters, S, per_rep)

        def f(state, sched, *args):
            out = run(state, sched, *args)
            if fault == "unchanged":
                return dataclasses.replace(state, sweep=out.sweep)
            if fault == "half_batch":
                h = state.m.shape[0] // 2
                return dataclasses.replace(
                    out, m=out.m.at[h:].set(state.m[h:]),
                    s=out.s.at[h:].set(state.s[h:]),
                    flips=out.flips.at[h:].set(state.flips[h:]))
            if fault == "altered":
                return dataclasses.replace(
                    out, m=out.m.at[0, 0, 0, 0].multiply(-1))
            raise ValueError(fault)
        return f
    monkeypatch.setattr(LatticeDSIM, "_run_chunk", broken)


@pytest.mark.parametrize("workload,fault", [
    ("ea3d_1m.anneal", "unchanged"), ("ea3d_1m.anneal", "half_batch"),
    ("ea3d_1m.anneal", "altered"), ("ea3d_1m.sample", "altered"),
    ("ea3d_1m.sample", "unchanged"), ("ea3d_1m.sample", "half_batch")])
def test_fault_is_not_correct(monkeypatch, workload, fault):
    _patch_runner(monkeypatch, fault)
    out = small_run(workload)
    # not correct because of the fault, not because nothing was answered
    assert out["attempted"] > 0 and not out["correct"], out["checks"]


@pytest.mark.parametrize("workload", ["ea3d_1m.anneal", "ea3d_1m.sample"])
def test_exchange_left_out_is_not_correct(monkeypatch, workload):
    """One chip: the z seam runs through the halo exchange."""
    from repro.core.lattice_dsim import LatticeDSIM

    def no_exchange(self, m, s, halos, sched_S, masks, h, w6, lut=None):
        m, s, fl = self._sweep_block(m, s, halos, sched_S, masks, h, w6,
                                     lut)
        return m, s, halos, fl
    monkeypatch.setattr(LatticeDSIM, "_iteration_block", no_exchange)
    out = small_run(workload)
    assert out["attempted"] > 0 and not out["correct"], out["checks"]
