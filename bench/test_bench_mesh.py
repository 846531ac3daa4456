"""The partitioned cell (``ea3d_1m_x4.anneal``) on four virtual CPU
devices: a sound run is correct, and the check tells the partitioned
machine from the unpartitioned one, a machine whose exchange is left out
and the lower-precision control.  The readers of its exchange and of its
fused kernel read what they should and nothing where it is absent."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

import harness

CASES = ("sound", "monolithic_reference", "exchange_left_out",
         "control_16bit")

CHILD = """
    import functools, json, os, sys
    sys.path[:0] = [{bench!r}, {src!r}]
    import jax
    import ea3d
    import harness
    from repro.core.lattice_dsim import LatticeDSIM

    SEED = 2 ** 33 + 17
    spec, cell, cfg, traffic = harness.load_cell("ea3d_1m_x4.anneal")
    traffic = dict(traffic, schedule=dict(traffic["schedule"], sweeps=16),
                   record_points=[8, 16])
    peaks = json.load(open(os.path.join(harness.BENCH, "peaks.json")))[
        "TPU v5 lite"]

    def no_exchange(self, m, s, halos, sched_S, masks, h, w6, lut=None):
        m, s, fl = self._sweep_block(m, s, halos, sched_S, masks, h, w6,
                                     lut)
        return m, s, halos, fl

    iteration, run = LatticeDSIM._iteration_block, ea3d.run
    out = {{}}
    for case in {cases!r}:
        c = dict(cfg, L=16)
        if case == "monolithic_reference":
            c["mesh"] = dict(c["mesh"], bricks=[1, 1, 1])
        if case == "exchange_left_out":
            LatticeDSIM._iteration_block = no_exchange
        if case == "control_16bit":
            # the machine against its reference at 16-bit draws and
            # thresholds, the precision step below its 24-bit compare
            ea3d.run = functools.partial(run, draw_bits=16)
        try:
            out[case] = harness.run_cell(spec, cell, c, traffic, SEED, 2.0,
                                         False, peaks, jax.devices()[:4])
        finally:
            LatticeDSIM._iteration_block, ea3d.run = iteration, run
    print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    """Every case of the cell at L=16, 16 sweeps, in one child process
    with four forced host devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = textwrap.dedent(CHILD.format(
        bench=harness.BENCH, src=os.path.join(harness.ROOT, "src"),
        cases=CASES))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):]), p.stdout


def test_cell_runs_on_four_bricks(runs):
    _, log = runs
    assert "devices 4 kernel_path fused" in log
    assert "brick (4, 16, 16)" in log and "spins held on 4 devices" in log


@pytest.mark.parametrize("case", CASES)
def test_mesh_cell_check(runs, case):
    out = runs[0][case]
    assert out["attempted"] > 0 and out["device"]["count"] == 4, out
    if case == "sound":
        assert out["correct"] and out["failed"] == 0, out["checks"]
        assert all(c["value"] == 0 for c in out["checks"].values())
    else:
        # not correct because of the fault, not because nothing finished
        assert not out["correct"] and out["failed"] == 1, out["checks"]
        assert out["checks"]["no_anneal_finished"]["value"] == 0


def _reader(name):
    return harness.load_module(os.path.join(harness.BENCH, "metrics",
                                            name + ".py"), "m")


ONE_BRICK = {"kernels": {"pbit_brick_update_int": (10, 1e9)},
             "busy_total_s": 2.0, "peaks": {"hbm_bytes_per_s": 819e9},
             "sweep_kernels": {"pbit_brick_update_int": 18_240_052}}


@pytest.mark.parametrize("name", ["core.exchange_wire_share",
                                  "kernel.pbit_sweep_roofline"])
def test_mesh_reader_reads_nothing_where_absent(name):
    assert _reader(name).read({}) is None


def test_exchange_reader_reads_nothing_on_one_brick():
    assert _reader("core.exchange_wire_share").read(ONE_BRICK) is None


def test_mesh_readers_on_a_synthetic_trace():
    """The wire share counts both halves of the collective-permute and no
    fusion; the sweep roofline reads the fused kernel by its trace name."""
    per_launch = 4_870_212
    ctx = {"kernels": {"collective-permute-start": (100, 2e6),
                       "collective-permute-done": (100, 6e6),
                       "pbit_brick_sweep_int": (2048, 2e8),
                       "fusion": (500, 1e7)},
           "busy_total_s": 0.4, "peaks": {"hbm_bytes_per_s": 819e9},
           "sweep_kernels": {"pbit_brick_sweep_int": per_launch}}
    assert _reader("core.exchange_wire_share").read(ctx) == pytest.approx(
        100 * 8e-3 / 0.4)
    assert _reader("kernel.pbit_sweep_roofline").read(ctx) == \
        pytest.approx(100 * 2048 * per_launch / 0.2 / 819e9)
