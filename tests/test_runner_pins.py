"""Bit-for-bit pins of every chunk-runner configuration of the mesh engines.

Each case runs a 32-sweep EA3D anneal (L=8, ``impl="ref"``,
``sync_every=4``, record points [16, 32]) and fingerprints what it left:
the sha256 of the final global spins and of the LFSR states, the flip
odometers, the recorded energies' float32 bits, the sweep counter and,
for checked runs, the health report.  ``fixtures/runner_pins.json`` holds
the fingerprints of the engines as they were before their chunk runners
were merged into one per engine; a case that drifts from it means a
representation x exchange combination now computes something else.

Cases: ``LatticeDSIM`` in six representations (f32 and int8, each fused
and per-phase; bitplane with one and with two word planes) under six
variants (one device with a shared and a per-replica schedule; two
devices on x plain, checked ``stale_hold`` without and with drop/corrupt
codes, checked ``freeze_boundary`` with codes), and ``DistDSIMEngine`` on
two devices in f32, int8 and bitplane, each plain at ``sync_every=4``,
plain per phase, and checked ``stale_hold`` with codes.  All run in one
child process with two forced host devices, like tests/test_dist.py.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "runner_pins.json")

LATTICE_REPS = {
    # name: (precision, fused, replicas)
    "f32-fused": ("f32", True, 2),
    "f32-phase": ("f32", False, 2),
    "int8-fused": ("int8", True, 2),
    "int8-phase": ("int8", False, 2),
    "bitplane-r3": ("bitplane", True, 3),
    "bitplane-r40": ("bitplane", True, 40),
}
LATTICE_VARIANTS = {
    # name: (devices, per-replica betas, degrade policy, fault codes)
    "1dev": (1, False, None, False),
    "1dev-betas_R": (1, True, None, False),
    "2dev": (2, False, None, False),
    "2dev-hold": (2, False, "stale_hold:8", False),
    "2dev-hold-codes": (2, False, "stale_hold:8", True),
    "2dev-freeze-codes": (2, False, "freeze_boundary", True),
}
DIST_PRECISIONS = ("f32", "int8", "bitplane")
DIST_VARIANTS = {
    # name: (sync_every, degrade policy, fault codes)
    "sync4": (4, None, False),
    "phase": ("phase", None, False),
    "hold-codes": (4, "stale_hold:8", True),
}
CASES = ([f"lattice/{r}/{v}" for r in LATTICE_REPS
          for v in LATTICE_VARIANTS]
         + [f"dist/{p}/{v}" for p in DIST_PRECISIONS
            for v in DIST_VARIANTS])

CHILD = """
    import hashlib, json
    import numpy as np
    import jax
    from repro.compat import auto_axes, make_mesh
    from repro.core.annealing import ea_schedule, replica_beta_arrays
    from repro.core.coloring import lattice3d_coloring
    from repro.core.dsim import build_partitioned
    from repro.core.dsim_dist import DistDSIMEngine
    from repro.core.graph import ea3d
    from repro.core.lattice import build_ea3d_lattice
    from repro.core.lattice_dsim import LatticeDSIM
    from repro.core.partition import slab_partition

    L, SWEEPS, SYNC, POINTS, SEED = 8, 32, 4, [16, 32], 3
    # one code per exchange: corrupt the 3rd, drop the 5th and 6th
    CODES = np.array([0, 0, 2, 0, 1, 1, 0, 0], np.int32)
    sched = ea_schedule(SWEEPS)

    def mesh(n, name):
        return make_mesh((n,), (name,), axis_types=auto_axes(1),
                         devices=jax.devices()[:n])

    def sha(x):
        return hashlib.sha256(np.ascontiguousarray(np.asarray(x))
                              .tobytes()).hexdigest()

    def fingerprint(eng, st, rec, lfsr):
        e = np.asarray(rec.energies, np.float32).reshape(-1)
        out = dict(spins=sha(eng.global_spins(st)), lfsr=sha(lfsr),
                   flips=np.asarray(st.flips).tolist(),
                   energies=e.view(np.uint32).tolist(),
                   sweep=int(st.sweep))
        if eng.health is not None:
            out["health"] = eng.health.report()
        return out

    lat = build_ea3d_lattice(L, seed=5)
    results = {{}}
    for rname, (prec, fused, R) in {reps!r}.items():
        for vname, (ndev, per_rep, degrade, codes) in {lvars!r}.items():
            eng = LatticeDSIM(lat, mesh(ndev, "x"), ("x", None, None),
                              impl="ref", fused=fused, replicas=R,
                              precision=prec, degrade=degrade)
            if codes:
                eng.set_exchange_faults(CODES)
            st = eng.init_state(seed=SEED)
            betas_R = replica_beta_arrays(sched, R, spread=0.3) \\
                if per_rep else None
            st, rec = eng.run_recorded_full(st, sched, POINTS,
                                            sync_every=SYNC,
                                            betas_R=betas_R)
            results[f"lattice/{{rname}}/{{vname}}"] = fingerprint(
                eng, st, rec, st.s)

    g = ea3d(L, seed=5)
    prob = build_partitioned(g, lattice3d_coloring(L),
                             slab_partition(L, 2), 2)
    for prec in {dprecs!r}:
        for vname, (sync, degrade, codes) in {dvars!r}.items():
            eng = DistDSIMEngine(prob, mesh(2, "data"), rng="lfsr",
                                 precision=prec, replicas=3,
                                 degrade=degrade)
            if codes:
                eng.set_exchange_faults(CODES)
            st = eng.init_state(seed=SEED)
            st, rec = eng.run_recorded_full(st, sched, POINTS,
                                            sync_every=sync)
            results[f"dist/{{prec}}/{{vname}}"] = fingerprint(
                eng, st, rec, st.rng)
    print("RESULT " + json.dumps(results, sort_keys=True))
"""


def run_cases() -> dict:
    """Fingerprint every case in one child with two host devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    code = textwrap.dedent(CHILD.format(
        reps=LATTICE_REPS, lvars=LATTICE_VARIANTS, dprecs=DIST_PRECISIONS,
        dvars=DIST_VARIANTS))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


@pytest.fixture(scope="module")
def results():
    return run_cases()


@pytest.fixture(scope="module")
def pinned():
    with open(FIXTURE) as f:
        return json.load(f)


@pytest.mark.parametrize("case", CASES)
def test_runner_matches_pin(results, pinned, case):
    assert results[case] == pinned[case]
    if case.endswith("/2dev-hold"):
        # with no fault the checked exchange ingests exactly what the
        # plain one does
        plain = results[case[:-len("-hold")]]
        for k in ("spins", "lfsr", "flips", "energies", "sweep"):
            assert results[case][k] == plain[k], k
        assert results[case]["health"]["detections"] == 0
