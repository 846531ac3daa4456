"""``LatticeDSIM`` hands its mesh programs every operand already in the
sharding the program declares: the field operands and the energy
operands are put on the mesh once per engine, each threshold LUT once,
replicated, and each chunk's schedule slice once, replicated, all from
the host.  No call then reshards anything from one device to another,
and ``lattice_operand_puts_total{operand}`` counts every put."""

import os
import subprocess
import sys
import textwrap

import pytest

from repro.compat import abstract_mesh
from repro.core.annealing import ea_schedule
from repro.core.lattice import build_ea3d_lattice
from repro.core.lattice_dsim import LatticeDSIM
from repro.engines import make_engine
from repro.obs import operand_puts

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

MESH_VARIANTS = {
    # name: (precision, replicas, degrade policy, fault codes)
    "int8-fused": ("int8", 2, None, False),
    "bitplane": ("bitplane", 3, None, False),
    "int8-checked": ("int8", 2, "stale_hold:8", True),
}

CHILD = """
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.compat import auto_axes, make_mesh
    from repro.core.annealing import ea_schedule
    from repro.core.lattice import build_ea3d_lattice
    from repro.engines import make_engine

    prec, R, degrade, codes = {variant!r}
    mesh = make_mesh((4,), ("x",), axis_types=auto_axes(1))
    h = make_engine("lattice", lattice=build_ea3d_lattice(8, seed=1),
                    mesh=mesh, dim_axes=("x", None, None), impl="ref",
                    precision=prec, replicas=R, degrade=degrade)
    eng = h.eng
    if codes:
        eng.set_exchange_faults(np.zeros(16, np.int32))
    put, puts = eng._put, []

    def spy(x, spec, operand):
        out = put(x, spec, operand)
        puts.append((operand, out))
        return out

    eng._put = spy
    # the fresh states are made outside the guard: only the run is checked
    states = [h.init_state_packed([7 * k + r for r in range(R)])
              for k in range(2)]
    with jax.transfer_guard_device_to_device("disallow"):
        for st in states:
            cur = h.start_recorded(st, ea_schedule(32), [8, 16, 32],
                                   sync_every=4)
            chunks = 0
            while not cur.done:
                chunks += cur.advance(1)
            rec = cur.record()
            e, fl = np.asarray(rec.energies), cur.flips_per_replica()
    flat = eng.spec_flat
    declared = dict(field=eng._field_specs, energy=(flat, flat, (flat,) * 6),
                    lut=P(), sched=P())
    bad = []
    for operand, out in puts:
        want = jax.tree.leaves(declared[operand])
        got = jax.tree.leaves(out)
        if len(got) != len(want):
            bad.append(operand + ":leaves")
        for a, sp in zip(got, want):
            if a.sharding != NamedSharding(mesh, sp):
                bad.append(f"{{operand}}:{{a.sharding}}")
    kinds = sorted({{op for op, _ in puts}})
    print("PUTS", kinds, len(puts), "CHUNKS", chunks, "BAD", bad)
"""


@pytest.mark.parametrize("variant", list(MESH_VARIANTS))
def test_mesh_anneal_moves_nothing_between_devices(variant):
    """Two whole recorded anneals (chunks, readouts, ``record()``) on a
    four-device x mesh run with device-to-device transfers disallowed,
    and every operand put is in the sharding its program declares."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run(
        [sys.executable, "-c",
         textwrap.dedent(CHILD.format(variant=MESH_VARIANTS[variant]))],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("PUTS")][-1]
    # one field, one energy, one LUT, and a slice per chunk of 2 anneals
    assert line.startswith("PUTS ['energy', 'field', 'lut', 'sched'] "
                           f"{3 + 2 * 3} CHUNKS 3 "), line
    assert line.endswith("BAD []"), line


def _counts():
    fam = operand_puts.operand_puts().counter(operand_puts.OPERAND_PUTS)
    return {k: fam.labels(operand=k).value for k in operand_puts.OPERANDS}


def _anneals(h, n, first=0):
    """``n`` recorded anneals; returns the chunks they dispatched."""
    chunks = 0
    for k in range(first, first + n):
        cur = h.start_recorded(h.init_state_packed([k, k + 7]),
                               ea_schedule(24), [8, 16, 24], sync_every=4)
        while not cur.done:
            chunks += cur.advance(1)
        cur.record()
    return chunks


@pytest.mark.parametrize("precision", ["int8", "bitplane", "f32"])
def test_operand_puts_counted_once_per_engine(precision):
    """Three anneals on one engine put the field and the energy operands
    once, the one LUT of their schedule once, and one schedule slice per
    chunk; after ``shard_state`` the next anneal puts each once again."""
    h = make_engine("lattice", L=6, seed=3, impl="ref", precision=precision,
                    replicas=2)
    luts = 0 if precision == "f32" else 1
    before = _counts()
    chunks = _anneals(h, 3)
    after = _counts()
    assert chunks == 9
    assert {k: after[k] - before[k] for k in after} == dict(
        field=1, energy=1, lut=luts, sched=chunks)
    h.eng.shard_state(h.init_state_packed([1, 2]))
    chunks = _anneals(h, 1, first=3)
    again = _counts()
    assert {k: again[k] - after[k] for k in after} == dict(
        field=1, energy=1, lut=luts, sched=chunks)


@pytest.mark.parametrize("degrade", [False, True])
def test_engine_traced_over_abstract_mesh_puts_nothing(degrade):
    """An engine built and traced over an ``AbstractMesh`` has no devices
    to put on, and counts no put."""
    eng = LatticeDSIM(build_ea3d_lattice(8, seed=1),
                      abstract_mesh((4,), ("x",)), ("x", None, None),
                      impl="ref", precision="int8", replicas=2,
                      degrade="stale_hold:8" if degrade else None)
    before = _counts()
    eng.trace_chunk(iters=2, S=4, degrade=degrade, has_codes=degrade)
    assert _counts() == before
    assert eng._placed == {} and eng._lut_cache == {}
