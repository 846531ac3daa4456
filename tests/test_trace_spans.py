"""The program's own spans, program names and counters: spans land on the
profiler's host timeline, the lattice engine's jitted programs carry
stable names, and JAX's traces are counted per program."""

import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.annealing import ArraySchedule
from repro.core.coloring import lattice3d_coloring
from repro.core.graph import ea3d
from repro.core.lattice import build_ea3d_lattice
from repro.engines import make_engine
from repro.obs import Tracer, install, programs
from repro.serve import SampleServer

INIT_CHILDREN = ("lattice.init_state.draw", "lattice.init_state.put",
                 "lattice.halo_refresh")
CURSOR = ("cursor.chunk", "cursor.readout", "cursor.read_flips",
          "cursor.record")
LATTICE_PROGRAMS = ("lattice_chunk", "lattice_energy",
                    "lattice_halo_refresh", "lattice_exchange_only")


@pytest.fixture(scope="module")
def handle():
    return make_engine("lattice", lattice=build_ea3d_lattice(8, seed=1),
                       precision="int8", replicas=2)


def _anneal(h, seeds=(1, 2), advances=None):
    """init_state_packed -> start_recorded -> advance -> record; 16
    sweeps in two chunks of 8, energies at 8 and 16."""
    st = h.init_state_packed(list(seeds))
    cur = h.start_recorded(st, ArraySchedule(np.full(16, 0.5, np.float32)),
                           [8, 16], sync_every=4)
    for _ in range(advances or 2):
        cur.advance(1)
    return cur, cur.record()


def _host_events(trace_dir):
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def _counts(family):
    reg = programs.watch()
    return {dict(k)["program"]: c.value
            for k, c in reg.counter(family).series()}


def test_spans_on_the_profiler_host_timeline(handle, tmp_path):
    srv = SampleServer(max_replicas_per_call=4)
    srv.register_problem("p", graph=ea3d(4, seed=3),
                         coloring=lattice3d_coloring(4), rng="lfsr")
    _anneal(handle)                                 # compile outside
    with jax.profiler.trace(str(tmp_path)):
        cur, rec = _anneal(handle)
        jax.block_until_ready(rec.energies)
        job = srv.submit("p", engine="gibbs", sweeps=8, replicas=2, seed=0)
        srv.drain()
    assert cur.done and srv.result(job)["status"] == "done"
    events = _host_events(tmp_path)
    names = {n for n, _, _ in events}
    for want in ("lattice.init_state",) + INIT_CHILDREN + CURSOR + (
            "pump.chunk",):
        assert want in names, want
    outer = [(a, b) for n, a, b in events if n == "lattice.init_state"]
    for n, a, b in events:
        if n in INIT_CHILDREN:
            assert any(oa <= a and b <= ob for oa, ob in outer), n


def test_programs_carry_stable_names(handle):
    eng = handle.eng
    st = handle.init_state_packed([1, 2])
    eng.energy(st)
    lowered = {
        "jit_lattice_chunk": eng.lower_chunk(),
        "jit_lattice_energy": eng._energy_fn.lower(
            st.m, eng.p.active, eng.p.h, eng.p.w6),
        "jit_lattice_halo_refresh": eng._halo_refresh_fn().lower(st.m),
    }
    for module, low in lowered.items():
        assert f"module @{module} " in low.as_text(), module


def test_traces_counted_per_program(handle):
    handle.init_state_packed([1, 2])            # warm every other program
    before = _counts(programs.TRACES)
    handle.init_state_packed([3, 4])
    handle.init_state_packed([5, 6])
    after = _counts(programs.TRACES)
    moved = {p: after.get(p, 0) - before.get(p, 0) for p in LATTICE_PROGRAMS}
    # the halo refresh is jitted once per engine and sharding, not per anneal
    assert moved == {"lattice_chunk": 0, "lattice_energy": 0,
                     "lattice_halo_refresh": 0, "lattice_exchange_only": 0}
    compiles = _counts(programs.COMPILES)
    assert compiles.get("lattice_halo_refresh", 0) >= 1
    fn = handle.eng.boundary_exchange_fn()
    fn(handle.init_state_packed([7, 8]))
    assert _counts(programs.TRACES)["lattice_exchange_only"] == \
        before.get("lattice_exchange_only", 0) + 1


def test_halo_refresh_traced_again_after_shard_state(handle):
    """A re-shard drops the cached refresh: the next fresh state traces
    it exactly once, and the one after that not at all."""
    st = handle.init_state_packed([1, 2])
    handle.eng.shard_state(st)
    moved = []
    for seeds in ([3, 4], [5, 6]):
        before = _counts(programs.TRACES).get("lattice_halo_refresh", 0)
        handle.init_state_packed(seeds)
        moved.append(_counts(programs.TRACES)["lattice_halo_refresh"] - before)
    assert moved == [1, 0]


def test_hot_path_spans_never_sync(handle):
    """With a recorder installed the ring holds the program's spans, and
    none of them asks the tracer to block on the device."""
    blocked = []
    tr = Tracer(block=blocked.append)
    prev = install(tr)
    try:
        cur, _ = _anneal(handle, seeds=(9, 10))
    finally:
        install(prev)
    assert cur.done and blocked == []
    names = {s["name"] for s in tr.spans()}
    assert {"lattice.init_state", *INIT_CHILDREN, *CURSOR} <= names
    by_id = {s["span_id"]: s for s in tr.spans()}
    for s in tr.spans():
        if s["name"] in ("lattice.init_state.draw", "lattice.init_state.put"):
            assert by_id[s["parent_id"]]["name"] == "lattice.init_state"
