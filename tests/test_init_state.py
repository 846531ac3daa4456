"""``LatticeDSIM.init_state`` builds the same state as the plain serial
construction: per-seed spins and salted LFSR states drawn one chain after
another, stacked, put on the mesh, then one halo refresh.  The chains are
drawn concurrently into one host buffer and put straight into their
shards; neither may change a bit, a pytree node or a sharding."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import lattice_dsim
from repro.core.lattice_dsim import draw_chains, draw_pool
from repro.core.packing import pack_lanes
from repro.engines import make_engine

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "..", "src")


# the serial construction, written out (the mesh child imports it)
def serial_host(seeds, dims):
    ms, ss = [], []
    for sd in seeds:
        rng = np.random.default_rng(sd)
        ms.append(rng.choice(np.array([-1, 1], np.int8), size=dims))
        salted = np.random.default_rng(
            np.uint64(sd) ^ np.uint64(0x9E3779B97F4A7C15))
        ss.append(salted.integers(1, 2 ** 32, size=int(np.prod(dims)),
                                  dtype=np.uint32).reshape(dims))
    return np.stack(ms), np.stack(ss)


def serial_state(eng, seeds):
    m, s = serial_host(seeds, tuple(eng.p.dims))
    R = len(seeds)
    if eng.precision == "bitplane":
        m = pack_lanes(jnp.asarray(m))
    put = lambda x, spec: jax.device_put(x, eng._shard(spec))
    m = put(m, eng.spec_m)
    halos = jax.jit(eng._halo_program())(m)
    return dict(m=m, s=put(s, eng.spec_m), halos=halos,
                sweep=put(np.zeros((), np.int32), P()),
                flips=put(np.zeros((R,), np.int32), P()))


def diffs(eng, st, seeds):
    want = serial_state(eng, seeds)
    bad = []
    for k, w in want.items():
        got = getattr(st, k)
        gl, wl = jax.tree.leaves(got), jax.tree.leaves(w)
        if jax.tree.structure(got) != jax.tree.structure(w):
            bad.append(k + ":structure")
            continue
        for i, (g, x) in enumerate(zip(gl, wl)):
            if g.dtype != x.dtype or g.shape != x.shape:
                bad.append(f"{k}[{i}]:dtype/shape")
            elif not np.array_equal(np.asarray(g), np.asarray(x)):
                bad.append(f"{k}[{i}]:values")
            elif not g.sharding.is_equivalent_to(x.sharding, g.ndim):
                bad.append(f"{k}[{i}]:sharding")
    return bad


def _engine(precision, R, L=4):
    return make_engine("lattice", L=L, seed=3, impl="ref",
                       precision=precision, replicas=R)


@pytest.mark.parametrize("R", [1, 8])
@pytest.mark.parametrize("precision", ["int8", "f32", "bitplane"])
def test_init_state_matches_serial_construction(precision, R):
    h = _engine(precision, R)
    seeds = [101 + 7 * r for r in range(R)]
    st = h.init_state_packed(seeds)
    assert type(st).__name__ == ("BitplaneLatticeState"
                                 if precision == "bitplane"
                                 else "LatticeState")
    assert diffs(h.eng, st, seeds) == []
    # the seed-only path derives its R seeds and builds the same way
    st0 = h.eng.init_state(seed=5)
    derived = [5] if R == 1 else lattice_dsim.spawn_seeds(5, R)
    assert diffs(h.eng, st0, derived) == []


@pytest.mark.parametrize("precision", ["int8", "bitplane"])
def test_chain_depends_on_its_seed_alone(precision):
    """Seeds in reverse order give the same chains in reverse order,
    however the pool's tasks finish."""
    R = 8
    h = _engine(precision, R)
    seeds = [900 + 13 * r for r in range(R)]
    fwd = h.init_state_packed(seeds)
    rev = h.init_state_packed(seeds[::-1])
    np.testing.assert_array_equal(np.asarray(rev.s),
                                  np.asarray(fwd.s)[::-1])
    np.testing.assert_array_equal(np.asarray(h.global_spins(rev)),
                                  np.asarray(h.global_spins(fwd))[::-1])
    assert diffs(h.eng, rev, seeds[::-1]) == []


def test_draw_pool_bound(monkeypatch):
    """``min(R, os.cpu_count(), 8)`` workers, one pool per count and
    process, none for a single chain; read from the pool, no thread
    started."""
    assert draw_pool(1) is None
    monkeypatch.setattr(lattice_dsim.os, "cpu_count", lambda: 3)
    assert draw_pool(1) is None
    assert draw_pool(2)._max_workers == 2
    assert draw_pool(8)._max_workers == 3
    monkeypatch.setattr(lattice_dsim.os, "cpu_count", lambda: 64)
    assert draw_pool(16)._max_workers == 8
    assert draw_pool(8) is draw_pool(16)
    monkeypatch.setattr(lattice_dsim.os, "cpu_count", lambda: None)
    assert draw_pool(8) is None


@pytest.mark.parametrize("cpus", [1, 2, 8])
def test_draw_chains_same_bits_on_any_pool(monkeypatch, cpus):
    monkeypatch.setattr(lattice_dsim.os, "cpu_count", lambda: cpus)
    seeds = [7, 3, 2 ** 31 - 1, 0, 12345]
    m, s = draw_chains(seeds, (4, 5, 6))
    wm, ws = serial_host(seeds, (4, 5, 6))
    assert m.dtype == np.int8 and s.dtype == np.uint32
    np.testing.assert_array_equal(m, wm)
    np.testing.assert_array_equal(s, ws)


def test_init_state_on_a_four_device_x_mesh():
    """Each chip's shard is its own slab of the serial state, each on its
    own device, for int8, f32 and bitplane at R = 1 and 8."""
    code = f"import sys\nsys.path.insert(0, {HERE!r})\n" + textwrap.dedent("""
        from test_init_state import diffs
        from repro.compat import auto_axes, make_mesh
        from repro.core.lattice import build_ea3d_lattice
        from repro.engines import make_engine
        mesh = make_mesh((4,), ("x",), axis_types=auto_axes(1))
        prob = build_ea3d_lattice(8, seed=1)
        for precision in ("int8", "f32", "bitplane"):
            for R in (1, 8):
                h = make_engine("lattice", lattice=prob, mesh=mesh,
                                dim_axes=("x", None, None), impl="ref",
                                precision=precision, replicas=R)
                seeds = [31 * r + 5 for r in range(R)][::-1]
                st = h.init_state_packed(seeds)
                bad = diffs(h.eng, st, seeds)
                devs = {sh.device for sh in st.m.addressable_shards}
                slabs = sorted(sh.index[1].start
                               for sh in st.s.addressable_shards)
                print("CASE", precision, R, bad, len(devs), slabs)
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=420)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("CASE")]
    assert len(lines) == 6, r.stdout
    for ln in lines:
        assert ln.endswith("[] 4 [0, 2, 4, 6]"), ln
