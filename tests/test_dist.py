"""Multi-device distribution tests.

These run in SUBPROCESSES with forced host device counts so the main pytest
process keeps the default single device (per the harness contract)."""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 4, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_dist_dsim_bitwise_matches_stacked():
    out = run_py("""
        import numpy as np, jax
        from repro.core.graph import ea3d
        from repro.core.coloring import lattice3d_coloring
        from repro.core.partition import slab_partition
        from repro.core.dsim import build_partitioned, DSIMEngine
        from repro.core.dsim_dist import DistDSIMEngine
        from repro.core.annealing import ea_schedule
        g = ea3d(8, seed=7); col = lattice3d_coloring(8)
        prob = build_partitioned(g, col, slab_partition(8, 4), 4)
        from repro.compat import make_mesh, auto_axes
        mesh = make_mesh((4,), ("data",), axis_types=auto_axes(1))
        sch = ea_schedule(256)
        d = DistDSIMEngine(prob, mesh, rng="lfsr", bitpack=True)
        sd = d.init_state(seed=3)
        sd, (_, Ed) = d.run_recorded(sd, sch, [64, 256], sync_every=4)
        s = DSIMEngine(prob, rng="lfsr")
        ss = s.init_state(seed=3)
        ss, (_, Es) = s.run_recorded(ss, sch, [64, 256], sync_every=4)
        md = np.asarray(d.global_spins(sd)); ms = np.asarray(s.global_spins(ss))
        print("BITWISE", bool((md == ms).all()))
        print("E", float(Ed[-1]), float(Es[-1]))
    """)
    assert "BITWISE True" in out


def test_lattice_dsim_multiaxis_halo():
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.lattice import build_ea3d_lattice
        from repro.core.lattice_dsim import LatticeDSIM
        from repro.core.graph import ea3d
        from repro.core.energy import energy
        from repro.core.annealing import ea_schedule
        from repro.compat import make_mesh, auto_axes
        mesh = make_mesh((2, 2, 2), ("x", "y", "z"), axis_types=auto_axes(3))
        prob = build_ea3d_lattice(8, seed=5)
        eng = LatticeDSIM(prob, mesh, dim_axes=("x", "y", "z"), impl="ref")
        st = eng.init_state(seed=0)
        g = ea3d(8, seed=5)
        m = jnp.asarray(np.asarray(st.m).reshape(-1))
        print("EQ", abs(float(eng.energy(st)) - float(energy(g, m))) < 1e-3)
        stf, (_, Es) = eng.run_recorded(st, ea_schedule(256), [256],
                                        sync_every=4)
        print("ANNEALED", float(Es[-1]) < float(eng.energy(st)) )
        print("E_final", float(Es[-1]))
    """, devices=8)
    assert "EQ True" in out and "ANNEALED True" in out


@pytest.mark.parametrize("devices,link", [(4, "chip"), (1, "local")])
def test_lattice_exchanges_counted_at_dispatch(devices, link):
    """``lattice_exchanges_total{link}``: a recorded run of N chunks of
    ``iters`` exchange periods counts N * iters, plus one for the halo
    refresh of its fresh state, under ``chip`` when the lattice is split
    over devices and under ``local`` on one brick."""
    out = run_py(f"""
        from repro.compat import auto_axes, make_mesh
        from repro.core.annealing import ea_schedule
        from repro.core.lattice import build_ea3d_lattice
        from repro.engines import make_engine
        from repro.obs import exchanges
        fam = exchanges.exchanges().counter(exchanges.EXCHANGES)
        counts = lambda: {{k: fam.labels(link=k).value
                          for k in exchanges.LINKS}}
        mesh = make_mesh(({devices},), ("x",), axis_types=auto_axes(1))
        h = make_engine("lattice", lattice=build_ea3d_lattice(8, seed=1),
                        mesh=mesh, dim_axes=("x", None, None),
                        precision="int8", replicas=2)
        before = counts()
        cur = h.start_recorded(h.init_state_packed([1, 2]), ea_schedule(24),
                               [8, 16, 24], sync_every=4)
        chunks = 0
        while not cur.done:
            chunks += cur.advance(1)
        after = counts()
        print("BRICK", h.eng.brick, "CHUNKS", chunks)
        for k in exchanges.LINKS:
            print("COUNT", k, after[k] - before[k])
    """, devices=devices)
    assert "CHUNKS 3" in out
    other = "local" if link == "chip" else "chip"
    assert f"COUNT {link} {3 * 2 + 1}" in out, out
    assert f"COUNT {other} 0" in out, out


def test_exchange_scope_adds_no_op():
    """``lattice.exchange`` names the exchange in the op metadata and
    adds no op: the lowered chunk has the same ops without it."""
    out = run_py("""
        import collections, contextlib, re
        import jax
        from repro.compat import auto_axes, make_mesh
        from repro.core.lattice import build_ea3d_lattice
        from repro.core.lattice_dsim import LatticeDSIM
        mesh = make_mesh((4,), ("x",), axis_types=auto_axes(1))
        prob = build_ea3d_lattice(8, seed=1)

        def lowered(scope):
            saved = jax.named_scope
            if not scope:
                jax.named_scope = lambda name: contextlib.nullcontext()
            try:
                eng = LatticeDSIM(prob, mesh, ("x", None, None),
                                  precision="int8", replicas=2)
                return eng.lower_chunk(iters=2, S=4).as_text(
                    debug_info=True)
            finally:
                jax.named_scope = saved

        ops = lambda t: collections.Counter(
            re.findall(r"\\b(?:stablehlo|chlo|sdy|func)\\.[a-z_]+", t))
        a, b = lowered(True), lowered(False)
        print("SAME", ops(a) == ops(b))
        print("PERMUTES", ops(a)["stablehlo.collective_permute"])
        print("NAMED", "lattice.exchange" in a, "lattice.exchange" in b)
    """)
    assert "SAME True" in out and "NAMED True False" in out, out
    assert "PERMUTES 0" not in out, out
