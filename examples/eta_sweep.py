"""Eta sweep with the CMFT twin — predict hardware behaviour in software.

Runs the partitioned DSIM and the parallel cluster-mean-field model on the
same instance/partition/schedule across staleness settings, fits kappa_f
for both, and prints the paired table (the paper's Fig. 3 protocol: CMFT as
a design-screening tool, Supplementary S3.2).

  PYTHONPATH=src python examples/eta_sweep.py
"""

import numpy as np

from repro.core.graph import ea3d
from repro.core.coloring import lattice3d_coloring
from repro.core.partition import slab_partition
from repro.core.dsim import build_partitioned, DSIMEngine
from repro.core.annealing import ea_schedule
from repro.core.analysis import fit_kappa
from repro.core.gibbs import GibbsEngine


def trace(eng_fn, g, Eg, sch, pts, sync, runs=3):
    """Mean residual-energy trace; returns (record times, rho).

    The DSIM engines quantize record points to multiples of S (collapsing
    duplicates), so the fit must use the times they actually recorded at —
    returned in the RunRecord.  The monolithic engine records at ``pts``
    verbatim and returns the energy trace directly."""
    rhos, times = [], np.asarray(pts)
    for r in range(runs):
        eng = eng_fn()
        st = eng.init_state(seed=r)
        st, out = eng.run_recorded(st, sch, pts, sync_every=sync) \
            if sync != "mono" else eng.run_recorded(st, sch, pts)
        if hasattr(out, "energies"):
            times, Es = np.asarray(out.times), out.energies
        else:
            Es = out
        rhos.append((np.asarray(Es) - Eg) / g.n)
    return times, np.mean(rhos, axis=0)


def main():
    L, K, budget = 8, 4, 4096
    g = ea3d(L, seed=42)
    col = lattice3d_coloring(L)
    prob = build_partitioned(g, col, slab_partition(L, K), K)
    sch = ea_schedule(budget)
    pts = sorted(set(np.geomspace(4, budget, 14).astype(int)))

    # putative ground (longer run, paper protocol)
    ref = GibbsEngine(g, col)
    st = ref.init_state(seed=0)
    st, (Etr, _) = ref.run_dense(st, ea_schedule(4 * budget).beta_array())
    Eg = float(np.asarray(Etr).min())
    print(f"L={L} K={K}, putative ground {Eg:.0f}\n")
    print(f"{'S':>6s} {'kappa_DSIM':>11s} {'kappa_CMFT':>11s}")

    ts, rho = trace(lambda: GibbsEngine(g, col), g, Eg, sch, pts, "mono")
    k_mono = fit_kappa(ts, rho, window=(8, budget)).kappa
    print(f"{'mono':>6s} {k_mono:11.3f} {'—':>11s}")

    for S in (1, 8, 64, 256):
        ks = {}
        for mode in ("dsim", "cmft"):
            ts, rho = trace(lambda: DSIMEngine(prob, rng="lfsr", mode=mode),
                            g, Eg, sch, pts, S)
            ks[mode] = fit_kappa(ts, rho, window=(8, budget)).kappa
        print(f"{S:6d} {ks['dsim']:11.3f} {ks['cmft']:11.3f}")

    print("\nBoth columns degrade together as S grows (eta shrinks):")
    print("staleness is a property of partitioned stochastic dynamics, so")
    print("CMFT predicts the hardware exponent before any hardware exists.")


if __name__ == "__main__":
    from repro.cache import enable_compile_cache
    enable_compile_cache()
    main()
