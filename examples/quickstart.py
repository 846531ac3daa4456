"""Quickstart: sample a 3D Edwards-Anderson spin glass with the p-computer.

Builds a small EA instance, then drives it through the unified engine layer
(`repro.engines.make_engine`): the monolithic chromatic Gibbs engine (the
paper's GPU-baseline role), the partitioned DSIM at several boundary-
exchange frequencies (the eta-staleness effect — the paper's core result),
and the fused-kernel lattice engine running a batch of independent replica
anneals — one screen of code, every backend behind one API.

For the *serving* story — async job queue, replica-packing scheduler,
engine pool, streaming results — see examples/serve_sampling.py.

  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.engines import make_engine
from repro.core.graph import ea3d
from repro.core.coloring import lattice3d_coloring
from repro.core.partition import slab_partition
from repro.core.commcost import (boundary_matrix, ChainTopology, comm_cost,
                                 eta_threshold)
from repro.core.annealing import ea_schedule
from repro.core.analysis import eta_from_sync


def main():
    L, K, budget = 10, 4, 2048
    print(f"EA spin glass L={L} (N={L**3}), {K}-FPGA-style chain, "
          f"{budget} sweeps\n")
    g = ea3d(L, seed=0)
    col = lattice3d_coloring(L)
    print(f"coloring: {col.n_colors} colors (paper: 2 for even L, 3 odd)")

    # monolithic reference through the registry
    eng = make_engine("gibbs", g, coloring=col, rng="philox")
    st = eng.init_state(seed=0)
    st, rec = eng.run_recorded(st, ea_schedule(budget), [budget])
    print(f"monolithic  : E = {float(rec.energies[-1, 0]):9.1f}   "
          f"({rec.flips:,} flips)")

    # the design rule (Eq. 2) for this partition on a chain
    labels = slab_partition(L, K)
    b = boundary_matrix(np.asarray(g.idx), np.asarray(g.w), labels, K)
    cm = comm_cost(b, ChainTopology(pins=[32] * (K - 1))).c_max
    thr = eta_threshold(col.n_colors, cm)
    print(f"\ncomm-cost model: C_max = {cm:.1f}, "
          f"eta threshold = 2*N_color*C_max = {thr:.0f}\n")

    from repro.core.dsim import build_partitioned
    prob = build_partitioned(g, col, labels, K)   # once, shared by all syncs
    for sync in ["phase", 1, 16, 128, None]:
        eng = make_engine("dsim", prob, rng="lfsr")
        st = eng.init_state(seed=0)
        st, rec = eng.run_recorded(st, ea_schedule(budget), [budget],
                                   sync_every=sync)
        eta = eta_from_sync(sync, col.n_colors, cm)
        tag = {"phase": "exact (per-phase exchange)",
               None: "disconnected links"}.get(sync, f"exchange every {sync}")
        print(f"DSIM S={str(sync):>5} : E = {float(rec.energies[-1, 0]):9.1f}   "
              f"eta ~ {eta:8.1f}   [{tag}]")

    # the production path: fused multi-phase kernel, R independent replicas
    R = 4
    eng = make_engine("lattice", L=L, seed=0, replicas=R)
    st = eng.init_state(seed=0)
    st, rec = eng.run_recorded(st, ea_schedule(budget), [budget],
                               sync_every=8)
    Es = np.asarray(rec.energies[-1])
    print(f"\nlattice x{R} replicas (fused kernel): "
          f"best E = {Es.min():9.1f}, per-replica {np.round(Es, 1)}")

    # the same path through the hardware's fixed-point pipeline: int8
    # on-chip couplings, integer fields, LUT-threshold accepts — zero
    # floating point in the inner loop (DESIGN.md "Fixed-point pipeline")
    eng = make_engine("lattice", L=L, seed=0, replicas=R, precision="int8")
    st = eng.init_state(seed=0)
    st, rec = eng.run_recorded(st, ea_schedule(budget), [budget],
                               sync_every=8)
    Es = np.asarray(rec.energies[-1])
    print(f"lattice x{R} replicas (int8 pipeline, {eng.kernel_path}): "
          f"best E = {Es.min():9.1f}, per-replica {np.round(Es, 1)}")

    # ... and the bit-plane form of the same pipeline: independent
    # replicas packed into the bit lanes of stacked uint32 word planes
    # (32 per word, up to 8 words) — multi-spin coding, the paper's
    # one-bit-per-spin claim in software (DESIGN.md "Bit-plane replica
    # pipeline")
    eng = make_engine("lattice", L=L, seed=0, replicas=32,
                      precision="bitplane")
    st = eng.init_state(seed=0)
    st, rec = eng.run_recorded(st, ea_schedule(budget), [budget],
                               sync_every=8)
    Es = np.asarray(rec.energies[-1])
    print(f"lattice x32 lanes (bit-plane words, {eng.kernel_path}): "
          f"best E = {Es.min():9.1f} ({rec.flips:,} lane-flips)")

    # lane-packed APT+ICM: the (chains x temperatures) tempering grid of
    # the G81 workload rides the word lanes — the paper's full T=64
    # ladder at 2 chains is 128 lanes across 4 stacked word planes.
    # Replica-exchange swap moves are lane permutations (a bit
    # gather/scatter across the word stack, cross-word moves included),
    # ICM disagreement is a per-pair (word, bit) extraction; bit-identical
    # to the unpacked fixed-point ladder at matched seeds
    # (DESIGN.md "The word wire format across engines")
    from repro.core.apt_icm import APTICM
    gs = ea3d(6, seed=0)
    cols = lattice3d_coloring(6)
    betas = np.geomspace(0.3, 3.0, 64)         # 2 chains x 64 temps = 128 lanes
    apt = APTICM(gs, cols, betas, chains=2, rng="lfsr", packed=True)
    stp, (_, best) = apt.run(apt.init_state(seed=0), 60, icm_every=10,
                             record_every=20)
    _, e_best = apt.best_config(stp)
    print(f"\nAPT+ICM packed (L=6, {apt.L} lanes / {apt.words} words): "
          f"best E = {e_best:9.1f}, "
          f"{int(stp.swaps)} swaps (lane permutations), "
          f"{int(stp.icms)} cluster moves")

    print("\nStale boundaries trade solution quality for throughput —")
    print("the single ratio eta governs it (benchmarks/fig2, fig3).")


if __name__ == "__main__":
    from repro.cache import enable_compile_cache
    enable_compile_cache()
    main()
