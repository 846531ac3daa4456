"""3SAT via invertible-logic Ising encoding (paper Sec. S12).

Generates a random 3SAT instance near the satisfiability transition,
encodes it with OR-gate invertible logic + copy-gate sparsification, runs
simulated annealing with the paper's s{4}{3} fixed point on the partitioned
DSIM, and decodes with majority vote over variable copies.

  PYTHONPATH=src python examples/sat3_invertible.py [--vars 80]
"""

import argparse

import numpy as np

from repro.core.coloring import greedy_coloring
from repro.core.partition import greedy_partition
from repro.core.dsim import build_partitioned, DSIMEngine
from repro.core.annealing import sat_schedule
from repro.core.pbit import S43
from repro.problems.sat import (random_3sat, encode_3sat, decode_assignment,
                                count_satisfied)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--vars", type=int, default=80)
    ap.add_argument("--alpha", type=float, default=4.26)
    ap.add_argument("--sweeps", type=int, default=4000)
    ap.add_argument("--partitions", type=int, default=4)
    args = ap.parse_args()

    m_cl = int(round(args.vars * args.alpha))
    clauses = random_3sat(args.vars, m_cl, seed=426)
    enc = encode_3sat(clauses, args.vars)
    g = enc.graph
    col = greedy_coloring(np.asarray(g.idx), np.asarray(g.w))
    print(f"3SAT n={args.vars} m={m_cl} (alpha={args.alpha}) -> "
          f"{g.n} p-bits after copy-gate sparsification, "
          f"{col.n_colors} colors")

    K = args.partitions
    labels = greedy_partition(np.asarray(g.idx), np.asarray(g.w), K, seed=0)
    prob = build_partitioned(g, col, labels, K)
    eng = DSIMEngine(prob, rng="lfsr", fmt=S43)
    pts = sorted(set(np.geomspace(64, args.sweeps, 6).astype(int)))
    best = 0
    for p in pts:
        # fresh run to each point so every trace gets the correct
        # annealing-schedule prefix (geometric points: ~2x total work)
        st = eng.init_state(seed=0)
        st, _ = eng.run_recorded(st, sat_schedule(p), [p], sync_every=4)
        assign = decode_assignment(enc, np.asarray(eng.global_spins(st)))
        ns = count_satisfied(clauses, assign)
        best = max(best, ns)
        print(f"  sweeps {p:6d}: satisfied {ns}/{m_cl} "
              f"({100 * ns / m_cl:.2f}%)")
    print(f"\nbest: {best}/{m_cl} = {100 * best / m_cl:.2f}% "
          f"(paper at 250k p-bits: 99.74%)")


if __name__ == "__main__":
    from repro.cache import enable_compile_cache
    enable_compile_cache()
    main()
