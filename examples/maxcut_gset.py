"""Max-Cut with adaptive parallel tempering + isoenergetic cluster moves.

The paper's G81 protocol (Sec. S9) at reduced size: a toroidal +-1 grid,
APT preprocessing for the temperature ladder, APT+ICM search, best-cut
distribution over trials, and the hex-encoded verification string.

  PYTHONPATH=src python examples/maxcut_gset.py [--rows 10 --cols 16]
"""

import argparse

import numpy as np

from repro.core.coloring import greedy_coloring
from repro.core.apt_icm import APTICM, adapt_ladder
from repro.problems.maxcut import (gset_like_toroidal, maxcut_to_ising,
                                   cut_of, spins_to_hex)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=10)
    ap.add_argument("--cols", type=int, default=16)
    ap.add_argument("--sweeps", type=int, default=1500)
    ap.add_argument("--trials", type=int, default=5)
    args = ap.parse_args()

    g = gset_like_toroidal(args.rows, args.cols, seed=81)
    gi = maxcut_to_ising(g)
    col = greedy_coloring(np.asarray(gi.idx), np.asarray(gi.w))
    print(f"toroidal grid {args.rows}x{args.cols} (n={g.n}), "
          f"{col.n_colors} colors")

    betas = adapt_ladder(gi, col, 1.0, 6.0, 8, pilot_sweeps=80)
    print("adaptive ladder:", np.round(betas, 2))

    cuts, best_m = [], None
    for t in range(args.trials):
        apt = APTICM(gi, col, betas, chains=2)
        st = apt.init_state(seed=t)
        st, _ = apt.run(st, args.sweeps, icm_every=10,
                        record_every=args.sweeps)
        m, E = apt.best_config(st)
        c = cut_of(g, m)
        cuts.append(c)
        if c == max(cuts):
            best_m = m
        print(f"trial {t}: cut = {c:.0f}  (E = {E:.0f}, "
              f"{int(st.swaps)} swaps, {int(st.icms)} cluster moves)")

    best = max(cuts)
    print(f"\nbest cut {best:.0f}; found in "
          f"{100 * np.mean(np.asarray(cuts) == best):.0f}% of trials")
    print("verification hex (paper S9 format):")
    print(spins_to_hex(best_m)[:120] + "...")


if __name__ == "__main__":
    from repro.cache import enable_compile_cache
    enable_compile_cache()
    main()
