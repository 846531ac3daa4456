# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
import argparse
import inspect
import sys
import traceback


MODULES = [
    "fig2_eta_collapse",
    "fig3_kappa_vs_eta",
    "fig45_time_to_target",
    "flip_rate",
    "serve_load",
    "tableS2_maxcut",
    "figS15_sat",
    "figS3_commcost",
    "figS5_partition",
    "figS9_disconnected",
    "figS13_planted",
    "roofline_table",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--full", action="store_true",
                    help="larger lattices / budgets (hours on CPU)")
    ap.add_argument("--engine", default=None,
                    choices=["gibbs", "dsim", "dsim_dist", "lattice"],
                    help="restrict engine-aware benchmarks to one registry "
                         "backend")
    ap.add_argument("--replicas", type=int, default=1,
                    help="replica batch (R independent chains per call) for "
                         "engine-aware benchmarks")
    args = ap.parse_args()
    from repro.cache import enable_compile_cache
    enable_compile_cache()

    mods = args.only if args.only else MODULES
    print("name,us_per_call,derived")
    failures = 0
    for name in mods:
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            kw = {"quick": not args.full}
            # engine/replicas forwarded to every benchmark whose run()
            # accepts them (the registry-migrated ones)
            params = inspect.signature(mod.run).parameters
            if "engine" in params and args.engine is not None:
                kw["engine"] = args.engine
            if "replicas" in params:
                kw["replicas"] = args.replicas
            for r in mod.run(**kw):
                print(f"{r['name']},{r['us_per_call']:.1f},\"{r['derived']}\"")
            sys.stdout.flush()
        except Exception as e:
            failures += 1
            print(f"{name},nan,\"FAILED: {type(e).__name__}: {e}\"")
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} benchmarks failed")


if __name__ == '__main__':
    main()
