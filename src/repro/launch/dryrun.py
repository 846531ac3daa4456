import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile the paper's 1M-p-bit sampling chunk
on placeholder devices, prove memory fits, and extract the roofline terms
(FLOPs / bytes / collective schedule).

MUST be run as its own process (the XLA_FLAGS line above has to execute
before any jax import — which is why it is the first statement of this
module and why nothing here is imported by conftest or the benchmarks).

Usage:
  python -m repro.launch.dryrun                  # one pod (16x16)
  python -m repro.launch.dryrun --multi-pod      # two pods (2x16x16)
  python -m repro.launch.dryrun --both-meshes
"""

import argparse
import json
import time
import traceback

import numpy as np

from repro.launch.roofline import roofline

REPORT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                          "reports", "dryrun")
# the one cell: the paper's 10^6-p-bit EA lattice, one sampling chunk
ARCH, SHAPE = "ea3d-1m", "sample_chunk"


def make_production_mesh(*, multi_pod: bool = False):
    """One pod: 16x16 = 256 chips (TPU v5e pod), axes ('data', 'model');
    two pods: (2, 16, 16) = 512 chips, axes ('pod', 'data', 'model')."""
    import jax
    from repro.compat import auto_axes, make_mesh
    shape, axes = ((2, 16, 16), ("pod", "data", "model")) if multi_pod \
        else ((16, 16), ("data", "model"))
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devs)} — the "
            f"dry-run entrypoint must set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            f"any jax import")
    return make_mesh(shape, axes, axis_types=auto_axes(len(axes)),
                     devices=devs[:n])


def lower_ising_cell(mesh, multi_pod: bool, L: int = 100,
                     iters: int = 2, S: int = 4):
    """The paper's 1M-p-bit production workload on the production mesh."""
    from repro.core.lattice import build_ea3d_lattice
    from repro.core.lattice_dsim import LatticeDSIM
    if multi_pod:
        dim_axes = ("data", "model", "pod")      # z (periodic) -> pod (2 | 100)
    else:
        dim_axes = ("data", "model", None)
    pad = (112, 112)                              # x,y padded to 16*7
    prob = build_ea3d_lattice(L, seed=0, pad_xy=pad)
    eng = LatticeDSIM(prob, mesh, dim_axes=dim_axes, impl="ref")
    lowered = eng.lower_chunk(iters=iters, S=S)
    extras = {"p_bits": L ** 3, "padded_sites": int(np.prod(prob.dims)),
              "n_colors": prob.n_colors, "sync_every": S}
    return lowered, extras


def run_cell(multi_pod: bool, report_dir: str = REPORT_DIR) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    lowered, extras = lower_ising_cell(mesh, multi_pod)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    memd = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes"):
        memd[f] = getattr(mem, f, None)
    rep = roofline(compiled, chips, model_flops=None)
    rec = {
        "arch": ARCH, "shape": SHAPE,
        "mesh": "multi_pod_2x16x16" if multi_pod else "single_pod_16x16",
        "chips": chips, "ok": True,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": memd, "extras": extras,
        "roofline": rep.as_dict(), "model_flops_global": None,
    }
    os.makedirs(report_dir, exist_ok=True)
    fn = f"{ARCH}__{SHAPE}__{rec['mesh']}.json"
    with open(os.path.join(report_dir, fn), "w") as f:
        json.dump(rec, f, indent=1, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--report-dir", default=REPORT_DIR)
    args = ap.parse_args()

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = 0
    for mp in meshes:
        mesh_tag = "multi_pod_2x16x16" if mp else "single_pod_16x16"
        tag = f"{ARCH:22s} {SHAPE}   {'2x16x16' if mp else '16x16  '}"
        if args.skip_existing and os.path.exists(os.path.join(
                args.report_dir, f"{ARCH}__{SHAPE}__{mesh_tag}.json")):
            print(f"SKIP {tag}")
            continue
        try:
            rec = run_cell(mp, args.report_dir)
            r = rec["roofline"]
            print(f"OK   {tag} compile={rec['compile_s']:7.1f}s "
                  f"flops={r['flops']:.3e} wire={r['wire_bytes']:.3e} "
                  f"bottleneck={r['bottleneck']}")
        except Exception as e:
            failures += 1
            print(f"FAIL {tag} {type(e).__name__}: {e}")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
