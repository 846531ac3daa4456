"""Back-to-back anneals through the library entry point.

Reads a traffic file with ``replicas``, ``schedule`` (``kind`` "ea" or
"constant", ``sweeps``, ``beta``), ``sync_every`` and either
``record_points`` or ``record_every``.  One instance of the configuration
is made from the seed; anneal k of a run starts its chains from
``ea3d.replica_seeds(seed, k, replicas)`` and goes through
``make_engine(...).init_state_packed`` -> ``start_recorded`` ->
``advance`` until the window closes.  ``updates_per_s`` counts every
site update of every chain, accepted or not, over all the time of the
window, the partial last anneal included.

The check, after the window: one finished anneal, drawn from the seed
among all that finished (a reservoir of one, so the window holds one
anneal's spins and not every anneal's), is re-run by the reference from
its seeds: final spins, energies at every record point and flip totals
must match exactly.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from repro.core.annealing import ArraySchedule
from repro.core.lattice import LatticeProblem
from repro.engines import make_engine

import ea3d
import kernel_bytes


def lattice_problem(L: int, seed: int):
    """The instance as the program takes it, and its couplings."""
    jx, jy, jz = ea3d.couplings(L, seed)
    w6 = tuple(w.astype(jnp.float32) for w in ea3d.six_planes(jx, jy, jz))
    prob = LatticeProblem(
        L=L, dims=(L, L, L), seed=int(seed), n_colors=2,
        h=jnp.zeros((L, L, L), jnp.float32), w6=w6,
        masks=jnp.asarray(ea3d.color_masks(L)),
        active=jnp.ones((L, L, L), jnp.int8))
    return prob, (jx, jy, jz)


def record_points(traffic) -> list:
    sweeps = int(traffic["schedule"]["sweeps"])
    if "record_every" in traffic:
        k = int(traffic["record_every"])
        return list(range(k, sweeps + 1, k))
    return [int(p) for p in traffic["record_points"]]


def schedule_betas(traffic) -> np.ndarray:
    s = traffic["schedule"]
    return ea3d.staircase(s["kind"], int(s["sweeps"]),
                          float(s.get("beta", 1.0)))


class Cell:
    def __init__(self, cfg, traffic, seed, log):
        self.cfg, self.traffic, self.seed, self.log = cfg, traffic, seed, log
        self.L = int(cfg["L"])
        self.R = int(traffic["replicas"])
        self.S = int(traffic["sync_every"])
        self.betas = schedule_betas(traffic)
        self.points = record_points(traffic)

    # -- set-up -------------------------------------------------------------

    def setup(self):
        prob, self.j = lattice_problem(self.L, self.seed)
        self.h = make_engine("lattice", lattice=prob,
                             precision=self.cfg["precision"],
                             replicas=self.R)
        eng = self.h.eng
        self.log(f"kernel_path {self.h.kernel_path} kernel_bx "
                 f"{eng.kernel_bx} energy_bx {eng.energy_bx} brick "
                 f"{eng.brick} fallback_reason {eng.fallback_reason}")
        self.sched = ArraySchedule(self.betas)
        # one whole anneal warms every program the window runs: the chunk
        # lengths, the energy readout, the halo refresh and the record
        self._anneal(0)

    def _start(self, k: int):
        st = self.h.init_state_packed(ea3d.replica_seeds(self.seed, k,
                                                         self.R))
        return self.h.start_recorded(st, self.sched, self.points,
                                     sync_every=self.S)

    def _anneal(self, k: int):
        cur = self._start(k)
        while not cur.done:
            cur.advance(1)
        return cur, cur.record()

    # -- the measured window -----------------------------------------------

    def window(self, seconds: float, tick=lambda: None) -> dict:
        self.done, self.kept = [], None
        draw = np.random.default_rng([int(self.seed), 1])
        t0 = time.perf_counter()
        deadline = t0 + seconds
        k, partial, ends = 0, 0, [t0]
        while True:
            with TraceAnnotation("bench.init_state"):
                cur = self._start(k)
            while not cur.done and time.perf_counter() < deadline:
                with TraceAnnotation("bench.advance"):
                    cur.advance(1)
                tick()
            if not cur.done:
                partial = cur.sweeps_done
                break
            with TraceAnnotation("bench.record"):
                rec = cur.record()
                self.done.append(dict(
                    k=k, energies=np.asarray(rec.energies),
                    times=np.asarray(rec.times),
                    flips=cur.flips_per_replica()))
            ends.append(time.perf_counter())
            if draw.integers(k + 1) == 0:      # anneal k is kept w.p. 1/(k+1)
                self.kept = (k, cur.state.m)
            k += 1
            if time.perf_counter() >= deadline:
                break
        jax.block_until_ready(cur.state)
        self.window_s = time.perf_counter() - t0
        self.last_cursor = cur
        self.sweeps = len(self.done) * len(self.betas) + partial
        updates = self.R * self.L ** 3 * self.sweeps
        flips = sum(int(d["flips"].sum()) for d in self.done)
        done_updates = self.R * self.L ** 3 * len(self.done) * len(self.betas)
        self.log(f"anneals finished {len(self.done)}, partial sweeps "
                 f"{partial}, sweeps {self.sweeps}, window "
                 f"{self.window_s:.6f} s")
        if len(ends) > 1:
            took = np.diff(ends)
            self.log(f"seconds an anneal: min {took.min():.6f} median "
                     f"{np.median(took):.6f} max {took.max():.6f} (argmax "
                     f"{int(took.argmax())})")
        if done_updates:
            self.log(f"acceptance ratio {flips / done_updates:.6f} "
                     f"(accepted flips over updates)")
        return {"updates_per_s": updates / self.window_s}

    def layer_inputs(self) -> dict:
        """Per-launch bytes of the sweep kernels, by the names the trace
        gives them, and the energy readout's name."""
        eng = self.h.eng
        if self.h.kernel_path == "fused":
            sweep = {"pbit_brick_sweep_int": kernel_bytes.sweep_int8(
                eng.brick, self.S)}
        else:
            sweep = {"pbit_brick_update_int": kernel_bytes.phase_int8(
                eng.brick)}
        return {"sweep_kernels": sweep, "energy_kernel": "brick_energy"}

    # -- correctness --------------------------------------------------------

    def check(self):
        k, m = self.kept or (None, None)
        m = None if m is None else np.asarray(m)   # the drawn anneal's spins
        self.h = self.last_cursor = self.kept = None   # free the program
        checks = {"no_anneal_finished": (int(not self.done), 0)}
        failed = 0
        if k is not None:
            j = tuple(jnp.asarray(a) for a in self.j)
            sp, eg, fg = self.reference_gaps(self.done[k], m, j)
            checks.update(spins_differ=(sp, 0), energy_gap=(eg, 0.0),
                          flips_gap=(fg, 0))
            failed = int(sp > 0 or eg > 0 or fg > 0)
        return len(self.done), failed, checks

    def reference_gaps(self, d, m_prog, j):
        """(spins that differ, largest energy gap, largest per-chain flip
        gap) between anneal ``d`` and the reference's run of it."""
        m, es, fls = ea3d.run(self.L, ea3d.replica_seeds(
            self.seed, d["k"], self.R), j, self.betas, self.S)
        e_ref = es[d["times"] // self.S - 1]
        return (int((m != m_prog).sum()),
                float(np.abs(d["energies"].astype(np.float64) - e_ref).max()),
                int(np.abs(d["flips"] - fls.sum(0)).max()))
