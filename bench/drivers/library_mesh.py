"""Back-to-back anneals through the library entry point, on a mesh.

The ``library`` driver's cell with the lattice split over devices: the
configuration's ``mesh`` gives the mesh's ``shape`` and ``axis_names``
(built over the first ``devices`` of ``jax.devices()``), the mesh axis of
each lattice dimension (``dim_axes``) and the bricks per dimension
(``bricks``), which the reference reads as the faces whose neighbours are
as stale as the last exchange.  The window, the reservoir of one, the
update count and the check are ``library.Cell``'s.
"""

from __future__ import annotations

import jax
import numpy as np
from repro.compat import auto_axes, make_mesh
from repro.core.annealing import ArraySchedule
from repro.engines import make_engine

import ea3d
from drivers import library


class Cell(library.Cell):
    def __init__(self, cfg, traffic, seed, log):
        super().__init__(cfg, traffic, seed, log)
        self.mesh_cfg = cfg["mesh"]
        self.bricks = tuple(int(b) for b in self.mesh_cfg["bricks"])

    def setup(self):
        prob, self.j = library.lattice_problem(self.L, self.seed)
        names = tuple(self.mesh_cfg["axis_names"])
        mesh = make_mesh(tuple(self.mesh_cfg["shape"]), names,
                         axis_types=auto_axes(len(names)),
                         devices=jax.devices()[:int(self.cfg["devices"])])
        self.h = make_engine("lattice", lattice=prob, mesh=mesh,
                             dim_axes=tuple(self.mesh_cfg["dim_axes"]),
                             precision=self.cfg["precision"],
                             replicas=self.R)
        eng = self.h.eng
        self.log(f"devices {mesh.devices.size} kernel_path "
                 f"{self.h.kernel_path} kernel_bx {eng.kernel_bx} energy_bx "
                 f"{eng.energy_bx} brick {eng.brick} fallback_reason "
                 f"{eng.fallback_reason}")
        self.sched = ArraySchedule(self.betas)
        cur, _ = self._anneal(0)
        held = sorted(str(d) for d in cur.state.m.sharding.device_set)
        self.log(f"spins held on {len(held)} devices: {held}")

    def reference_gaps(self, d, m_prog, j):
        """``library.Cell.reference_gaps`` with the reference cut into the
        configuration's bricks."""
        m, es, fls = ea3d.run(self.L, ea3d.replica_seeds(
            self.seed, d["k"], self.R), j, self.betas, self.S,
            bricks=self.bricks)
        e_ref = es[d["times"] // self.S - 1]
        return (int((m != m_prog).sum()),
                float(np.abs(d["energies"].astype(np.float64) - e_ref).max()),
                int(np.abs(d["flips"] - fls.sum(0)).max()))
