"""Brick-partitioned lattice DSIM on a device mesh (the production engine).

The global lattice arrays are sharded directly over mesh axes — one brick
per device.  Inside ``shard_map`` each device runs the fused multi-phase
Pallas sweep on its brick: one kernel launch executes the full color cycle
for up to ``sync_every`` sweeps (the per-phase kernel is kept as the
reference path, selected with ``fused=False``).  The ONLY collectives
during sampling are the halo ``ppermute``s of 1-byte boundary spin planes,
every ``sync_every`` sweeps (x/y open chains, z a periodic ring — exactly
the paper's boundary traffic, with ppermute as the source-synchronous link).

Replicas: states always carry a leading replica axis R (default 1).  The
R chains share the brick layout — the replica axis is a plain leading data
dim on every sharded array, so halo ppermutes ship all R planes in one
collective and the update kernel runs per replica (vmapped for the jnp
reference path, an in-block loop for the Pallas paths).

This is the path the 1M-p-bit production config (`ea3d_1m`) lowers through
in the multi-pod dry-run.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import warnings
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .annealing import ArraySchedule, beta_row_indices, beta_table
from .degrade import (DegradePolicy, MeshHealthMonitor, fault_code,
                      health_init, health_step, wire_checksum)
from .lattice import LatticeProblem
from .packing import (LANE_WIDTH, pack_lanes, pack_pm1, unpack_lanes,
                      unpack_pm1, pad_to_multiple)
from .pbit import (FixedPoint, LUT_SELECT_MAX_WIDTH, bitplane_planes,
                   field_bound, flips_publish, lfsr_states,
                   quantize_couplings, threshold_lut_cached)
from repro.compat import shard_map
from repro.obs import exchanges, operand_puts, programs
from repro.obs.trace import span
from repro.engines.base import (RecordedCursor, check_lanes,
                                run_recorded_driver, spawn_seeds)
from repro.kernels.ops import (pbit_update_op, pbit_sweep_op,
                               pbit_update_int_op, pbit_sweep_int_op,
                               pbit_bitplane_sweep_op, brick_energy_op)

__all__ = ["LatticeDSIM", "LatticeState", "BitplaneLatticeState",
           "fused_working_set_bytes", "fused_brick_ceiling",
           "tiled_working_set_bytes", "pick_x_tile"]

# VMEM working-set model (DESIGN.md "VMEM working-set math").  Mosaic keeps
# a brick as (By, Bz) planes with z on the 128 lanes and y on sublanes
# packed 4/itemsize deep (8 rows of 32-bit words, 32 rows of int8), so a
# plane costs its padded tile bytes, not By*Bz*itemsize: a 100^3 int8
# brick pays 128*128 B per x-plane, a 16^3 one 32*128 B.  Each kernel's
# buffers are counted per x-plane as (int8 planes, 32-bit planes):
#   fused f32    n_c masks + in/out spins  |  h + 6 weights + in/out LFSR
#   fused int8   n_c masks + in/out spins + h_q + 6 w_q  |  in/out LFSR
#   bitplane     —  |  in/out words (W each) + n_c W masks + 12 sign/nonzero
#                planes + base + in/out LFSR columns (one per lane)
# plus the halo faces, widened to 32-bit (x faces as planes, y/z faces as
# one (1, n) row per x-plane).  Threshold rows and betas live in SMEM.
# ``tiled=False`` gives the unpadded byte count — what a jaxpr sees, and
# what the static auditor (rule IR-F) compares against.
DEFAULT_VMEM_BUDGET = 16 << 20  # 16 MiB/core, the TPU VMEM working budget
_LANES = 128

# The six halo faces in state order, (lattice dim, up, periodic): up=True
# receives the -1 neighbor's high face (my low halo).  x and y are open
# chains, z is a periodic ring.
_FACES = ((0, True, False), (0, False, False), (1, True, False),
          (1, False, False), (2, True, True), (2, False, True))


def _drop(plane, d):
    """A face plane without its one-wide lattice dim ``d`` (the leading
    replica or word axis kept)."""
    return plane[(slice(None),) * (d + 1) + (0,)]


def _lift_all(halos):
    """Six squeezed halo planes back into the state's layout, each with
    its one-wide lattice dim."""
    return tuple(x[(slice(None),) * (d + 1) + (None,)]
                 for x, (d, _, _) in zip(halos, _FACES))


def _round_up(n: int, m: int) -> int:
    return -(-int(n) // m) * m


def _plane_bytes(by: int, bz: int, itemsize: int, tiled: bool = True) -> int:
    """VMEM bytes of one (by, bz) plane of ``itemsize``-byte elements."""
    if not tiled:
        return by * bz * itemsize
    return (_round_up(by, 8 * (4 // itemsize)) * _round_up(bz, _LANES)
            * itemsize)


def _fused_planes(precision: str, n_colors: int,
                  lanes: int = LANE_WIDTH) -> Tuple[int, int]:
    """(int8, 32-bit) brick-shaped buffers of the single-block kernel."""
    if precision == "bitplane":
        words = max(1, (int(lanes) + LANE_WIDTH - 1) // LANE_WIDTH)
        return 0, 2 * words + n_colors * words + 13 + 2 * int(lanes)
    if precision == "int8":
        return n_colors + 9, 2
    return n_colors + 2, 9


def _halo_bytes(brick, bx: int, tiled: bool) -> int:
    """Two x-face planes plus 2+2 y/z face rows for ``bx`` x-planes."""
    _, by, bz = brick
    if not tiled:
        return 2 * 4 * (by * bz + bx * bz + bx * by)
    return (2 * _plane_bytes(by, bz, 4)
            + 2 * bx * (_plane_bytes(1, bz, 4) + _plane_bytes(1, by, 4)))


def fused_working_set_bytes(brick: Tuple[int, int, int], n_colors: int,
                            precision: str = "f32",
                            lanes: int = LANE_WIDTH,
                            tiled: bool = True) -> int:
    """VMEM bytes the single-block fused sweep kernel needs for one brick.

    ``lanes`` only matters on the bitplane path (per-lane LFSR columns)."""
    bx, by, bz = brick
    n8, n32 = _fused_planes(precision, n_colors, lanes)
    plane = (n8 * _plane_bytes(by, bz, 1, tiled)
             + n32 * _plane_bytes(by, bz, 4, tiled))
    return bx * plane + _halo_bytes(brick, bx, tiled)


def tiled_working_set_bytes(brick: Tuple[int, int, int], bx: int,
                            kernel: str = "int8") -> int:
    """VMEM bytes of one x-tiled kernel launch with x-slabs of ``bx``
    planes: the per-phase update (``kernel`` "f32" / "int8") or the energy
    readout ("energy").  The grid double-buffers every block; the planes
    just outside the slab arrive as one-plane blocks."""
    _, by, bz = brick
    # (int8 planes, 32-bit planes) per x-plane of the slab
    n8, n32 = {"f32": (3, 9), "int8": (10, 2), "energy": (2, 7)}[kernel]
    slab = bx * (n8 * _plane_bytes(by, bz, 1) + n32 * _plane_bytes(by, bz, 4))
    edges = 2 * _plane_bytes(by, bz, 1)
    return 2 * (slab + edges + _halo_bytes(brick, bx, True))


def pick_x_tile(brick: Tuple[int, int, int], kernel: str,
                budget: int = DEFAULT_VMEM_BUDGET) -> Optional[int]:
    """Largest divisor of the brick's x extent whose tiled working set
    fits ``budget`` (None if even one plane does not)."""
    Bx = int(brick[0])
    fits = [d for d in range(1, Bx + 1) if Bx % d == 0
            and tiled_working_set_bytes(brick, d, kernel) <= budget]
    return max(fits) if fits else None


def fused_brick_ceiling(n_colors: int, precision: str = "f32",
                        budget: int = DEFAULT_VMEM_BUDGET,
                        lanes: int = LANE_WIDTH) -> int:
    """Largest cubic brick extent whose fused working set fits ``budget``."""
    n8, n32 = _fused_planes(precision, n_colors, lanes)
    side = int((budget / (n8 + 4 * n32)) ** (1.0 / 3.0)) + 1
    while side > 0 and fused_working_set_bytes(
            (side, side, side), n_colors, precision, lanes=lanes) > budget:
        side -= 1
    return side


_DRAW_WORKERS_MAX = 8


@functools.lru_cache(maxsize=None)
def _pool(workers: int) -> ThreadPoolExecutor:
    return ThreadPoolExecutor(max_workers=workers,
                              thread_name_prefix="lattice-draw")


# a forked child inherits the pools but not their threads
os.register_at_fork(after_in_child=_pool.cache_clear)


def draw_pool(chains: int) -> Optional[ThreadPoolExecutor]:
    """The process's pool for drawing ``chains`` chains at once, with
    ``min(chains, os.cpu_count(), 8)`` workers; None where that is one,
    and the chains are drawn on the calling thread."""
    workers = min(int(chains), os.cpu_count() or 1, _DRAW_WORKERS_MAX)
    return _pool(workers) if workers > 1 else None


def draw_chains(seeds: Sequence[int], dims: Tuple[int, int, int]):
    """Host spins and LFSR states of one chain per seed: (R, X, Y, Z) int8
    and uint32.  Chain r depends on ``seeds[r]`` alone.  The chains are
    drawn concurrently (numpy's bounded-integer fill releases the GIL),
    each from its own generators, straight into its slice of the output."""
    R, dims = len(seeds), tuple(dims)
    m = np.empty((R,) + dims, np.int8)
    s = np.empty((R,) + dims, np.uint32)
    n = int(np.prod(dims))

    def draw(r):
        rng = np.random.default_rng(seeds[r])
        m[r] = rng.choice(np.array([-1, 1], np.int8), size=dims)
        s[r] = lfsr_states(n, seeds[r]).reshape(dims)

    pool = draw_pool(R)
    if pool is None:
        for r in range(R):
            draw(r)
    else:
        list(pool.map(draw, range(R)))
    return m, s


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LatticeState:
    m: jnp.ndarray        # (R, X, Y, Z) int8
    s: jnp.ndarray        # (R, X, Y, Z) uint32 LFSR states
    halos: tuple          # 6 halo-plane arrays, each (R, ...) (see _halo_shapes)
    sweep: jnp.ndarray    # scalar int32
    flips: jnp.ndarray    # (R,) int32 modular odometers (exact totals are
                          # accumulated host-side by the recording driver)

    @property
    def replicas(self) -> int:
        return int(self.m.shape[0])


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BitplaneLatticeState:
    """Multi-spin-coded state: replicas live in the bit lanes of ``m``.

    ``m`` stacks W = ceil(R/32) word planes — bit b of plane w is replica
    lane ``w*32 + b``'s spin (1 = +1); only the LFSR columns and flip
    odometers keep an explicit replica axis — each lane owns its own RNG
    stream (the lane-independence contract)."""

    m: jnp.ndarray        # (W, X, Y, Z) uint32 stacked spin word planes
    s: jnp.ndarray        # (R, X, Y, Z) uint32 per-lane LFSR states
    halos: tuple          # 6 packed word halo planes, leading W axis
    sweep: jnp.ndarray    # scalar int32
    flips: jnp.ndarray    # (R,) int32 per-lane modular odometers

    @property
    def replicas(self) -> int:
        return int(self.s.shape[0])


class LatticeDSIM:
    """dim_axes: mesh axis name (or None) for each lattice dim (x, y, z).

    ``bitpack_halos``: ship halo planes as 1-bit bitmaps over the ppermute
    links (8x less wire than int8 — the paper's exact 1-bit-per-boundary-
    p-bit traffic; §Perf H8).

    ``fused``: run the multi-phase fused sweep kernel (one launch per
    ``sync_every`` sweeps); ``fused=False`` keeps the per-phase reference
    dispatch (one launch per color phase), bitwise identical.  A fused
    request whose brick working set exceeds ``vmem_budget_bytes`` falls
    back to the per-phase path with a one-time warning; the decision is
    exposed as ``kernel_path`` / ``fallback_reason``.

    ``precision``: "f32" (reference) or "int8" — the hardware's fixed-point
    pipeline: couplings quantized to int8 at init with one per-problem
    scale, int32 field accumulation, and tanh + float compare replaced by a
    uint32 compare against a per-(beta, field) threshold LUT; annealing
    staircases become LUT row indices.  ``fmt`` folds into the LUT."""

    def __init__(self, prob: LatticeProblem, mesh: Mesh,
                 dim_axes: Tuple[Optional[str], Optional[str], Optional[str]],
                 fmt: Optional[FixedPoint] = None, impl: str = "auto",
                 kernel_bx: Optional[int] = None, bitpack_halos: bool = True,
                 fused: bool = True, replicas: int = 1,
                 precision: str = "f32",
                 vmem_budget_bytes: int = DEFAULT_VMEM_BUDGET,
                 degrade: Union[None, str, DegradePolicy] = None):
        if precision not in ("f32", "int8", "bitplane"):
            raise ValueError(f"unknown precision {precision!r}")
        programs.watch()      # traces and compiles counted per program
        self.p = prob
        self.mesh = mesh
        self.dim_axes = dim_axes
        self.fmt = fmt
        self.impl = impl
        self.kernel_bx = kernel_bx
        self.bitpack_halos = bitpack_halos
        self.precision = precision
        self.vmem_budget_bytes = int(vmem_budget_bytes)
        self.replicas = int(replicas)
        # the shared lane-cap guard; W word planes for the packed path
        self.words = check_lanes(precision, self.replicas)
        if precision == "bitplane" and kernel_bx is not None:
            raise ValueError("kernel_bx (per-phase x-tiling) is not "
                             "available on the bitplane path")
        self.n_sites = prob.n_active
        X, Y, Z = prob.dims
        if precision in ("int8", "bitplane"):
            self.h_q, self.w6_q, self.q_scale = quantize_couplings(prob.h,
                                                                   prob.w6)
            self.f_max = field_bound(self.h_q, self.w6_q)
            # Mosaic cannot gather per element from VMEM: the Pallas int
            # kernels evaluate the accept as a rank count over threshold
            # rows held in SMEM, which caps the row width.  The bitplane
            # path uses the rank count on EVERY impl (the word math has no
            # per-lane gather form at all).
            # Fail at init with a clear message, not at first lowering.
            from repro.kernels.ops import default_impl
            resolved = impl if impl != "auto" else default_impl()
            if (resolved == "pallas" or precision == "bitplane") and \
                    2 * self.f_max + 1 > LUT_SELECT_MAX_WIDTH:
                raise ValueError(
                    f"precision={precision!r} needs a threshold LUT row of "
                    f"<= {LUT_SELECT_MAX_WIDTH} entries (gather-free "
                    f"rank-count accept); this problem quantizes to "
                    f"f_max={self.f_max} (width {2 * self.f_max + 1}).  "
                    f"Use impl='ref' with precision='int8' or coarser "
                    f"couplings.")
        else:
            self.h_q = self.w6_q = None
            self.q_scale, self.f_max = 1.0, 0
        self._lut_cache = {}
        self.nb = tuple(1 if a is None else mesh.shape[a] for a in dim_axes)
        for d, (ext, k) in enumerate(zip(prob.dims, self.nb)):
            if ext % k != 0:
                raise ValueError(f"dim {d} extent {ext} not divisible by mesh factor {k}")
        self.brick = tuple(e // k for e, k in zip(prob.dims, self.nb))
        # the ``link`` label of ``lattice_exchanges_total``
        self.link = "chip" if max(self.nb) > 1 else "local"
        # fused-vs-per-phase decision (DESIGN.md "VMEM working-set math"):
        # x-tiling forces per-phase; so does a brick working set beyond the
        # VMEM budget, and the fallback then picks the largest x-tile whose
        # double-buffered slab fits.  The bitplane path has exactly one
        # dispatch (the single-block word kernel), so an over-budget brick
        # is refused.
        self.fused_requested = bool(fused)
        # bitplane launches are per WORD PLANE, so the kernel working set
        # is bounded by one full word (<= 32 lanes) regardless of W
        launch_lanes = min(self.replicas, LANE_WIDTH) \
            if precision == "bitplane" else self.replicas
        self.fused_working_set = fused_working_set_bytes(
            self.brick, prob.n_colors, precision, lanes=launch_lanes)
        self.fallback_reason = None
        fused = bool(fused)
        budget = self.vmem_budget_bytes
        if precision == "bitplane":
            if self.fused_working_set > budget:
                ceiling = fused_brick_ceiling(prob.n_colors, precision,
                                              budget, lanes=launch_lanes)
                raise ValueError(
                    f"bitplane sweep kernel needs "
                    f"{self.fused_working_set:,} B of VMEM for brick "
                    f"{self.brick} ({launch_lanes} lanes per word-plane "
                    f"launch, {prob.n_colors} colors), over the "
                    f"{budget:,} B budget, and the word kernel has no "
                    f"per-phase fallback: its ceiling is a {ceiling}^3 "
                    f"brick.  Shard over more devices or use "
                    f"precision='int8'.")
            self.fused = True
        else:
            if fused and kernel_bx is not None:
                fused, self.fallback_reason = False, "kernel_bx"
            if fused and self.fused_working_set > budget:
                ceiling = fused_brick_ceiling(prob.n_colors, precision,
                                              budget)
                fused, self.fallback_reason = False, "vmem"
                self.kernel_bx = pick_x_tile(self.brick, precision, budget)
                if self.kernel_bx is None:
                    raise ValueError(
                        f"no x-tile of brick {self.brick} fits the "
                        f"{budget:,} B VMEM budget ({precision} per-phase "
                        f"kernel); shard over more devices")
                warnings.warn(
                    f"lattice fused sweep kernel needs "
                    f"{self.fused_working_set:,} B of VMEM for brick "
                    f"{self.brick} ({precision}, {prob.n_colors} colors) — "
                    f"over the {budget:,} B budget; falling back to the "
                    f"per-phase dispatch with x-tiles of {self.kernel_bx} "
                    f"planes.  Fused single-block ceiling at this budget "
                    f"is ~{ceiling}^3 per brick.",
                    RuntimeWarning, stacklevel=2)
            self.fused = fused
        # the energy readout is x-tiled the same way (its f32 couplings
        # make it the widest per-plane kernel); one plane is the floor
        self.energy_bx = pick_x_tile(self.brick, "energy", budget) or 1
        ax, ay, az = dim_axes
        self.spec_m = P(None, ax, ay, az)        # leading replica axis
        self.spec_flat = P(ax, ay, az)           # problem constants (no R)
        # halo plane specs: (R, nbx, Y, Z), ... each sharded so every device
        # holds exactly its (1-plane) halo slice for all replicas.  On the
        # bitplane path the replica axis lives inside the words and the
        # leading axis is the W stacked word planes.
        self.halo_specs = tuple(P(None, ax, ay, az) for _ in range(6))
        self._field, self._field_specs = self._field_operands()
        self._shard = lambda spec: NamedSharding(mesh, spec)
        # the field and energy operands as put on the mesh, filled on
        # first use (never here: an engine over an AbstractMesh has no
        # devices) and dropped by ``shard_state``, with the LUTs
        self._placed = {}
        self._chunk_cache = {}
        self._energy_fn = None
        self._exchange_only_fn = None
        self._halo_refresh = None
        # degraded-mode fabric: the six faces are the boundary sources
        self.degrade = DegradePolicy.parse(degrade)
        self.health = MeshHealthMonitor(self.degrade, 6, kind="faces") \
            if self.degrade is not None else None
        self._fault_codes = None

    @property
    def kernel_path(self) -> str:
        """Which update dispatch actually runs: "fused", "per_phase", or
        "bitplane" (the multi-spin-coded word kernel)."""
        if self.precision == "bitplane":
            return "bitplane"
        return "fused" if self.fused else "per_phase"

    def _lut_for(self, table: np.ndarray) -> jnp.ndarray:
        """Threshold LUT for a beta table (cached; fmt folded in), put
        replicated on the mesh."""
        return threshold_lut_cached(
            self._lut_cache, table, self.q_scale, self.f_max, fmt=self.fmt,
            put=lambda t: self._put(t, P(), "lut"))

    def _put(self, x, spec, operand: str):
        """``x`` (a pytree of arrays, specs ``spec``) put from the host in
        the sharding its program declares, so that jit dispatches it with
        no reshard; counted in ``lattice_operand_puts_total{operand}``."""
        operand_puts.count(operand)
        return jax.tree.map(
            lambda a, sp: jax.device_put(np.asarray(a), self._shard(sp)),
            x, spec)

    def _operands(self, operand: str):
        """The ``"field"`` operands of the chunk or the ``"energy"`` ones
        of the readout (active sites, h, w6), put on the mesh once."""
        if operand not in self._placed:
            p, flat = self.p, self.spec_flat
            value, spec = (self._field, self._field_specs) \
                if operand == "field" else \
                ((p.active, p.h, tuple(p.w6)), (flat, flat, (flat,) * 6))
            self._placed[operand] = self._put(value, spec, operand)
        return self._placed[operand]

    def _field_operands(self):
        """The chunk's field operands ``(masks, h, w6)`` and their specs,
        in this precision's representation: f32 couplings, int8
        couplings, or on the bitplane path ``(masks_w, base_w, (signs6_w,
        nz6_w))`` — lane-masked uint32 color masks, the LUT-column base and
        the per-direction sign/nonzero word planes."""
        p = self.p
        flat6 = tuple(self.spec_flat for _ in range(6))
        if self.precision != "bitplane":
            h, w6 = (self.h_q, self.w6_q) if self.precision == "int8" \
                else (p.h, p.w6)
            return ((p.masks, h, tuple(w6)),
                    (self.spec_m, self.spec_flat, flat6))
        # sign-plane quantization (validates couplings land on +-1/0)
        # + lane-masked uint32 color masks: lanes >= R never update.
        # Dead lanes live only in the LAST word plane, so every other
        # plane carries the full 32-lane mask.
        signs6, nz6, base, _ = bitplane_planes(self.h_q, self.w6_q)
        W = self.words
        last = self.replicas - (W - 1) * LANE_WIDTH
        lane_masks = np.full((W,), 0xFFFFFFFF, np.uint64)
        lane_masks[-1] = (np.uint64(1) << np.uint64(last)) - \
            np.uint64(1) if last < LANE_WIDTH else np.uint64(0xFFFFFFFF)
        mk = np.asarray(p.masks)                 # (n_colors, X, Y, Z)
        masks_w = jnp.asarray(
            np.where(mk[:, None] != 0,
                     lane_masks.astype(np.uint32)[None, :, None, None, None],
                     0).astype(np.uint32))       # (n_colors, W, X, Y, Z)
        ax, ay, az = self.dim_axes
        # the color masks carry two replicated leading axes (n_colors, W)
        return ((masks_w, base, (tuple(signs6), tuple(nz6))),
                (P(None, None, ax, ay, az), self.spec_flat, (flat6, flat6)))

    # -- halo plumbing -------------------------------------------------------------

    def _halo_shapes(self):
        (X, Y, Z), (kx, ky, kz) = self.p.dims, self.nb
        if self.precision == "bitplane":
            # word planes: 32 replica lanes ride inside each uint32, and
            # the W stacked planes lead (one face payload per word plane)
            W = self.words
            return [(W, kx, Y, Z), (W, kx, Y, Z), (W, X, ky, Z),
                    (W, X, ky, Z), (W, X, Y, kz), (W, X, Y, kz)]
        R = self.replicas
        return [(R, kx, Y, Z), (R, kx, Y, Z), (R, X, ky, Z), (R, X, ky, Z),
                (R, X, Y, kz), (R, X, Y, kz)]

    def _halo_shift(self, plane, axis_name, k, up: bool, periodic: bool,
                    bitpack_pm1: bool):
        """Ship one face plane to the neighbor along a mesh axis.

        up=True: receive the plane of my -1 neighbor (their high face).
        The ONE place the neighbor permutation tables and the k==1
        wrap/zero boundary rule live — the plain and the checked
        exchanges of every representation route through it, so the
        profile's op metadata names all of it ``lattice.exchange``.
        """
        with jax.named_scope("lattice.exchange"):
            if axis_name is None or k == 1:
                if periodic:
                    return plane  # my own opposite face wraps to me
                return jnp.zeros_like(plane)
            if up:
                perm = [(i, (i + 1) % k) for i in range(k)] if periodic \
                    else [(i, i + 1) for i in range(k - 1)]
            else:
                perm = [(i, (i - 1) % k) for i in range(k)] if periodic \
                    else [(i, i - 1) for i in range(1, k)]
            if not bitpack_pm1:
                return jax.lax.ppermute(plane, axis_name, perm)
            shape = plane.shape
            n = int(np.prod(shape))
            npad = pad_to_multiple(n, 8)
            flat = jnp.pad(plane.reshape(-1), (0, npad - n),
                           constant_values=1)
            packed = pack_pm1(flat)
            packed = jax.lax.ppermute(packed, axis_name, perm)
            return unpack_pm1(packed, n).reshape(shape)

    def _faces(self, m):
        """The six outgoing face planes of brick ``m``, in halo order:
        yields ``(plane, axis name, k, up, periodic, d)`` per face, the
        plane keeping its one-wide lattice dim ``d`` (see ``_FACES``)."""
        for d, up, periodic in _FACES:
            edge = slice(-1, None) if up else slice(None, 1)
            yield (m[(slice(None),) * (d + 1) + (edge,)], self.dim_axes[d],
                   self.nb[d], up, periodic, d)

    def _exchange_block(self, m):
        """Refresh the six halo planes of this brick via neighbor ppermute.

        ``m`` is (R, bx, by, bz) spins — all R planes of one face cross
        the link in one (1-bit packed) ppermute; padding spins in the
        packed tail are inert (their couplings are zero) — or (W, bx, by,
        bz) bitplane words, whose face slices already are the 1-bit wire
        format (one bit per boundary p-bit per lane) and ship unpacked.
        Boundary words of zero-coupling directions are inert."""
        bitpack = self.bitpack_halos and self.precision != "bitplane"
        return tuple(
            _drop(self._halo_shift(plane, a, k, up, periodic,
                                   bitpack_pm1=bitpack), d)
            for plane, a, k, up, periodic, d in self._faces(m))

    def boundary_exchange_fn(self):
        """Jitted exchange-ONLY closure: the six-face halo ppermute of
        ``_exchange_block`` with the sweep elided.  ``fn(state) -> halos``
        on live state — the measured-η probe
        (``obs.EtaMeter.measure_exchange`` times it to get t_exchange)."""
        cached = getattr(self, "_exchange_only_fn", None)
        if cached is not None:
            return cached
        smapped = self._halo_program()

        @jax.jit
        def lattice_exchange_only(m):
            return smapped(m)

        fn = lambda state: lattice_exchange_only(state.m)  # noqa: E731
        self._exchange_only_fn = fn
        return fn

    def _halo_program(self):
        """The six-face halo exchange of the spins alone, as a fresh
        ``shard_map``: ``m -> halos`` in the state's halo layout."""
        return shard_map(lambda m: _lift_all(self._exchange_block(m)),
                         mesh=self.mesh, in_specs=(self.spec_m,),
                         out_specs=self.halo_specs, check_vma=False)

    # -- block step -------------------------------------------------------------------

    def _sweep_phases_block(self, m, s, sched_S, update):
        """S sweeps of one replica's brick via per-phase dispatch (the
        reference path): ``update(m, s, b, c) -> (m, s)`` is one color
        phase ``c`` at schedule entry ``b``.  m/s (bx, by, bz)."""
        def body(carry, b):
            m, s, fl = carry
            for c in range(self.p.n_colors):
                m2, s = update(m, s, b, c)
                fl = fl + (m2 != m).sum().astype(jnp.int32)
                m = m2
            return (m, s, fl), None
        (m, s, fl), _ = jax.lax.scan(
            body, (m, s, jnp.zeros((), jnp.int32)), sched_S)
        return m, s, fl

    def _one_replica_sweeps(self, masks, h, w6, lut):
        """(m, s, halos, sched_S) -> (m, s, flips) for one replica's brick:
        fused or per-phase, float betas or integer LUT rows."""
        if self.precision == "int8":
            sweep, update = pbit_sweep_int_op, pbit_update_int_op
            lut_opt, kw = (lut,), dict(impl=self.impl)
        else:
            sweep, update = pbit_sweep_op, pbit_update_op
            lut_opt, kw = (), dict(fmt=self.fmt, impl=self.impl)
        if self.fused:
            return lambda mr, sr, hr, ps: sweep(mr, sr, ps, masks, h, w6, hr,
                                                *lut_opt, **kw)
        return lambda mr, sr, hr, ps: self._sweep_phases_block(
            mr, sr, ps, lambda m, s, b, c: update(
                m, s, b, masks[c], h, w6, hr, *lut_opt, bx=self.kernel_bx,
                **kw))

    def _sweep_block(self, m, s, halos, sched_S, masks, h, w6, lut=None):
        """S sweeps for all R replicas against fixed halos (no exchange).

        m/s (R, bx, by, bz); halos 6 x (R, plane).  ``sched_S`` is the
        per-sweep schedule — (S,) shared or (S, R) per-replica; f32 betas on
        the float path, int32 LUT row indices on the integer paths.  On the
        bitplane path m and the halos lead with W word planes, ``masks, h,
        w6`` are ``(masks_w, base_w, (signs6_w, nz6_w))``, and the word op
        sweeps every lane at once."""
        if self.precision == "bitplane":
            signs, nz = w6
            return pbit_bitplane_sweep_op(m, s, sched_S, masks, signs, nz, h,
                                          halos, lut, impl=self.impl)
        one = self._one_replica_sweeps(masks, h, w6, lut)
        per_rep = sched_S.ndim == 2
        from repro.kernels.ops import default_impl
        resolved = self.impl if self.impl != "auto" else default_impl()
        if resolved == "ref":
            # pure-jnp path: replicas vmap cleanly
            m, s, fl = jax.vmap(one, in_axes=(0, 0, 0, 1 if per_rep else
                                              None))(m, s, halos, sched_S)
        else:
            # pallas paths: unrolled replica loop (no pallas_call batching)
            outs = [one(m[r], s[r], jax.tree.map(lambda x: x[r], halos),
                        sched_S[:, r] if per_rep else sched_S)
                    for r in range(m.shape[0])]
            m = jnp.stack([o[0] for o in outs])
            s = jnp.stack([o[1] for o in outs])
            fl = jnp.stack([o[2] for o in outs])
        return m, s, fl

    def _iteration_block(self, m, s, halos, sched_S, masks, h, w6, lut=None):
        """S sweeps for all R replicas, then one halo exchange."""
        m, s, fl = self._sweep_block(m, s, halos, sched_S, masks, h, w6, lut)
        halos = self._exchange_block(m)
        return m, s, halos, fl

    # -- degraded-mode exchange (integrity header + stale hold) ----------------------

    def _exchange_block_checked(self, m, halos_prev, health, codes,
                                freeze: bool):
        """The six-face halo exchange with the integrity layer on.

        Every wired face ships a ``[seq, checksum]`` uint32 header over the
        same ppermute link as its payload; the receiver re-checksums what
        actually arrived and compares.  A face that fails (or a ``codes``
        fault injected at this — the engine — boundary) is *held* at its
        last-known-good plane from ``halos_prev``; its staleness counter
        advances.  Open-chain edge devices have no inbound neighbor on
        their outer faces: those planes are legitimate zeros, not wire
        traffic, and are always accepted (``has_src`` mask).  Unwired axes
        (k == 1) never touch a link and are always accepted.  With zero
        faults the selected halos are bitwise the unchecked exchange's.

        ``halos_prev`` and the returned halos are the *squeezed* planes (as
        carried inside the chunk scan).  Health carries per-face staleness;
        per-device divergence (edges) is pmax-reduced at chunk end.
        """
        seq = health[0]
        word = self.precision == "bitplane"
        bitpack = (not word) and self.bitpack_halos
        if codes is not None:
            corrupt, drop = fault_code(codes, seq)

        new_faces, oks = [], []
        for plane, axis_name, k, up, periodic, d in self._faces(m):
            wired = axis_name is not None and k > 1
            if not wired:
                # no link: periodic k==1 wraps my own face, open k==1 is a
                # fixed zero boundary — nothing to verify
                rx = self._halo_shift(plane, axis_name, k, up, periodic,
                                      bitpack_pm1=False)
                new_faces.append(_drop(rx, d))
                oks.append(jnp.bool_(True))
                continue
            rx = self._halo_shift(plane, axis_name, k, up, periodic,
                                  bitpack_pm1=bitpack)
            hdr = jnp.stack([seq, wire_checksum(plane)])
            hdr_rx = self._halo_shift(hdr, axis_name, k, up, periodic,
                                      bitpack_pm1=False)
            idx = jax.lax.axis_index(axis_name)
            has_src = jnp.bool_(True) if periodic else \
                (idx > 0 if up else idx < k - 1)
            if codes is not None:
                hit, dr = corrupt & has_src, drop & has_src
                flip = jnp.uint32(1) if word else jnp.int8(2)
                rx = jnp.where(hit, rx ^ flip, rx)
                rx = jnp.where(dr, jnp.zeros_like(rx), rx)
                hdr_rx = jnp.where(dr, jnp.full_like(hdr_rx, 0xFFFFFFFF),
                                   hdr_rx)
            ok = (wire_checksum(rx) == hdr_rx[1]) & (hdr_rx[0] == seq)
            oks.append(ok | ~has_src)
            new_faces.append(_drop(rx, d))

        bad6, health = health_step(health, jnp.stack(oks), freeze)
        halos = tuple(jnp.where(bad6[i], halos_prev[i], new_faces[i])
                      for i in range(6))
        return halos, health

    @staticmethod
    def _health_pmax(health, axes_all):
        """Replicate the health carry: per-device staleness diverges at
        open-chain edges (outer faces carry no wire), so keep the mesh-wide
        worst case.  seq advances identically everywhere."""
        if not axes_all:
            return health
        seq, stale, frozen, det, held, maxst = health
        pm = lambda x: jax.lax.pmax(x, axes_all)  # noqa: E731
        return (seq, pm(stale), pm(frozen), pm(det), pm(held), pm(maxst))

    # -- runner ------------------------------------------------------------------------

    def _axes_all(self):
        return tuple(a for a in self.dim_axes if a is not None)

    def _run_chunk(self, iters: int, S: int, per_rep: bool = False,
                   check: Optional[Tuple[bool, bool]] = None):
        """The jitted chunk of every representation: ``iters`` x (S sweeps,
        one halo exchange), called as ``(state, sched, field, *rest)``.
        ``field`` is the engine's field operands (``_field_operands``);
        ``rest`` is the threshold LUT on the integer paths, then — with
        ``check = (freeze, has_codes)``, the integrity layer on — the
        health carry and, with ``has_codes``, the fault codes.  Returns the
        new state, or with ``check`` the (state, health) pair: the scan
        then threads the carry and runs the checked exchange in place of
        the plain one."""
        key = (iters, S, per_rep, check)
        if key in self._chunk_cache:
            return self._chunk_cache[key]
        spec_m, hspecs = self.spec_m, self.halo_specs
        axes_all = self._axes_all()
        R = self.replicas
        has_lut = self.precision != "f32"
        freeze, has_codes = check or (False, False)
        hlspec = tuple(P() for _ in range(6))

        def block(m, s, halos, sched, field, *rest):
            lut = rest[0] if has_lut else None
            codes = rest[-1] if has_codes else None
            # halos arrive as (R, k?, ...) plane stacks; squeeze the brick dims
            halos = tuple(_drop(x, d) for x, (d, _, _) in zip(halos, _FACES))
            carry = (m, s, halos, jnp.zeros((R,), jnp.uint32))
            if check is not None:
                carry += (rest[int(has_lut)],)

            def it(carry, b):
                if check is None:
                    m, s, halos, fl = carry
                    m, s, halos, f = self._iteration_block(m, s, halos, b,
                                                           *field, lut)
                    return (m, s, halos, fl + f.astype(jnp.uint32)), None
                m, s, halos, fl, health = carry
                m, s, f = self._sweep_block(m, s, halos, b, *field, lut)
                halos, health = self._exchange_block_checked(
                    m, halos, health, codes, freeze)
                return (m, s, halos, fl + f.astype(jnp.uint32), health), None
            carry, _ = jax.lax.scan(it, carry, sched)
            m, s, halos, local = carry[:4]
            flips = jax.lax.psum(local, axes_all) if axes_all else local
            out = (m, s, _lift_all(halos), flips)
            if check is not None:
                out += (self._health_pmax(carry[4], axes_all),)
            return out

        in_specs = (spec_m, spec_m, hspecs, P(), self._field_specs) \
            + ((P(),) if has_lut else ()) \
            + ((hlspec,) if check is not None else ()) \
            + ((P(),) if has_codes else ())
        smapped = shard_map(
            block, mesh=self.mesh, in_specs=in_specs,
            out_specs=(spec_m, spec_m, hspecs, P())
            + ((hlspec,) if check is not None else ()),
            check_vma=False,
        )

        @jax.jit
        def lattice_chunk(state, sched, field, *rest):
            m, s, halos, fl, *health = smapped(state.m, state.s, state.halos,
                                               sched, field, *rest)
            st = type(state)(
                m=m, s=s, halos=halos,
                sweep=state.sweep + sched.shape[0] * sched.shape[1],
                flips=flips_publish(state.flips, fl))
            return (st, health[0]) if check is not None else st

        self._chunk_cache[key] = lattice_chunk
        return lattice_chunk

    def set_exchange_faults(self, codes):
        """Schedule engine-boundary exchange faults: ``codes[seq]`` in
        {0 ok, 1 drop, 2 corrupt} applied to the *received* halo planes at
        global exchange ``seq`` (see ``serve.faults.FaultPlan``).  ``None``
        clears.  Requires a degrade policy — an unchecked engine would
        silently ingest the damage."""
        if codes is None:
            self._fault_codes = None
            return
        if self.degrade is None:
            raise ValueError("set_exchange_faults needs a degrade policy "
                             "(unchecked engines must not ingest damage)")
        self._fault_codes = np.asarray(codes, np.int32)

    def resync(self, state):
        """Quarantine exit: instantaneous full-boundary refresh.

        Re-derives every halo plane from the *current* spins — exactly the
        exchange a no-fault run would have performed here, so the returned
        halos are bitwise the no-fault trajectory's (verified in tests).
        Clears staleness/freeze on the health monitor."""
        st = dataclasses.replace(state, halos=self._refresh_halos(state.m))
        if self.health is not None:
            self.health.on_resync()
        return st

    def init_state(self, seed: int = 0,
                   seeds: Optional[Sequence[int]] = None) -> LatticeState:
        """Fresh replicated state.  ``seeds=[...]`` (length R) gives every
        replica its own explicit seed — the packed-batch path, where
        replica r's trajectory depends only on seeds[r]."""
        R = self.replicas
        if seeds is not None:
            seeds = [int(s) for s in seeds]
            if len(seeds) != R:
                raise ValueError(f"need exactly R={R} seeds, got {len(seeds)}")
        else:
            seeds = [seed] if R == 1 else spawn_seeds(seed, R)
        bitplane = self.precision == "bitplane"
        with span("lattice.init_state"):
            with span("lattice.init_state.draw"):
                m, s = draw_chains(seeds, self.p.dims)
            with span("lattice.init_state.put"):
                def put(x, spec=self.spec_m):
                    return jax.device_put(x, self._shard(spec))

                # each device receives only its own slab of the host buffers
                s = put(s)
                # lane r's spins and LFSR column come from seeds[r] exactly
                # as replica r of the unpacked engines would — lane r of a
                # packed run is bit-identical to int8 replica r at matched
                # schedules
                m = put(pack_lanes(jnp.asarray(m)) if bitplane else m)
                sweep = put(np.zeros((), np.int32), P())
                flips = put(np.zeros((R,), np.int32), P())
            # one synchronizing exchange so the first sweeps see real halos
            cls = BitplaneLatticeState if bitplane else LatticeState
            return cls(m=m, s=s, halos=self._refresh_halos(m), sweep=sweep,
                       flips=flips)

    def shard_state(self, st):
        # drop the cached exchange closures and the operands put on the
        # mesh: they hold the old sharding, and a restore()/re-shard must
        # not probe stale layouts
        self._exchange_only_fn = None
        self._halo_refresh = None
        self._placed, self._lut_cache = {}, {}
        put = jax.device_put
        cls = type(st)
        # bitplane words lead with the W stacked planes, unpacked spins
        # with R — either way one replicated leading axis
        return cls(
            m=put(st.m, self._shard(self.spec_m)),
            s=put(st.s, self._shard(self.spec_m)),
            halos=tuple(put(hh, self._shard(sp))
                        for hh, sp in zip(st.halos, self.halo_specs)),
            sweep=put(st.sweep, self._shard(P())),
            flips=put(st.flips, self._shard(P())))

    def _halo_refresh_fn(self):
        """The jitted halo refresh, built once per engine and sharding
        (``shard_state`` drops it)."""
        if self._halo_refresh is None:
            smapped = self._halo_program()

            @jax.jit
            def lattice_halo_refresh(m):
                return smapped(m)

            self._halo_refresh = lattice_halo_refresh
        return self._halo_refresh

    def _refresh_halos(self, m):
        """Every halo plane of spins ``m``, one exchange."""
        exchanges.count(self.link)
        with span("lattice.halo_refresh"):
            return self._halo_refresh_fn()(m)

    def run_recorded_full(self, state: LatticeState, schedule,
                          record_points: Sequence[int], sync_every: int = 1,
                          betas_R: Optional[np.ndarray] = None,
                          cursor: bool = False):
        """Shared-driver runner; returns (state, RunRecord).

        ``betas_R`` (total_sweeps, R) optionally gives each replica its own
        beta staircase (:func:`repro.core.annealing.replica_beta_arrays`);
        on the integer path each staircase becomes a fan of LUT row
        indices, so the replica axis rides the fixed-point kernels
        unchanged."""
        if betas_R is not None:
            betas_R = np.asarray(betas_R, np.float32)
            if betas_R.ndim != 2 or betas_R.shape[1] != self.replicas:
                raise ValueError(
                    f"betas_R must be (total_sweeps, R={self.replicas})")
            schedule = ArraySchedule(betas_R)
        beta_arr = np.asarray(schedule.beta_array(), np.float32)
        per_rep = beta_arr.ndim == 2

        if self.precision == "f32":
            sched = ArraySchedule(beta_arr) if per_rep else schedule
            lut_opt = ()
        else:
            table = beta_table(beta_arr)
            lut_opt = (self._lut_for(table),)
            sched = ArraySchedule(beta_row_indices(beta_arr, table))
        check = None
        replicated = self._shard(P())
        if self.degrade is not None:
            self.health.reset()
            codes = self._fault_codes
            check = (self.degrade.mode == "freeze_boundary", codes is not None)
            codes_opt = () if codes is None else \
                (jax.device_put(codes, replicated),)
        field = self._operands("field")

        def chunk(st, sched2d, iters, S):
            exchanges.count(self.link, iters)     # one per S sweeps
            sched2d = self._put(sched2d, P(), "sched")
            if check is None:
                return self._run_chunk(iters, S, per_rep)(st, sched2d, field,
                                                          *lut_opt)
            # a fresh carry is on the host; a chunk's is already in place
            health = jax.device_put(self.health.carry, replicated)
            st, carry = self._run_chunk(iters, S, per_rep, check=check)(
                st, sched2d, field, *lut_opt, health, *codes_opt)
            self.health.update(carry, exchanges=iters)
            return st

        kw = dict(
            state=state, schedule=sched, record_points=record_points,
            chunk_fn=chunk, record_fn=self.energy, sync_every=int(sync_every),
            flips_of=lambda st: st.flips,
            flips_per_sweep=self.n_sites * self.replicas)
        if cursor:
            return RecordedCursor(**kw)
        return run_recorded_driver(**kw)

    def run_recorded(self, state: LatticeState, schedule,
                     record_points: Sequence[int], sync_every: int = 1):
        """Run to each record point; returns (state, (times, energies))."""
        return self.run_recorded_full(state, schedule, record_points,
                                      sync_every=sync_every)

    # -- observables -----------------------------------------------------------------------

    def energy(self, state) -> jnp.ndarray:
        """True global energies, one per replica (halos refreshed for the
        readout).  Returns (R,) — or a scalar when replicas == 1, keeping
        the legacy contract."""
        if self._energy_fn is None:
            axes_all = self._axes_all()
            R = self.replicas
            bitplane = self.precision == "bitplane"

            def lattice_energy(m, active, h, w6):
                if bitplane:
                    # unpack lanes + word halos, then the shared per-replica
                    # energy readout — identical float ops to the unpacked
                    # engines, so equal spins give equal energies
                    halos = tuple(unpack_lanes(hw, R)
                                  for hw in self._exchange_block(m))
                    m = unpack_lanes(m, R)
                else:
                    halos = self._exchange_block(m)
                e = jax.vmap(
                    lambda mr, hr: brick_energy_op(mr, active, h, w6, hr,
                                                   bx=self.energy_bx,
                                                   impl=self.impl),
                    in_axes=(0, 0))(m, halos)
                return jax.lax.psum(e, axes_all) if axes_all else e

            self._energy_fn = jax.jit(shard_map(
                lattice_energy, mesh=self.mesh,
                in_specs=(self.spec_m, self.spec_flat, self.spec_flat,
                          tuple(self.spec_flat for _ in range(6))),
                out_specs=P(), check_vma=False))
        e = self._energy_fn(state.m, *self._operands("energy"))
        return e[0] if self.replicas == 1 else e

    def global_spins(self, state) -> jnp.ndarray:
        """(R, L^3) active-site spins in ea3d node order ((L,L,L) row-major);
        squeezed to (L^3,) when replicas == 1."""
        L = self.p.L
        if self.precision == "bitplane":
            spins = unpack_lanes(state.m[:, :L, :L, :L], self.replicas) \
                .reshape(self.replicas, L ** 3)
        else:
            spins = state.m[:, :L, :L, :L].reshape(self.replicas, L ** 3)
        return spins[0] if self.replicas == 1 else spins

    # -- dry-run hook -----------------------------------------------------------------------

    def _chunk_args(self, iters: int, S: int, lut_rows: int,
                    degrade: bool = False, freeze: bool = False,
                    has_codes: bool = False):
        """(runner, abstract args) for one sampling chunk — shared by the
        lowering dry-run and the static contract auditor's tracer.  With
        ``degrade`` the runner runs checked (per-face health carry,
        optional fault-code operand)."""
        def sds(shape, dtype, spec=P()):
            return jax.ShapeDtypeStruct(tuple(shape), dtype,
                                        sharding=self._shard(spec))
        X, Y, Z = self.p.dims
        R = self.replicas
        bitplane = self.precision == "bitplane"
        dt = jnp.uint32 if bitplane else jnp.int8
        cls = BitplaneLatticeState if bitplane else LatticeState
        st = cls(
            m=sds((self.words if bitplane else R, X, Y, Z), dt, self.spec_m),
            s=sds((R, X, Y, Z), jnp.uint32, self.spec_m),
            halos=tuple(sds(sh, dt, sp) for sh, sp in
                        zip(self._halo_shapes(), self.halo_specs)),
            sweep=sds((), jnp.int32), flips=sds((R,), jnp.int32))
        field = jax.tree.map(lambda x, sp: sds(np.shape(x), x.dtype, sp),
                             self._field, self._field_specs)
        if self.precision == "f32":
            args = (st, sds((iters, S), jnp.float32), field)
        else:
            args = (st, sds((iters, S), jnp.int32), field,
                    sds((lut_rows, 2 * self.f_max + 1), jnp.uint32))
        if not degrade:
            return self._run_chunk(iters, S), args
        args += (tuple(sds(np.shape(h), np.asarray(h).dtype)
                       for h in health_init(6)),)
        if has_codes:
            args += (sds((8,), jnp.uint32),)
        return self._run_chunk(iters, S, check=(freeze, has_codes)), args

    def lower_chunk(self, iters: int = 2, S: int = 4, lut_rows: int = 10):
        """Lower (not run) one sampling chunk — used by the launch dry-run."""
        run, args = self._chunk_args(iters, S, lut_rows)
        return run.lower(*args)

    def trace_chunk(self, iters: int = 2, S: int = 4, lut_rows: int = 10,
                    degrade: bool = False, freeze: bool = False,
                    has_codes: bool = False):
        """Trace (not lower) one sampling chunk and return the jitted
        runner's Traced object, whose ``.jaxpr`` the static contract
        auditor walks.  Unlike :meth:`lower_chunk` this works over an
        ``AbstractMesh`` — halo dtype/count contracts are auditable on a
        single-device host, no multi-device subprocess needed."""
        run, args = self._chunk_args(iters, S, lut_rows, degrade=degrade,
                                     freeze=freeze, has_codes=has_codes)
        return run.trace(*args)
