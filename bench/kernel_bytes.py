"""Bytes each lattice kernel launch must move, from its shapes.

Counted as operand plus result bytes of one ``pallas_call``, each array
once, in the dtypes the program passes (int8 spins, couplings and masks on
the integer path, uint32 LFSR states, 32-bit halo faces).  This is the
least HBM traffic of the launch: a
kernel whose device time equals ``bytes / HBM peak`` sits on the memory
roofline.  The shapes are the brick one launch covers; the per-phase
kernel covers the whole brick in one launch (its x-tiles are grid steps).
"""

from __future__ import annotations

from typing import Sequence

LUT_WIDTH = 13       # threshold row of a +-J cubic lattice: f in [-6, 6]


def _halo_bytes(brick: Sequence[int]) -> int:
    """Six 32-bit halo faces: two (By, Bz) x faces, two (Bx, Bz) y rows,
    two (Bx, By) z rows."""
    bx, by, bz = brick
    return 4 * 2 * (by * bz + bx * bz + bx * by)


def phase_int8(brick: Sequence[int]) -> int:
    """``pbit_brick_update_int``: one color phase of one replica's brick.
    Reads the color mask, h, six couplings and spins (int8), the LFSR
    states (uint32), the halos and one threshold row; writes spins and
    LFSR states."""
    bx, by, bz = brick
    n = bx * by * bz
    reads = 9 * n + 4 * n + _halo_bytes(brick) + 4 * LUT_WIDTH
    writes = n + 4 * n
    return reads + writes


def sweep_int8(brick: Sequence[int], sweeps: int, n_colors: int = 2) -> int:
    """``pbit_brick_sweep_int``: ``sweeps`` full color cycles of one
    replica's brick in one launch.  Reads every color mask, h, six
    couplings and spins (int8), the LFSR states, the halos and one
    threshold row per sweep; writes spins, LFSR states and a flip count."""
    bx, by, bz = brick
    n = bx * by * bz
    reads = (n_colors + 7) * n + n + 4 * n + _halo_bytes(brick) \
        + 4 * LUT_WIDTH * sweeps
    writes = n + 4 * n + 4
    return reads + writes

