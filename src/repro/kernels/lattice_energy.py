"""Blocked Ising-energy reduction Pallas kernel for lattice bricks.

E_brick = -1/2 sum_i m_i (field_i - h_i) - sum_i h_i m_i, with the field
assembled from the same shifted-plane neighbor reads as the update kernel.
Shadow (cross-device) couplings are halved correctly because both sides hold
a copy: summing -1/2 m_i J_ij m_j over both devices yields each cut edge
exactly once after the global psum.

Grid steps (x-slabs) accumulate into one (1, 1) SMEM scalar — the standard
Pallas reduction idiom (output index map constant, init at step 0).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pbit_lattice import (SMEM, eye_mask, kernel_halos, plane_neighbors,
                           sweep_planes)

__all__ = ["brick_energy"]


def _kernel(active_ref, h_ref, wxm_ref, wxp_ref, wym_ref, wyp_ref,
            wzm_ref, wzp_ref, m_l_ref, m_ref, m_r_ref,
            xlo_ref, xhi_ref, ylo_ref, yhi_ref, zlo_ref, zhi_ref,
            out_ref, *, nblocks: int):
    i = pl.program_id(0)
    f32, i32 = jnp.float32, jnp.int32
    bx, By, Bz = m_ref.shape
    eye = eye_mask(By)
    w_refs = (wxm_ref, wxp_ref, wym_ref, wyp_ref, wzm_ref, wzp_ref)
    first_prev = jnp.where(i == 0, xlo_ref[...], m_l_ref[0].astype(i32))
    last_next = jnp.where(i == nblocks - 1, xhi_ref[...],
                          m_r_ref[0].astype(i32))

    def step(x, prev, cur, nxt, acc):
        nbs = plane_neighbors(prev, cur, nxt, ylo_ref[x], yhi_ref[x],
                              zlo_ref[x], zhi_ref[x], eye)
        pair = w_refs[0][x] * nbs[0].astype(f32)
        for w, nb in zip(w_refs[1:], nbs[1:]):
            pair = pair + w[x] * nb.astype(f32)
        mc = cur.astype(f32)
        act = active_ref[x].astype(f32)
        return acc + (-0.5 * (mc * pair) - h_ref[x] * mc) * act

    e = sweep_planes(lambda x: m_ref[x].astype(i32), bx, first_prev,
                     last_next, step, jnp.zeros((By, Bz), f32))

    @pl.when(i == 0)
    def _init():
        out_ref[0, 0] = jnp.float32(0)

    out_ref[0, 0] += jnp.sum(e)


@functools.partial(jax.jit, static_argnames=("bx", "interpret"))
def brick_energy(m, active, h, w6, halos, bx: Optional[int] = None,
                 interpret: bool = False):
    """Brick-local Ising energy (psum across bricks gives the global E).

    ``bx`` tiles x (any divisor of Bx; default the whole brick)."""
    Bx, By, Bz = m.shape
    bx = Bx if bx is None else bx
    if Bx % bx != 0:
        raise ValueError(f"Bx={Bx} not divisible by tile bx={bx}")
    nb = Bx // bx

    cur = pl.BlockSpec((bx, By, Bz), lambda i: (i, 0, 0))
    prv = pl.BlockSpec((1, By, Bz), lambda i: (jnp.maximum(i * bx - 1, 0),
                                               0, 0))
    nxt = pl.BlockSpec((1, By, Bz), lambda i: (jnp.minimum((i + 1) * bx,
                                                           Bx - 1), 0, 0))
    face_x = pl.BlockSpec((By, Bz), lambda i: (0, 0))
    row = lambda n: pl.BlockSpec((bx, 1, n), lambda i: (i, 0, 0))  # noqa: E731

    out = pl.pallas_call(
        functools.partial(_kernel, nblocks=nb),
        grid=(nb,),
        in_specs=[
            cur, cur, cur, cur, cur, cur, cur, cur,
            prv, cur, nxt,
            face_x, face_x, row(Bz), row(Bz), row(By), row(By),
        ],
        out_specs=SMEM,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        interpret=interpret,
    )(active, h, *w6, m, m, m, *kernel_halos(halos, jnp.int32))
    return out[0, 0]
