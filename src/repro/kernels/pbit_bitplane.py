"""Multi-spin-coded Pallas sweep kernel: 32 replica lanes per uint32 word.

The paper's machine keeps every spin as literally one bit; this kernel does
the same in software — spins arrive as bit-planes (bit r of a word is
replica lane r's spin), so the neighbor gather, sign application, and field
count advance all 32 lanes with word-wide bitwise ops:

  * the six neighbor word-planes are the usual shifted-plane reads of the
    VMEM-resident brick (word halo planes at the faces);
  * the +-J coupling collapses to one XOR with a per-site *sign plane*
    (all-ones words where w < 0) and an AND with the nonzero mask;
  * the +1-contribution count c (the only lane-varying part of the field)
    is a bit-sliced carry-save adder tree — two 3:2 full adders plus a
    combine, 3 bit-slices for c in [0, 6]; 4 slices bound the 13-value
    +-J field once the lane-independent ``base = h_q - nnz + f_max`` plane
    folds in the rest.

Only the RNG and the threshold accept are per lane (an unrolled lane loop):
each lane owns its LFSR column — packed chains share NO randomness — and
acceptance is PR 2's rank-count compare against the threshold-LUT row of
that lane's staircase entry.  Everything is integer; lane r is bit-exact
against replica r of the int8 pipeline.

This kernel is the ONE-WORD primitive of the multi-word lane fabric:
replica counts past 32 stack extra word planes, and the word loop lives in
``kernels.ops.pbit_bitplane_sweep_op`` — word planes are independent
replica sets, so each plane is its own launch at the same traced shapes,
and one compiled executable serves every replica count in a word bucket.

VMEM working set for a (Bx, By, Bz) brick of R lanes:
  in/out spin words (u32)                 8 B/site
  in/out LFSR columns (u32, R lanes)      8R B/site
  6 sign + 6 nonzero planes (u32)         48 B/site
  base (i32) + n_c color masks (u32)      (4 + 4 n_c) B/site
~= (60 + 4 n_c + 8 R) B/site before tile padding — ~328 B/site at R=32,
n_c=3, i.e. ~10.3 B/site/replica-lane (vs the int8 path's 17 + n_c) and
ONE launch where the int8 path needs R.  The brick is walked one x-plane
at a time, like the int8 kernels (``kernels.pbit_lattice``); each sweep's
per-lane threshold rows are gathered in XLA and read from SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pbit_lattice import (SMEM, VMEM, eye_mask, kernel_halos, lfsr_draw,
                           plane_neighbors, rank_accept, sweep_planes)

__all__ = ["pbit_bitplane_sweep"]


def _bitplane_kernel(thr_ref, masks_ref,
                     sxm_ref, sxp_ref, sym_ref, syp_ref, szm_ref, szp_ref,
                     nxm_ref, nxp_ref, nym_ref, nyp_ref, nzm_ref, nzp_ref,
                     base_ref, m_ref,
                     xlo_ref, xhi_ref, ylo_ref, yhi_ref, zlo_ref, zhi_ref,
                     s_ref,
                     m_out_ref, s_out_ref, flips_ref,
                     *, n_colors: int, n_sweeps: int, n_lanes: int,
                     lut_width: int):
    i32 = jnp.int32
    u32 = jnp.uint32
    one = u32(1)
    Bx, By, Bz = m_ref.shape
    eye = eye_mask(By)
    sign_refs = (sxm_ref, sxp_ref, sym_ref, syp_ref, szm_ref, szp_ref)
    nz_refs = (nxm_ref, nxp_ref, nym_ref, nyp_ref, nzm_ref, nzp_ref)
    m_out_ref[...] = m_ref[...]
    s_out_ref[...] = s_ref[...]
    for r in range(n_lanes):
        flips_ref[r] = i32(0)
    xlo, xhi = xlo_ref[...], xhi_ref[...]

    def sweep(t, carry):
        for c in range(n_colors):
            def step(x, prev, cur, nxt, acc, c=c):
                nbs = plane_neighbors(prev, cur, nxt, ylo_ref[x], yhi_ref[x],
                                      zlo_ref[x], zhi_ref[x], eye)
                tb = [(nb ^ sg[x]) & nz[x] for nb, sg, nz in
                      zip(nbs, sign_refs, nz_refs)]
                # carry-save adder tree: c = b0 + 2 b1 + 4 b2, all 32 lanes
                s1 = tb[0] ^ tb[1] ^ tb[2]
                c1 = (tb[0] & tb[1]) | (tb[2] & (tb[0] ^ tb[1]))
                s2 = tb[3] ^ tb[4] ^ tb[5]
                c2 = (tb[3] & tb[4]) | (tb[5] & (tb[3] ^ tb[4]))
                b0 = s1 ^ s2
                k = s1 & s2
                b1 = c1 ^ c2 ^ k
                b2 = (c1 & c2) | (k & (c1 ^ c2))
                base = base_ref[x]

                upd = jnp.zeros(cur.shape, u32)
                for r in range(n_lanes):          # per-lane RNG + accept
                    s, u = lfsr_draw(s_out_ref[r, x])
                    s_out_ref[r, x] = s
                    ur = u32(r)
                    cnt = (((b0 >> ur) & one).astype(i32)
                           + 2 * ((b1 >> ur) & one).astype(i32)
                           + 4 * ((b2 >> ur) & one).astype(i32))
                    idx = jnp.clip(base + 2 * cnt, 0, lut_width - 1)
                    accept = rank_accept(u, idx, thr_ref,
                                         (t * n_lanes + r) * lut_width,
                                         lut_width)
                    upd = upd | (accept.astype(u32) << ur)

                mask = masks_ref[c, x]
                new = (cur & ~mask) | (upd & mask)
                m_out_ref[x] = new
                diff = cur ^ new
                for r in range(n_lanes):
                    flips_ref[r] += jnp.sum(((diff >> u32(r)) & one)
                                            .astype(i32))
                return acc
            sweep_planes(lambda x: m_out_ref[x], Bx, xlo, xhi, step, i32(0))
        return carry

    jax.lax.fori_loop(0, n_sweeps, sweep, i32(0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def pbit_bitplane_sweep(mw, s, rows, masks_w, signs6, nz6, base, halos_w,
                        lut, interpret: bool = False):
    """``rows.shape[0]`` fused multi-spin-coded sweeps of one brick.

    Args match :func:`repro.kernels.ref.pbit_bitplane_sweep_ref` (rows must
    already be (S, R)).  Returns (mw_new, s_new, flips) with flips (R,)
    int32 per-lane counts.  Bit-exact against the oracle.
    """
    Bx, By, Bz = mw.shape
    R = int(s.shape[0])
    S = int(rows.shape[0])
    n_colors = int(masks_w.shape[0])
    lw = int(lut.shape[1])
    rows = jnp.asarray(rows, jnp.int32).reshape(S, R)
    thr = lut[rows].astype(jnp.int32).reshape(-1)     # (S*R*lw,) for SMEM

    return pl.pallas_call(
        functools.partial(_bitplane_kernel, n_colors=n_colors, n_sweeps=S,
                          n_lanes=R, lut_width=lw),
        in_specs=[SMEM] + [VMEM] * 22,
        out_specs=[VMEM, VMEM, SMEM],
        out_shape=[
            jax.ShapeDtypeStruct((Bx, By, Bz), jnp.uint32),
            jax.ShapeDtypeStruct((R, Bx, By, Bz), jnp.uint32),
            jax.ShapeDtypeStruct((R,), jnp.int32),
        ],
        interpret=interpret,
    )(thr, masks_w, *signs6, *nz6, base, mw,
      *kernel_halos(halos_w, jnp.uint32), s)
