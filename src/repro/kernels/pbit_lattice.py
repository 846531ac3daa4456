"""Fused p-bit color-update Pallas kernels for 3D lattice bricks.

This is the compute hot-spot of the paper's machine: for every site of one
color group, gather the six neighbor spins, accumulate the local field from
on-chip weights, draw an LFSR random number, threshold a (quantized) tanh,
and write the new spin — all in one pass, exactly what one FPGA clock does
for a color group.

TPU adaptation (DESIGN.md): the FPGA's hardwired neighbor fabric becomes
shifted-plane reads of a VMEM-resident brick; the per-p-bit LFSR column
becomes a vectorized xorshift32 lane; s{4}{1} fixed point becomes a
round+clip on the activation.  The ``*_int`` kernel variants go all the way
to the hardware arithmetic: int8 on-chip couplings, int32 field
accumulation, and the tanh + float compare replaced by one compare of the
raw LFSR draw against a precomputed threshold row (DESIGN.md "Fixed-point
pipeline and threshold LUTs") — zero floating-point ops in the inner loop.

Mosaic layout (the shape every kernel here is written for):

  * a brick is (Bx, By, Bz) with z on the 128 lanes and y on the sublanes;
    every kernel walks it one x-plane at a time (``lax.fori_loop``), so the
    vector work of one step is a (By, Bz) plane whatever the brick size;
  * spins stay int8 in memory and widen to 32-bit per plane — Mosaic
    shifts and concatenates 32-bit planes, not packed int8 ones;
  * per-sweep scalars (betas, threshold rows gathered in XLA before the
    call) and the flip/energy scalars live in SMEM;
  * halo faces arrive as 32-bit planes, the y and z faces as (Bx, 1, n)
    rows, so an x-tile never splits the two tiled (minor) dimensions and
    any divisor of Bx is a legal tile.

The per-phase kernels tile x by BlockSpec (grid over x-slabs, the planes
just outside the slab bound as one-plane blocks); the fused kernels hold
the whole brick in VMEM and run every color phase of ``S`` sweeps in one
launch.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.pbit import FixedPoint, LUT_SELECT_MAX_WIDTH, lfsr_next

__all__ = ["pbit_brick_update", "pbit_brick_sweep",
           "pbit_brick_update_int", "pbit_brick_sweep_int",
           "kernel_halos", "plane_neighbors", "row_to_col", "eye_mask",
           "sweep_planes", "rank_accept", "lfsr_draw"]

_f32, _i32, _u32 = jnp.float32, jnp.int32, jnp.uint32
SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)
VMEM = pl.BlockSpec(memory_space=pltpu.VMEM)


# ---------------------------------------------------------------------------
# plane helpers shared by every lattice kernel
# ---------------------------------------------------------------------------

def kernel_halos(halos, dtype):
    """Six halo faces in kernel layout, in ``dtype``: x faces (By, Bz),
    y faces (Bx, 1, Bz), z faces (Bx, 1, By) — one row per x-plane."""
    xlo, xhi, ylo, yhi, zlo, zhi = (jnp.asarray(a).astype(dtype)
                                    for a in halos)
    return (xlo, xhi, ylo[:, None, :], yhi[:, None, :],
            zlo[:, None, :], zhi[:, None, :])


def row_to_col(row, eye):
    """(1, n) row -> (n, 1) column.  Mosaic has no narrow transpose, so
    mask the broadcast row with the identity and sum across lanes (one
    nonzero term per row: exact for any 32-bit pattern)."""
    if row.dtype == _u32:
        col = row_to_col(jax.lax.bitcast_convert_type(row, _i32), eye)
        return jax.lax.bitcast_convert_type(col, _u32)
    full = jnp.broadcast_to(row, eye.shape)
    return jnp.sum(jnp.where(eye, full, jnp.zeros_like(full)), axis=1,
                   keepdims=True)


def plane_neighbors(prev, cur, nxt, ylo, yhi, zlo, zhi, eye):
    """The six neighbor planes (xm, xp, ym, yp, zm, zp) of x-plane ``cur``
    (By, Bz): ``prev``/``nxt`` are the x-1/x+1 planes, ``ylo``/``yhi``
    (1, Bz) and ``zlo``/``zhi`` (1, By) this plane's face rows."""
    ym = jnp.concatenate([ylo, cur[:-1]], axis=0)
    yp = jnp.concatenate([cur[1:], yhi], axis=0)
    zm = jnp.concatenate([row_to_col(zlo, eye), cur[:, :-1]], axis=1)
    zp = jnp.concatenate([cur[:, 1:], row_to_col(zhi, eye)], axis=1)
    return prev, nxt, ym, yp, zm, zp


def eye_mask(n: int):
    """(n, n) boolean identity for :func:`row_to_col`."""
    return (jax.lax.broadcasted_iota(_i32, (n, n), 0)
            == jax.lax.broadcasted_iota(_i32, (n, n), 1))


def sweep_planes(load, n, first_prev, last_next, step, acc):
    """Walk x-planes 0..n-1: ``step(x, prev, cur, nxt, acc) -> acc``.

    ``load(x)`` reads plane x; ``first_prev`` / ``last_next`` stand in for
    the planes before 0 and after n-1.  The old plane x rides the loop
    carry into step x+1 as its ``prev``, so a step may overwrite plane x
    in place and every step still sees the pre-phase spins — the exact
    semantics of the whole-array update for ANY mask, not only a proper
    coloring."""
    def body(x, carry):
        prev, cur, acc = carry
        nxt = jnp.where(x == n - 1, last_next, load(jnp.minimum(x + 1,
                                                                n - 1)))
        acc = step(x, prev, cur, nxt, acc)
        return cur, nxt, acc
    return jax.lax.fori_loop(0, n, body, (first_prev, load(0), acc))[2]


def rank_accept(u, idx, thr_ref, off, lw: int):
    """``u >= thr[idx]`` for one threshold row at ``thr_ref[off:off+lw]``.

    Rank-count form (:func:`repro.core.pbit.lut_accept`): rows are
    nonincreasing in the field index, so ``u`` passes exactly the top
    ``count = #{k : u >= thr[k]}`` entries and the test is
    ``idx + count >= lw`` — scalar SMEM reads and vector compares, no
    gather.  Exact for every row width (narrow rows unroll)."""
    def body(k, cnt):
        return cnt + (u >= thr_ref[off + k]).astype(_i32)
    count = jax.lax.fori_loop(0, lw, body, jnp.zeros(u.shape, _i32),
                              unroll=lw <= LUT_SELECT_MAX_WIDTH)
    return idx + count >= lw


def lfsr_draw(s):
    """Advance an LFSR plane; returns (s, u) with u the 24-bit draw as an
    int32 (exact: u < 2^24)."""
    s = lfsr_next(s)
    return s, jax.lax.bitcast_convert_type(s >> _u32(8), _i32)


def _f32_accept(beta, field, u, fmt: Optional[FixedPoint]):
    r = u.astype(_f32) * _f32(2.0 / 16777216.0) - _f32(1.0)
    act = beta * field
    if fmt is not None:
        act = jnp.clip(jnp.round(act / fmt.step) * fmt.step, fmt.lo, fmt.hi)
    return jnp.tanh(act) + r >= 0


def _load_i32(ref):
    return lambda x: ref[x].astype(_i32)


def _face_rows(ylo_ref, yhi_ref, zlo_ref, zhi_ref, x):
    return ylo_ref[x], yhi_ref[x], zlo_ref[x], zhi_ref[x]


def _f32_field(h, ws, nbs):
    """h + sum_d w_d * m_d in f32, in the oracle's association order."""
    field = h
    for w, nb in zip(ws, nbs):
        field = field + w * nb.astype(_f32)
    return field


def _int_field(h, ws, nbs):
    field = h.astype(_i32)
    for w, nb in zip(ws, nbs):
        field = field + w.astype(_i32) * nb
    return field


# ---------------------------------------------------------------------------
# per-phase kernels (x-tiled by the grid)
# ---------------------------------------------------------------------------

def _phase_kernel(par_ref, scal_ref, h_ref, wxm_ref, wxp_ref, wym_ref,
                  wyp_ref, wzm_ref, wzp_ref, m_l_ref, m_ref, m_r_ref,
                  xlo_ref, xhi_ref, ylo_ref, yhi_ref, zlo_ref, zhi_ref,
                  s_ref, m_out_ref, s_out_ref,
                  *, nblocks: int, fmt: Optional[FixedPoint],
                  lut_width: Optional[int]):
    """One color phase of one x-slab; f32 (``lut_width`` None: scal_ref
    holds beta) or integer (scal_ref holds the LUT row)."""
    i = pl.program_id(0)
    bx = m_ref.shape[0]
    eye = eye_mask(m_ref.shape[1])
    w_refs = (wxm_ref, wxp_ref, wym_ref, wyp_ref, wzm_ref, wzp_ref)
    first_prev = jnp.where(i == 0, xlo_ref[...], m_l_ref[0].astype(_i32))
    last_next = jnp.where(i == nblocks - 1, xhi_ref[...],
                          m_r_ref[0].astype(_i32))

    def step(x, prev, cur, nxt, acc):
        nbs = plane_neighbors(prev, cur, nxt,
                              *_face_rows(ylo_ref, yhi_ref, zlo_ref,
                                          zhi_ref, x), eye)
        ws = [r[x] for r in w_refs]
        s, u = lfsr_draw(s_ref[x])
        if lut_width is None:
            acc_ = _f32_accept(scal_ref[0], _f32_field(h_ref[x], ws, nbs),
                               u, fmt)
        else:
            field = _int_field(h_ref[x], ws, nbs)
            idx = jnp.clip(field + (lut_width - 1) // 2, 0, lut_width - 1)
            acc_ = rank_accept(u, idx, scal_ref, 0, lut_width)
        upd = jnp.where(acc_, 1, -1)
        new = jnp.where(par_ref[x].astype(_i32) != 0, upd, cur)
        m_out_ref[x] = new.astype(jnp.int8)
        s_out_ref[x] = s
        return acc

    sweep_planes(_load_i32(m_ref), bx, first_prev, last_next, step,
                 jnp.zeros((), _i32))


def _phase_call(m, s, scal, parity_mask, h, w6, halos, *, bx, fmt,
                lut_width, interpret):
    Bx, By, Bz = m.shape
    bx = Bx if bx is None else bx
    if Bx % bx != 0:
        raise ValueError(f"Bx={Bx} not divisible by tile bx={bx}")
    nb = Bx // bx
    xlo, xhi, ylo, yhi, zlo, zhi = kernel_halos(halos, _i32)

    cur = pl.BlockSpec((bx, By, Bz), lambda i: (i, 0, 0))
    prv = pl.BlockSpec((1, By, Bz), lambda i: (jnp.maximum(i * bx - 1, 0),
                                               0, 0))
    nxt = pl.BlockSpec((1, By, Bz), lambda i: (jnp.minimum((i + 1) * bx,
                                                           Bx - 1), 0, 0))
    face_x = pl.BlockSpec((By, Bz), lambda i: (0, 0))
    row = lambda n: pl.BlockSpec((bx, 1, n), lambda i: (i, 0, 0))  # noqa: E731

    return pl.pallas_call(
        functools.partial(_phase_kernel, nblocks=nb, fmt=fmt,
                          lut_width=lut_width),
        grid=(nb,),
        in_specs=[
            cur,                      # parity_mask
            SMEM,                     # beta or threshold row
            cur, cur, cur, cur, cur, cur, cur,   # h + 6 weights
            prv, cur, nxt,            # plane before, slab, plane after
            face_x, face_x,           # xlo, xhi
            row(Bz), row(Bz),         # ylo, yhi rows
            row(By), row(By),         # zlo, zhi rows
            cur,                      # lfsr state
        ],
        out_specs=[cur, cur],
        out_shape=[
            jax.ShapeDtypeStruct((Bx, By, Bz), jnp.int8),
            jax.ShapeDtypeStruct((Bx, By, Bz), _u32),
        ],
        interpret=interpret,
    )(parity_mask, scal, h, *w6, m, m, m, xlo, xhi, ylo, yhi, zlo, zhi, s)


@functools.partial(jax.jit, static_argnames=("fmt", "bx", "interpret"))
def pbit_brick_update(m, s, beta, parity_mask, h, w6, halos,
                      fmt: Optional[FixedPoint] = None,
                      bx: Optional[int] = None,
                      interpret: bool = False):
    """One fused color-phase update of a lattice brick.

    Args:
      m: (Bx, By, Bz) int8 spins.
      s: (Bx, By, Bz) uint32 LFSR states.
      beta: scalar f32 inverse temperature.
      parity_mask: (Bx, By, Bz) int8 — 1 where this color updates (also folds
        the active-site mask for padded lattices).
      h: (Bx, By, Bz) f32 biases.
      w6: tuple (wxm, wxp, wym, wyp, wzm, wzp), each (Bx, By, Bz) f32 —
        coupling to the -x/+x/-y/+y/-z/+z neighbor (0 on open boundaries);
        cross-device couplings appear on both sides (shadow weights).
      halos: tuple (xlo (By,Bz), xhi (By,Bz), ylo (Bx,Bz), yhi (Bx,Bz),
        zlo (Bx,By), zhi (Bx,By)) int8 neighbor boundary planes.
      fmt: optional fixed-point format for the activation (s{4}{1} etc).
      bx: x tile size (defaults to whole brick); any divisor of Bx.
      interpret: run the Pallas interpreter (CPU validation).

    Returns: (m_new, s_new).
    """
    beta_arr = jnp.asarray(beta, _f32).reshape(1)
    return _phase_call(m, s, beta_arr, parity_mask, h, w6, halos, bx=bx,
                       fmt=fmt, lut_width=None, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bx", "interpret"))
def pbit_brick_update_int(m, s, row, parity_mask, h_q, w6_q, halos, lut,
                          bx: Optional[int] = None,
                          interpret: bool = False):
    """One fixed-point color-phase update of a lattice brick (x-tiled).

    Args match :func:`pbit_brick_update` except ``row`` (scalar int32 LUT
    row index replacing beta), int8 ``h_q``/``w6_q``, and the uint32
    threshold ``lut``.  Bit-exact against
    :func:`repro.kernels.ref.pbit_brick_update_int_ref`.
    """
    lw = int(lut.shape[1])
    thr = jax.lax.dynamic_index_in_dim(lut, jnp.asarray(row, _i32), axis=0,
                                       keepdims=False).astype(_i32)
    return _phase_call(m, s, thr, parity_mask, h_q, w6_q, halos, bx=bx,
                       fmt=None, lut_width=lw, interpret=interpret)


# ---------------------------------------------------------------------------
# fused multi-phase sweep kernels (whole brick in VMEM)
# ---------------------------------------------------------------------------
#
# One pallas_call runs the ENTIRE color cycle — and up to ``sweeps_per_call``
# sweeps between halo exchanges — against halos held fixed: the analogue of
# the FPGA retiring one color group per clock with no host round-trips.  The
# whole brick is a single block: later phases must read the spins earlier
# phases just wrote, which grid steps cannot do.  Spins and LFSR states are
# updated in place in the output buffers, one x-plane at a time.
# ``fused_working_set_bytes`` in core.lattice_dsim models the VMEM this
# takes, tile padding included.

def _sweep_kernel(sched_ref, masks_ref, h_ref, wxm_ref, wxp_ref, wym_ref,
                  wyp_ref, wzm_ref, wzp_ref, m_ref,
                  xlo_ref, xhi_ref, ylo_ref, yhi_ref, zlo_ref, zhi_ref,
                  s_ref, m_out_ref, s_out_ref, flips_ref,
                  *, fmt: Optional[FixedPoint], n_colors: int, n_sweeps: int,
                  lut_width: Optional[int]):
    Bx, By, Bz = m_ref.shape
    eye = eye_mask(By)
    w_refs = (wxm_ref, wxp_ref, wym_ref, wyp_ref, wzm_ref, wzp_ref)
    m_out_ref[...] = m_ref[...]
    s_out_ref[...] = s_ref[...]
    xlo, xhi = xlo_ref[...], xhi_ref[...]

    def sweep(t, flips):
        for c in range(n_colors):
            def step(x, prev, cur, nxt, fl, c=c):
                nbs = plane_neighbors(prev, cur, nxt,
                                      *_face_rows(ylo_ref, yhi_ref, zlo_ref,
                                                  zhi_ref, x), eye)
                ws = [r[x] for r in w_refs]
                s, u = lfsr_draw(s_out_ref[x])
                if lut_width is None:
                    acc = _f32_accept(sched_ref[t],
                                      _f32_field(h_ref[x], ws, nbs), u, fmt)
                else:
                    field = _int_field(h_ref[x], ws, nbs)
                    idx = jnp.clip(field + (lut_width - 1) // 2, 0,
                                   lut_width - 1)
                    acc = rank_accept(u, idx, sched_ref, t * lut_width,
                                      lut_width)
                upd = jnp.where(acc, 1, -1)
                new = jnp.where(masks_ref[c, x].astype(_i32) != 0, upd, cur)
                m_out_ref[x] = new.astype(jnp.int8)
                s_out_ref[x] = s
                return fl + (new != cur).astype(_i32)
            flips = sweep_planes(_load_i32(m_out_ref), Bx, xlo, xhi, step,
                                 flips)
        return flips

    flips = jax.lax.fori_loop(0, n_sweeps, sweep, jnp.zeros((By, Bz), _i32))
    flips_ref[0, 0] = jnp.sum(flips)


def _sweep_call(m, s, sched, masks, h, w6, halos, *, fmt, lut_width,
                interpret):
    Bx, By, Bz = m.shape
    n_colors = int(masks.shape[0])
    S = int(sched.shape[0]) // (1 if lut_width is None else lut_width)
    m_new, s_new, flips = pl.pallas_call(
        functools.partial(_sweep_kernel, fmt=fmt, n_colors=n_colors,
                          n_sweeps=S, lut_width=lut_width),
        in_specs=[SMEM] + [VMEM] * 16,
        out_specs=[VMEM, VMEM, SMEM],
        out_shape=[
            jax.ShapeDtypeStruct((Bx, By, Bz), jnp.int8),
            jax.ShapeDtypeStruct((Bx, By, Bz), _u32),
            jax.ShapeDtypeStruct((1, 1), _i32),
        ],
        interpret=interpret,
    )(sched, masks, h, *w6, m, *kernel_halos(halos, _i32), s)
    return m_new, s_new, flips[0, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def pbit_brick_sweep_int(m, s, rows, masks, h_q, w6_q, halos, lut,
                         interpret: bool = False):
    """``len(rows)`` fused fixed-point sweeps of one brick.

    Args match :func:`pbit_brick_sweep` except:
      rows: (S,) int32 — LUT row index (= beta staircase entry) per sweep.
      h_q / w6_q: int8 quantized biases and couplings
        (:func:`repro.core.pbit.quantize_couplings`).
      lut: (n_rows, 2*f_max+1) uint32 acceptance thresholds
        (:func:`repro.core.pbit.threshold_lut`); each sweep's row is
        gathered here, in XLA, and handed to the kernel through SMEM.

    Returns (m_new, s_new, flips).  Bit-exact against
    :func:`repro.kernels.ref.pbit_brick_sweep_int_ref`.
    """
    lw = int(lut.shape[1])
    thr = lut[jnp.asarray(rows, _i32).reshape(-1)].astype(_i32).reshape(-1)
    return _sweep_call(m, s, thr, masks, h_q, w6_q, halos, fmt=None,
                       lut_width=lw, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("fmt", "interpret"))
def pbit_brick_sweep(m, s, betas, masks, h, w6, halos,
                     fmt: Optional[FixedPoint] = None,
                     interpret: bool = False):
    """``len(betas)`` fused full sweeps (all color phases) of one brick.

    Args match :func:`pbit_brick_update` except:
      betas: (S,) f32 — one inverse temperature per sweep; the whole batch
        runs between two halo exchanges, so halos stay fixed throughout.
      masks: (n_colors, Bx, By, Bz) int8 color parity masks, updated in
        index order each sweep.

    Returns (m_new, s_new, flips) — flips is the int32 number of accepted
    spin changes over all S * n_colors phases, counted in-kernel.

    Bitwise-identical to S * n_colors chained :func:`pbit_brick_update`
    calls (the per-phase reference path, kept for exactly that comparison).
    """
    betas = jnp.asarray(betas, _f32).reshape(-1)
    return _sweep_call(m, s, betas, masks, h, w6, halos, fmt=fmt,
                       lut_width=None, interpret=interpret)
