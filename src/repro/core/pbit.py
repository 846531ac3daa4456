"""The p-bit update rule and its numeric-format options.

Paper Sec. II:  m_i = sgn[tanh(I_i) + r],  r ~ U(-1, 1),
I_i = beta * (h_i + sum_j J_ij m_j).

Numeric formats (paper Methods): the GPU baseline uses floating point +
Philox; the hardware uses fixed point s{a}{b} + on-chip LFSRs.  Both are
first-class here: ``rng='philox'`` uses jax.random, ``rng='lfsr'`` uses a
vectorized xorshift32 (one 32-bit LFSR state per p-bit, mirroring the
hardware's per-p-bit LFSR fabric).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["FixedPoint", "quantize", "pbit_update", "lfsr_init",
           "lfsr_states", "lfsr_next",
           "lfsr_uniform", "S41", "S43", "S46",
           "LFSR_UNIFORM_BITS", "quantize_couplings", "field_bound",
           "threshold_lut", "threshold_lut_cached", "lut_accept",
           "bitplane_planes", "flips_publish"]


def flips_publish(flips_i32: jnp.ndarray, delta_u32: jnp.ndarray):
    """Fold a uint32-modular flip delta into the int32 odometer view.

    Flip odometers are carried across chunk boundaries as int32 (pytree/
    snapshot dtype contract) but their arithmetic must be mod-2^32 in the
    unsigned domain: accumulate in uint32, add into the bitcast view, and
    bitcast back.  In-range totals are bit-identical to a plain int32 add;
    past 2^31 the unsigned view keeps the exact modular count the recording
    driver folds host-side.  The static contract auditor (rule IR-E)
    requires every published counter to end in this u32 -> i32 bitcast.
    """
    u = jax.lax.bitcast_convert_type(flips_i32, jnp.uint32)
    return jax.lax.bitcast_convert_type(u + delta_u32, jnp.int32)


@dataclasses.dataclass(frozen=True)
class FixedPoint:
    """Signed fixed point s{int_bits}{frac_bits}: step 2^-frac, saturating."""

    int_bits: int
    frac_bits: int

    @property
    def step(self) -> float:
        return 2.0 ** (-self.frac_bits)

    @property
    def lo(self) -> float:
        return -(2.0 ** self.int_bits)

    @property
    def hi(self) -> float:
        return 2.0 ** self.int_bits - self.step


S41 = FixedPoint(4, 1)  # EA benchmarks
S43 = FixedPoint(4, 3)  # Pegasus / Zephyr / 3SAT
S46 = FixedPoint(4, 6)  # G81 adaptive parallel tempering


def quantize(x: jnp.ndarray, fmt: Optional[FixedPoint]) -> jnp.ndarray:
    """Round-to-nearest + saturate to the fixed-point grid (no-op if fmt None)."""
    if fmt is None:
        return x
    q = jnp.round(x / fmt.step) * fmt.step
    return jnp.clip(q, fmt.lo, fmt.hi)


def pbit_update(field: jnp.ndarray, beta, rand_u: jnp.ndarray,
                fmt: Optional[FixedPoint] = None) -> jnp.ndarray:
    """One synchronous p-bit update for an independent (same-color) set.

    ``field`` is h + sum_j J_ij m_j (pre-beta); ``rand_u`` uniform in (-1, 1).
    Returns int8 spins in {-1, +1}.
    """
    act = quantize(beta * field, fmt)
    val = jnp.tanh(act) + rand_u
    # sgn with the (measure-zero) tie broken toward +1
    return jnp.where(val >= 0, 1, -1).astype(jnp.int8)


# ---------------------------------------------------------------------------
# LFSR (xorshift32) — the hardware RNG, vectorized one state per p-bit
# ---------------------------------------------------------------------------

def lfsr_states(n: int, seed: int) -> np.ndarray:
    """Nonzero uint32 states, seeded reproducibly (host-side splitmix64),
    as a host array: the one definition of the LFSR seed stream."""
    rng = np.random.default_rng(np.uint64(seed) ^ np.uint64(0x9E3779B97F4A7C15))
    return rng.integers(1, 2 ** 32, size=n, dtype=np.uint32)


def lfsr_init(n: int, seed: int) -> jnp.ndarray:
    """:func:`lfsr_states` on the device."""
    return jnp.asarray(lfsr_states(n, seed))


def lfsr_next(state: jnp.ndarray) -> jnp.ndarray:
    """xorshift32 step (Marsaglia); acts elementwise on uint32 states."""
    s = state
    s = s ^ (s << jnp.uint32(13))
    s = s ^ (s >> jnp.uint32(17))
    s = s ^ (s << jnp.uint32(5))
    return s


def lfsr_uniform(state: jnp.ndarray) -> jnp.ndarray:
    """Map uint32 state -> uniform float32 in (-1, 1)."""
    # keep 24 mantissa-safe bits
    bits = (state >> jnp.uint32(8)).astype(jnp.float32)
    return bits * jnp.float32(2.0 / 16777216.0) - jnp.float32(1.0)


# ---------------------------------------------------------------------------
# fixed-point coupling quantization + threshold LUTs (the hardware pipeline)
# ---------------------------------------------------------------------------
#
# The machine never evaluates tanh at runtime: couplings live on chip as
# small signed integers, the local field is an integer accumulate, and the
# Boltzmann acceptance is a single unsigned compare of the raw LFSR draw
# against a pre-tabulated threshold
#
#   accept(+1)  <=>  tanh(beta*field) + r >= 0,   r = u / 2^23 - 1
#               <=>  u >= ceil((1 - tanh(beta * scale * f)) * 2^23) = T[beta, f]
#
# with u the 24-bit LFSR draw (state >> 8) and f the *integer* field.  T is
# one small uint32 row per beta-staircase entry, computed host-side in f64;
# annealing staircases become row indices into the table.

LFSR_UNIFORM_BITS = 24  # the draw u = state >> 8 is uniform on [0, 2^24)
_HALF = 1 << (LFSR_UNIFORM_BITS - 1)   # 2^23: u/2^23 - 1 is the (-1,1) map


def quantize_couplings(h, w6, bits: int = 8):
    """Quantize biases + the six directional couplings to signed ``bits``.

    One per-problem scale (symmetric, max-abs / qmax) covers h and all six
    weight planes, so the integer field  f = h_q + sum_d w_q[d] * m_d  obeys
    scale * f ~= h + sum_d w[d] * m_d.  For the paper's +-J EA instances the
    quantization is *exact*.  A common integer factor of the quantized
    values is divided out (and folded into the scale): +-J couplings land
    on +-1 — the hardware's actual small-integer weights — which keeps the
    integer field range, and with it the threshold-LUT width, minimal.

    Returns ``(h_q, w6_q, scale)`` with int8 arrays and a float scale.
    """
    qmax = float(2 ** (bits - 1) - 1)
    h = np.asarray(h, np.float64)
    ws = [np.asarray(w, np.float64) for w in w6]
    amax = max([np.abs(h).max()] + [np.abs(w).max() for w in ws])
    scale = (amax / qmax) if amax > 0 else 1.0
    to_int = lambda a: np.clip(np.rint(a / scale), -qmax, qmax).astype(np.int64)
    qs = [to_int(h)] + [to_int(w) for w in ws]
    g = int(np.gcd.reduce([np.gcd.reduce(np.abs(q), axis=None) for q in qs]))
    if g > 1:
        qs = [q // g for q in qs]
        scale *= g
    qs = [q.astype(np.int8) for q in qs]
    return jnp.asarray(qs[0]), tuple(jnp.asarray(q) for q in qs[1:]), \
        float(scale)


def field_bound(h_q, w6_q) -> int:
    """Tight per-site bound on |h_q + sum_d w_q[d] * m_d| over m in {-1,+1}."""
    b = np.abs(np.asarray(h_q, np.int64))
    for w in w6_q:
        b = b + np.abs(np.asarray(w, np.int64))
    return int(b.max())


# Widest LUT row evaluated by the unrolled rank-count accept (below).
# Per-element gather is a scalar loop on XLA:CPU and unsupported from VMEM
# on Mosaic; the rank count is Lw scalar compares that fuse into ONE
# elementwise pass — and GCD-reduced +-J problems need only 2*6+1 = 13.
LUT_SELECT_MAX_WIDTH = 64


def lut_accept(thr: jnp.ndarray, field: jnp.ndarray, f_off: int,
               u: jnp.ndarray) -> jnp.ndarray:
    """The LUT accept test ``u >= thr[field + f_off]`` (thr is one LUT row).

    Narrow rows exploit the row's monotonicity (thr is nonincreasing in the
    field index, guaranteed by :func:`threshold_lut`): the number of
    entries already satisfied by ``u`` is ``count = #{k : u >= thr[k]}``,
    and those entries are exactly the top ``count`` field indices, so

        u >= thr[idx]   <=>   idx + count >= len(thr)

    — an unrolled chain of compares against scalars, pure vector-unit work
    with no gather and no select traffic.  Wide rows fall back to a gather.
    """
    lw = int(thr.shape[0])
    idx = jnp.clip(field + f_off, 0, lw - 1)
    if lw <= LUT_SELECT_MAX_WIDTH:
        count = jnp.zeros(u.shape, jnp.int32)
        for k in range(lw):
            count = count + (u >= thr[k]).astype(jnp.int32)
        return idx + count >= lw
    return u >= jnp.take(thr, idx, mode="clip")


def bitplane_planes(h_q, w6_q):
    """Sign-plane quantization: the bit-plane engine's per-site constants.

    With couplings quantized to {-1, 0, +1} (:func:`quantize_couplings` on
    +-J problems), the product w_d * m_d collapses to one XOR per neighbor
    bit: encoding spin +1 as bit 1, the contribution of a nonzero coupling
    is +1 exactly when ``m_bit XOR (w_d < 0)`` is 1.  The integer field of
    lane r is then

        f = h_q + 2*c - nnz,    c = #{nonzero d : contribution +1}

    so the threshold-LUT column index ``f + f_max`` equals ``base + 2*c``
    with the lane-independent ``base = h_q - nnz + f_max`` precomputed per
    site.  Returns ``(signs6, nz6, base, f_max)``:

      signs6: 6 uint32 planes, all-ones words where w_d < 0 (XOR operand,
        broadcast across the 32 lanes of a word);
      nz6: 6 uint32 planes, all-ones words where w_d != 0 (AND mask);
      base: int32 plane, h_q - nnz + f_max (in [0, 2*f_max] by the field
        bound);
      f_max: the :func:`field_bound` of the quantized problem.

    Raises ValueError when any |w_q| > 1 — multi-bit couplings have no
    single sign plane; such problems stay on the int8 path.
    """
    ones = np.uint32(0xFFFFFFFF)
    h_q = np.asarray(h_q, np.int64)
    ws = [np.asarray(w, np.int64) for w in w6_q]
    bad = max(int(np.abs(w).max()) for w in ws)
    if bad > 1:
        raise ValueError(
            f"bitplane needs couplings quantized to {{-1, 0, +1}} (one sign "
            f"bit per neighbor); this problem quantizes to |w_q| up to "
            f"{bad}.  Use precision='int8' instead.")
    f_max = field_bound(h_q, ws)
    signs6 = tuple(jnp.asarray(np.where(w < 0, ones, 0).astype(np.uint32))
                   for w in ws)
    nz6 = tuple(jnp.asarray(np.where(w != 0, ones, 0).astype(np.uint32))
                for w in ws)
    nnz = sum((w != 0).astype(np.int64) for w in ws)
    base = jnp.asarray((h_q - nnz + f_max).astype(np.int32))
    return signs6, nz6, base, f_max


def threshold_lut(betas, scale: float, f_max: int,
                  fmt: Optional[FixedPoint] = None) -> np.ndarray:
    """(len(betas), 2*f_max+1) uint32 acceptance thresholds.

    Row b, column f + f_max holds T such that the p-bit update at inverse
    temperature betas[b] and integer field f accepts +1 iff the raw 24-bit
    LFSR draw u satisfies u >= T.  ``fmt`` (the s{a}{b} activation format of
    the f32 path) folds into the table for free: the activation is rounded
    and saturated *before* tanh, exactly as the float kernel would.

    Monotone in beta by construction: for f > 0 rows are non-increasing
    down the staircase, for f < 0 non-decreasing, and T(f=0) == 2^23.
    Each *row* is monotone non-increasing in f (beta >= 0 and tanh is
    monotone) — the invariant :func:`lut_accept`'s rank count relies on.
    """
    betas = np.asarray(betas, np.float64).reshape(-1)
    if (betas < 0).any():
        raise ValueError("threshold LUTs need beta >= 0 (rows must be "
                         "monotone in the field for the rank-count accept)")
    f = np.arange(-int(f_max), int(f_max) + 1, dtype=np.float64)
    act = betas[:, None] * (float(scale) * f)[None, :]
    if fmt is not None:
        act = np.clip(np.round(act / fmt.step) * fmt.step, fmt.lo, fmt.hi)
    t = np.ceil((1.0 - np.tanh(act)) * _HALF)
    return np.clip(t, 0, 1 << LFSR_UNIFORM_BITS).astype(np.uint32)


def threshold_lut_cached(cache: dict, table: np.ndarray, scale: float,
                         f_max: int, fmt: Optional[FixedPoint] = None,
                         put=jnp.asarray) -> jnp.ndarray:
    """Device-resident :func:`threshold_lut`, memoized in the caller-owned
    ``cache`` — the one LUT-construction path shared by every engine.  The
    key covers everything that determines the table, so one cache dict may
    be shared across problems.  ``put`` takes the host table to the
    device (a mesh engine puts it in the sharding its programs declare)."""
    key = (table.tobytes(), float(scale), int(f_max), fmt)
    if key not in cache:
        cache[key] = put(threshold_lut(table, scale, f_max, fmt=fmt))
    return cache[key]
