"""The benchmark's data and its plain reference: the 3D Edwards-Anderson
spin glass of arXiv 2606.25313 (Methods) and the p-bit dynamics run on it.

Nothing here imports the program.  The instance is made from the seed in
the order of the paper's generator (``J_ij`` = +-1 i.i.d. on the +x, +y and
+z edges of an L^3 lattice, open in x and y, periodic in z), so the program
and the reference see the same couplings whether the benchmark hands the
instance over or the program builds it from ``(L, seed)``.

The reference states the machine's semantics in plain ``jax.numpy``:

* each color phase advances every site's xorshift32 state once and updates
  the sites of that color: ``m = +1`` iff ``u >= T(beta, f)``, with ``u``
  the 24-bit draw ``state >> 8``, ``f`` the integer local field and
  ``T = ceil((1 - tanh(beta f)) 2^23)``;
* the lattice is cut into bricks; a neighbor across a brick face (and
  across the periodic z seam, which runs through the halo even on one
  chip) is read as it was at the last exchange, every ``S`` sweeps;
* replica ``r`` starts from the spins and LFSR states of its seed.

``draw_bits=16`` is the control: the same dynamics with 16-bit draws and
thresholds, the precision step below the machine's 24-bit compare.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F_MAX = 6            # |f| <= 6 on the +-J cubic lattice
LFSR_SALT = np.uint64(0x9E3779B97F4A7C15)


def couplings(L: int, seed: int) -> Tuple[np.ndarray, np.ndarray,
                                          np.ndarray]:
    """(jx, jy, jz), each (L, L, L) int8: ``jx[x, y, z]`` couples site
    (x, y, z) to (x+1, y, z) (0 on the last x plane), ``jy`` likewise in
    y, ``jz[x, y, z]`` to (x, y, (z+1) % L)."""
    if L < 3:
        raise ValueError("the EA3D lattice needs L >= 3")
    rng = np.random.default_rng(seed)
    nx, ny = (L - 1) * L * L, L * (L - 1) * L
    ew = rng.choice(np.array([-1.0, 1.0], np.float32),
                    size=nx + ny + L ** 3).astype(np.int8)
    jx = np.zeros((L, L, L), np.int8)
    jy = np.zeros((L, L, L), np.int8)
    jx[:-1] = ew[:nx].reshape(L - 1, L, L)
    jy[:, :-1] = ew[nx:nx + ny].reshape(L, L - 1, L)
    jz = ew[nx + ny:].reshape(L, L, L)
    return jx, jy, jz


def six_planes(jx, jy, jz):
    """The six directional coupling planes (-x, +x, -y, +y, -z, +z) of
    every site, as the site sees them (0 across an open face)."""
    jx, jy, jz = (jnp.asarray(a) for a in (jx, jy, jz))
    wxm = jnp.concatenate([jnp.zeros_like(jx[:1]), jx[:-1]], axis=0)
    wym = jnp.concatenate([jnp.zeros_like(jy[:, :1]), jy[:, :-1]], axis=1)
    return wxm, jx, wym, jy, jnp.roll(jz, 1, axis=2), jz


def color_masks(L: int) -> np.ndarray:
    """(2, L, L, L) int8 checkerboard: color c updates where
    (x + y + z) % 2 == c; color 0 first.  Even L only."""
    if L % 2:
        raise ValueError("the two-color checkerboard needs an even L")
    x, y, z = np.meshgrid(*(np.arange(L),) * 3, indexing="ij")
    par = (x + y + z) % 2
    return np.stack([par == 0, par == 1]).astype(np.int8)


def replica_seeds(seed: int, k: int, replicas: int) -> list:
    """31-bit seeds of the ``replicas`` chains of anneal ``k`` of a run."""
    st = np.random.SeedSequence([int(seed), int(k)]).generate_state(replicas)
    return [int(s) & 0x7FFFFFFF for s in st]


def initial_state(L: int, seeds: Sequence[int]):
    """(m, s): (R, L, L, L) int8 spins and uint32 LFSR states of the
    chains seeded by ``seeds``."""
    ms, ss = [], []
    for sd in seeds:
        ms.append(np.random.default_rng(int(sd)).choice(
            np.array([-1, 1], np.int8), size=(L, L, L)))
        rng = np.random.default_rng(np.uint64(int(sd)) ^ LFSR_SALT)
        ss.append(rng.integers(1, 2 ** 32, size=L ** 3,
                               dtype=np.uint32).reshape(L, L, L))
    return np.stack(ms), np.stack(ss)


def thresholds(betas, draw_bits: int = 24) -> np.ndarray:
    """(len(betas), 2*F_MAX+1) uint32: T[b, f + F_MAX] for a draw of
    ``draw_bits`` bits; f64 on the host."""
    half = 2.0 ** (draw_bits - 1)
    f = np.arange(-F_MAX, F_MAX + 1, dtype=np.float64)
    b = np.asarray(betas, np.float32).astype(np.float64).reshape(-1)
    t = np.ceil((1.0 - np.tanh(b[:, None] * f[None, :])) * half)
    return np.clip(t, 0, 2 ** draw_bits).astype(np.uint32)


def staircase(kind: str, sweeps: int, beta: float = 1.0) -> np.ndarray:
    """(sweeps,) f32 inverse temperatures: ``"ea"`` is the paper's EA
    staircase 0.5, 1.0, ..., 5.0 in equal stages; ``"constant"`` holds
    ``beta``."""
    if kind == "constant":
        return np.full((sweeps,), beta, np.float32)
    if kind != "ea":
        raise ValueError(f"unknown schedule {kind!r}")
    betas = np.arange(0.5, 5.0 + 1e-6, 0.5).astype(np.float32)
    bounds = np.linspace(0, sweeps, len(betas) + 1).astype(np.int64)
    out = np.empty(sweeps, np.float32)
    for k, b in enumerate(betas):
        out[bounds[k]:bounds[k + 1]] = b
    return out


def _xorshift(s):
    s = s ^ (s << jnp.uint32(13))
    s = s ^ (s >> jnp.uint32(17))
    return s ^ (s << jnp.uint32(5))


def _shift(a, axis: int, d: int, periodic: bool):
    """a[..., c + d, ...] along ``axis`` (zero outside an open axis)."""
    if periodic:
        return jnp.roll(a, -d, axis=axis)
    n = a.shape[axis]
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, 1) if d > 0 else (1, 0)
    sl = [slice(None)] * a.ndim
    sl[axis] = slice(1, n + 1) if d > 0 else slice(0, n)
    return jnp.pad(a, pad)[tuple(sl)]


def _stale_masks(L: int, bricks: Tuple[int, int, int]):
    """Per direction (-x, +x, -y, +y, -z, +z): where the neighbor lies
    across a brick face and is read from the last exchange."""
    out = []
    for axis, k in enumerate(bricks):
        b = L // k
        c = np.arange(L) % b
        shape = [1, 1, 1]
        shape[axis] = L
        out.append((c == 0).reshape(shape))        # -axis neighbor
        out.append((c == b - 1).reshape(shape))    # +axis neighbor
    return tuple(jnp.asarray(m) for m in out)


def energy(m, jx, jy, jz):
    """(R,) int32 Ising energies -sum_<ij> J_ij m_i m_j of (R, L, L, L)
    spins (z periodic)."""
    m = m.astype(jnp.int32)
    e = (jx.astype(jnp.int32) * m * _shift(m, 1, 1, False)
         + jy.astype(jnp.int32) * m * _shift(m, 2, 1, False)
         + jz.astype(jnp.int32) * m * _shift(m, 3, 1, True))
    return -e.sum(axis=(1, 2, 3))


@functools.partial(jax.jit, static_argnames=("bricks", "draw_bits"))
def anneal(m, s, jx, jy, jz, masks, rows, table, *,
           bricks=(1, 1, 1), draw_bits: int = 24):
    """Run ``rows.shape`` = (iterations, S) sweeps from (m, s).

    ``rows[i, t]`` indexes ``table`` (the thresholds of sweep t of
    iteration i); an exchange ends every iteration.  Returns the final
    (m, s) and, per iteration, the (R,) energies and accepted flips."""
    L = m.shape[1]
    ws = [w.astype(jnp.int32) for w in six_planes(jx, jy, jz)]
    stale = _stale_masks(L, bricks)
    dirs = [(1, -1, False), (1, 1, False), (2, -1, False), (2, 1, False),
            (3, -1, True), (3, 1, True)]
    shift_down = 32 - draw_bits

    def field(cur, snap):
        f = jnp.zeros(cur.shape, jnp.int32)
        for w, st, (axis, d, per) in zip(ws, stale, dirs):
            nb = jnp.where(st[None], _shift(snap, axis, d, per),
                           _shift(cur, axis, d, per))
            f = f + w[None] * nb
        return f

    def sweep(carry, row):
        m, s, snap, fl = carry
        thr = table[row]
        for c in range(masks.shape[0]):
            s = _xorshift(s)
            idx = field(m, snap) + F_MAX
            t = jnp.full(idx.shape, thr[0])
            for k in range(1, thr.shape[0]):
                t = jnp.where(idx == k, thr[k], t)
            up = (s >> jnp.uint32(shift_down)) >= t
            new = jnp.where(masks[c][None] != 0, jnp.where(up, 1, -1), m)
            fl = fl + (new != m).sum(axis=(1, 2, 3), dtype=jnp.int32)
            m = new
        return (m, s, snap, fl), None

    def iteration(carry, rows_s):
        m, s = carry
        zero = jnp.zeros((m.shape[0],), jnp.int32)
        (m, s, _, fl), _ = jax.lax.scan(sweep, (m, s, m, zero), rows_s)
        return (m, s), (energy(m, jx, jy, jz), fl)

    (m, s), (es, fls) = jax.lax.scan(
        iteration, (m.astype(jnp.int32), s), rows)
    return m.astype(jnp.int8), s, es, fls


def run(L: int, seeds: Sequence[int], j, betas, S: int,
        bricks=(1, 1, 1), draw_bits: int = 24):
    """The reference's run of the chains seeded by ``seeds`` through the
    staircase ``betas`` on couplings ``j`` = (jx, jy, jz), exchanging every
    ``S`` sweeps: (final spins, per-iteration energies, per-iteration
    flips), each (.., R) and on the host."""
    m0, s0 = initial_state(L, seeds)
    table = np.unique(betas)
    rows = np.searchsorted(table, betas).astype(np.int32).reshape(-1, S)
    args = (m0, s0, *j, color_masks(L), rows, thresholds(table, draw_bits))
    m, _, es, fls = anneal(*(jnp.asarray(a) for a in args), bricks=bricks,
                           draw_bits=draw_bits)
    return np.asarray(m), np.asarray(es, np.int64), np.asarray(fls, np.int64)
