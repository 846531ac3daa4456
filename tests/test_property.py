"""Property-based tests (hypothesis) on the system's invariants."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

# hypothesis is a dev-only dependency (requirements-dev.txt); without it this
# module must skip cleanly instead of failing tier-1 collection
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st, HealthCheck

from repro.core.graph import from_edges, random_regular
from repro.core.coloring import greedy_coloring, validate_coloring
from repro.core.energy import energy
from repro.core.packing import pack_pm1, unpack_pm1
from repro.core.gibbs import chunk_plan
from repro.core.pbit import FixedPoint, quantize
from repro.launch.roofline import _shape_bytes, _group_size

SET = dict(deadline=None, max_examples=20,
           suppress_health_check=[HealthCheck.too_slow])


@given(st.integers(2, 40), st.integers(0, 10 ** 6))
@settings(**SET)
def test_energy_gauge_invariance(n, seed):
    """E is invariant under J_ij -> J_ij s_i s_j, m -> m*s (gauge symmetry)."""
    rng = np.random.default_rng(seed)
    d = 3 if (n * 3) % 2 == 0 else 4
    try:
        g = random_regular(max(n, d + 1), d, seed=seed)
    except (RuntimeError, ValueError):
        return
    m = rng.choice([-1, 1], g.n).astype(np.int8)
    s = rng.choice([-1, 1], g.n).astype(np.int8)
    idx = np.asarray(g.idx)
    w = np.asarray(g.w) * s[:, None] * s[idx]
    g2 = from_edges(*_to_edges(idx, w, g.n))
    e1 = float(energy(g, jnp.asarray(m)))
    e2 = float(energy(g2, jnp.asarray((m * s).astype(np.int8))))
    assert abs(e1 - e2) < 1e-3


def _to_edges(idx, w, n):
    src = np.repeat(np.arange(n), idx.shape[1])
    dst = idx.ravel()
    wt = w.ravel()
    mask = (wt != 0) & (src < dst)
    return n, src[mask], dst[mask], wt[mask].astype(np.float32)


@given(st.integers(0, 10 ** 6))
@settings(**SET)
def test_energy_global_flip_invariance(seed):
    g = random_regular(20, 3, seed=seed % 100)
    m = np.random.default_rng(seed).choice([-1, 1], g.n).astype(np.int8)
    e1 = float(energy(g, jnp.asarray(m)))
    e2 = float(energy(g, jnp.asarray((-m).astype(np.int8))))
    assert abs(e1 - e2) < 1e-3   # h = 0: Z2 symmetry


@given(st.integers(1, 64), st.integers(1, 8), st.integers(0, 10 ** 6))
@settings(**SET)
def test_pack_unpack_roundtrip(words, rows, seed):
    n = words * 8
    x = np.random.default_rng(seed).choice([-1, 1], (rows, n)).astype(np.int8)
    assert (unpack_pm1(pack_pm1(jnp.asarray(x)), n) == x).all()


@given(st.lists(st.integers(1, 5000), min_size=1, max_size=12))
@settings(**SET)
def test_chunk_plan_hits_every_point(raw):
    pts = sorted(set(raw))
    plan = chunk_plan(pts)
    acc, hits = 0, set()
    for c in plan:
        assert c & (c - 1) == 0
        acc += c
        hits.add(acc)
    assert set(pts) <= hits
    assert acc == pts[-1]


@given(st.integers(1, 6), st.integers(0, 6),
       st.floats(-100, 100, allow_nan=False))
@settings(**SET)
def test_fixedpoint_properties(ib, fb, x):
    fmt = FixedPoint(ib, fb)
    q = float(quantize(jnp.asarray(x), fmt))
    assert fmt.lo <= q <= fmt.hi
    # idempotent & on-grid
    assert abs(float(quantize(jnp.asarray(q), fmt)) - q) < 1e-9
    assert abs(q / fmt.step - round(q / fmt.step)) < 1e-6
    # within half a step when in range
    if fmt.lo <= x <= fmt.hi:
        assert abs(q - x) <= fmt.step / 2 + 1e-9


@given(st.integers(2, 30), st.integers(3, 6), st.integers(0, 10 ** 5))
@settings(**SET)
def test_greedy_coloring_always_valid(n, d, seed):
    if (n * d) % 2 != 0 or n <= d:
        return
    try:
        g = random_regular(n, d, seed=seed)
    except (RuntimeError, ValueError):
        return
    col = greedy_coloring(np.asarray(g.idx), np.asarray(g.w))
    assert validate_coloring(np.asarray(g.idx), np.asarray(g.w), col.colors)
    assert col.n_colors <= d + 1


def test_hlo_shape_parser():
    assert _shape_bytes("f32[4,8]{1,0}") == 128
    assert _shape_bytes("(bf16[2,2], u8[16])") == 24
    assert _shape_bytes("pred[7]") == 7
    assert _shape_bytes("s32[]") == 4  # scalar: product of no dims = 1 elem
    assert _group_size("replica_groups={{0,1,2,3}}") == 4
    assert _group_size("replica_groups=[2,8]<=[16]") == 8
    assert _group_size("source_target_pairs={{0,1}}") == 2
