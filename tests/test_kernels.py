"""Pallas kernels vs pure-jnp oracles: shape/dtype/format sweeps.

Spin and LFSR outputs must be bitwise equal (identical integer math);
energies allclose (f32 reduction order differs across tilings)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.ops import pbit_update_op, brick_energy_op
from repro.kernels.ref import pbit_brick_update_ref, brick_energy_ref
from repro.core.pbit import S41, S43, FixedPoint

RNG = np.random.default_rng(0)


def make_inputs(shape, hscale=0.1):
    Bx, By, Bz = shape
    m = jnp.asarray(RNG.choice([-1, 1], size=shape).astype(np.int8))
    s = jnp.asarray(RNG.integers(1, 2 ** 32, size=shape, dtype=np.uint32))
    h = jnp.asarray(RNG.normal(0, hscale, shape).astype(np.float32))
    w6 = tuple(jnp.asarray(RNG.choice([-1.0, 0.0, 1.0], size=shape)
                           .astype(np.float32)) for _ in range(6))
    halos = (jnp.asarray(RNG.choice([-1, 1], (By, Bz)).astype(np.int8)),
             jnp.asarray(RNG.choice([-1, 1], (By, Bz)).astype(np.int8)),
             jnp.asarray(RNG.choice([-1, 1], (Bx, Bz)).astype(np.int8)),
             jnp.asarray(RNG.choice([-1, 1], (Bx, Bz)).astype(np.int8)),
             jnp.asarray(RNG.choice([-1, 1], (Bx, By)).astype(np.int8)),
             jnp.asarray(RNG.choice([-1, 1], (Bx, By)).astype(np.int8)))
    par = jnp.asarray((RNG.random(shape) < 0.5).astype(np.int8))
    active = jnp.asarray(np.ones(shape, np.int8))
    return m, s, h, w6, halos, par, active


@pytest.mark.parametrize("shape,bx", [
    ((8, 4, 4), 2), ((8, 4, 4), 4), ((8, 4, 4), 8),
    ((16, 8, 8), 4), ((6, 3, 5), 3), ((12, 2, 2), 6),
])
@pytest.mark.parametrize("fmt", [None, S41, S43])
def test_pbit_kernel_matches_ref(shape, bx, fmt):
    m, s, h, w6, halos, par, active = make_inputs(shape)
    m1, s1 = pbit_update_op(m, s, 1.7, par, h, w6, halos, fmt=fmt, bx=bx,
                            impl="interpret")
    m2, s2 = pbit_brick_update_ref(m, s, 1.7, par, h, w6, halos, fmt=fmt)
    assert (np.asarray(m1) == np.asarray(m2)).all()
    assert (np.asarray(s1) == np.asarray(s2)).all()


@pytest.mark.parametrize("beta", [0.1, 1.0, 5.0])
def test_pbit_kernel_beta_sweep(beta):
    m, s, h, w6, halos, par, active = make_inputs((8, 4, 4))
    m1, s1 = pbit_update_op(m, s, beta, par, h, w6, halos, bx=4,
                            impl="interpret")
    m2, s2 = pbit_brick_update_ref(m, s, beta, par, h, w6, halos)
    assert (np.asarray(m1) == np.asarray(m2)).all()


def test_pbit_kernel_respects_mask():
    m, s, h, w6, halos, par, active = make_inputs((8, 4, 4))
    frozen = jnp.zeros_like(par)
    m1, _ = pbit_update_op(m, s, 2.0, frozen, h, w6, halos, impl="interpret")
    assert (np.asarray(m1) == np.asarray(m)).all()


@pytest.mark.parametrize("shape,bx", [((8, 4, 4), 2), ((16, 8, 8), 8),
                                      ((6, 3, 5), 2)])
def test_energy_kernel_matches_ref(shape, bx):
    m, s, h, w6, halos, par, active = make_inputs(shape)
    e1 = brick_energy_op(m, active, h, w6, halos, bx=bx, impl="interpret")
    e2 = brick_energy_ref(m, active, h, w6, halos)
    np.testing.assert_allclose(float(e1), float(e2), rtol=1e-5, atol=1e-3)


def test_energy_kernel_active_mask():
    m, s, h, w6, halos, par, active = make_inputs((8, 4, 4))
    none = jnp.zeros_like(active)
    e = brick_energy_op(m, none, h, w6, halos, impl="interpret")
    assert float(e) == 0.0


def test_kernel_under_jit_and_grad_free():
    # the kernel composes under jit (as used inside shard_map scans)
    m, s, h, w6, halos, par, active = make_inputs((8, 4, 4))

    @jax.jit
    def two_phases(m, s):
        m, s = pbit_update_op(m, s, 1.0, par, h, w6, halos, impl="interpret")
        m, s = pbit_update_op(m, s, 1.0, 1 - par, h, w6, halos,
                              impl="interpret")
        return m, s
    m1, s1 = two_phases(m, s)
    mr, sr = pbit_brick_update_ref(m, s, 1.0, par, h, w6, halos)
    mr, sr = pbit_brick_update_ref(mr, sr, 1.0, 1 - par, h, w6, halos)
    assert (np.asarray(m1) == np.asarray(mr)).all()


@pytest.mark.parametrize("bx", [1, 5, 20])
def test_x_tiles_off_the_sublane_grid_match_ref(bx):
    """x-tiles need not be multiples of 8 (the engine picks 25 at L=100):
    the per-phase f32 and int8 kernels agree bitwise with the oracles for
    every tile, the planes just outside each slab included."""
    from repro.core.pbit import field_bound, quantize_couplings, threshold_lut
    from repro.kernels.ops import pbit_update_int_op
    from repro.kernels.ref import pbit_brick_update_int_ref
    m, s, h, w6, halos, par, active = make_inputs((20, 12, 20))
    m1, s1 = pbit_update_op(m, s, 1.3, par, h, w6, halos, bx=bx,
                            impl="interpret")
    m2, s2 = pbit_brick_update_ref(m, s, 1.3, par, h, w6, halos)
    assert (np.asarray(m1) == np.asarray(m2)).all()
    assert (np.asarray(s1) == np.asarray(s2)).all()
    h_q, w6_q, scale = quantize_couplings(np.zeros((20, 12, 20)), w6)
    lut = jnp.asarray(threshold_lut([0.5, 1.5], scale,
                                    field_bound(h_q, w6_q)))
    m1, s1 = pbit_update_int_op(m, s, 1, par, h_q, w6_q, halos, lut, bx=bx,
                                impl="interpret")
    m2, s2 = pbit_brick_update_int_ref(m, s, 1, par, h_q, w6_q, halos, lut)
    assert (np.asarray(m1) == np.asarray(m2)).all()
    assert (np.asarray(s1) == np.asarray(s2)).all()


def test_fused_sweep_any_mask_matches_ref():
    """The fused kernels update x-planes in place; the loop carry keeps the
    pre-phase plane, so even masks that are not a proper coloring (a site
    and its neighbor in one phase) match the whole-array oracle."""
    from repro.kernels.ops import pbit_sweep_op
    from repro.kernels.ref import pbit_brick_sweep_ref
    m, s, h, w6, halos, par, active = make_inputs((6, 5, 7))
    masks = jnp.stack([par, jnp.ones_like(par), 1 - par])
    got = pbit_sweep_op(m, s, [0.7, 2.0], masks, h, w6, halos,
                        impl="interpret")
    want = pbit_brick_sweep_ref(m, s, [0.7, 2.0], masks, h, w6, halos)
    for a, b in zip(got, want):
        assert (np.asarray(a) == np.asarray(b)).all()
