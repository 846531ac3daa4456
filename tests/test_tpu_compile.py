"""Compile the lattice Pallas kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached.  These catch what interpret
mode cannot — Mosaic lowering refusals, tiling misalignment and VMEM
overruns — at the brick sizes the engine really runs:

  * the fused f32 and int8 sweeps at a 32^3 brick;
  * the per-phase int8 update at the paper's one-chip 100^3 brick, with
    the x-tile the engine's over-budget fallback picks;
  * the bitplane sweep at 32 lanes on its VMEM-ceiling brick;
  * the energy readout at the 100^3 brick with its x-tile.

The topology is described inside a fixture (never at import), so only the
worker that runs this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.lattice_dsim import fused_brick_ceiling, pick_x_tile
from repro.kernels import lattice_energy, pbit_bitplane, pbit_lattice

i8, u32, i32, f32 = jnp.int8, jnp.uint32, jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _halos(sh, brick, dtype=i8):
    bx, by, bz = brick
    return tuple(_sds(sh, s, dtype) for s in
                 [(by, bz), (by, bz), (bx, bz), (bx, bz), (bx, by), (bx, by)])


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _fused_f32(sh):
    b, n_c = (32, 32, 32), 2
    return _compile(
        lambda m, s, betas, mk, h, w6, hl: pbit_lattice.pbit_brick_sweep(
            m, s, betas, mk, h, w6, hl),
        _sds(sh, b, i8), _sds(sh, b, u32), _sds(sh, (4,), f32),
        _sds(sh, (n_c,) + b, i8), _sds(sh, b, f32),
        tuple(_sds(sh, b, f32) for _ in range(6)), _halos(sh, b))


def _fused_int8(sh):
    b, n_c = (32, 32, 32), 2
    return _compile(
        lambda m, s, rows, mk, h, w6, hl, lut:
            pbit_lattice.pbit_brick_sweep_int(m, s, rows, mk, h, w6, hl, lut),
        _sds(sh, b, i8), _sds(sh, b, u32), _sds(sh, (4,), i32),
        _sds(sh, (n_c,) + b, i8), _sds(sh, b, i8),
        tuple(_sds(sh, b, i8) for _ in range(6)), _halos(sh, b),
        _sds(sh, (10, 13), u32))


def _per_phase_int8(sh):
    b = (100, 100, 100)
    bx = pick_x_tile(b, "int8")
    assert bx is not None and bx < b[0]
    return _compile(
        lambda m, s, row, p, h, w6, hl, lut:
            pbit_lattice.pbit_brick_update_int(m, s, row, p, h, w6, hl, lut,
                                               bx=bx),
        _sds(sh, b, i8), _sds(sh, b, u32), _sds(sh, (), i32),
        _sds(sh, b, i8), _sds(sh, b, i8),
        tuple(_sds(sh, b, i8) for _ in range(6)), _halos(sh, b),
        _sds(sh, (10, 13), u32))


def _bitplane(sh):
    L, R, n_c = fused_brick_ceiling(2, "bitplane", lanes=32), 32, 2
    b = (L, L, L)
    return _compile(
        lambda mw, s, rows, mk, sg, nz, base, hl, lut:
            pbit_bitplane.pbit_bitplane_sweep(mw, s, rows, mk, sg, nz, base,
                                              hl, lut),
        _sds(sh, b, u32), _sds(sh, (R,) + b, u32), _sds(sh, (4, R), i32),
        _sds(sh, (n_c,) + b, u32), tuple(_sds(sh, b, u32) for _ in range(6)),
        tuple(_sds(sh, b, u32) for _ in range(6)), _sds(sh, b, i32),
        _halos(sh, b, u32), _sds(sh, (10, 13), u32))


def _energy(sh):
    b = (100, 100, 100)
    bx = pick_x_tile(b, "energy")
    return _compile(
        lambda m, a, h, w6, hl: lattice_energy.brick_energy(m, a, h, w6, hl,
                                                            bx=bx),
        _sds(sh, b, i8), _sds(sh, b, i8), _sds(sh, b, f32),
        tuple(_sds(sh, b, f32) for _ in range(6)), _halos(sh, b))


@pytest.mark.parametrize("build", [_fused_f32, _fused_int8, _per_phase_int8,
                                   _bitplane, _energy],
                         ids=["fused_f32_32", "fused_int8_32",
                              "per_phase_int8_100", "bitplane_r32_ceiling",
                              "brick_energy_100"])
def test_kernel_compiles_for_v5e(one_chip, build):
    assert "tpu_custom_call" in build(one_chip)
