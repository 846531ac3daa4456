"""Pallas TPU kernels for the paper's compute hot-spot.

pbit_lattice   — fused color-group p-bit update (shifted-plane neighbor
                 reads, in-kernel xorshift32 LFSR, fixed-point tanh
                 threshold, masked flip) with BlockSpec x-slab tiling.
lattice_energy — blocked Ising-energy reduction over a brick.
ops            — jit'd dispatch (pallas on TPU / interpret for validation /
                 jnp ref on CPU); ref — pure-jnp oracles.

Validated in interpret mode against the oracles across shape/format sweeps
(bitwise-equal spins and LFSR states; allclose energies), and compiled for
a described TPU v5e at real brick sizes by tests/test_tpu_compile.py.
"""
