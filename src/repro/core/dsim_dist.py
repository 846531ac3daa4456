"""Distributed (shard_map) backend of the DSIM.

Each mesh device hosts one partition: local spins, shadow weights, and ghost
slots live device-local; the *only* collective during sampling is the
boundary-state exchange — an all-gather of the boundary spins, every
``sync_every`` sweeps.  This is the TPU-native realization of the paper's
"devices exchange nothing but 1-bit boundary states".

Semantics are identical to the stacked backend in :mod:`repro.core.dsim`
(verified in tests with a multi-device subprocess); the same
:class:`PartitionedProblem` feeds both.

Replicas: the engine runs R independent chains per call (fixed at
construction).  The replica axis sits between the partition axis and the
site axis — (K, R, n_max) — so the partition axis stays the sharded leading
dim and all R boundary payloads of one exchange travel in a single
all-gather.  R=1 states are bitwise identical to the legacy layout.

Precisions (mirroring the stacked engine plus the lattice engine's word
format):

* ``"f32"`` — floating reference (tanh + float compare; Philox or LFSR;
  boundary payloads bit-packed uint8 per replica by default).
* ``"int8"`` — the fixed-point pipeline: int8 shadow couplings, int32 field
  accumulation, LUT-threshold accepts against the raw 24-bit LFSR draw.
  Replica streams are seeded per replica (:func:`spawn_seeds`), so replica
  r is bitwise identical to replica r of the stacked int8 engine and is
  *prefix-stable* in R (growing the batch never reshuffles existing
  chains).
* ``"bitplane"`` — multi-spin coding over the int8 substrate: spins stored
  as (K, W, n_max) uint32 word planes, 32 replica lanes per word and
  W = ceil(R/32) stacked planes (lane l = word l//32, bit l%32).  The
  boundary all-gather ships the *native words* — 4 B per boundary site
  *per word plane*, with ZERO pack/unpack compute on the collective path
  (a word slice IS the wire payload) — and the phase update runs the
  bit-sliced carry-save adder tree over XOR'd sign planes with per-lane
  LFSR columns and the same LUT accept.  Lane (w, b) is bit-identical to
  replica ``w*32 + b`` of the unpacked int8 path at matched seeds, and
  prefix-stable in both b and w.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .dsim import PartitionedProblem, DSIMState
from .degrade import (DegradePolicy, MeshHealthMonitor, fault_code,
                      health_init, health_step, wire_checksum)
from .annealing import ArraySchedule, beta_row_indices, beta_table
from .pbit import (FixedPoint, bitplane_planes, field_bound, flips_publish,
                   lfsr_init, lfsr_next, lfsr_uniform, lut_accept, quantize,
                   quantize_couplings, threshold_lut_cached)
from .packing import pack_pm1, unpack_pm1, pad_to_multiple, pack_lanes, \
    unpack_lanes, lane_coords
from .energy import energy as direct_energy
from repro.compat import shard_map
from repro.engines.base import (RecordedCursor, check_lanes,
                                run_recorded_driver, spawn_seeds)
from repro.kernels.ops import bitplane_gather_count_op

__all__ = ["DistDSIMEngine"]

SyncSpec = Union[int, str, None]


class DistDSIMEngine:
    """One partition per device along ``axis`` of ``mesh`` (K = axis size)."""

    def __init__(self, prob: PartitionedProblem, mesh: Mesh,
                 axis: Union[str, tuple] = "data",
                 rng: str = "philox", fmt: Optional[FixedPoint] = None,
                 mode: str = "dsim", bitpack: bool = True,
                 replicas: int = 1, precision: str = "f32",
                 degrade: Union[None, str, DegradePolicy] = None):
        axis_tuple = (axis,) if isinstance(axis, str) else tuple(axis)
        ndev = int(np.prod([mesh.shape[a] for a in axis_tuple]))
        if ndev != prob.K:
            raise ValueError(f"mesh axis size {ndev} != K={prob.K}")
        if mode not in ("dsim", "cmft"):
            raise ValueError(mode)
        if precision not in ("f32", "int8", "bitplane"):
            raise ValueError(f"unknown precision {precision!r}")
        if precision != "f32" and (rng != "lfsr" or mode != "dsim"):
            # the fixed-point/word paths are the hardware pipeline: per-p-bit
            # LFSRs (the LUT thresholds the raw 24-bit draw) and
            # instantaneous +-1 ghosts (cmft's fractional window-means fit
            # neither integer fields nor 1-bit lanes)
            raise ValueError(
                f"precision={precision!r} needs rng='lfsr', mode='dsim'")
        self.degrade = DegradePolicy.parse(degrade)
        if self.degrade is not None and mode != "dsim":
            # cmft publishes fractional window means — there is no 1-bit
            # wire representation to checksum, and held means are not a
            # meaningful last-known-good
            raise ValueError("degrade policies need mode='dsim'")
        self.health = (MeshHealthMonitor(self.degrade, prob.K,
                                         kind="partitions")
                       if self.degrade is not None else None)
        # host-scheduled engine-boundary fault codes (0 ok / 1 drop /
        # 2 corrupt), indexed by the traced exchange sequence number
        self._fault_codes = None
        # the shared lane-cap guard; W stacked word planes for the word path
        self.words = check_lanes(precision, replicas)
        self.p = prob
        self.mesh = mesh
        self.axis = axis_tuple if len(axis_tuple) > 1 else axis_tuple[0]
        self.rng_kind = rng
        self.fmt = fmt
        self.mode = mode
        self.precision = precision
        self.replicas = int(replicas)
        self.n_sites = prob.n
        # bit-packing needs b_max % 8 == 0; re-pad the packed pool coords
        self.b_pad = pad_to_multiple(prob.b_max, 8)
        self.bitpack = bitpack and mode == "dsim" and precision == "f32"
        self._shard = NamedSharding(mesh, P(self.axis))
        self._repl = NamedSharding(mesh, P())
        self._chunk_cache = {}
        self._energy = jax.jit(self._energy_impl)

        bs = np.asarray(prob.bnd_slots)
        pad = np.zeros((prob.K, self.b_pad - prob.b_max), dtype=bs.dtype)
        self._bnd_slots = jnp.asarray(np.concatenate([bs, pad], axis=1))
        gsp = np.asarray(prob.ghost_src_packed)
        gk, gc = gsp // prob.b_max, gsp % prob.b_max
        self._ghost_src_pool = jnp.asarray((gk * self.b_pad + gc).astype(np.int32))

        self._consts = dict(
            local_idx=prob.local_idx,
            color_slots=prob.color_slots, color_mask=prob.color_mask,
            bnd_slots=self._bnd_slots, ghost_src_pool=self._ghost_src_pool,
            # source partition of each ghost slot — the per-source
            # last-known-good hold mask of the degraded-mode exchange
            ghost_src_part=jnp.asarray(gk.astype(np.int32)),
        )
        if precision == "f32":
            self._consts.update(local_w=prob.local_w, local_h=prob.local_h)
        else:
            h_q, (w_q,), self.q_scale = quantize_couplings(
                prob.local_h, (prob.local_w,))
            wq = np.asarray(w_q)
            self.f_max = field_bound(
                h_q, tuple(wq[..., d] for d in range(wq.shape[-1])))
            self._lut_cache = {}
            if precision == "int8":
                self._consts.update(local_h_q=h_q, local_w_q=w_q)
            else:
                # per-direction sign/nonzero word planes + the lane-
                # independent LUT-column base (validates |w_q| <= 1)
                signs, nz, base, _ = bitplane_planes(
                    h_q, tuple(wq[..., d] for d in range(wq.shape[-1])))
                self._consts.update(
                    bp_signs=jnp.stack(signs, axis=-1),   # (K, n_max, D)
                    bp_nz=jnp.stack(nz, axis=-1),
                    bp_base=base)                          # (K, n_max)
                # lane l lives at word plane _lane_w[l], bit _lane_b[l]
                self._lane_w, self._lane_b = lane_coords(self.replicas, 1)

    def _lut_for(self, table: np.ndarray) -> jnp.ndarray:
        return threshold_lut_cached(self._lut_cache, table, self.q_scale,
                                    self.f_max, fmt=self.fmt)

    # -- state ------------------------------------------------------------------

    def init_state(self, seed: int = 0) -> DSIMState:
        p, R = self.p, self.replicas
        if self.precision != "f32":
            # per-replica seeding: replica r's spins and LFSR column depend
            # on spawn_seeds(seed, R)[r] alone, exactly like the stacked
            # int8 engine's batched init — so dist int8 replica r is
            # bitwise the stacked replica r, bitplane lane r is bitwise the
            # unpacked replica r, and lanes are prefix-stable in R
            ms, rngs = [], []
            for s_r in spawn_seeds(seed, R):
                key, sub = jax.random.split(jax.random.PRNGKey(s_r))
                ms.append(jnp.where(
                    jax.random.bernoulli(sub, 0.5, (p.K, p.n_max)),
                    1, -1).astype(jnp.int8))
                rngs.append(lfsr_init(p.K * p.n_max, s_r).reshape(p.K,
                                                                  p.n_max))
            m_r = jnp.stack(ms)                              # (R, K, n_max)
            rng = jnp.stack(rngs).transpose(1, 0, 2)         # (K, R, n_max)
            zero = jnp.zeros((), dtype=jnp.int32)
            flips = jnp.zeros((R,), jnp.int32)
            if self.precision == "bitplane":
                W = self.words
                mw = jnp.swapaxes(pack_lanes(m_r), 0, 1)     # (K, W, n_max)
                # per-word flat-pool gather, the word analogue of
                # _exchange_host's per-replica gather
                pool = jnp.swapaxes(mw, 0, 1).reshape(W, -1)
                ghosts = jnp.swapaxes(pool[:, p.ghost_src], 0, 1)
                st = DSIMState(m=mw, ghosts=ghosts,          # (K, W, g_max)
                               macc=jnp.zeros((p.K, 1), jnp.float32),
                               rng=rng, sweep=zero, flips=flips)
            else:
                m = m_r.transpose(1, 0, 2)                   # (K, R, n_max)
                st = DSIMState(m=m, ghosts=self._exchange_host(m),
                               macc=jnp.zeros((p.K, R, p.n_max),
                                              jnp.float32),
                               rng=rng, sweep=zero, flips=flips)
            return self.shard_state(st)
        key = jax.random.PRNGKey(seed)
        key, sub = jax.random.split(key)
        m = jnp.where(jax.random.bernoulli(sub, 0.5, (p.K, R, p.n_max)), 1, -1)
        m = m.astype(jnp.int8)
        if self.rng_kind == "philox":
            # legacy uint32[2] keys: split returns (K*R, 2) raw key rows
            rng = jax.random.split(key, p.K * R).reshape(p.K, R, 2)
        else:
            rng = lfsr_init(p.K * R * p.n_max, seed).reshape(p.K, R, p.n_max)
        ghosts = self._exchange_host(m)
        zero = jnp.zeros((), dtype=jnp.int32)
        st = DSIMState(m=m, ghosts=ghosts,
                       macc=jnp.zeros((p.K, R, p.n_max), jnp.float32),
                       rng=rng, sweep=zero,
                       flips=jnp.zeros((R,), jnp.int32))
        return self.shard_state(st)

    def shard_state(self, st: DSIMState) -> DSIMState:
        # re-sharding (init, restore from snapshot) invalidates the cached
        # exchange-only closure: it closed over constants placed for the
        # previous sharding, and a stale cache would let the eta probe run
        # against dead buffers
        self._exchange_only_fn = None
        put = lambda x: jax.device_put(x, self._shard)
        return DSIMState(m=put(st.m), ghosts=put(st.ghosts), macc=put(st.macc),
                         rng=put(st.rng),
                         sweep=jax.device_put(st.sweep, self._repl),
                         flips=jax.device_put(st.flips, self._repl))

    def _exchange_host(self, m) -> jnp.ndarray:
        # m (K, R, n_max): ghost_src indexes the flat (K * n_max) pool per
        # replica — gather per replica on the replica-transposed view
        R = self.replicas
        flat = m.transpose(1, 0, 2).reshape(R, -1).astype(jnp.float32)
        ghosts = flat[:, self.p.ghost_src]            # (R, K, g_max)
        return ghosts.transpose(1, 0, 2)              # (K, R, g_max)

    # -- device-local block functions (run inside shard_map) -----------------------
    # All block arrays have their partition dim squeezed away: m (R, n_max)
    # int8 — or (W, n_max) uint32 word planes on the bitplane path —,
    # ghosts (R, g_max) | (W, g_max) words, rng (R,) keys | (R, n_max)
    # LFSR, consts rows (…).

    def _exchange_block(self, m, macc, S, consts, inst: bool = False):
        """Publish boundary states, all-gather, gather this device's ghosts.

        ``inst`` forces instantaneous +-1 states even in cmft mode — the
        per-phase refresh path, matching the stacked engine's
        ``_exchange_inst``.  (Publishing ``macc/1`` there was wrong: the
        accumulator is zeroed at every S-sweep boundary, so the first
        phases of each iteration would broadcast all-zero ghost means.)
        """
        R = self.replicas
        bnd_slots = consts["bnd_slots"]                       # (b_pad,)
        if self.mode == "cmft" and not inst:
            vals = (macc / jnp.float32(S))[:, bnd_slots]      # (R, b_pad)
            pool = jax.lax.all_gather(vals, self.axis, tiled=True)
        elif self.bitpack:
            bnd = m[:, bnd_slots]                             # (R, b_pad)
            packed = pack_pm1(bnd)                            # (R, b_pad/8)
            pool_p = jax.lax.all_gather(packed, self.axis, tiled=True)
            pool = unpack_pm1(pool_p, self.b_pad).astype(jnp.float32)
        else:
            bnd = m[:, bnd_slots]
            pool = jax.lax.all_gather(bnd, self.axis,
                                      tiled=True).astype(jnp.float32)
        # pool (K*R, b_pad) device-order-major -> (R, K*b_pad) per replica
        pool = pool.reshape(self.p.K, R, self.b_pad)
        pool = pool.transpose(1, 0, 2).reshape(R, -1)
        return pool[:, consts["ghost_src_pool"]]              # (R, g_max)

    def _exchange_block_w(self, mw, consts):
        """Native-word boundary exchange: a slice of the spin words IS the
        wire payload — 4 B per boundary site per word plane (32 lanes each),
        no pack/unpack compute anywhere on the collective path.  ``mw`` is
        the device-local (W, n_max); the all-gather ships all W planes of
        the boundary in one collective."""
        W = int(mw.shape[0])
        bnd = mw[:, consts["bnd_slots"]]                      # (W, b_pad)
        pool = jax.lax.all_gather(bnd, self.axis, tiled=True)  # (K*W, b_pad)
        pool = pool.reshape(self.p.K, W, self.b_pad)
        pool = jnp.swapaxes(pool, 0, 1).reshape(W, -1)        # (W, K*b_pad)
        return pool[:, consts["ghost_src_pool"]]              # (W, g_max)

    def boundary_exchange_fn(self):
        """Jitted exchange-ONLY closure: exactly the ``_exchange_block*``
        collective (publish -> all-gather -> ghost gather) with every
        p-bit update elided.  ``fn(state) -> ghosts`` on live state — the
        measured-η probe (``obs.EtaMeter.measure_exchange`` times it to
        get t_exchange, hence f_comm, without touching the run path)."""
        cached = getattr(self, "_exchange_only_fn", None)
        if cached is not None:
            return cached
        spec_m = P(self.axis)
        cspec = jax.tree.map(lambda _: spec_m, self._consts)
        word = self.precision == "bitplane"

        def block(m, macc, consts):
            m, macc = m[0], macc[0]
            consts = jax.tree.map(lambda x: x[0], consts)
            if word:
                g = self._exchange_block_w(m, consts)
            else:
                g = self._exchange_block(m, macc, 1, consts)
            return g[None]

        smapped = shard_map(block, mesh=self.mesh,
                            in_specs=(spec_m, spec_m, cspec),
                            out_specs=spec_m, check_vma=False)
        run = jax.jit(lambda m, macc: smapped(m, macc, self._consts))
        fn = lambda state: run(state.m, state.macc)  # noqa: E731
        self._exchange_only_fn = fn
        return fn

    def _phase_block(self, c, m, ghosts, rng, beta, consts, lut=None):
        """One color phase; ``beta`` is the f32 inverse temperature — or,
        with ``lut``, the int32 LUT row index the staircase resolved to."""
        slots, mask = consts["color_slots"][c], consts["color_mask"][c]  # (nc,)
        idx_c = consts["local_idx"][slots]                    # (nc, D)
        int8 = lut is not None
        acc = jnp.int32 if int8 else jnp.float32
        h_c = (consts["local_h_q"] if int8 else consts["local_h"])[slots] \
            .astype(acc)
        w_c = (consts["local_w_q"] if int8 else consts["local_w"])[slots] \
            .astype(acc)
        mext = jnp.concatenate([m.astype(acc), ghosts.astype(acc)], axis=1)
        nbr = jnp.take(mext, idx_c, axis=1)                   # (R, nc, D)
        field = h_c + (w_c * nbr).sum(axis=-1)                # (R, nc)
        if self.rng_kind == "philox":
            ks = jax.vmap(jax.random.split)(rng)              # (R, 2) keys
            rng = ks[:, 0]
            nc = field.shape[1]
            r = jax.vmap(lambda k: jax.random.uniform(
                k, (nc,), minval=-1.0, maxval=1.0))(ks[:, 1])
        else:
            s = rng[:, slots]
            s = lfsr_next(s)
            # int8 accepts draw raw bits from s — skip the f32 uniform so
            # the integer body stays float-free (contract rule IR-A)
            r = None if int8 else lfsr_uniform(s)
            rng = rng.at[:, slots].set(s)
        old = m[:, slots]
        if int8:
            # pure-integer accept: raw 24-bit draw vs tabulated threshold
            u = s >> jnp.uint32(8)
            thr = jax.lax.dynamic_index_in_dim(
                lut, jnp.asarray(beta, jnp.int32), axis=0, keepdims=False)
            new = jnp.where(lut_accept(thr, field, self.f_max, u),
                            1, -1).astype(jnp.int8)
        else:
            act = quantize(beta * field, self.fmt)
            new = jnp.where(jnp.tanh(act) + r >= 0, 1, -1).astype(jnp.int8)
        new = jnp.where(mask, new, old)
        flips = (new != old).sum(axis=1).astype(jnp.int32)    # (R,)
        m = m.at[:, slots].set(new)
        return m, rng, flips

    def _phase_block_w(self, c, mw, ghosts_w, rng, row, consts, lut):
        """One color phase on packed words: XOR sign application, bit-sliced
        adder tree for the +1-contribution count, per-lane LFSR draw + LUT
        accept.  ``mw``/``ghosts_w`` carry the leading W word-plane axis;
        lane l reads word ``_lane_w[l]`` at bit ``_lane_b[l]``, and the
        accepted bits scatter back per word (disjoint bit positions, so the
        adds are bitwise ORs).  Lane (w, b) is bit-identical to replica
        ``w*32 + b`` of :meth:`_phase_block` on the int8 path (same integer
        field, same LFSR column, same threshold compare)."""
        slots, mask = consts["color_slots"][c], consts["color_mask"][c]
        mext = jnp.concatenate([mw, ghosts_w], axis=-1)       # (W, n_ext)
        counts = bitplane_gather_count_op(
            mext, consts["local_idx"][slots], consts["bp_signs"][slots],
            consts["bp_nz"][slots])                           # (W, nc) each
        wl, bl = self._lane_w, self._lane_b                   # (R,), (R, 1)
        one = jnp.uint32(1)
        s = rng[:, slots]
        s = lfsr_next(s)
        rng = rng.at[:, slots].set(s)
        u = s >> jnp.uint32(8)                                # (R, nc)
        cnt = jnp.zeros(u.shape, jnp.int32)
        for i, b in enumerate(counts):
            cnt = cnt + (((b[wl] >> bl) & one)
                         << jnp.uint32(i)).astype(jnp.int32)
        # f = h_q + 2c - nnz = (base - f_max) + 2c, per lane
        field = consts["bp_base"][slots][None, :] - self.f_max + 2 * cnt
        thr = jax.lax.dynamic_index_in_dim(
            lut, jnp.asarray(row, jnp.int32), axis=0, keepdims=False)
        accept = lut_accept(thr, field, self.f_max, u)        # (R, nc)
        upd = jnp.zeros((int(mw.shape[0]), u.shape[1]), jnp.uint32) \
            .at[wl].add(accept.astype(jnp.uint32) << bl)      # (W, nc)
        old = mw[:, slots]
        new = jnp.where(mask, upd, old)
        diff = old ^ new
        flips = ((diff[wl] >> bl) & one).astype(jnp.int32) \
            .sum(axis=1)                                      # (R,)
        mw = mw.at[:, slots].set(new)
        return mw, rng, flips

    def _iteration_block(self, m, ghosts, macc, rng, flips, betas_S, sync,
                         consts, lut=None):
        S = betas_S.shape[0]

        def body(carry, beta):
            m, ghosts, macc, rng, flips = carry
            for c in range(len(consts["color_slots"])):
                if sync == "phase":
                    ghosts = self._exchange_block(m, macc, 1, consts,
                                                  inst=True)
                m, rng, f = self._phase_block(c, m, ghosts, rng, beta,
                                              consts, lut)
                flips = flips + f.astype(flips.dtype)
            if self.mode == "cmft":
                # dsim mode never reads the window accumulator — keeping
                # the add there would put dead f32 arithmetic in the int8
                # chunk body (contract rule IR-A)
                macc = macc + m.astype(jnp.float32)
            return (m, ghosts, macc, rng, flips), None

        (m, ghosts, macc, rng, flips), _ = jax.lax.scan(
            body, (m, ghosts, macc, rng, flips), betas_S)
        if sync not in ("phase", None):
            ghosts = self._exchange_block(m, macc, S, consts)
        macc = jnp.zeros_like(macc)
        return m, ghosts, macc, rng, flips

    def _iteration_block_w(self, mw, ghosts, macc, rng, flips, rows_S, sync,
                           consts, lut):
        def body(carry, row):
            mw, ghosts, rng, flips = carry
            for c in range(len(consts["color_slots"])):
                if sync == "phase":
                    ghosts = self._exchange_block_w(mw, consts)
                mw, rng, f = self._phase_block_w(c, mw, ghosts, rng, row,
                                                 consts, lut)
                flips = flips + f.astype(flips.dtype)
            return (mw, ghosts, rng, flips), None

        (mw, ghosts, rng, flips), _ = jax.lax.scan(
            body, (mw, ghosts, rng, flips), rows_S)
        if sync not in ("phase", None):
            ghosts = self._exchange_block_w(mw, consts)
        return mw, ghosts, macc, rng, flips

    # -- degraded-mode exchange (integrity header + stale hold) ---------------------

    def _exchange_block_checked(self, m, consts, ghosts_prev, health,
                                codes, freeze: bool):
        """The boundary exchange with the integrity layer on.

        Every device publishes its payload plus a ``[seq, checksum]``
        header; the receiver recomputes the checksum of each source's
        slice of the gathered pool and compares.  A source that fails
        (wrong checksum, wrong/missing sequence number) has ALL its ghost
        entries held at the carried last-known-good values — a bad
        exchange is detected and *not ingested*.  With zero detections the
        ingested ghosts are bitwise the unchecked `_exchange_block*`
        values.  ``codes`` (optional, host-scheduled via
        :meth:`set_exchange_faults`) corrupts/drops the *received* pool at
        indexed sequence numbers — the engine-boundary fault site; the
        detection below derives only from the wire contents.
        """
        seq = health[0]
        K = self.p.K
        word = self.precision == "bitplane"
        lanes = int(m.shape[0])                   # W word planes | R chains
        bnd_slots = consts["bnd_slots"]
        if word:
            bnd = m[:, bnd_slots]                 # (W, b_pad) uint32
            pool = jax.lax.all_gather(bnd, self.axis, tiled=True)
            wire = pool.reshape(K, lanes, self.b_pad)
            sent = bnd
        elif self.bitpack:
            bnd = m[:, bnd_slots]                 # (R, b_pad) int8
            packed = pack_pm1(bnd)
            pool_p = jax.lax.all_gather(packed, self.axis, tiled=True)
            pool = unpack_pm1(pool_p, self.b_pad).astype(jnp.float32)
            wire = jax.lax.bitcast_convert_type(
                pool.reshape(K, lanes, self.b_pad), jnp.uint32)
            sent = bnd.astype(jnp.float32)
        else:
            # int8 boundary states ARE the wire (1 B/site, the declared
            # boundary_payload); widening to f32 happens AFTER the gather
            bnd = m[:, bnd_slots]                 # (R, b_pad) int8
            pool = jax.lax.all_gather(bnd, self.axis, tiled=True)
            wire = pool.reshape(K, lanes, self.b_pad)
            sent = bnd
        # header: my exchange counter + the checksum of what I published
        hdr = jnp.stack([seq, wire_checksum(sent)])
        hdrs = jax.lax.all_gather(hdr, self.axis, tiled=True).reshape(K, 2)
        if codes is not None:
            # engine-boundary fault injection on the RECEIVED pool: the
            # detection below sees only the (possibly damaged) wire bits
            corrupt, drop = fault_code(codes, seq)
            flip = jnp.asarray(2 if wire.dtype == jnp.int8 else 0x00400000,
                               wire.dtype)
            wire = jnp.where(corrupt, wire ^ flip, wire)
            wire = jnp.where(drop, jnp.zeros_like(wire), wire)
            hdrs = jnp.where(drop, jnp.full_like(hdrs, 0xFFFFFFFF), hdrs)
        ck_k = jax.vmap(wire_checksum)(wire)                     # (K,)
        ok_k = (ck_k == hdrs[:, 1]) & (hdrs[:, 0] == seq)
        bad_k, health = health_step(health, ok_k, freeze)
        # ingest per source: held sources keep last-known-good ghosts
        if word:
            vals = wire
        elif wire.dtype == jnp.int8:
            vals = wire.astype(jnp.float32)       # widen off the wire
        else:
            vals = jax.lax.bitcast_convert_type(wire, jnp.float32)
        pool2 = vals.transpose(1, 0, 2).reshape(lanes, -1)
        ghosts_new = pool2[:, consts["ghost_src_pool"]]
        bad_entry = bad_k[consts["ghost_src_part"]]              # (g_max,)
        ghosts = jnp.where(bad_entry[None, :], ghosts_prev, ghosts_new)
        return ghosts, health

    # -- runner ---------------------------------------------------------------------

    def _run_chunk(self, iters: int, S: int, sync: SyncSpec,
                   check: Optional[Tuple[bool, bool]] = None):
        """The jitted chunk of every precision, called as ``(state, betas,
        consts, *rest)``: ``rest`` is the threshold LUT off the f32 path,
        then — with ``check = (freeze, has_codes)``, the integrity layer
        on — the health carry and, with ``has_codes``, the fault codes.
        Returns the new state, or with ``check`` the (state, health) pair:
        each iteration then sweeps with no exchange of its own and runs
        the checked exchange (one per S sweeps; ``sync`` is not read)."""
        key = (iters, S, sync, check)
        if key in self._chunk_cache:
            return self._chunk_cache[key]

        spec_m = P(self.axis)
        cspec = jax.tree.map(lambda _: spec_m, self._consts)
        has_lut = self.precision != "f32"
        iteration = self._iteration_block_w \
            if self.precision == "bitplane" else self._iteration_block
        freeze, has_codes = check or (False, False)
        hspec = tuple(P() for _ in range(6))

        def block(m, ghosts, macc, rng, flips_in, betas, consts, *rest):
            # squeeze the device-local partition dim from state and consts
            m, ghosts, macc, rng = m[0], ghosts[0], macc[0], rng[0]
            consts = jax.tree.map(lambda x: x[0], consts)
            lut = rest[0] if has_lut else None
            codes = rest[-1] if has_codes else None
            # per-chunk flips accumulate in uint32 (modular-exact at any
            # magnitude): the old int32 accumulator overflowed *within* a
            # single long chunk at ~2.1e9 lane-flips, before the psum and
            # the driver's mod-2^32 odometer read ever saw it
            carry = (m, ghosts, macc, rng, jnp.zeros(flips_in.shape,
                                                      jnp.uint32))
            if check is not None:
                carry += (rest[int(has_lut)],)

            def it(carry, b):
                if check is None:
                    return iteration(*carry, b, sync, consts, lut), None
                m, ghosts, macc, rng, fl, health = carry
                m, _, macc, rng, fl = iteration(m, ghosts, macc, rng, fl, b,
                                                None, consts, lut)
                ghosts, health = self._exchange_block_checked(
                    m, consts, ghosts, health, codes, freeze)
                return (m, ghosts, macc, rng, fl, health), None
            carry, _ = jax.lax.scan(it, carry, betas)
            m, ghosts, macc, rng, local = carry[:5]
            flips = flips_publish(flips_in, jax.lax.psum(local, self.axis))
            return (m[None], ghosts[None], macc[None], rng[None], flips) \
                + carry[5:]

        in_specs = (spec_m, spec_m, spec_m, spec_m, P(), P(), cspec) \
            + ((P(),) if has_lut else ()) \
            + ((hspec,) if check is not None else ()) \
            + ((P(),) if has_codes else ())
        smapped = shard_map(
            block, mesh=self.mesh,
            in_specs=in_specs,
            out_specs=(spec_m, spec_m, spec_m, spec_m, P())
            + ((hspec,) if check is not None else ()),
            check_vma=False,
        )

        @jax.jit
        def run(state: DSIMState, betas, consts, *rest):
            m, ghosts, macc, rng, flips, *health = smapped(
                state.m, state.ghosts, state.macc, state.rng, state.flips,
                betas, consts, *rest)
            st = DSIMState(
                m=m, ghosts=ghosts, macc=macc, rng=rng,
                sweep=state.sweep + betas.shape[0] * betas.shape[1],
                flips=flips)
            return (st, health[0]) if check is not None else st

        self._chunk_cache[key] = run
        return run

    def set_exchange_faults(self, codes):
        """Schedule engine-boundary exchange faults: ``codes[seq]`` in
        {0 ok, 1 drop, 2 corrupt} applied to the *received* pool at global
        exchange ``seq`` (see ``serve.faults.FaultPlan.exchange_codes``).
        ``None`` clears.  Requires a degrade policy — an unchecked engine
        would silently ingest the damage, which is exactly the failure
        mode this subsystem removes."""
        if codes is None:
            self._fault_codes = None
            return
        if self.degrade is None:
            raise ValueError("set_exchange_faults needs a degrade policy "
                             "(unchecked engines must not ingest damage)")
        self._fault_codes = jnp.asarray(np.asarray(codes), jnp.int32)

    def resync(self, state: DSIMState) -> DSIMState:
        """Quarantine exit: instantaneous full-boundary refresh.

        Recomputes every ghost from the *current* spins — exactly the
        exchange a no-fault run would have performed at this point, so the
        returned ghosts are bitwise the no-fault trajectory's (verified in
        tests).  Clears staleness/freeze on the health monitor."""
        ghosts = self.boundary_exchange_fn()(state)
        if self.health is not None:
            self.health.on_resync()
        return dataclasses.replace(state, ghosts=ghosts)

    def run_recorded_full(self, state: DSIMState, schedule,
                          record_points: Sequence[int], *,
                          cursor: bool = False,
                          sync_every: SyncSpec = 1):
        """Shared-driver runner; returns (state, RunRecord) — or, with
        ``cursor=True``, the resumable RecordedCursor."""
        sync = sync_every if sync_every in ("phase", None) else int(sync_every)

        check = None
        if self.degrade is not None:
            if sync in ("phase", None):
                raise ValueError("degrade policies need an integer "
                                 "sync_every (one checked exchange per S "
                                 "sweeps)")
            self.health.reset()
            codes = self._fault_codes
            check = (self.degrade.mode == "freeze_boundary",
                     codes is not None)
            codes_opt = () if codes is None else (codes,)

        if self.precision != "f32":
            # the staircase becomes LUT row indices (beta is in the table)
            beta_arr = np.asarray(schedule.beta_array(), np.float32)
            table = beta_table(beta_arr)
            lut_opt = (self._lut_for(table),)
            sched = ArraySchedule(beta_row_indices(beta_arr, table))
        else:
            sched, lut_opt = schedule, ()

        def chunk(st, sched2d, iters, S):
            if check is None:
                return self._run_chunk(iters, S, sync)(
                    st, sched2d, self._consts, *lut_opt)
            st, carry = self._run_chunk(iters, S, sync, check=check)(
                st, sched2d, self._consts, *lut_opt, self.health.carry,
                *codes_opt)
            self.health.update(carry, exchanges=iters)
            return st

        kw = dict(
            state=state, schedule=sched, record_points=record_points,
            chunk_fn=chunk, record_fn=self.energy, sync_every=sync_every,
            flips_of=lambda st: st.flips,
            flips_per_sweep=self.p.n * self.replicas)
        if cursor:
            return RecordedCursor(**kw)
        return run_recorded_driver(**kw)

    def run_recorded(self, state: DSIMState, schedule,
                     record_points: Sequence[int], sync_every: SyncSpec = 1):
        """Run to each record point; returns (state, (times, energies))."""
        return self.run_recorded_full(state, schedule, record_points,
                                      sync_every=sync_every)

    # -- observables -------------------------------------------------------------------

    def global_spins(self, state: DSIMState) -> jnp.ndarray:
        """(R, N) global spins; squeezed to (N,) when replicas == 1."""
        p, R = self.p, self.replicas

        def one(m_r):                                     # (K, n_max)
            buf = jnp.ones((p.n + 1,), dtype=jnp.int8)
            buf = buf.at[p.global_ids.reshape(-1)].set(m_r.reshape(-1))
            return buf[: p.n]

        if self.precision == "bitplane":
            m_r = unpack_lanes(jnp.swapaxes(state.m, 0, 1), R)  # (R, K, n_max)
        else:
            m_r = state.m.transpose(1, 0, 2)
        spins = jax.vmap(one)(m_r)
        return spins[0] if R == 1 else spins

    def _energy_impl(self, state: DSIMState) -> jnp.ndarray:
        spins = self.global_spins(state)
        if self.replicas == 1:
            return direct_energy(self.p.graph, spins)
        return jax.vmap(lambda m: direct_energy(self.p.graph, m))(spins)

    def energy(self, state: DSIMState) -> jnp.ndarray:
        """(R,) true global energies (scalar when replicas == 1)."""
        return self._energy(state)

    def boundary_payload(self) -> dict:
        """Wire-format accounting of one boundary publication per device:
        dtype, total bytes, and bytes per boundary site covering ALL
        replicas/lanes (the roofline collective term and the benchmark's
        recorded payload)."""
        R = self.replicas
        if self.precision == "bitplane":
            W = self.words
            return {"dtype": "uint32", "bytes": 4 * W * self.b_pad,
                    "bytes_per_site_all_chains": 4.0 * W, "chains": R,
                    "word_planes": W, "bytes_per_site_per_word": 4.0,
                    "pack_compute": "none"}
        if self.mode == "cmft":
            return {"dtype": "float32", "bytes": 4 * R * self.b_pad,
                    "bytes_per_site_all_chains": 4.0 * R, "chains": R,
                    "pack_compute": "none"}
        if self.bitpack:
            return {"dtype": "uint8-bitmap", "bytes": R * self.b_pad // 8,
                    "bytes_per_site_all_chains": R / 8.0, "chains": R,
                    "pack_compute": "pack+unpack per exchange"}
        return {"dtype": "int8", "bytes": R * self.b_pad,
                "bytes_per_site_all_chains": float(R), "chains": R,
                "pack_compute": "none"}

    # -- dry-run / audit hooks -----------------------------------------------------------

    def _chunk_args(self, iters: int, S: int, sync: SyncSpec,
                    degrade: bool = False, freeze: bool = False,
                    has_codes: bool = False):
        """(runner, abstract args) for one sampling chunk — shared by the
        lowering dry-run and the static contract auditor's tracer.  With
        ``degrade`` the runner runs checked (health carry, optional
        fault-code operand)."""
        p, R = self.p, self.replicas

        def sds(x, shard):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=shard)

        zero = jnp.zeros((), jnp.int32)
        flips = jnp.zeros((R,), jnp.int32)
        if self.precision == "bitplane":
            st = DSIMState(
                m=jax.ShapeDtypeStruct((p.K, self.words, p.n_max),
                                       jnp.uint32, sharding=self._shard),
                ghosts=jax.ShapeDtypeStruct((p.K, self.words, p.g_max),
                                            jnp.uint32,
                                            sharding=self._shard),
                macc=jax.ShapeDtypeStruct((p.K, 1), jnp.float32,
                                          sharding=self._shard),
                rng=jax.ShapeDtypeStruct((p.K, R, p.n_max), jnp.uint32,
                                         sharding=self._shard),
                sweep=sds(zero, self._repl),
                flips=sds(flips, self._repl),
            )
        else:
            rng_t = jax.random.split(jax.random.PRNGKey(0),
                                     p.K * R).reshape(p.K, R, 2) \
                if self.rng_kind == "philox" else \
                jnp.zeros((p.K, R, p.n_max), jnp.uint32)
            st = DSIMState(
                m=jax.ShapeDtypeStruct((p.K, R, p.n_max), jnp.int8,
                                       sharding=self._shard),
                ghosts=jax.ShapeDtypeStruct((p.K, R, p.g_max), jnp.float32,
                                            sharding=self._shard),
                macc=jax.ShapeDtypeStruct((p.K, R, p.n_max), jnp.float32,
                                          sharding=self._shard),
                rng=sds(rng_t, self._shard),
                sweep=sds(zero, self._repl),
                flips=sds(flips, self._repl),
            )
        consts = jax.tree.map(lambda x: sds(x, self._shard), self._consts)
        sched_dt = jnp.float32 if self.precision == "f32" else jnp.int32
        sched = jax.ShapeDtypeStruct((iters, S), sched_dt,
                                     sharding=self._repl)
        lut_opt = () if self.precision == "f32" else (
            jax.ShapeDtypeStruct((1, 2 * self.f_max + 1), jnp.uint32,
                                 sharding=self._repl),)
        args = (st, sched, consts) + lut_opt
        if not degrade:
            return self._run_chunk(iters, S, sync), args
        args += (tuple(jax.ShapeDtypeStruct(np.shape(h), np.asarray(h).dtype,
                                            sharding=self._repl)
                       for h in health_init(p.K)),)
        if has_codes:
            args += (jax.ShapeDtypeStruct((8,), jnp.uint32,
                                          sharding=self._repl),)
        return self._run_chunk(iters, S, sync, check=(freeze, has_codes)), \
            args

    def lower_chunk(self, iters: int = 4, S: int = 4, sync: SyncSpec = 4):
        """Lower (not run) one sampling chunk — used by the launch dry-run."""
        run, args = self._chunk_args(iters, S, sync)
        return run.lower(*args)

    def trace_chunk(self, iters: int = 4, S: int = 4, sync: SyncSpec = 4,
                    degrade: bool = False, freeze: bool = False,
                    has_codes: bool = False):
        """Trace (not lower) one sampling chunk and return the jitted
        runner's Traced object, whose ``.jaxpr`` the static contract
        auditor walks.  Unlike :meth:`lower_chunk` this works over an
        ``AbstractMesh`` — collective dtype/count contracts are auditable
        on a single-device host, no multi-device subprocess needed."""
        run, args = self._chunk_args(iters, S, sync, degrade=degrade,
                                     freeze=freeze, has_codes=has_codes)
        return run.trace(*args)
