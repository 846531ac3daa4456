"""Shared transformer layers: norms, rotary embeddings (incl. M-RoPE),
GQA/MQA attention with sliding-window and KV-cache support, (Sw)iGLU MLP.

Pure functional: ``init_*`` build parameter pytrees (dict leaves), ``*_fwd``
apply them.  No collectives here — distribution is applied externally via
jit shardings, so every layer also runs single-device for smoke tests.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["rms_norm", "init_rms", "init_dense", "dense",
           "rope_freqs", "apply_rope", "apply_mrope",
           "init_attention", "attention_fwd", "init_mlp", "mlp_fwd",
           "KVCache", "init_kv_cache"]

# ---------------------------------------------------------------------------
# basics
# ---------------------------------------------------------------------------


def init_rms(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rms_norm(p, x, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = (x32 * x32).mean(axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(dt)


def init_dense(key, d_in: int, d_out: int, dtype=jnp.float32, scale=None):
    scale = (1.0 / np.sqrt(d_in)) if scale is None else scale
    w = jax.random.normal(key, (d_in, d_out), jnp.float32) * scale
    return {"w": w.astype(dtype)}


def dense(p, x):
    return x @ p["w"].astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(d_head: int, theta: float = 10000.0):
    half = d_head // 2
    return 1.0 / (theta ** (np.arange(half) / half))   # (half,)


def _rotate(x, cos, sin):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def apply_rope(q, k, positions, freqs):
    """q/k: (B, S, H, Dh); positions: (B, S) int32."""
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :].astype(q.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(q.dtype)
    return _rotate(q, cos, sin), _rotate(k, cos, sin)


def apply_mrope(q, k, positions3, freqs, sections):
    """Qwen2-VL multimodal RoPE: positions3 (3, B, S) for (t, h, w);
    ``sections`` splits the half-dim across the three components."""
    half = freqs.shape[0]
    assert sum(sections) == half, (sections, half)
    angs = positions3[..., None].astype(jnp.float32) * freqs  # (3, B, S, half)
    parts, off = [], 0
    for i, s in enumerate(sections):
        parts.append(angs[i, :, :, off:off + s])
        off += s
    ang = jnp.concatenate(parts, axis=-1)                     # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :].astype(q.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(q.dtype)
    return _rotate(q, cos, sin), _rotate(k, cos, sin)


# ---------------------------------------------------------------------------
# attention (GQA / MQA, causal / bidirectional / sliding window, KV cache)
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    k: jnp.ndarray        # (B, Hkv, Smax, Dh)
    v: jnp.ndarray        # (B, Hkv, Smax, Dh)
    pos: jnp.ndarray      # scalar int32 — tokens already cached


def init_kv_cache(batch: int, n_kv: int, s_max: int, d_head: int,
                  dtype=jnp.bfloat16) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, n_kv, s_max, d_head), dtype),
        v=jnp.zeros((batch, n_kv, s_max, d_head), dtype),
        pos=jnp.zeros((), jnp.int32))


def init_attention(key, d_model: int, n_heads: int, n_kv: int, d_head: int,
                   dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    return {
        "wq": init_dense(ks[0], d_model, n_heads * d_head, dtype),
        "wk": init_dense(ks[1], d_model, n_kv * d_head, dtype),
        "wv": init_dense(ks[2], d_model, n_kv * d_head, dtype),
        "wo": init_dense(ks[3], n_heads * d_head, d_model, dtype,
                         scale=1.0 / np.sqrt(n_heads * d_head)),
    }


def shard_hint(x, *spec):
    """Best-effort sharding constraint against the ambient mesh.

    Entries name mesh axes (or tuples of axes); axes missing from the
    ambient mesh or not dividing the dim are dropped; all other dims stay
    UNCONSTRAINED.  A no-op outside a `jax.sharding.set_mesh(...)` scope
    (single-device tests), so the model code stays mesh-agnostic."""
    from repro.compat import ambient_mesh, mesh_is_auto
    mesh = ambient_mesh()
    if mesh is None or not getattr(mesh, "axis_names", ()):
        return x
    # inside shard_map (Manual axes) data is already device-local — skip
    if not mesh_is_auto(mesh):
        return x
    from jax.sharding import PartitionSpec as P
    import numpy as _np
    clean = []
    used = False
    for i, a in enumerate(spec):
        if a is None:
            clean.append(P.UNCONSTRAINED)
            continue
        axes = (a,) if isinstance(a, str) else tuple(
            ax for ax in a if ax in mesh.axis_names)
        if axes and all(ax in mesh.axis_names for ax in axes):
            k = int(_np.prod([mesh.shape[ax] for ax in axes]))
            if k > 1 and x.shape[i] % k == 0:
                clean.append(axes[0] if len(axes) == 1 else axes)
                used = True
                continue
        clean.append(P.UNCONSTRAINED)
    if not used:
        return x
    return jax.lax.with_sharding_constraint(x, P(*clean))


BATCH_AXES = ("pod", "data")


def batch_hint(x):
    """Pin the leading (batch) dim of an activation to the data axes —
    XLA was observed to drop batch sharding through the layer scan when
    FSDP param shardings compete (EXPERIMENTS.md §Perf H2)."""
    if x.ndim < 2:
        return x
    return shard_hint(x, BATCH_AXES, *([None] * (x.ndim - 1)))


def residual_hint(x, seq_parallel: bool = False):
    """Residual-stream layout at layer boundaries: batch on the data axes
    and, when ``seq_parallel``, the sequence dim on 'model' (Megatron-SP
    style) — the layer-scan carry then stores 1/TP of each residual, the
    lever that fits the 95-layer train cells in HBM (§Perf H5)."""
    if x.ndim != 3:
        return batch_hint(x)
    if seq_parallel:
        return shard_hint(x, BATCH_AXES, "model", None)
    return batch_hint(x)


def _sdpa(q, k, v, mask, d_head):
    """q (B,S,H,Dh), k/v (B,Skv,Hkv,Dh); mask (B,1,S,Skv) bool.

    GQA is handled by repeating K/V up to H heads *at use* (a head-gather,
    cheap under SPMD) rather than a grouped (Hkv, g) einsum: the flat-head
    einsum partitions over the full 'model' axis, while the grouped form
    was observed to shard only g-ways.  Explicit head-dim hints keep the
    f32 logits sharded over 'model' (XLA was observed to replicate them
    otherwise — see EXPERIMENTS.md §Perf)."""
    B, S, H, Dh = q.shape
    Hkv = k.shape[2]
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    q = shard_hint(q, None, None, "model", None)
    k = shard_hint(k, None, None, "model", None)
    v = shard_hint(v, None, None, "model", None)
    logits = jnp.einsum("bshd,bthd->bhst", q, k)     # (B,H,S,Skv)
    logits = shard_hint(logits, None, "model", None, None)
    logits = logits.astype(jnp.float32) / np.sqrt(d_head)
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhst,bthd->bshd", probs, v)
    return out.reshape(B, S, H, Dh)


# query-chunked attention: bounds the logits working set to
# (B, H, q_chunk, Skv) per scan step instead of (B, H, S, Skv) — what makes
# 32k prefill fit HBM.  (Causal block-skipping is a §Perf candidate.)
Q_CHUNK = 1024


def _chunked_causal_sdpa(q, k, v, positions, window, d_head, q_chunk):
    B, S, H, Dh = q.shape
    nc = S // q_chunk
    qs = jnp.moveaxis(q.reshape(B, nc, q_chunk, H, Dh), 1, 0)
    pq = jnp.moveaxis(positions.reshape(B, nc, q_chunk), 1, 0)
    kp = positions[:, None, :]                      # (B, 1, Skv)

    def body(_, inp):
        qc, pqc = inp
        valid = kp <= pqc[:, :, None]
        if window is not None:
            valid &= kp > pqc[:, :, None] - window
        out = _sdpa(qc, k, v, valid[:, None], d_head)
        return None, out

    # flash-style residency: recompute each chunk's f32 probs during the
    # backward pass instead of stacking them across the scan — the saved
    # residual per layer drops from O(S^2) f32 to one chunk (see §Perf H1)
    body = jax.checkpoint(body)
    _, outs = jax.lax.scan(body, None, (qs, pq))
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, H, Dh)


def attention_fwd(p, x, positions, freqs, *, n_heads: int, n_kv: int,
                  d_head: int, causal: bool = True,
                  window: Optional[int] = None,
                  cache: Optional[KVCache] = None,
                  kv_x: Optional[jnp.ndarray] = None,
                  mrope_sections=None, positions3=None):
    """Returns (out, new_cache).

    Modes:
      cache None, kv_x None      — full self-attention (train / scoring).
      cache given, S == q tokens — decode/prefill append: writes new K/V at
                                   cache.pos and attends over the cache.
      kv_x given                 — cross-attention onto kv_x (no rope).
    """
    B, S, D = x.shape
    q = dense(p["wq"], x).reshape(B, S, n_heads, d_head)
    src = x if kv_x is None else kv_x
    k = dense(p["wk"], src).reshape(B, src.shape[1], n_kv, d_head)
    v = dense(p["wv"], src).reshape(B, src.shape[1], n_kv, d_head)

    if kv_x is None:
        if mrope_sections is not None:
            q, k = apply_mrope(q, k, positions3, freqs, mrope_sections)
        else:
            q, k = apply_rope(q, k, positions, freqs)

    new_cache = None
    if cache is not None and kv_x is None:
        smax = cache.k.shape[2]
        rolling = window is not None and smax <= window
        kT = k.transpose(0, 2, 1, 3).astype(cache.k.dtype)
        vT = v.transpose(0, 2, 1, 3).astype(cache.v.dtype)
        q_pos = positions[:, :, None]            # (B, S, 1) global positions
        if rolling:
            # ring buffer of the last `smax` tokens (Mistral-style SWA cache)
            if S >= smax:
                idx = (cache.pos + S - smax + jnp.arange(smax)) % smax
                ck = cache.k.at[:, :, idx].set(kT[:, :, -smax:])
                cv = cache.v.at[:, :, idx].set(vT[:, :, -smax:])
            else:
                idx = (cache.pos + jnp.arange(S)) % smax
                ck = cache.k.at[:, :, idx].set(kT)
                cv = cache.v.at[:, :, idx].set(vT)
            new_cache = KVCache(k=ck, v=cv, pos=cache.pos + S)
            if S > 1:
                # prefill: the ring only retains the last `smax` keys, so
                # attention must run over the full fresh K/V (early queries
                # need in-window keys the ring has already evicted); the ring
                # write above still seeds subsequent decode steps.
                if S % Q_CHUNK == 0 and S > Q_CHUNK:
                    out = _chunked_causal_sdpa(q, k, v, positions, window,
                                               d_head, Q_CHUNK)
                else:
                    qp = positions[:, :, None]
                    kp = positions[:, None, :]
                    valid = (kp <= qp) & (kp > qp - window)
                    out = _sdpa(q, k, v, valid[:, None], d_head)
                out = dense(p["wo"], out.reshape(B, S, n_heads * d_head))
                return out, new_cache
            # decode: global position held by ring slot j after this write
            top = cache.pos + S - 1
            slots = jnp.arange(smax)[None, :]
            gpos = top - jnp.mod(top - slots, smax)   # (1, Smax)
            valid = (gpos[:, None, :] <= q_pos) & (gpos[:, None, :] >= 0)
            valid &= gpos[:, None, :] > q_pos - window
        else:
            ck = jax.lax.dynamic_update_slice(cache.k, kT, (0, 0, cache.pos, 0))
            cv = jax.lax.dynamic_update_slice(cache.v, vT, (0, 0, cache.pos, 0))
            new_cache = KVCache(k=ck, v=cv, pos=cache.pos + S)
            kv_pos = jnp.arange(smax)[None, :]   # (1, Smax)
            valid = kv_pos[:, None, :] <= q_pos  # causal within cache
            valid &= (kv_pos < cache.pos + S)[:, None, :]
            if window is not None:
                valid &= (kv_pos[:, None, :] > q_pos - window)
        k_all = ck.transpose(0, 2, 1, 3)         # (B, Smax, Hkv, Dh)
        v_all = cv.transpose(0, 2, 1, 3)
        mask = valid[:, None]                    # (B,1,S,Smax)
        out = _sdpa(q, k_all, v_all, mask, d_head)
    else:
        Skv = src.shape[1]
        if kv_x is not None:
            out = _sdpa(q, k, v, jnp.ones((B, 1, S, Skv), bool), d_head)
        elif causal and S % Q_CHUNK == 0 and S > Q_CHUNK:
            out = _chunked_causal_sdpa(q, k, v, positions, window, d_head,
                                       Q_CHUNK)
        else:
            qp = positions[:, :, None]
            kp = positions[:, None, :]
            if causal:
                valid = kp <= qp
            else:
                valid = jnp.ones((B, S, Skv), bool)
            if window is not None:
                valid &= kp > qp - window
            out = _sdpa(q, k, v, valid[:, None], d_head)

    out = dense(p["wo"], out.reshape(B, S, n_heads * d_head))
    return out, new_cache


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return {
        "wi": init_dense(ks[0], d_model, d_ff, dtype),
        "wg": init_dense(ks[1], d_model, d_ff, dtype),
        "wo": init_dense(ks[2], d_ff, d_model, dtype, scale=1.0 / np.sqrt(d_ff)),
    }


def mlp_fwd(p, x):
    return dense(p["wo"], jax.nn.silu(dense(p["wg"], x)) * dense(p["wi"], x))
