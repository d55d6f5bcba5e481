"""Pure-jnp oracles for the fused wave-attention kernels."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.wave_attention.kernel import NEG


def wave_attention_ref(q, k, v, valid, est_logit, cs, vs, *, softcap=None):
    """Flat-batch oracle. q: (BH, G, hd); k/v: (BH, T, hd); valid: (BH, T);
    est_logit/cs: (BH, G, E); vs: (BH, E, hd) -> (BH, G, hd) f32."""
    from repro.core.attention import tripartite_merge_jnp
    add = lambda a: a[:, None]                     # (BH, ...) -> (BH, 1, ...)
    out = tripartite_merge_jnp(add(q), add(k), add(v), add(valid > 0),
                               add(est_logit), add(cs), add(vs),
                               softcap=softcap)
    return out[:, 0]


def paged_wave_attention_jnp(idx, rowb, live, q, sink_k, sink_v,
                             local_k, local_v, local_pos,
                             k_store, v_store, pos_store,
                             est_logit, cs, vs, *, sink_len: int,
                             softcap=None):
    """Gather-free zone-walk in plain jnp — the interpretable twin of
    ``kernel.paged_wave_attention_pallas`` (same fold order: sink -> local
    buffer -> one scan step per retrieved cluster -> estimation finalize;
    same arguments, except that it takes the whole ``pos_store`` and a flat
    (BH, Lb) ``local_pos``). This is what "fused" resolves to on CPU. It
    keeps the gather-free dataflow — the
    ``lax.scan`` body slices ONE (cap, hd) block per row per step, so no
    (BH, r, cap, hd) gather temp and no execution-buffer concat ever
    materializes.

    Like the kernel, ``idx`` is just an address into the (BH, N, cap, ...)
    block store handed in: cluster ids against the monolithic stores (direct
    path) or translated cache slots against the serve engine's device block
    cache + miss staging tail (host-offload path) — this function is the CPU
    data plane of ``ServeEngine(offload=True)``.
    """
    BH, G, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    f32 = jnp.float32
    q = q.astype(f32)
    lo = rowb[:, 0][:, None].astype(jnp.int32)     # (BH, 1) excl lower bound
    hi = rowb[:, 1][:, None].astype(jnp.int32)     # (BH, 1) incl upper bound

    def fold(carry, k, v, pos, extra_ok=None):
        """Online-softmax accumulate of one (BH, T, hd) tile (identical math
        to the kernel's per-block fold). pos: (BH, T) int32, -1 = empty."""
        m, l, acc = carry                          # (BH,G) (BH,G) (BH,G,hd)
        s = jnp.einsum("bgd,btd->bgt", q, k.astype(f32)) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        ok = (pos >= 0) & (pos <= hi) & (pos > lo)
        if extra_ok is not None:
            ok = ok & extra_ok
        s = jnp.where(ok[:, None, :], s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        m_safe = jnp.maximum(m_new, -1e20)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(ok[:, None, :], p, 0.0)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bgt,btd->bgd", p,
                                                 v.astype(f32))
        return m_new, l, acc

    carry = (jnp.full((BH, G), -jnp.inf, f32), jnp.zeros((BH, G), f32),
             jnp.zeros((BH, G, hd), f32))

    sink_pos = jnp.broadcast_to(
        jnp.arange(sink_len, dtype=jnp.int32)[None, :], (BH, sink_len))
    carry = fold(carry, sink_k[:, :sink_len], sink_v[:, :sink_len], sink_pos)
    carry = fold(carry, local_k, local_v, local_pos)

    def cluster_step(carry, xs):
        idx_j, live_j = xs                         # (BH,), (BH,)
        take = lambda a: jnp.take_along_axis(
            a, idx_j.reshape((BH,) + (1,) * (a.ndim - 1)), axis=1)[:, 0]
        return fold(carry, take(k_store), take(v_store), take(pos_store),
                    extra_ok=(live_j > 0)[:, None]), None

    carry, _ = jax.lax.scan(cluster_step, carry, (idx.T, live.T))

    m, l, acc = carry
    m_fin = jnp.maximum(jnp.maximum(m, jnp.max(est_logit, axis=-1)), -1e20)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_fin), 0.0)
    est_live = est_logit > NEG / 2
    w_den = jnp.where(est_live, jnp.exp(est_logit - m_fin[..., None]), 0.0)
    w_num = jnp.where(est_live, jnp.exp(cs - m_fin[..., None]), 0.0)
    den = l * corr + jnp.sum(w_den, axis=-1)
    num = acc * corr[..., None] + jnp.einsum("bge,bed->bgd", w_num, vs)
    return num / jnp.maximum(den, 1e-30)[..., None]
