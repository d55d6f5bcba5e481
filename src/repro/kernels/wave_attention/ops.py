"""Jit'd wrappers around the fused wave-attention Pallas kernels.

Handles layout: flattens (B, Hkv) -> BH, pads T to the kernel's block size
and E/hd to VPU-friendly multiples, then restores shapes. Padded exec-buffer
slots are masked invalid; padded estimation slots carry NEG logits.

``paged_wave_attention`` is the gather-free variant (see README.md): it takes
the raw wave-index zones — sink, local buffer, cluster stores + retrieved
ids — and never materializes a gather temp or execution-buffer concat; only
the tiny steady zone and estimation tensors are padded/copied for alignment.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.wave_attention.kernel import (NEG,
                                                 paged_wave_attention_pallas,
                                                 wave_attention_pallas)
from repro.kernels.wave_attention.ref import paged_wave_attention_jnp


def on_cpu() -> bool:
    return jax.devices()[0].platform == "cpu"


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


@functools.partial(jax.jit, static_argnames=("softcap", "block_t", "interpret"))
def wave_attention_merge(qg, k_exec, v_exec, valid, est_logit, cs_e, vs_e, *,
                         softcap=None, block_t: int = 512,
                         interpret: bool = False):
    """Same contract as ``core.attention.tripartite_merge_jnp``:
    qg (B,H,G,hd), k/v (B,H,T,hd), valid (B,H,T) bool,
    est_logit/cs_e (B,H,G,E), vs_e (B,H,E,hd) -> (B,H,G,hd) f32."""
    B, H, G, hd = qg.shape
    T = k_exec.shape[2]
    E = vs_e.shape[2]
    f32 = jnp.float32

    def flat(a):
        return a.reshape((B * H,) + a.shape[2:])

    q = flat(qg).astype(f32)
    k = flat(k_exec).astype(f32)
    v = flat(v_exec).astype(f32)
    ok = flat(valid).astype(jnp.int32)[:, None, :]           # (BH, 1, T)
    el = flat(est_logit).astype(f32)
    cs = flat(cs_e).astype(f32)
    vs = flat(vs_e).astype(f32)

    bt = min(block_t, max(128, T))
    k, _ = _pad_to(k, 1, bt)
    v, _ = _pad_to(v, 1, bt)
    ok, _ = _pad_to(ok, 2, bt)                      # pads are 0 => invalid
    el = jnp.pad(el, ((0, 0), (0, 0), (0, (-E) % 128)), constant_values=NEG)
    cs = jnp.pad(cs, ((0, 0), (0, 0), (0, (-E) % 128)), constant_values=NEG)
    vs, _ = _pad_to(vs, 1, 128)

    out = wave_attention_pallas(q, k, v, ok, el, cs, vs, softcap=softcap,
                                block_t=bt, interpret=interpret)
    return out.reshape(B, H, G, hd)


@functools.partial(jax.jit, static_argnames=("softcap", "block_l",
                                             "interpret", "emulate",
                                             "double_buffer"))
def paged_wave_attention(qg, sink_k, sink_v, local_k, local_v, local_pos,
                         k_store, v_store, pos_store, idx_r, live, rowb,
                         est_logit, cs_e, vs_e, *, softcap=None,
                         block_l: int = 512, interpret: bool = False,
                         emulate: bool = None, double_buffer: bool = True):
    """Gather-free fused decode merge over the raw wave-index zones.

    qg: (B, H, G, hd); sink_k/v: (B, H, S, hd); local_k/v: (B, H, Lb, hd)
    with local_pos (B, H, Lb) int32 (-1 = empty slot); k/v_store:
    (B, H, M, cap, hd) with pos_store (B, H, M, cap) — passed through in
    their storage dtype and read in place by the kernel; idx_r/live:
    (B, H, r) int32 retrieved ids + validity; rowb: (B, H, 2) int32
    [window_lo (exclusive), q_pos (inclusive)]; est_logit/cs_e: (B, H, G, E)
    f32; vs_e: (B, H, E, hd) f32. Returns (B, H, G, hd) f32 with semantics
    identical to ``core.attention.tripartite_merge_jnp`` on the gathered
    execution buffer.

    The stores may be the monolithic cluster stores (``idx_r`` = cluster
    ids) or the serve engine's device block cache + miss staging buffer
    (``idx_r`` = cache slots, host-offload configuration) — the kernel only
    sees an id-addressed block store.

    ``emulate`` (default: follows ``interpret``) swaps the Pallas kernel for
    ``ref.paged_wave_attention_jnp`` — the same zone-walk in plain jnp,
    which the CPU serving path uses; interpret=True + emulate=False runs the
    actual kernel through the Pallas interpreter (parity tests).
    ``double_buffer`` selects the kernel's cluster walk: explicit
    double-buffered DMA (default — cluster j+1 streams while j folds) vs the
    one-grid-step-per-cluster BlockSpec walk.
    """
    B, H, G, hd = qg.shape
    sink = sink_k.shape[2]
    Lb = local_k.shape[2]
    E = vs_e.shape[2]
    f32 = jnp.float32
    if emulate is None:
        emulate = interpret

    def flat(a):
        return a.reshape((B * H,) + a.shape[2:])

    if emulate:
        out = paged_wave_attention_jnp(
            flat(idx_r).astype(jnp.int32), flat(rowb).astype(jnp.int32),
            flat(live).astype(jnp.int32), flat(qg).astype(f32),
            flat(sink_k), flat(sink_v), flat(local_k), flat(local_v),
            flat(local_pos).astype(jnp.int32), flat(k_store), flat(v_store),
            flat(pos_store).astype(jnp.int32), flat(est_logit).astype(f32),
            flat(cs_e).astype(f32), flat(vs_e).astype(f32), sink_len=sink,
            softcap=softcap)
        return out.reshape(B, H, G, hd)

    # Alignment pads touch only the O(steady)-sized zones and the meta-index
    # estimation tensors — never the cluster stores, which flow through
    # unconverted (an outside astype would copy the ENTIRE store; the kernel
    # casts per block in VMEM). Only the retrieved blocks' (cap,) position
    # rows are gathered: Mosaic cannot DMA or block a 32-lane int32 row.
    sk, _ = _pad_to(flat(sink_k), 1, 16)
    sv, _ = _pad_to(flat(sink_v), 1, 16)
    bl = min(block_l, max(128, Lb))
    lk, _ = _pad_to(flat(local_k), 1, bl)
    lv, _ = _pad_to(flat(local_v), 1, bl)
    lp = flat(local_pos).astype(jnp.int32)
    lp = jnp.pad(lp, ((0, 0), (0, lk.shape[1] - Lb)),
                 constant_values=-1)[:, None, :]              # (BH, 1, Lp)
    idx = flat(idx_r).astype(jnp.int32)
    rp = jnp.take_along_axis(flat(pos_store), idx[..., None],
                             axis=1).astype(jnp.int32)        # (BH, r, cap)
    el = flat(est_logit).astype(f32)
    cs = flat(cs_e).astype(f32)
    vs = flat(vs_e).astype(f32)
    el = jnp.pad(el, ((0, 0), (0, 0), (0, (-E) % 128)), constant_values=NEG)
    cs = jnp.pad(cs, ((0, 0), (0, 0), (0, (-E) % 128)), constant_values=NEG)
    vs, _ = _pad_to(vs, 1, 128)

    out = paged_wave_attention_pallas(
        idx, flat(rowb).astype(jnp.int32),
        flat(live).astype(jnp.int32), flat(qg).astype(f32), sk, sv, lk, lv,
        lp, flat(k_store), flat(v_store), rp, el, cs, vs, sink_len=sink, softcap=softcap, block_l=bl,
        double_buffer=double_buffer, interpret=interpret)
    return out.reshape(B, H, G, hd)
