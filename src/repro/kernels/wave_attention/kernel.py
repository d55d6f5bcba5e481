"""Pallas TPU kernels: fused tripartite wave attention (decode step).

The paper modifies FlashAttention to (a) run over the retrieved KV blocks
(steady zone + retrieval zone) and (b) merge the centroid estimation zone
into the same online softmax (Sec. 4.6). Two TPU adaptations live here:

``wave_attention_pallas`` — the original gathered-buffer kernel:

* grid = (B*Hkv, T_blocks): each step streams one (Tb, hd) K/V tile
  HBM->VMEM; the (G, hd) query tile and (G,) running (m, l) plus the (G, hd)
  accumulator live in VMEM scratch across the T-block loop (classic flash).
* the estimation zone — (G, E) cluster logits + (E, hd) value sums — is folded
  in at the *last* grid step, re-using the same max-stabilized merge; this is
  the "weighted attention" modification of the paper's FlashAttention kernel.
* hd / Tb / E are padded by ops.py to MXU/VPU-friendly multiples (128 lanes).

``paged_wave_attention_pallas`` — the gather-free paged kernel (see
README.md): same online softmax, but the retrieved clusters are read from
``k_store``/``v_store`` IN PLACE via scalar-prefetched cluster ids driving the
BlockSpec index maps (the paged-attention idiom of ``kernels/gather``) — the
caller never materializes a (B, H, r, cap, hd) gather temp nor an
execution-buffer concat.

Validated on CPU with interpret=True against ``ref.tripartite_merge_jnp``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, valid_ref, est_logit_ref, cs_ref, vs_ref,
            o_ref, m_scr, l_scr, acc_scr, *, softcap, scale, nblocks):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                    # (G, hd) f32
    k = k_ref[0]                                    # (Tb, hd)
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    ok = valid_ref[0] > 0                           # (1, Tb)
    s = jnp.where(ok, s, NEG)                       # (G, Tb)

    m_prev = m_scr[...]                             # (G, 1) layout -> (G,)
    m_new = jnp.maximum(m_prev[:, 0], jnp.max(s, axis=-1))
    m_safe = jnp.maximum(m_new, -1e20)
    corr = jnp.where(jnp.isfinite(m_prev[:, 0]),
                     jnp.exp(m_prev[:, 0] - m_safe), 0.0)
    p = jnp.exp(s - m_safe[:, None])
    p = jnp.where(ok, p, 0.0)
    l_scr[...] = (l_scr[...] * corr[:, None]
                  + jnp.sum(p, axis=-1, keepdims=True))
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new[:, None]

    @pl.when(j == nblocks - 1)
    def _finalize():
        est_logit = est_logit_ref[0]                # (G, E)
        cs = cs_ref[0]                              # (G, E)
        vs = vs_ref[0]                              # (E, hd)
        m_prev = m_scr[...][:, 0]
        m_fin = jnp.maximum(jnp.maximum(m_prev, jnp.max(est_logit, axis=-1)),
                            -1e20)
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_fin), 0.0)
        live = est_logit > NEG / 2
        w_den = jnp.where(live, jnp.exp(est_logit - m_fin[:, None]), 0.0)
        w_num = jnp.where(live, jnp.exp(cs - m_fin[:, None]), 0.0)
        den = l_scr[...][:, 0] * corr + jnp.sum(w_den, axis=-1)
        num = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            w_num, vs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0] = num / jnp.maximum(den, 1e-30)[:, None]


def wave_attention_pallas(q, k, v, valid, est_logit, cs, vs, *,
                          softcap=None, block_t: int = 512,
                          interpret: bool = False):
    """q: (BH, G, hd) f32; k/v: (BH, T, hd) f32; valid: (BH, 1, T) int32;
    est_logit/cs: (BH, G, E) f32; vs: (BH, E, hd) f32 -> (BH, G, hd) f32.
    T must be a multiple of block_t (ops.py pads)."""
    BH, G, hd = q.shape
    T = k.shape[1]
    E = vs.shape[1]
    assert T % block_t == 0, (T, block_t)
    nblocks = T // block_t
    scale = 1.0 / math.sqrt(hd)

    kern = functools.partial(_kernel, softcap=softcap, scale=scale,
                             nblocks=nblocks)
    grid = (BH, nblocks)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G, hd), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_t, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_t, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, 1, block_t), lambda b, j: (b, 0, j)),
            pl.BlockSpec((1, G, E), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, G, E), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, E, hd), lambda b, j: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, hd), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, G, hd), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, valid, est_logit, cs, vs)


# ---------------------------------------------------------------------------
# Gather-free paged kernel: steady zone + in-place retrieved clusters.
#
# K/V blocks are read from the stores in place. Their token positions are
# not: a (cap,) int32 row is 32 lanes, and Mosaic DMAs and blocks int32
# arrays in (1, 128)-lane tiles, so the caller gathers the r retrieved
# position rows ((BH, r, cap) int32, 4 B per token against 2*hd*2 B of K+V)
# and the kernel holds them in VMEM for the whole walk.
#
# Two cluster-walk flavors share the fold/finalize math:
#   * BlockSpec walk (``double_buffer=False``): one grid step per retrieved
#     cluster; the scalar-prefetched ids drive the store BlockSpec index maps
#     (the automatic Pallas pipeline moves the blocks).
#   * double-buffered DMA walk (``double_buffer=True``, default): the stores
#     stay in ANY/HBM and one final grid step walks all r clusters with
#     explicit ``make_async_copy`` into a 2-slot VMEM scratch — the DMA for
#     cluster j+1 is started BEFORE folding cluster j, so the j+1 transfer
#     overlaps the j compute (the paper's async data movement, Sec. 4.3/4.6).
# ---------------------------------------------------------------------------


def _paged_kernel(idx_ref, rowb_ref, live_ref,
                  q_ref, sk_ref, sv_ref, lk_ref, lv_ref, lp_ref,
                  kst_ref, vst_ref, rp_ref, el_ref, cs_ref, vs_ref,
                  o_ref, m_scr, l_scr, acc_scr, *,
                  softcap, scale, sink, n_local_blocks, nblocks):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                # (G, hd)
    lo = rowb_ref[b, 0]                             # window lower bound (excl)
    hi = rowb_ref[b, 1]                             # q_pos (incl)
    fold = _make_fold(q, lo, hi, m_scr, l_scr, acc_scr, softcap=softcap,
                      scale=scale)

    @pl.when(j == 0)
    def _fold_sink():
        # sink positions are implicit: slot t holds token t; ops.py pads the
        # sink axis, so slots >= the true sink width are statically dead
        pos = jax.lax.broadcasted_iota(jnp.int32, (1, sk_ref.shape[1]), 1)
        fold(sk_ref[0].astype(jnp.float32), sv_ref[0].astype(jnp.float32),
             pos, extra_ok=pos < sink)

    @pl.when((j >= 1) & (j < 1 + n_local_blocks))
    def _fold_local():
        fold(lk_ref[0].astype(jnp.float32), lv_ref[0].astype(jnp.float32),
             lp_ref[0])

    @pl.when(j >= 1 + n_local_blocks)
    def _fold_cluster():
        jc = j - (1 + n_local_blocks)
        fold(kst_ref[0, 0].astype(jnp.float32),
             vst_ref[0, 0].astype(jnp.float32),
             rp_ref[0, pl.ds(jc, 1), :], extra_ok=live_ref[b, jc] > 0)

    @pl.when(j == nblocks - 1)
    def _finalize():
        _est_finalize(el_ref, cs_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr)


def _make_fold(q, lo, hi, m_scr, l_scr, acc_scr, *, softcap, scale):
    """Online-softmax accumulate of one (T, hd) tile against the (G,) running
    (m, l) + (G, hd) accumulator scratch; pos: (1, T) int32 token positions
    (-1 = empty slot). Shared by both cluster-walk flavors."""
    def fold(k, v, pos, extra_ok=True):
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        ok = (pos >= 0) & (pos <= hi) & (pos > lo) & extra_ok   # (1, T)
        s = jnp.where(ok, s, NEG)                   # (G, T)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev[:, 0], jnp.max(s, axis=-1))
        m_safe = jnp.maximum(m_new, -1e20)
        corr = jnp.where(jnp.isfinite(m_prev[:, 0]),
                         jnp.exp(m_prev[:, 0] - m_safe), 0.0)
        p = jnp.exp(s - m_safe[:, None])
        p = jnp.where(ok, p, 0.0)
        l_scr[...] = (l_scr[...] * corr[:, None]
                      + jnp.sum(p, axis=-1, keepdims=True))
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new[:, None]
    return fold


def _est_finalize(el_ref, cs_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr):
    """Merge the estimation zone into the accumulated exact softmax and write
    the output (the paper's 'weighted attention' finalize)."""
    est_logit = el_ref[0]                       # (G, E)
    cs = cs_ref[0]                              # (G, E)
    vs = vs_ref[0]                              # (E, hd)
    m_prev = m_scr[...][:, 0]
    m_fin = jnp.maximum(jnp.maximum(m_prev, jnp.max(est_logit, axis=-1)),
                        -1e20)
    corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_fin), 0.0)
    live = est_logit > NEG / 2
    w_den = jnp.where(live, jnp.exp(est_logit - m_fin[:, None]), 0.0)
    w_num = jnp.where(live, jnp.exp(cs - m_fin[:, None]), 0.0)
    den = l_scr[...][:, 0] * corr + jnp.sum(w_den, axis=-1)
    num = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        w_num, vs, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[0] = num / jnp.maximum(den, 1e-30)[:, None]


def _paged_db_kernel(idx_ref, rowb_ref, live_ref,
                     q_ref, sk_ref, sv_ref, lk_ref, lv_ref, lp_ref,
                     kst_ref, vst_ref, rp_ref, el_ref, cs_ref, vs_ref,
                     o_ref, m_scr, l_scr, acc_scr,
                     kdb_scr, vdb_scr, ksem, vsem, *,
                     softcap, scale, sink, n_local_blocks, nblocks, r):
    """Double-buffered flavor: the stores stay in ANY/HBM; the LAST grid step
    walks all r retrieved clusters, DMA'ing cluster j+1's (cap, hd) blocks
    into the other half of a 2-slot VMEM scratch while folding cluster j."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                # (G, hd)
    lo = rowb_ref[b, 0]                             # window lower bound (excl)
    hi = rowb_ref[b, 1]                             # q_pos (incl)
    fold = _make_fold(q, lo, hi, m_scr, l_scr, acc_scr, softcap=softcap,
                      scale=scale)

    @pl.when(j == 0)
    def _fold_sink():
        pos = jax.lax.broadcasted_iota(jnp.int32, (1, sk_ref.shape[1]), 1)
        fold(sk_ref[0].astype(jnp.float32), sv_ref[0].astype(jnp.float32),
             pos, extra_ok=pos < sink)

    @pl.when((j >= 1) & (j < 1 + n_local_blocks))
    def _fold_local():
        fold(lk_ref[0].astype(jnp.float32), lv_ref[0].astype(jnp.float32),
             lp_ref[0])

    @pl.when(j == nblocks - 1)
    def _fold_clusters_finalize():
        def dmas(slot, jc):
            cid = idx_ref[b, jc]
            return (
                pltpu.make_async_copy(kst_ref.at[b, cid], kdb_scr.at[slot],
                                      ksem.at[slot]),
                pltpu.make_async_copy(vst_ref.at[b, cid], vdb_scr.at[slot],
                                      vsem.at[slot]),
            )

        for c in dmas(0, 0):                        # warm up: cluster 0
            c.start()

        def body(jc, carry):
            cur = jax.lax.rem(jc, 2)
            nxt = jax.lax.rem(jc + 1, 2)

            @pl.when(jc + 1 < r)
            def _prefetch_next():                   # overlap j+1 DMA w/ fold j
                for c in dmas(nxt, jc + 1):
                    c.start()

            for c in dmas(cur, jc):
                c.wait()
            fold(kdb_scr[cur].astype(jnp.float32),
                 vdb_scr[cur].astype(jnp.float32),
                 rp_ref[0, pl.ds(jc, 1), :], extra_ok=live_ref[b, jc] > 0)
            return carry

        jax.lax.fori_loop(0, r, body, 0)
        _est_finalize(el_ref, cs_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr)


def paged_wave_attention_pallas(idx, rowb, live, q, sink_k, sink_v,
                                local_k, local_v, local_pos,
                                k_store, v_store, ret_pos,
                                est_logit, cs, vs, *,
                                sink_len: int, softcap=None,
                                block_l: int = 512,
                                double_buffer: bool = True,
                                interpret: bool = False):
    """Gather-free fused decode attention over the raw wave-index zones.

    idx/live: (BH, r) int32 retrieved cluster ids + validity (scalar
    prefetch); rowb: (BH, 2) int32 [window_lo (exclusive), q_pos (inclusive)];
    q: (BH, G, hd) f32; sink_k/v: (BH, Ss, hd) — slot t holds token t, slots
    >= ``sink_len`` are alignment padding; local_k/v: (BH, Lp, hd) with
    local_pos (BH, 1, Lp) int32 (-1 = empty, Lp a multiple of block_l);
    k/v_store: (BH, M, cap, hd) — read IN PLACE, one (cap, hd) block per
    retrieved cluster; ret_pos: (BH, r, cap) int32 positions of the
    retrieved blocks (gathered by the caller, see the section note);
    est_logit/cs: (BH, G, E) f32; vs: (BH, E, hd) f32. Returns (BH, G, hd)
    f32.

    ``idx`` may address any block store with a (BH, N, cap, ...) layout —
    the monolithic cluster stores (direct path, ids = cluster ids) or the
    serve engine's device block cache + miss staging buffer (host-offload
    path, ids = cache slots); the kernel is agnostic.

    ``double_buffer=True`` (default): grid (BH, 1 + Lp/block_l + 1) — the
    final step walks all r clusters with explicit double-buffered DMA
    (cluster j+1's blocks stream HBM->VMEM while cluster j folds).
    ``double_buffer=False``: grid (BH, 1 + Lp/block_l + r) — one step per
    cluster, the prefetched ``idx`` driving the store BlockSpec index maps
    (paged-attention idiom; the automatic pipeline moves the blocks).
    """
    BH, G, hd = q.shape
    cap = k_store.shape[2]
    r = idx.shape[1]
    Ss = sink_k.shape[1]
    Lp = local_k.shape[1]
    E = vs.shape[1]
    assert r >= 1 and Lp % block_l == 0, (r, Lp, block_l)
    nlb = Lp // block_l
    nblocks = (1 + nlb + 1) if double_buffer else (1 + nlb + r)
    scale = 1.0 / math.sqrt(hd)

    lmap = lambda b, j, *_: (b, jnp.clip(j - 1, 0, nlb - 1), 0)
    lpmap = lambda b, j, *_: (b, 0, jnp.clip(j - 1, 0, nlb - 1))
    cmap = lambda b, j, idx_ref, *_: \
        (b, idx_ref[b, jnp.clip(j - 1 - nlb, 0, r - 1)], 0, 0)
    park = lambda b, j, *_: (b, 0, 0)

    scratch = [
        pltpu.VMEM((G, 1), jnp.float32),
        pltpu.VMEM((G, 1), jnp.float32),
        pltpu.VMEM((G, hd), jnp.float32),
    ]
    if double_buffer:
        kern = functools.partial(_paged_db_kernel, softcap=softcap,
                                 scale=scale, sink=sink_len,
                                 n_local_blocks=nlb, nblocks=nblocks, r=r)
        store_specs = [
            pl.BlockSpec(memory_space=pl.ANY),               # k_store
            pl.BlockSpec(memory_space=pl.ANY),               # v_store
        ]
        scratch = scratch + [
            pltpu.VMEM((2, cap, hd), k_store.dtype),            # k double buf
            pltpu.VMEM((2, cap, hd), v_store.dtype),            # v double buf
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ]
    else:
        kern = functools.partial(_paged_kernel, softcap=softcap, scale=scale,
                                 sink=sink_len, n_local_blocks=nlb,
                                 nblocks=nblocks)
        store_specs = [
            pl.BlockSpec((1, 1, cap, hd), cmap),                # k_store
            pl.BlockSpec((1, 1, cap, hd), cmap),                # v_store
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(BH, nblocks),
        in_specs=[
            pl.BlockSpec((1, G, hd), park),                     # q
            pl.BlockSpec((1, Ss, hd), park),                    # sink_k
            pl.BlockSpec((1, Ss, hd), park),                    # sink_v
            pl.BlockSpec((1, block_l, hd), lmap),               # local_k
            pl.BlockSpec((1, block_l, hd), lmap),               # local_v
            pl.BlockSpec((1, 1, block_l), lpmap),               # local_pos
        ] + store_specs + [
            pl.BlockSpec((1, r, cap), park),                    # ret_pos
            pl.BlockSpec((1, G, E), park),                      # est_logit
            pl.BlockSpec((1, G, E), park),                      # cs
            pl.BlockSpec((1, E, hd), park),                     # vs
        ],
        out_specs=pl.BlockSpec((1, G, hd), park),
        scratch_shapes=scratch,
    )
    # a stable name: the HLO custom call (and the profile's op events) are
    # named after it, whatever Python function wraps the call
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, G, hd), jnp.float32),
        interpret=interpret,
        name="paged_wave_attention",
    )(idx, rowb, live, q, sink_k, sink_v, local_k, local_v, local_pos,
      k_store, v_store, ret_pos, est_logit, cs, vs)
