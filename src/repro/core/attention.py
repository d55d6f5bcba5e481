"""Tripartite wave attention (paper Sec. 4.2) — decode-step attention.

o = merge(o_steady, o_retrieval, o_estimation)

* steady zone: sinks + local window, exact.
* retrieval zone: top-r clusters by q·centroid, KV blocks gathered, exact.
* estimation zone: next-e clusters, contribution ã_i·VS_i with
  ã_i = exp(q·C_i/√d)/Z and Z accumulating s_i·exp(q·C_i/√d) — the Jensen
  lower bound (Eq. 2–4).

GQA: clusters belong to kv heads; the retrieval decision is shared across a
kv head's query group (group-max centroid score), estimation stays per-query.

This module is the pure-jnp reference path; ``repro.kernels.wave_attention``
provides the fused Pallas kernel with identical semantics.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import RetroConfig
from repro.core.wave_index import WaveState
from repro.core.zones import ZonePlan
from repro.models.layers import soft_cap
from repro.serving import trace

NEG = -1e30


class WaveAttnOut(NamedTuple):
    out: jax.Array           # (B, Hq, hd)
    retrieved: jax.Array     # (B, Hkv, r) int32 cluster ids (for cache stats)


def rank_clusters(q_group: jax.Array, state: WaveState, plan: ZonePlan,
                  window: Optional[jax.Array] = None,
                  softcap: Optional[float] = None, cluster_offset=0):
    """Rank clusters by centroid score.

    q_group: (B, Hkv, G, hd). Returns (cscore (B,Hkv,G,M) f32, idx_re (B,Hkv,r+e)).
    ``cluster_offset`` is the global index of local cluster 0 (sharded
    retrieval: each shard holds an M/n slice of the cluster axis).
    """
    hd = q_group.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    cs = jnp.einsum("bhgd,bhmd->bhgm", q_group.astype(jnp.float32),
                    state.centroid) * scale
    cs = soft_cap(cs, softcap)
    M = state.centroid.shape[2]
    # per-row active range + dead clusters (size 0: ragged-padding artefacts)
    in_range = jnp.arange(M)[None, :] + cluster_offset \
        < state.n_clusters[:, None]                       # (B, M)
    valid = in_range[:, None, :] & (state.size > 0)       # (B, Hkv, M)
    if window is not None:
        q_pos = state.length - 1                          # (B,)
        valid = valid & (state.max_pos > q_pos[:, None, None] - window)
    cs = jnp.where(valid[:, :, None, :], cs, NEG)
    group_score = jnp.max(cs, axis=2)                     # (B, Hkv, M)
    _, idx_re = jax.lax.top_k(group_score, plan.r + plan.e)
    return cs, idx_re


def _gather_clusters(state: WaveState, idx: jax.Array):
    """Gather cluster blocks. idx: (B, Hkv, r) -> stores (B, Hkv, r, cap, hd)."""
    def take(a):
        return jnp.take_along_axis(
            a, idx.reshape(idx.shape + (1,) * (a.ndim - 3)), axis=2)
    return (take(state.k_store), take(state.v_store), take(state.pos_store))


def _estimation_zone(state: WaveState, cs, idx_r, idx_e, *,
                     use_estimation: bool, overflow_correction: bool):
    """Estimation-zone inputs for the fused merge (shared by every impl).

    cs: (B, H, G, M) centroid scores; idx_r/idx_e: (B, H, r/e) cluster ids.
    Returns (est_logit, cs_e (B, H, G, E), vs_e (B, H, E, hd)) — all O(meta
    index)-sized: the only cluster-store-sized tensors of the decode step are
    the stores themselves.
    """
    B, Hkv = cs.shape[:2]
    hd = state.vsum.shape[-1]
    e = idx_e.shape[2]
    if use_estimation and e > 0:
        cs_e = jnp.take_along_axis(cs, idx_e[:, :, None, :], axis=3)   # (B,H,G,e)
        sz_e = jnp.take_along_axis(state.size, idx_e, axis=2)          # (B,H,e)
        vs_e = jnp.take_along_axis(
            state.vsum, idx_e[..., None], axis=2)                      # (B,H,e,hd)
        log_sz = jnp.log(jnp.maximum(sz_e.astype(jnp.float32), 1.0))
        est_logit = cs_e + log_sz[:, :, None, :]                       # s_i·exp(cs)
        est_valid = sz_e > 0
        est_logit = jnp.where(est_valid[:, :, None, :], est_logit, NEG)
    else:
        est_logit = jnp.full((B, Hkv, cs.shape[2], 1), NEG, jnp.float32)
        cs_e = est_logit
        vs_e = jnp.zeros((B, Hkv, 1, hd), jnp.float32)

    # overflow correction: tokens dropped from retrieved stores (size > cap)
    # re-enter through their cluster's estimate, scaled by the dropped fraction.
    if overflow_correction and use_estimation and idx_r.shape[2] > 0:
        cs_r = jnp.take_along_axis(cs, idx_r[:, :, None, :], axis=3)   # (B,H,G,r)
        sz_r = jnp.take_along_axis(state.size, idx_r, axis=2)
        st_r = jnp.take_along_axis(state.stored, idx_r, axis=2)
        vs_r = jnp.take_along_axis(state.vsum, idx_r[..., None], axis=2)
        over = jnp.maximum(sz_r - st_r, 0).astype(jnp.float32)         # (B,H,r)
        frac = over / jnp.maximum(sz_r.astype(jnp.float32), 1.0)
        log_over = jnp.where(over > 0, jnp.log(jnp.maximum(over, 1.0)), NEG)
        ov_logit = cs_r + log_over[:, :, None, :]
        est_logit = jnp.concatenate([est_logit, ov_logit], axis=3)
        cs_e = jnp.concatenate([cs_e, cs_r], axis=3)
        vs_e = jnp.concatenate([vs_e, vs_r * frac[..., None]], axis=2)
    return est_logit, cs_e, vs_e


def _retrieval_cover(state: WaveState, cs, idx_r):
    """Estimation-zone COVER for the retrieved clusters (degraded decode).

    For each retrieved cluster, the Jensen estimate of its STORED tokens:
    ``cov_logit = cs + log(stored_eff)``, ``cov_vs = vsum * stored_frac``
    (the overflow fraction is excluded — the unconditional overflow entry of
    :func:`_estimation_zone` already covers it, so cover + overflow together
    equal the full-cluster estimate with no double count). The attend path
    enables a cluster's cover entry only when its validity mask is 0 — a
    fetch that missed its deadline loses exact attention for the step but
    keeps its estimated attention mass (paper Eq. 2-4 accuracy bound).

    Touches only the META index, like the rest of the rank half. Dead or
    empty clusters get ``cov_logit = NEG`` exactly (inert in every merge
    impl). Returns ``(cov_logit (B,H,G,r), cs_r (B,H,G,r), cov_vs
    (B,H,r,hd))``.
    """
    cs_r = jnp.take_along_axis(cs, idx_r[:, :, None, :], axis=3)   # (B,H,G,r)
    sz_r = jnp.take_along_axis(state.size, idx_r, axis=2)          # (B,H,r)
    st_r = jnp.take_along_axis(state.stored, idx_r, axis=2)
    vs_r = jnp.take_along_axis(state.vsum, idx_r[..., None], axis=2)
    over = jnp.maximum(sz_r - st_r, 0).astype(jnp.float32)         # (B,H,r)
    st_eff = sz_r.astype(jnp.float32) - over                       # stored part
    frac = st_eff / jnp.maximum(sz_r.astype(jnp.float32), 1.0)
    log_st = jnp.where(st_eff > 0, jnp.log(jnp.maximum(st_eff, 1.0)), NEG)
    cov_logit = jnp.where(st_eff[:, :, None, :] > 0,
                          cs_r + log_st[:, :, None, :], NEG)
    cov_vs = vs_r * frac[..., None]
    return cov_logit, cs_r, cov_vs


ATTN_IMPLS = ("jnp", "fused", "pallas")


def resolve_attn_impl(impl: Optional[str]) -> str:
    """Normalize an attention-impl selection. ``None`` -> "jnp"; "fused"
    (paged gather-free kernel) auto-resolves to the interpretable path on CPU
    inside the kernel wrapper; "pallas" is the legacy gathered-buffer kernel."""
    impl = impl or "jnp"
    if impl not in ATTN_IMPLS:
        raise ValueError(f"unknown attn impl {impl!r}; expected {ATTN_IMPLS}")
    return impl


def _local_positions(state: WaveState):
    """Absolute position of every local-buffer slot, -1 for empty. (B, lbuf)."""
    lbuf = state.local_k.shape[2]
    l0 = state.length - state.local_len              # (B,) abs pos of buffer[0]
    local_pos = l0[:, None] + jnp.arange(lbuf, dtype=jnp.int32)[None, :]
    return jnp.where(jnp.arange(lbuf)[None, :] < state.local_len[:, None],
                     local_pos, -1)                  # (B, lbuf)


def _fused_wave_attention(qg, state: WaveState, idx_r, est_logit, cs_e, vs_e,
                          *, window, softcap, kv_src=None, valid=None):
    """Gather-free decode merge: hand the raw zones to the paged Pallas
    kernel (``kernels.wave_attention``), which walks sink -> local buffer ->
    the r retrieved clusters IN PLACE via scalar-prefetched ids and folds the
    estimation zone into the same online softmax. No (B, H, r, cap, hd)
    gather temp, no execution-buffer concat.

    ``kv_src``: optional ``(k_blocks, v_blocks, pos_blocks)`` replacing the
    state's monolithic cluster stores as the block source — the cache-slot
    indirection hook of the host-offload serve path, where ``idx_r`` holds
    device-cache slots (hits + per-step miss staging slots) instead of
    cluster ids. Block payloads are bit-identical either way, so placement
    never changes the result."""
    from repro.kernels.wave_attention import ops as wa_ops
    B, Hkv, G, hd = qg.shape
    r = idx_r.shape[2]
    k_blk, v_blk, p_blk = kv_src if kv_src is not None else (
        state.k_store, state.v_store, state.pos_store)
    q_pos = state.length - 1                                   # (B,)

    # per-row validity bounds: pos <= hi (= q_pos) and pos > lo. ``lo`` folds
    # the sliding window: for integer positions p, p > q_pos - window (the
    # f32 comparison of the jnp path) <=> p > floor(q_pos - window).
    hi = q_pos.astype(jnp.int32)
    if window is None:
        lo = jnp.full_like(hi, -1)
    else:
        lo = jnp.floor(q_pos.astype(jnp.float32)
                       - jnp.asarray(window, jnp.float32)).astype(jnp.int32)
        lo = jnp.maximum(lo, -1)
    rowb = jnp.broadcast_to(
        jnp.stack([lo, hi], axis=-1)[:, None, :], (B, Hkv, 2))

    local_pos = jnp.broadcast_to(_local_positions(state)[:, None, :],
                                 (B, Hkv, state.local_k.shape[2]))
    if r == 0:            # steady-zone-only plan: pad one dead retrieval slot
        idx_k = jnp.zeros((B, Hkv, 1), jnp.int32)
        live = jnp.zeros((B, Hkv, 1), jnp.int32)
    else:
        idx_k = idx_r
        # degraded decode: the per-cluster validity mask rides the kernel's
        # existing ``live`` operand — an invalid (fetch-failed) cluster is
        # skipped by the paged walk exactly like a dead padding slot.
        live = (valid.astype(jnp.int32) if valid is not None
                else jnp.ones((B, Hkv, r), jnp.int32))

    return wa_ops.paged_wave_attention(
        qg, state.sink_k, state.sink_v, state.local_k, state.local_v,
        local_pos, k_blk, v_blk, p_blk, idx_k,
        live, rowb, est_logit, cs_e, vs_e, softcap=softcap,
        interpret=wa_ops.on_cpu())


def wave_attention_decode(q: jax.Array, state: WaveState, retro: RetroConfig,
                          plan: ZonePlan, *, window: Optional[jax.Array] = None,
                          softcap: Optional[float] = None,
                          use_estimation: bool = True,
                          overflow_correction: bool = True,
                          impl: str = "jnp", cluster_offset=0,
                          include_steady=True,
                          return_parts: bool = False) -> WaveAttnOut:
    """One decode step of tripartite attention.

    q: (B, Hq, hd) — query at position state.length - 1 (the current token's
    K/V must already be appended to the local buffer).

    ``impl``: "jnp" (reference execution-buffer path), "fused" (gather-free
    paged Pallas kernel — zones read in place, interpret mode on CPU), or
    "pallas" (legacy gathered-buffer kernel). ``return_parts`` and sharded
    retrieval always use the reference path.

    Sharded-retrieval hooks (core.distributed): ``cluster_offset`` maps local
    cluster ids to global for validity; ``include_steady`` (may be traced)
    gates the steady zone so exactly one shard contributes it;
    ``return_parts`` yields the unnormalized (num, den, m, idx_r) for a
    cross-shard LSE merge.
    """
    B, Hq, hd = q.shape
    Hkv = state.centroid.shape[1]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, hd)
    impl = resolve_attn_impl(impl)

    idx_r, est_logit, cs_e, vs_e = wave_decode_rank(
        qg, state, retro, plan, window=window, softcap=softcap,
        use_estimation=use_estimation,
        overflow_correction=overflow_correction,
        cluster_offset=cluster_offset)
    return wave_attention_attend(
        q, state, retro, plan, idx_r, est_logit, cs_e, vs_e, window=window,
        softcap=softcap, impl=impl, include_steady=include_steady,
        return_parts=return_parts)


@trace.scope(trace.RANK)
def wave_decode_rank(qg, state: WaveState, retro: RetroConfig, plan: ZonePlan,
                     *, window: Optional[jax.Array] = None,
                     softcap: Optional[float] = None,
                     use_estimation: bool = True,
                     overflow_correction: bool = True, cluster_offset=0,
                     with_cover: bool = False):
    """Control-plane half of the decode step: rank clusters and build the
    estimation-zone inputs. Touches only the META index (centroids, value
    sums, sizes) and per-row counters — never the cluster payload stores —
    so the host-offload serve path can run it with the payload stores absent,
    translate ``idx_r`` through its ``ClusterMappingTable``, and hand cache
    slots to :func:`wave_attention_attend`.

    qg: (B, Hkv, G, hd). Returns (idx_r, est_logit, cs_e, vs_e); with
    ``with_cover`` additionally the :func:`_retrieval_cover` triple the
    attend half needs to estimation-cover fetch-failed clusters (degraded
    decode) — computed here because the attend half of the offload path has
    no access to the meta index."""
    cs, idx_re = rank_clusters(qg, state, plan, window, softcap,
                               cluster_offset)
    idx_r, idx_e = idx_re[:, :, :plan.r], idx_re[:, :, plan.r:]
    est_logit, cs_e, vs_e = _estimation_zone(
        state, cs, idx_r, idx_e, use_estimation=use_estimation,
        overflow_correction=overflow_correction)
    if with_cover:
        cover = _retrieval_cover(state, cs, idx_r)
        return idx_r, est_logit, cs_e, vs_e, cover
    return idx_r, est_logit, cs_e, vs_e


@trace.scope(trace.ATTEND)
def wave_attention_attend(q, state: WaveState, retro: RetroConfig,
                          plan: ZonePlan, idx, est_logit, cs_e, vs_e, *,
                          kv_src=None, window: Optional[jax.Array] = None,
                          softcap: Optional[float] = None, impl: str = "jnp",
                          include_steady=True, return_parts: bool = False,
                          valid=None, cover=None):
    """Data-plane half of the decode step: exact attention over the steady
    zone plus the ``idx``-addressed blocks of ``kv_src``, merged with the
    estimation zone.

    ``kv_src``: optional ``(k_blocks, v_blocks, pos_blocks)`` with leading
    dims (B, Hkv, N_slots, ...) replacing the state's monolithic cluster
    stores as the block source. This is the cache-slot indirection of the
    host-offload configuration: ``idx`` then holds device-cache slots
    (cache hits + per-step miss staging slots) translated on the control
    plane, not cluster ids. Block payloads are identical bits either way, so
    cache placement is accuracy-agnostic.

    ``valid``: optional (B, Hkv, r) per-cluster validity mask (degraded
    decode): a 0 cluster is masked OUT of the retrieval zone — its blocks
    never fetched in time — and, when ``cover`` (the
    :func:`_retrieval_cover` triple from ``wave_decode_rank(...,
    with_cover=True)``) is given, its attention mass re-enters through the
    estimation zone. With an all-ones mask the cover entries are NEG/zero
    gated and the result is bit-identical to ``valid=None``."""
    B, Hq, hd = q.shape
    Hkv = state.centroid.shape[1]
    G = Hq // Hkv
    r = idx.shape[2]
    q_pos = state.length - 1                               # (B,) per-row
    qg = q.reshape(B, Hkv, G, hd)
    impl = resolve_attn_impl(impl)

    # ---- degraded decode: estimation-cover the masked-out clusters ---------
    # A valid cluster's cover entry is gated to (NEG logit, zero vsum): it
    # contributes exactly 0.0 to num/den and cannot move the softmax max, so
    # all-valid steps are bit-identical with or without the cover concat.
    if valid is not None and cover is not None and r > 0:
        v_ok = valid > 0                                   # (B, Hkv, r)
        cov_logit, cov_cs, cov_vs = cover
        cov_logit = jnp.where(v_ok[:, :, None, :], NEG, cov_logit)
        cov_vs = jnp.where(v_ok[..., None], 0.0, cov_vs)
        est_logit = jnp.concatenate([est_logit, cov_logit], axis=3)
        cs_e = jnp.concatenate([cs_e, cov_cs], axis=3)
        vs_e = jnp.concatenate([vs_e, cov_vs], axis=2)

    # ---- gather-free paged kernel: zones handed over unconcatenated --------
    # (the sharded return_parts merge keeps the reference path: partial
    # (num, den, m) are what shards LSE-combine, see core.distributed)
    if impl == "fused" and not return_parts and include_steady is True:
        out = _fused_wave_attention(qg, state, idx, est_logit, cs_e, vs_e,
                                    window=window, softcap=softcap,
                                    kv_src=kv_src, valid=valid)
        return WaveAttnOut(out.reshape(B, Hq, hd).astype(q.dtype), idx)

    # ---- execution buffer: steady zone + retrieved blocks ------------------
    if kv_src is None:
        kb, vb, pb = _gather_clusters(state, idx)          # (B,H,r,cap,hd)
    else:
        k_blk, v_blk, p_blk = kv_src
        take = lambda a: jnp.take_along_axis(
            a, idx.reshape(idx.shape + (1,) * (a.ndim - 3)), axis=2)
        kb, vb, pb = take(k_blk), take(v_blk), take(p_blk)
    cap = kb.shape[3]
    k_ret = kb.reshape(B, Hkv, r * cap, hd)
    v_ret = vb.reshape(B, Hkv, r * cap, hd)
    p_ret = pb.reshape(B, Hkv, r * cap)

    sink_pos = jnp.broadcast_to(jnp.arange(retro.sink, dtype=jnp.int32),
                                (B, Hkv, retro.sink))
    lbuf = state.local_k.shape[2]
    local_pos = jnp.broadcast_to(_local_positions(state)[:, None, :],
                                 (B, Hkv, lbuf))

    k_exec = jnp.concatenate([state.sink_k, state.local_k, k_ret], axis=2)
    v_exec = jnp.concatenate([state.sink_v, state.local_v, v_ret], axis=2)
    p_exec = jnp.concatenate([sink_pos, local_pos, p_ret], axis=2)

    # ---- validity mask over the execution buffer (per-row q_pos) -----------
    qp = q_pos[:, None, None]
    ok = (p_exec >= 0) & (p_exec <= qp)
    if window is not None:
        ok = ok & (p_exec > qp - window)
    if valid is not None and r > 0:        # degraded decode: mask failed
        ret_ok = jnp.repeat(valid > 0, cap, axis=2)        # (B,Hkv,r·cap)
        n_steady = p_exec.shape[2] - r * cap
        ok = ok & jnp.concatenate(
            [jnp.ones((B, Hkv, n_steady), bool), ret_ok], axis=2)
    if include_steady is not True:                 # traced gate (sharding)
        n_steady = retro.sink + lbuf
        is_steady = jnp.arange(p_exec.shape[2]) < n_steady
        ok = ok & (jnp.asarray(include_steady) | ~is_steady)

    if return_parts:
        num, den, m = tripartite_merge_parts_jnp(
            qg, k_exec, v_exec, ok, est_logit, cs_e, vs_e, softcap=softcap)
        return num, den, m, idx
    out = tripartite_merge(qg, k_exec, v_exec, ok, est_logit, cs_e, vs_e,
                           softcap=softcap, impl=impl)
    return WaveAttnOut(out.reshape(B, Hq, hd).astype(q.dtype), idx)


def tripartite_merge_parts_jnp(qg, k_exec, v_exec, valid, est_logit, cs_e,
                               vs_e, *, softcap: Optional[float] = None):
    """Unnormalized fused merge: returns (num (B,H,G,hd), den (B,H,G),
    m (B,H,G)) with num/den scaled by exp(-m). Distribution-friendly: partial
    results from shards LSE-combine via pmax/psum (core.distributed)."""
    hd = qg.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    # keep K/V operands in their storage dtype (bf16) with f32 ACCUMULATION:
    # an explicit .astype(f32) gets hoisted through the gather by XLA and
    # converts the ENTIRE cluster store every step (§Perf iteration, ~2x the
    # store in temps + bytes). MXU takes bf16 natively; accumulate in f32.
    s = jnp.einsum("bhgd,bhtd->bhgt", qg.astype(k_exec.dtype), k_exec,
                   preferred_element_type=jnp.float32) * scale
    s = soft_cap(s, softcap)
    s = jnp.where(valid[:, :, None, :], s, NEG)

    m = jnp.maximum(jnp.max(s, axis=-1), jnp.max(est_logit, axis=-1))  # (B,H,G)
    m = jnp.maximum(m, -1e20)
    p = jnp.exp(s - m[..., None])
    den = jnp.sum(p, axis=-1)
    num = jnp.einsum("bhgt,bhtd->bhgd", p.astype(v_exec.dtype), v_exec,
                     preferred_element_type=jnp.float32)

    live = est_logit > NEG / 2
    w_den = jnp.where(live, jnp.exp(est_logit - m[..., None]), 0.0)    # s_i·e^{cs}
    w_num = jnp.where(live, jnp.exp(cs_e - m[..., None]), 0.0)         # e^{cs}
    den = den + jnp.sum(w_den, axis=-1)
    num = num + jnp.einsum("bhge,bhed->bhgd", w_num, vs_e.astype(jnp.float32))
    return num, den, m


def tripartite_merge_jnp(qg, k_exec, v_exec, valid, est_logit, cs_e, vs_e, *,
                         softcap: Optional[float] = None) -> jax.Array:
    """Reference fused exact-attention + estimation merge.

    qg: (B,H,G,hd); k_exec/v_exec: (B,H,T,hd); valid: (B,H,T) bool;
    est_logit/cs_e: (B,H,G,E) f32 (NEG-masked); vs_e: (B,H,E,hd) f32.
    Returns (B,H,G,hd) f32. The Pallas kernel in
    ``repro.kernels.wave_attention`` implements identical semantics.
    """
    num, den, _ = tripartite_merge_parts_jnp(
        qg, k_exec, v_exec, valid, est_logit, cs_e, vs_e, softcap=softcap)
    return num / jnp.maximum(den, 1e-30)[..., None]


def tripartite_merge(qg, k_exec, v_exec, valid, est_logit, cs_e, vs_e, *,
                     softcap: Optional[float] = None, impl: str = "jnp"):
    if impl == "jnp":
        return tripartite_merge_jnp(qg, k_exec, v_exec, valid, est_logit,
                                    cs_e, vs_e, softcap=softcap)
    from repro.kernels.wave_attention import ops as wa_ops
    return wa_ops.wave_attention_merge(qg, k_exec, v_exec, valid, est_logit,
                                       cs_e, vs_e, softcap=softcap,
                                       interpret=wa_ops.on_cpu())


# ---------------------------------------------------------------------------
# Dense full-attention decode baseline (paper's "full attention" comparator)
# ---------------------------------------------------------------------------

class DenseCache(NamedTuple):
    k: jax.Array            # (B, H, S_max, hd)
    v: jax.Array            # (B, H, S_max, hd)
    length: jax.Array       # (B,) int32 — valid prefix per row


def init_dense_cache(B, H, S_max, hd, dtype=jnp.bfloat16) -> DenseCache:
    return DenseCache(jnp.zeros((B, H, S_max, hd), dtype),
                      jnp.zeros((B, H, S_max, hd), dtype),
                      jnp.zeros((B,), jnp.int32))


def dense_cache_append(cache: DenseCache, k_new, v_new,
                       active: Optional[jax.Array] = None) -> DenseCache:
    """Append (B, H, hd) K/V at each row's own cursor. ``active``: optional
    (B,) bool — inactive rows (free continuous-batching slots) are untouched.
    Right-padded ragged prefills stay correct: appends overwrite the pad slots
    just past each row's true length, so ``pos < length`` only ever admits
    real tokens.

    The mask is applied to the per-row write CURSOR, not the cache: an
    inactive row routes its write out of range, which the dropped scatter
    discards — O(token) per step, in place on the donated cache. The previous
    ``jnp.where(active, new, cache)`` select read AND wrote the full cache
    every step (§Perf: asserted via cost_analysis in tests). A row at
    capacity likewise drops the append instead of clobbering its last slot —
    and its cursor stays put, so ``length`` never claims tokens the cache
    doesn't hold.
    """
    S_max = cache.k.shape[2]
    idx = cache.length
    step = jnp.ones_like(cache.length)
    if active is not None:
        act = jnp.asarray(active)
        idx = jnp.where(act, idx, S_max)       # out of range => dropped write
        step = act.astype(cache.length.dtype)
    step = jnp.where(cache.length < S_max, step, 0)

    def row(buf, new, i):
        return buf.at[:, i].set(new.astype(buf.dtype), mode="drop")

    new_k = jax.vmap(row)(cache.k, k_new, idx)
    new_v = jax.vmap(row)(cache.v, v_new, idx)
    return DenseCache(new_k, new_v, cache.length + step)


def full_attention_decode(q, cache: DenseCache, *, window=None, softcap=None):
    """q: (B, Hq, hd) vs the dense cache. Exact softmax over valid positions
    (per-row lengths)."""
    B, Hq, hd = q.shape
    Hkv = cache.k.shape[1]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, Hkv, G, hd)
    # storage-dtype operands + f32 ACCUMULATION (same contract as the wave
    # merge above): an explicit cache.astype(f32) is hoisted by XLA and
    # rewrites the whole (B,H,S_max,hd) cache every step — RL402.
    s = jnp.einsum("bhgd,bhtd->bhgt", qg.astype(cache.k.dtype), cache.k,
                   preferred_element_type=jnp.float32) * scale
    s = soft_cap(s, softcap)
    pos = jnp.arange(cache.k.shape[2])
    ok = pos[None, :] < cache.length[:, None]              # (B, T)
    if window is not None:
        ok = ok & (pos[None, :] > cache.length[:, None] - 1 - window)
    s = jnp.where(ok[:, None, None, :], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgt,bhtd->bhgd", p.astype(cache.v.dtype), cache.v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Hq, hd).astype(q.dtype)
