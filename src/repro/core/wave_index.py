"""Wave index: attention-aware cluster index over the KV cache (paper Sec. 4.2).

Per attention layer the index state holds, for every (batch, kv_head):

* fixed-capacity cluster stores (keys/values/positions) in "CPU memory" —
  on TPU: sharded HBM (see DESIGN §2),
* the meta index (centroid, value-sum, size) — small, fast-memory resident,
* the steady zone: attention sinks + a local-window ring buffer that doubles
  as the staging area for decode-time segmented clustering (flushed into new
  clusters every ``update_segment`` generated tokens).

All shapes are static. Sequence bookkeeping (``length``, ``local_len``,
``n_clusters``) is PER ROW — (B,) arrays — so a single state can hold ragged
requests at different positions, admitted and flushed independently
(continuous batching). Batch-uniform callers simply see every row agree.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import RetroConfig
from repro.core.clustering import (ClusterResult, cluster_segment,
                                   segmented_cluster)
from repro.serving import trace


class WaveState(NamedTuple):
    """Per-layer wave-index state. Leading dims: (B, Hkv, ...)."""
    k_store: jax.Array      # (B, H, M, cap, hd)
    v_store: jax.Array      # (B, H, M, cap, hd)
    pos_store: jax.Array    # (B, H, M, cap) int32, -1 = pad
    centroid: jax.Array     # (B, H, M, hd) f32 — meta index
    vsum: jax.Array         # (B, H, M, hd) f32 — meta index
    size: jax.Array         # (B, H, M) int32  — meta index
    stored: jax.Array       # (B, H, M) int32
    max_pos: jax.Array      # (B, H, M) int32
    n_clusters: jax.Array   # (B,) int32 — active clusters per row
    sink_k: jax.Array       # (B, H, sink, hd)
    sink_v: jax.Array       # (B, H, sink, hd)
    local_k: jax.Array      # (B, H, Lbuf, hd) ring/staging buffer
    local_v: jax.Array      # (B, H, Lbuf, hd)
    local_len: jax.Array    # (B,) int32 — valid tail of the local buffer
    length: jax.Array       # (B,) int32 — total tokens seen per row


def local_buffer_size(retro: RetroConfig) -> int:
    return retro.local + retro.update_segment


def prefill_layout(seq_len: int, retro: RetroConfig) -> Tuple[int, int, int]:
    """(n_full_segments, tail_len, n_prefill_clusters) for a prompt of seq_len.

    Clustered region = [sink, seq_len - local); full segments of
    ``prefill_segment`` plus one partial tail segment. Prompts shorter than
    sink + local have an empty clustered region (steady-zone-only plan) —
    the region is clamped to >= 0 so counts never go negative.
    """
    region = max(0, seq_len - retro.sink - retro.local)
    n_full = region // retro.prefill_segment
    tail = region - n_full * retro.prefill_segment
    m = n_full * (retro.prefill_segment // retro.avg_cluster)
    if tail > 0:
        m += max(1, tail // retro.avg_cluster)
    return n_full, tail, m


def max_clusters(seq_len: int, retro: RetroConfig, gen_headroom: int = 4096,
                 pad_multiple: int = 256) -> int:
    """Static cluster-store size: prefill clusters + decode-flush headroom,
    rounded up so the cluster axis divides the production 'model' mesh axis
    (padded clusters sit beyond ``n_clusters`` and are masked everywhere)."""
    _, _, m = prefill_layout(seq_len, retro)
    m = m + (gen_headroom // retro.update_segment) * (
        retro.update_segment // retro.avg_cluster)
    return max(pad_multiple, ((m + pad_multiple - 1) // pad_multiple) * pad_multiple)


def init_wave_state(B: int, H: int, hd: int, M: int, retro: RetroConfig,
                    dtype=jnp.bfloat16) -> WaveState:
    cap, sink, lbuf = retro.cluster_cap, retro.sink, local_buffer_size(retro)
    z = jnp.zeros
    return WaveState(
        k_store=z((B, H, M, cap, hd), dtype), v_store=z((B, H, M, cap, hd), dtype),
        pos_store=jnp.full((B, H, M, cap), -1, jnp.int32),
        centroid=z((B, H, M, hd), jnp.float32), vsum=z((B, H, M, hd), jnp.float32),
        size=z((B, H, M), jnp.int32), stored=z((B, H, M), jnp.int32),
        max_pos=jnp.full((B, H, M), -1, jnp.int32),
        n_clusters=jnp.zeros((B,), jnp.int32),
        sink_k=z((B, H, sink, hd), dtype), sink_v=z((B, H, sink, hd), dtype),
        local_k=z((B, H, lbuf, hd), dtype), local_v=z((B, H, lbuf, hd), dtype),
        local_len=jnp.zeros((B,), jnp.int32), length=jnp.zeros((B,), jnp.int32),
    )


def _write_clusters(state: WaveState, res: ClusterResult, offset) -> WaveState:
    """Write a block of freshly clustered segments at cluster ``offset``.

    res leaves have leading (B, H, k_new, ...); offset is per-row (B,) (a
    scalar broadcasts) and may be traced — rows at different fill levels
    receive their new clusters at different slots.

    ``None`` payload stores (the host-offload live view — k/v/pos live
    host-side) pass through untouched: only the meta index is written.
    """
    B = state.size.shape[0]
    off = jnp.broadcast_to(jnp.asarray(offset, jnp.int32), (B,))

    def upd(store, new):
        if store is None:
            return None
        def row(sb, nb, ob):
            start = (0, ob) + (0,) * (nb.ndim - 2)
            return jax.lax.dynamic_update_slice(sb, nb.astype(sb.dtype), start)
        return jax.vmap(row)(store, new, off)

    return state._replace(
        k_store=upd(state.k_store, res.k_store),
        v_store=upd(state.v_store, res.v_store),
        pos_store=upd(state.pos_store, res.pos_store),
        centroid=upd(state.centroid, res.centroid),
        vsum=upd(state.vsum, res.vsum),
        size=upd(state.size, res.size),
        stored=upd(state.stored, res.stored),
        max_pos=upd(state.max_pos, res.max_pos),
        n_clusters=state.n_clusters + res.size.shape[2],
    )


def prefill_build(k: jax.Array, v: jax.Array, retro: RetroConfig, M: int,
                  dtype=None, lengths: Optional[jax.Array] = None) -> WaveState:
    """Build the wave index from prefill K/V.

    k, v: (B, S, H, hd) post-RoPE. Returns a WaveState with the prompt's
    sink/local/steady zones populated and all segments clustered.

    ``lengths``: optional (B,) int32 true prompt lengths for right-padded
    ragged batches (each row's real tokens occupy [0, lengths[b])). Each row's
    local window is its last ``local`` REAL tokens and only tokens in
    [sink, lengths[b] - local) enter clusters — padding never reaches a store,
    so it can never leak into attention as generation extends past it.
    Requires lengths[b] >= sink + local. None = every row uses all S tokens.
    """
    B, S, H, hd = k.shape
    dtype = dtype or k.dtype
    retro_sink = retro.sink
    # S <= sink would under-fill the fixed-width sink zone, whose positions
    # are implicit (arange(sink)): the empty slots' zero keys would become
    # attendable once generation pushes length past them.
    if S <= retro_sink:
        raise ValueError(
            f"prompt length {S} must exceed the sink width {retro_sink}")
    local = min(retro.local, max(S - retro_sink, 0))
    n_full, tail, _ = prefill_layout(S, retro)
    state = init_wave_state(B, H, hd, M, retro, dtype)

    kbh = jnp.swapaxes(k, 1, 2)                            # (B, H, S, hd)
    vbh = jnp.swapaxes(v, 1, 2)

    if lengths is None:
        lens = jnp.full((B,), S, jnp.int32)
        valid = None
    else:
        lens = jnp.asarray(lengths, jnp.int32)
        # cluster-valid tokens: [sink, lens - local) per row
        valid = jnp.arange(S)[None, :] < (lens - local)[:, None]

    # per-row local window: the last ``local`` real tokens [lens-local, lens)
    win0 = jnp.maximum(lens - local, 0)

    def take_local(xb, s):
        return jax.lax.dynamic_slice(xb, (0, s, 0), (H, local, hd))

    lk = jax.vmap(take_local)(kbh, win0).astype(state.local_k.dtype)
    lv = jax.vmap(take_local)(vbh, win0).astype(state.local_v.dtype)

    state = state._replace(
        sink_k=kbh[:, :, :retro_sink].astype(state.sink_k.dtype),
        sink_v=vbh[:, :, :retro_sink].astype(state.sink_v.dtype),
        local_k=jax.lax.dynamic_update_slice(state.local_k, lk, (0, 0, 0, 0)),
        local_v=jax.lax.dynamic_update_slice(state.local_v, lv, (0, 0, 0, 0)),
        local_len=jnp.full((B,), local, jnp.int32),
        length=lens,
    )

    pos = jnp.arange(S, dtype=jnp.int32)
    seg = retro.prefill_segment

    if n_full > 0:
        s0, span = retro_sink, n_full * seg

        def row_full(kk, vv, vm):
            def bh(k1, v1):
                return segmented_cluster(
                    k1[s0:s0 + span], v1[s0:s0 + span], pos[s0:s0 + span],
                    seg, retro.avg_cluster, retro.cluster_cap,
                    retro.kmeans_iters, retro.centering,
                    serial=retro.serial_prefill_segments, valid=vm)
            return jax.vmap(bh)(kk, vv)

        if valid is None:
            res = jax.vmap(partial(row_full, vm=None))(kbh, vbh)
        else:
            res = jax.vmap(row_full)(kbh, vbh, valid[:, s0:s0 + span])
        state = _write_clusters(state, res, 0)

    if tail > 0:
        t0 = retro_sink + n_full * seg

        def row_tail(kk, vv, vm):
            def bh(k1, v1):
                return cluster_segment(k1[t0:t0 + tail], v1[t0:t0 + tail],
                                       pos[t0:t0 + tail], retro.avg_cluster,
                                       retro.cluster_cap, retro.kmeans_iters,
                                       retro.centering, valid=vm)
            return jax.vmap(bh)(kk, vv)

        if valid is None:
            res_t = jax.vmap(partial(row_tail, vm=None))(kbh, vbh)
        else:
            res_t = jax.vmap(row_tail)(kbh, vbh, valid[:, t0:t0 + tail])
        state = _write_clusters(state, res_t, state.n_clusters)

    return state


# ---------------------------------------------------------------------------
# Chunked (streaming) prefill build — admission interleaved with decode.
#
# ``prefill_build`` consumes the whole prompt at once; a serving engine that
# wants to admit a request WITHOUT stalling in-flight decodes instead streams
# the prompt through ``prefill_append_chunk`` a fixed-size chunk at a time and
# closes the build with ``prefill_finalize``. The final WaveState is
# bit-identical to ``prefill_build`` on the full prompt for ANY chunk split:
# segment boundaries are position- (not chunk-) aligned, and a full segment is
# only clustered once ``local`` further tokens have arrived — those tokens can
# no longer end up in the final local window, so greedy flushing reproduces
# exactly the segments the monolithic layout would cluster.
# ---------------------------------------------------------------------------


class ChunkedPrefill(NamedTuple):
    """Streaming prefill-build state.

    ``state`` is the WaveState under construction: the sink zone and cluster
    stores fill as chunks arrive; the local window and length bookkeeping are
    written by ``prefill_finalize``. ``stage_*`` hold the not-yet-clustered
    tokens past the sink — row b's staged tokens sit at absolute positions
    [seen[b] - staged[b], seen[b]).
    """
    state: WaveState
    stage_k: jax.Array      # (B, H, stage_cap, hd)
    stage_v: jax.Array
    staged: jax.Array       # (B,) int32 — valid tokens in the staging buffer
    seen: jax.Array         # (B,) int32 — prompt tokens consumed so far


def stage_capacity(retro: RetroConfig, chunk: int) -> int:
    """Staging-buffer size for chunked prefill: between flushes the buffer
    holds < prefill_segment + local tokens, plus one incoming chunk."""
    return retro.prefill_segment + retro.local + chunk


def init_chunked_prefill(B: int, H: int, hd: int, M: int, retro: RetroConfig,
                         chunk: int, dtype=jnp.bfloat16,
                         stage_dtype=None) -> ChunkedPrefill:
    """Fresh streaming build for prompts fed in chunks of <= ``chunk`` tokens.

    ``stage_dtype`` should match the dtype of the incoming K/V chunks (default:
    ``dtype``) — clustering reads the staged copies, and bit-identity with
    ``prefill_build`` (which clusters the raw input) needs them unconverted.
    """
    cap = stage_capacity(retro, chunk)
    sd = dtype if stage_dtype is None else stage_dtype
    return ChunkedPrefill(
        state=init_wave_state(B, H, hd, M, retro, dtype),
        stage_k=jnp.zeros((B, H, cap, hd), sd),
        stage_v=jnp.zeros((B, H, cap, hd), sd),
        staged=jnp.zeros((B,), jnp.int32),
        seen=jnp.zeros((B,), jnp.int32))


def _where_rows(rows: jax.Array, new, old):
    """Per-row select over matching pytrees (leading dim B)."""
    B = rows.shape[0]
    return jax.tree.map(
        lambda n, o: jnp.where(rows.reshape((B,) + (1,) * (n.ndim - 1)), n, o),
        new, old)


def scatter_chunk_rows(buf: jax.Array, chunk: jax.Array,
                       idx: jax.Array) -> jax.Array:
    """Per-row scatter of a token chunk into a buffer's token axis.

    buf: (B, H, N, hd); chunk: (B, H, C, hd); idx: (B, C) target token slots —
    out-of-range entries (>= N) are DROPPED, so callers route/pad by clamping
    unwanted writes past the end instead of masking."""
    return jax.vmap(
        lambda b, c, i: b.at[:, i].set(c.astype(b.dtype), mode="drop")
    )(buf, chunk, idx)


def _flush_stage(cp: ChunkedPrefill, retro: RetroConfig) -> ChunkedPrefill:
    """Cluster the oldest full prefill segment of each SAFE staging buffer.

    A row is flushed when its staging buffer holds prefill_segment + local
    tokens: the oldest segment then provably ends >= ``local`` before the
    final prompt end, so it is one of the full segments ``prefill_build``
    would cluster. Rows below the threshold pass through bit-unchanged.
    """
    seg = retro.prefill_segment
    rows = cp.staged >= seg + retro.local
    start = cp.seen - cp.staged                  # abs position of stage[0]
    pos = start[:, None] + jnp.arange(seg, dtype=jnp.int32)[None, :]

    def row_fn(kk, vv, p):
        def bh(k1, v1):
            return cluster_segment(k1[:seg], v1[:seg], p, retro.avg_cluster,
                                   retro.cluster_cap, retro.kmeans_iters,
                                   retro.centering)
        return jax.vmap(bh)(kk, vv)

    res = jax.vmap(row_fn)(cp.stage_k, cp.stage_v, pos)
    flushed = _write_clusters(cp.state, res, cp.state.n_clusters)
    return ChunkedPrefill(
        state=_where_rows(rows, flushed, cp.state),
        stage_k=_where_rows(rows, jnp.roll(cp.stage_k, -seg, axis=2),
                            cp.stage_k),
        stage_v=_where_rows(rows, jnp.roll(cp.stage_v, -seg, axis=2),
                            cp.stage_v),
        staged=jnp.where(rows, cp.staged - seg, cp.staged),
        seen=cp.seen)


def prefill_append_chunk(cp: ChunkedPrefill, k_chunk: jax.Array,
                         v_chunk: jax.Array, retro: RetroConfig,
                         chunk_lens: Optional[jax.Array] = None
                         ) -> ChunkedPrefill:
    """Extend a streaming build with the next (B, C, H, hd) chunk of prompt K/V.

    Tokens are routed by absolute position: positions < sink fill the sink
    zone, the rest append to the staging buffer; whenever a row has staged a
    full ``prefill_segment`` plus the ``local`` safety margin, the oldest
    segment is clustered (per-row masked) exactly as ``prefill_build`` would.

    ``chunk_lens``: optional (B,) int32 valid prefix of this chunk per row
    (right-padded final chunks; rows may advance at different rates — a row
    with 0 consumes nothing and is bit-unchanged).
    """
    B, C, H, hd = k_chunk.shape
    sink = retro.sink
    clens = jnp.full((B,), C, jnp.int32) if chunk_lens is None \
        else jnp.asarray(chunk_lens, jnp.int32)
    with trace.scope(trace.CACHE_UPDATE):
        kc = jnp.swapaxes(k_chunk, 1, 2)                    # (B, H, C, hd)
        vc = jnp.swapaxes(v_chunk, 1, 2)

        j = jnp.arange(C, dtype=jnp.int32)[None, :]         # (1, C)
        p = cp.seen[:, None] + j                            # (B, C) abs pos
        valid = j < clens[:, None]

        # scatter with out-of-range index => dropped write (per-row routing)
        sink_idx = jnp.where(valid & (p < sink), p, sink)
        j0 = jnp.clip(sink - cp.seen, 0, C)                 # first staged j
        stage_cap = cp.stage_k.shape[2]
        stage_idx = jnp.where(valid & (p >= sink),
                              cp.staged[:, None] + j - j0[:, None], stage_cap)

        scat = scatter_chunk_rows
        state = cp.state._replace(sink_k=scat(cp.state.sink_k, kc, sink_idx),
                                  sink_v=scat(cp.state.sink_v, vc, sink_idx))
        cp = ChunkedPrefill(
            state=state,
            stage_k=scat(cp.stage_k, kc, stage_idx),
            stage_v=scat(cp.stage_v, vc, stage_idx),
            staged=cp.staged + (clens - jnp.clip(sink - cp.seen, 0, clens)),
            seen=cp.seen + clens)
    # a C-token chunk can complete at most ceil(C / segment) segments
    with trace.scope(trace.INDEX_BUILD):
        for _ in range(-(-C // retro.prefill_segment)):
            cp = _flush_stage(cp, retro)
    return cp


@trace.scope(trace.INDEX_BUILD)
def prefill_finalize(cp: ChunkedPrefill, retro: RetroConfig,
                     total_len: int) -> WaveState:
    """Close a streaming build: cluster the partial tail segment and install
    the local window. ``total_len`` is static and must equal every row's
    consumed token count (``cp.seen``); rows that streamed at different rates
    must have converged. The result is bit-identical to ``prefill_build`` on
    the same prompt."""
    if total_len <= retro.sink:
        raise ValueError(
            f"prompt length {total_len} must exceed the sink width {retro.sink}")
    local = min(retro.local, total_len - retro.sink)
    _, tail, _ = prefill_layout(total_len, retro)
    state = cp.state
    B, H, _, hd = state.local_k.shape

    if tail > 0:
        start = cp.seen - cp.staged
        pos = start[:, None] + jnp.arange(tail, dtype=jnp.int32)[None, :]

        def row_fn(kk, vv, p):
            def bh(k1, v1):
                return cluster_segment(k1[:tail], v1[:tail], p,
                                       retro.avg_cluster, retro.cluster_cap,
                                       retro.kmeans_iters, retro.centering)
            return jax.vmap(bh)(kk, vv)

        res = jax.vmap(row_fn)(cp.stage_k, cp.stage_v, pos)
        state = _write_clusters(state, res, state.n_clusters)

    lk = cp.stage_k[:, :, tail:tail + local].astype(state.local_k.dtype)
    lv = cp.stage_v[:, :, tail:tail + local].astype(state.local_v.dtype)
    return state._replace(
        local_k=jax.lax.dynamic_update_slice(state.local_k, lk, (0, 0, 0, 0)),
        local_v=jax.lax.dynamic_update_slice(state.local_v, lv, (0, 0, 0, 0)),
        local_len=jnp.full((B,), local, jnp.int32),
        length=cp.seen)


@trace.scope(trace.CACHE_UPDATE)
def append_token(state: WaveState, k_new: jax.Array, v_new: jax.Array,
                 active: Optional[jax.Array] = None) -> WaveState:
    """Append one generated token's (B, H, hd) K/V to the local buffer.

    Rows write at their own ``local_len`` cursor. ``active``: optional (B,)
    bool — inactive rows (free slots in a continuous batch) are left
    untouched so their counters never drift or overflow the staging buffer.
    """
    k_new = k_new[:, :, None, :].astype(state.local_k.dtype)
    v_new = v_new[:, :, None, :].astype(state.local_v.dtype)

    def row(buf, new, idx):
        return jax.lax.dynamic_update_slice(buf, new, (0, idx, 0))

    new_lk = jax.vmap(row)(state.local_k, k_new, state.local_len)
    new_lv = jax.vmap(row)(state.local_v, v_new, state.local_len)
    step = jnp.ones_like(state.local_len)
    if active is not None:
        act = jnp.asarray(active)
        sel = act[:, None, None, None]
        new_lk = jnp.where(sel, new_lk, state.local_k)
        new_lv = jnp.where(sel, new_lv, state.local_v)
        step = act.astype(state.local_len.dtype)
    return state._replace(
        local_k=new_lk, local_v=new_lv,
        local_len=state.local_len + step,
        length=state.length + step,
    )


def flush_segment(state: WaveState, retro: RetroConfig,
                  rows: Optional[jax.Array] = None,
                  return_clusters: bool = False):
    """Cluster the oldest ``update_segment`` tokens of each FULL local buffer
    into new clusters (paper: decode-time index update, every 1K tokens) and
    slide the remaining ``local`` tokens to the front.

    Per-row masked: under continuous batching rows fill their staging buffers
    at different steps, so only rows selected by ``rows`` (default: buffer
    full) are flushed; the rest pass through bit-unchanged.

    ``return_clusters=True`` additionally returns the freshly clustered
    ``ClusterResult`` (all rows — callers apply their own ``rows`` mask);
    with ``None`` payload stores (host-offload live view) only the meta index
    is written on device and the returned blocks are the host store's append.
    """
    useg = retro.update_segment
    lbuf = local_buffer_size(retro)
    B, H, _, hd = state.local_k.shape
    if rows is None:
        rows = state.local_len >= lbuf
    rows = jnp.asarray(rows)
    start = state.length - state.local_len                 # abs pos of buffer[0]
    pos = start[:, None] + jnp.arange(useg, dtype=jnp.int32)[None, :]

    def row_fn(kk, vv, p):
        def bh(k1, v1):
            return cluster_segment(k1[:useg], v1[:useg], p, retro.avg_cluster,
                                   retro.cluster_cap, retro.kmeans_iters,
                                   retro.centering)
        return jax.vmap(bh)(kk, vv)

    res = jax.vmap(row_fn)(state.local_k, state.local_v, pos)
    flushed = _write_clusters(state, res, state.n_clusters)

    rolled_k = jnp.roll(state.local_k, -useg, axis=2)
    rolled_v = jnp.roll(state.local_v, -useg, axis=2)

    def sel(new, old):
        if new is None:                    # host-resident payload store
            return None
        return jnp.where(rows.reshape((B,) + (1,) * (new.ndim - 1)), new, old)

    out = state._replace(
        k_store=sel(flushed.k_store, state.k_store),
        v_store=sel(flushed.v_store, state.v_store),
        pos_store=sel(flushed.pos_store, state.pos_store),
        centroid=sel(flushed.centroid, state.centroid),
        vsum=sel(flushed.vsum, state.vsum),
        size=sel(flushed.size, state.size),
        stored=sel(flushed.stored, state.stored),
        max_pos=sel(flushed.max_pos, state.max_pos),
        n_clusters=jnp.where(rows, flushed.n_clusters, state.n_clusters),
        local_k=sel(rolled_k, state.local_k),
        local_v=sel(rolled_v, state.local_v),
        local_len=jnp.where(rows, state.local_len - useg, state.local_len),
    )
    return (out, res) if return_clusters else out


def flush_segment_offload(state: WaveState, retro: RetroConfig,
                          rows: Optional[jax.Array] = None
                          ) -> Tuple[WaveState, ClusterResult]:
    """``flush_segment`` for the host-offload configuration: identical
    clustering and meta-index update, with the PAYLOAD blocks returned for
    the host control plane to append to its resident store (at each flushed
    row's old ``n_clusters`` offset). ``state`` carries ``None`` payload
    stores (the serve engine's live view); they pass through untouched."""
    return flush_segment(state, retro, rows=rows, return_clusters=True)


def maybe_flush(state: WaveState, retro: RetroConfig) -> WaveState:
    """Flush inside jit iff any row's staging buffer is full (per-row masked)."""
    full = state.local_len >= local_buffer_size(retro)
    return jax.lax.cond(jnp.any(full),
                        lambda s: flush_segment(s, retro), lambda s: s, state)
