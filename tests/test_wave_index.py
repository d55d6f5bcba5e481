"""Wave-index construction / update invariants + retrieval quality."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import RetroConfig
from repro.core.clustering import (cluster_segment, segmented_cluster,
                                   spherical_kmeans)
from repro.core.wave_index import (append_token, flush_segment,
                                   init_chunked_prefill, max_clusters,
                                   maybe_flush, prefill_append_chunk,
                                   prefill_build, prefill_finalize,
                                   prefill_layout, stage_flushes)
from repro.core.zones import plan_zones
from repro.data.pipeline import clustered_keys

RETRO = RetroConfig(avg_cluster=8, cluster_cap=16, prefill_segment=256,
                    update_segment=128, sink=4, local=32, kmeans_iters=3)


def _build(n=1100, hd=32, B=1, H=1, seed=0):
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.standard_normal((B, n, H, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, n, H, hd)), jnp.float32)
    M = max_clusters(n, RETRO, gen_headroom=128)
    return prefill_build(k, v, RETRO, M, dtype=jnp.float32), k, v


def test_prefill_accounting():
    state, k, v = _build()
    n = k.shape[1]
    clustered = n - RETRO.sink - RETRO.local
    assert int(state.size[0, 0].sum()) == clustered
    assert int(state.stored[0, 0].sum()) <= clustered
    assert int(state.length[0]) == n
    assert int(state.local_len[0]) == RETRO.local
    # all stored positions unique and within the clustered region
    pos = np.asarray(state.pos_store[0, 0]).reshape(-1)
    pos = pos[pos >= 0]
    assert len(np.unique(pos)) == len(pos)
    assert pos.min() >= RETRO.sink and pos.max() < n - RETRO.local


def test_vsum_matches_members():
    """Meta-index value sums equal the sum of member values (incl. overflow)."""
    state, k, v = _build(n=612, seed=2)
    active = int(state.n_clusters[0])
    vs = np.asarray(state.vsum[0, 0][:active])
    pos = np.asarray(state.pos_store[0, 0][:active])
    size = np.asarray(state.size[0, 0][:active])
    stored = np.asarray(state.stored[0, 0][:active])
    vals = np.asarray(v[0, :, 0])
    full = size == stored                   # clusters without overflow
    for i in np.where(full)[0]:
        p = pos[i][pos[i] >= 0]
        np.testing.assert_allclose(vs[i], vals[p].sum(0), rtol=1e-4, atol=1e-4)


def test_centroid_is_member_mean():
    state, k, v = _build(n=612, seed=4)
    active = int(state.n_clusters[0])
    cent = np.asarray(state.centroid[0, 0][:active])
    pos = np.asarray(state.pos_store[0, 0][:active])
    size = np.asarray(state.size[0, 0][:active])
    stored = np.asarray(state.stored[0, 0][:active])
    keys = np.asarray(k[0, :, 0])
    for i in np.where(size == stored)[0][:20]:
        p = pos[i][pos[i] >= 0]
        np.testing.assert_allclose(cent[i], keys[p].mean(0), rtol=1e-4,
                                   atol=1e-4)


def test_decode_append_and_flush():
    state, k, v = _build()
    n0 = int(state.n_clusters[0])
    B, H, hd = 1, 1, 32
    lbuf = RETRO.local + RETRO.update_segment
    rng = np.random.default_rng(9)
    for t in range(RETRO.update_segment):
        kn = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.float32)
        state = append_token(state, kn, kn)
    assert int(state.local_len[0]) == lbuf
    state = flush_segment(state, RETRO)
    assert int(state.n_clusters[0]) == n0 + RETRO.update_segment // RETRO.avg_cluster
    assert int(state.local_len[0]) == RETRO.local
    # flushed clusters carry correct positions
    new = np.asarray(state.pos_store[0, 0][n0:int(state.n_clusters[0])])
    got = np.sort(new[new >= 0])
    n = k.shape[1]
    expect = np.arange(n - RETRO.local, n - RETRO.local + RETRO.update_segment)
    np.testing.assert_array_equal(got, expect)


def test_maybe_flush_noop_when_not_full():
    state, _, _ = _build()
    out = maybe_flush(state, RETRO)
    assert int(out.n_clusters[0]) == int(state.n_clusters[0])


def test_segmented_vs_global_recall():
    """Paper Fig. 19b: segmented clustering keeps retrieval recall close to
    global k-means on spatially-local key fields."""
    n, hd = 2048, 32
    keys, q, hot = clustered_keys(n, hd, n_hot=6, seed=0)
    kj = jnp.asarray(keys)
    scores = keys @ q
    top100 = np.argsort(-scores)[:100]

    def recall(res, r):
        csc = np.asarray(res.centroid) @ q
        order = np.argsort(-csc)[:r]
        pos = np.asarray(res.pos_store)[order].reshape(-1)
        sel = set(pos[pos >= 0].tolist())
        return np.mean([t in sel for t in top100])

    vv = jnp.asarray(np.zeros_like(keys))
    pos = jnp.arange(n, dtype=jnp.int32)
    seg = segmented_cluster(kj, vv, pos, 256, 8, 16, 5, True)
    r = max(8, int(0.1 * n // 8))
    rec_seg = recall(seg, r)
    # global k-means (single segment)
    glob = segmented_cluster(kj, vv, pos, n, 8, 16, 5, True)
    rec_glob = recall(glob, r)
    assert rec_seg >= 0.9
    assert rec_seg >= rec_glob - 0.05      # within 5% of global (paper: <1%)


def test_overflow_rate_is_low():
    """cap = 2x avg keeps the store-truncation rate small (DESIGN §2)."""
    n, hd = 2048, 32
    keys, _, _ = clustered_keys(n, hd, n_hot=4, seed=1)
    vv = jnp.asarray(np.zeros_like(keys))
    pos = jnp.arange(n, dtype=jnp.int32)
    res = segmented_cluster(jnp.asarray(keys), vv, pos, 256, 8, 16, 5, True)
    dropped = 1.0 - int(res.stored.sum()) / int(res.size.sum())
    assert dropped < 0.10


def test_layout_and_padding():
    nf, tail, m = prefill_layout(1100, RETRO)
    assert nf == 4 and tail == 1100 - 36 - 4 * 256
    M = max_clusters(1100, RETRO, gen_headroom=128, pad_multiple=256)
    assert M % 256 == 0 and M >= m


def test_short_prompt_layout_degenerates():
    """Regression: prompts shorter than sink + local used to produce NEGATIVE
    full-segment / cluster counts (floor division of a negative region). The
    layout must clamp to a steady-zone-only plan and the zone plan / store
    sizing must stay usable."""
    nf, tail, m = prefill_layout(64, RetroConfig())       # sink=4, local=64
    assert (nf, tail, m) == (0, 0, 0)
    for s in (1, 4, 67, 68, 69):
        nf, tail, m = prefill_layout(s, RetroConfig())
        assert nf >= 0 and tail >= 0 and m >= 0
    M = max_clusters(64, RetroConfig())
    assert M > 0 and M % 256 == 0
    plan = plan_zones(64, RetroConfig())
    assert plan.r == 0 and plan.e == 0 and plan.m_max == M


def test_prompt_not_longer_than_sink_rejected():
    """S <= sink cannot fill the fixed-width sink zone (implicit arange
    positions): the builder must refuse instead of leaving attendable
    zero-key slots."""
    hd = 16
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.standard_normal((1, RETRO.sink, 1, hd)), jnp.float32)
    with pytest.raises(ValueError, match="sink"):
        prefill_build(k, k, RETRO, 256, dtype=jnp.float32)


def test_short_prompt_prefill_build():
    """A prompt shorter than sink + local builds a steady-zone-only state:
    no clusters, the local window covers everything past the sinks."""
    n, hd = 20, 16
    rng = np.random.default_rng(3)
    k = jnp.asarray(rng.standard_normal((1, n, 1, hd)), jnp.float32)
    M = max_clusters(n, RETRO, gen_headroom=128)
    state = prefill_build(k, k, RETRO, M, dtype=jnp.float32)
    assert int(state.n_clusters[0]) == 0
    assert int(state.length[0]) == n
    assert int(state.local_len[0]) == n - RETRO.sink


def test_ragged_prefill_build_masks_padding():
    """Right-padded ragged build: pad tokens never enter any store, each
    row's clustered region ends exactly ``local`` before its true length."""
    B, S, hd = 3, 640, 16
    lens = np.array([640, 417, 300], np.int32)
    rng = np.random.default_rng(5)
    k = jnp.asarray(rng.standard_normal((B, S, 1, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, 1, hd)), jnp.float32)
    M = max_clusters(S, RETRO, gen_headroom=128)
    state = prefill_build(k, v, RETRO, M, dtype=jnp.float32,
                          lengths=jnp.asarray(lens))
    np.testing.assert_array_equal(np.asarray(state.length), lens)
    for b in range(B):
        clustered = int(np.asarray(state.size[b, 0]).sum())
        assert clustered == lens[b] - RETRO.sink - RETRO.local
        pos = np.asarray(state.pos_store[b, 0]).reshape(-1)
        pos = pos[pos >= 0]
        assert len(np.unique(pos)) == len(pos)
        assert pos.min() >= RETRO.sink
        assert pos.max() < lens[b] - RETRO.local          # pads excluded


def test_per_row_masked_flush():
    """Rows flush independently: only rows with a full staging buffer gain
    clusters; the others are bit-unchanged."""
    state, k, v = _build(B=2, H=1)
    n0 = int(state.n_clusters[0])
    rng = np.random.default_rng(11)
    hd = 32
    # row 0 appends a full update segment; row 1 stays behind by one token
    for t in range(RETRO.update_segment):
        kn = jnp.asarray(rng.standard_normal((2, 1, hd)), jnp.float32)
        act = jnp.asarray([True, t < RETRO.update_segment - 1])
        state = append_token(state, kn, kn, active=act)
    lbuf = RETRO.local + RETRO.update_segment
    np.testing.assert_array_equal(np.asarray(state.local_len), [lbuf, lbuf - 1])
    before_row1 = jax.tree.map(lambda a: np.asarray(a[1]), state)
    out = flush_segment(state, RETRO)
    assert int(out.n_clusters[0]) == n0 + RETRO.update_segment // RETRO.avg_cluster
    assert int(out.n_clusters[1]) == n0                   # row 1 untouched
    assert int(out.local_len[0]) == RETRO.local
    assert int(out.local_len[1]) == lbuf - 1
    after_row1 = jax.tree.map(lambda a: np.asarray(a[1]), out)
    for name, a, b in zip(out._fields, before_row1, after_row1):
        np.testing.assert_array_equal(a, b, err_msg=name)


def _feed_chunks(cp, k, v, C, jit=False):
    """Stream (B, n, H, hd) K/V through prefill_append_chunk in C-sized
    chunks (last chunk right-padded)."""
    B, n, H, hd = k.shape
    app = prefill_append_chunk
    if jit:
        app = jax.jit(lambda cp, kc, vc, cl: prefill_append_chunk(
            cp, kc, vc, RETRO, cl))
    t = 0
    while t < n:
        c = min(C, n - t)
        kc = jnp.zeros((B, C, H, hd), k.dtype).at[:, :c].set(k[:, t:t + c])
        vc = jnp.zeros((B, C, H, hd), v.dtype).at[:, :c].set(v[:, t:t + c])
        cl = jnp.full((B,), c, jnp.int32)
        cp = app(cp, kc, vc, cl) if jit else app(cp, kc, vc, RETRO, cl)
        t += c
    return cp


def _assert_states_equal(out, ref):
    for f, a, b in zip(out._fields, out, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f)


@pytest.mark.parametrize("chunk", (96, 256, 300, 1100))
def test_chunked_prefill_matches_build_exactly(chunk):
    """Acceptance: streaming the prompt through prefill_append_chunk +
    prefill_finalize reproduces prefill_build BIT-IDENTICALLY for any chunk
    split — segment boundaries are position-aligned, not chunk-aligned."""
    ref, k, v = _build()
    B, n, H, hd = k.shape
    M = ref.k_store.shape[2]
    cp = init_chunked_prefill(B, H, hd, M, RETRO, chunk, dtype=jnp.float32)
    cp = _feed_chunks(cp, k, v, chunk, jit=(chunk == 256))
    out = prefill_finalize(cp, RETRO, n)
    _assert_states_equal(out, ref)


def test_gated_stage_flush_matches_direct_clustering():
    """Of two rows, one reaches prefill_segment + local in a chunk and one
    does not: the gated (jitted) flush clusters the first row's oldest
    segment exactly as ``cluster_segment`` does, shifts its staging buffer
    by a segment, and leaves the second row bit-unchanged."""
    B, H, hd, C = 2, 2, 32, 128
    seg, k_new = RETRO.prefill_segment, RETRO.prefill_segment // RETRO.avg_cluster
    rng = np.random.default_rng(5)
    M = max_clusters(1100, RETRO, gen_headroom=128)
    cp = init_chunked_prefill(B, H, hd, M, RETRO, C, dtype=jnp.float32)

    def chunk():
        return jnp.asarray(rng.standard_normal((B, C, H, hd)), jnp.float32)

    app = jax.jit(lambda cp, kc, vc, cl: prefill_append_chunk(
        cp, kc, vc, RETRO, cl))
    for _ in range(2):      # row 0 stages 252 tokens, row 1 none
        cp = app(cp, chunk(), chunk(), jnp.asarray([C, 0], jnp.int32))
    kc, vc, cl = chunk(), chunk(), jnp.full((B,), C, jnp.int32)
    out = app(cp, kc, vc, cl)

    # the chunk's routing alone: a threshold no row reaches
    never = dataclasses.replace(RETRO, local=10**6)
    routed = prefill_append_chunk(cp, kc, vc, never, cl)
    assert int(routed.staged[0]) >= seg + RETRO.local
    assert int(routed.staged[1]) < seg + RETRO.local

    p0 = jnp.arange(seg, dtype=jnp.int32) + int(routed.seen[0]
                                                - routed.staged[0])
    want = jax.vmap(lambda k1, v1: cluster_segment(
        k1[:seg], v1[:seg], p0, RETRO.avg_cluster, RETRO.cluster_cap,
        RETRO.kmeans_iters, RETRO.centering))(routed.stage_k[0],
                                              routed.stage_v[0])
    for f, w in zip(want._fields, want):
        np.testing.assert_array_equal(
            np.asarray(getattr(out.state, f)[0, :, :k_new]), np.asarray(w),
            err_msg=f)
    assert int(out.state.n_clusters[0]) == k_new
    assert int(out.staged[0]) == int(routed.staged[0]) - seg
    for a, b in ((out.stage_k, routed.stage_k), (out.stage_v, routed.stage_v)):
        np.testing.assert_array_equal(np.asarray(a[0]),
                                      np.roll(np.asarray(b[0]), -seg, axis=1))

    leaves = jax.tree_util.tree_flatten_with_path(out)[0]
    for (path, a), r in zip(leaves, jax.tree.leaves(routed)):
        np.testing.assert_array_equal(np.asarray(a)[1], np.asarray(r)[1],
                                      err_msg=jax.tree_util.keystr(path))


def test_stage_flush_is_gated_by_cond():
    """The staging flush sits behind a ``cond``: a refactor that drops the
    gate clusters a segment on every chunk again."""
    cp = init_chunked_prefill(1, 1, 16, 256, RETRO, 64, dtype=jnp.float32)
    k = jnp.zeros((1, 64, 1, 16), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.jit(
        lambda cp, k: prefill_append_chunk(cp, k, k, RETRO)))(cp, k)
    assert " cond[" in str(jaxpr)


@pytest.mark.parametrize("chunk", (96, 256, 300, 1100))
def test_stage_flushes_counts_device_flushes(chunk):
    """The host count of staging flushes per chunk equals the segments the
    device clustered: each flush lowers ``staged`` by one segment."""
    _, k, v = _build()
    B, n, H, hd = k.shape
    M = max_clusters(n, RETRO, gen_headroom=128)
    cp = init_chunked_prefill(B, H, hd, M, RETRO, chunk, dtype=jnp.float32)
    app = jax.jit(lambda cp, kc, vc, cl: prefill_append_chunk(
        cp, kc, vc, RETRO, cl))
    total = 0
    for t in range(0, n, chunk):
        c = min(chunk, n - t)
        kc = jnp.zeros((B, chunk, H, hd)).at[:, :c].set(k[:, t:t + c])
        vc = jnp.zeros((B, chunk, H, hd)).at[:, :c].set(v[:, t:t + c])
        before = int(cp.staged[0])
        cp = app(cp, kc, vc, jnp.full((B,), c, jnp.int32))
        added = c - min(max(RETRO.sink - t, 0), c)
        dropped = before + added - int(cp.staged[0])
        assert dropped % RETRO.prefill_segment == 0
        assert stage_flushes(t, c, RETRO) == dropped // RETRO.prefill_segment
        total += dropped // RETRO.prefill_segment
    assert total == prefill_layout(n, RETRO)[0]


@pytest.mark.slow
def test_chunked_prefill_per_row_rates():
    """Rows of one batch may stream at different rates (per-row chunk_lens);
    once they converge to the same total the state matches the monolithic
    build row-for-row."""
    B, n, H, hd = 2, 1100, 1, 32
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.standard_normal((B, n, H, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, n, H, hd)), jnp.float32)
    M = max_clusters(n, RETRO, gen_headroom=128)
    ref = prefill_build(k, v, RETRO, M, dtype=jnp.float32)

    C = 128
    cp = init_chunked_prefill(B, H, hd, M, RETRO, C, dtype=jnp.float32)
    t = np.zeros(B, int)
    rng2 = np.random.default_rng(1)
    while (t < n).any():
        cl = np.minimum(rng2.integers(0, C + 1, B), n - t)
        kc = jnp.zeros((B, C, H, hd), jnp.float32)
        vc = jnp.zeros((B, C, H, hd), jnp.float32)
        for b in range(B):
            kc = kc.at[b, :cl[b]].set(k[b, t[b]:t[b] + cl[b]])
            vc = vc.at[b, :cl[b]].set(v[b, t[b]:t[b] + cl[b]])
        cp = prefill_append_chunk(cp, kc, vc, RETRO,
                                  jnp.asarray(cl, jnp.int32))
        t += cl
    out = prefill_finalize(cp, RETRO, n)
    _assert_states_equal(out, ref)


def test_chunked_prefill_short_prompt():
    """A streamed prompt shorter than sink + local finalizes to the same
    steady-zone-only state as the monolithic build."""
    B, n, H, hd = 1, 20, 1, 16
    rng = np.random.default_rng(3)
    k = jnp.asarray(rng.standard_normal((B, n, H, hd)), jnp.float32)
    M = max_clusters(n, RETRO, gen_headroom=128)
    ref = prefill_build(k, k, RETRO, M, dtype=jnp.float32)
    cp = init_chunked_prefill(B, H, hd, M, RETRO, 16, dtype=jnp.float32)
    cp = _feed_chunks(cp, k, k, 16)
    out = prefill_finalize(cp, RETRO, n)
    _assert_states_equal(out, ref)
    assert int(out.n_clusters[0]) == 0
    assert int(out.local_len[0]) == n - RETRO.sink


def test_chunked_finalize_rejects_sink_only_prompt():
    """Same contract as prefill_build: a prompt that cannot overfill the
    fixed-width sink zone is refused."""
    cp = init_chunked_prefill(1, 1, 16, 256, RETRO, 4, dtype=jnp.float32)
    k = jnp.zeros((1, 4, 1, 16), jnp.float32)
    cp = prefill_append_chunk(cp, k, k, RETRO)
    with pytest.raises(ValueError, match="sink"):
        prefill_finalize(cp, RETRO, RETRO.sink)


def test_kmeans_clusters_separable_data():
    rng = np.random.default_rng(0)
    centers = rng.standard_normal((4, 16)) * 4
    pts = np.concatenate([c + 0.05 * rng.standard_normal((32, 16))
                          for c in centers])
    assign, cent = spherical_kmeans(jnp.asarray(pts, jnp.float32), 4, 8)
    a = np.asarray(assign)
    for g in range(4):
        grp = a[g * 32:(g + 1) * 32]
        assert len(np.unique(grp)) == 1      # each blob in one cluster
