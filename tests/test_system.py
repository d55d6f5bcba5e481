"""End-to-end system behaviour: the serving engine with the wave index vs the
full-attention baseline, flush equivalence, and engine waves."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AttnConfig, InputShape, ModelConfig, RetroConfig
from repro.configs.registry import materialize_batch
from repro.core.zones import plan_zones
from repro.models import model as M
from repro.serving.engine import Request, ServeEngine

# capacity = prefill segment => provably overflow-free exact coverage
RETRO_X = RetroConfig(avg_cluster=8, cluster_cap=64, prefill_segment=64,
                      update_segment=32, sink=4, local=32,
                      retrieval_frac=1.0, estimation_frac=0.0, kmeans_iters=3)

CFG = ModelConfig(
    arch_id="sys-tiny", family="dense", n_layers=2, d_model=64, d_ff=128,
    vocab=256, attn=AttnConfig(n_heads=4, n_kv_heads=2, head_dim=16),
    dtype="float32", retro=RETRO_X)

S, B = 384, 2


@pytest.fixture(scope="module")
def setup():
    params = M.init_params(CFG, jax.random.PRNGKey(0))
    batch = materialize_batch(CFG, InputShape("p", S, B, "prefill"))
    plan = plan_zones(S, CFG.retro, 256)

    @partial(jax.jit, static_argnames=("runtime", "inline_flush"))
    def decode(params, state, token, runtime="retro", inline_flush=False):
        return M.apply_decode(params, CFG, state, token, runtime=runtime,
                              plan=plan, inline_flush=inline_flush)

    @jax.jit
    def flush(state):
        return M.flush_state(CFG, state, runtime="retro")

    return params, batch, plan, decode, flush


def test_retro_full_budget_matches_full_attention(setup):
    """With retrieval covering all clusters the wave-index runtime reproduces
    the dense-cache runtime's logits on a real model end-to-end."""
    params, batch, plan, decode, _ = setup
    lg_r, st_r = M.apply_prefill(params, CFG, batch, runtime="retro",
                                 plan=plan, gen_headroom=256)
    lg_f, st_f = M.apply_prefill(params, CFG, batch, runtime="full",
                                 gen_headroom=256)
    np.testing.assert_allclose(np.asarray(lg_r), np.asarray(lg_f), atol=1e-3,
                               rtol=1e-3)
    tok = jnp.argmax(lg_r, -1).astype(jnp.int32)
    for _ in range(5):
        lg_r, st_r = decode(params, st_r, tok, runtime="retro")
        lg_f, st_f = decode(params, st_f, tok, runtime="full")
        np.testing.assert_allclose(np.asarray(lg_r), np.asarray(lg_f),
                                   atol=2e-3, rtol=2e-3)
        t_r = np.argmax(np.asarray(lg_r), -1)
        t_f = np.argmax(np.asarray(lg_f), -1)
        np.testing.assert_array_equal(t_r, t_f)
        tok = jnp.asarray(t_r, jnp.int32)


def test_engine_flush_matches_inline_flush(setup):
    """External (engine-driven) index updates == inline (in-step) updates."""
    params, batch, plan, decode, flush = setup
    n_steps = CFG.retro.update_segment + 4

    _, st_a = M.apply_prefill(params, CFG, batch, runtime="retro", plan=plan,
                              gen_headroom=256)
    _, st_b = M.apply_prefill(params, CFG, batch, runtime="retro", plan=plan,
                              gen_headroom=256)
    tok_a = tok_b = jnp.zeros((B,), jnp.int32)
    appended = 0
    for i in range(n_steps):
        lg_a, st_a = decode(params, st_a, tok_a, inline_flush=True)
        lg_b, st_b = decode(params, st_b, tok_b, inline_flush=False)
        appended += 1
        if M.needs_flush(CFG, appended):
            st_b = flush(st_b)
            appended = 0
        np.testing.assert_allclose(np.asarray(lg_a), np.asarray(lg_b),
                                   atol=1e-4, rtol=1e-4)
        tok_a = jnp.argmax(lg_a, -1).astype(jnp.int32)
        tok_b = jnp.argmax(lg_b, -1).astype(jnp.int32)
    assert int(st_b.kv.n_clusters[0, 0]) == int(st_a.kv.n_clusters[0, 0])


def test_engine_continuous_queue(setup):
    """A queue longer than the slot count drains through continuous batching;
    only real sampled tokens are counted (no padding inflation)."""
    params = setup[0]
    eng = ServeEngine(CFG, params, runtime="retro", gen_headroom=256)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, CFG.vocab, S).astype(np.int32),
                    max_new_tokens=6) for _ in range(3)]
    m = eng.serve(reqs, batch_size=2)
    for r in reqs:
        assert len(r.out_tokens) == 6
        assert r.done
    assert m.tokens_out == 3 * 6            # odd queue: no padding slot counted
    assert m.decode_tps > 0
    assert m.n_slots == 2
    assert 0 < m.slot_occupancy <= 1
    assert len(m.ttft_s) == 3 and len(m.request_tps) == 3


@pytest.mark.slow
def test_continuous_matches_solo_bitwise(setup):
    """Acceptance: a mixed queue of >= 3 distinct prompt lengths with
    staggered max_new_tokens; every request's greedy output is bit-identical
    to running it alone at batch size 1 (same engine geometry)."""
    params = setup[0]
    rng = np.random.default_rng(7)
    lens = [S, 256, 320, 200]               # 4 distinct lengths, ragged
    news = [20, 6, 41, 12]                  # staggered; 41 crosses a flush
    prompts = [rng.integers(0, CFG.vocab, L).astype(np.int32) for L in lens]

    eng = ServeEngine(CFG, params, runtime="retro", gen_headroom=256,
                      max_context=S)
    reqs = [Request(prompt=p.copy(), max_new_tokens=n)
            for p, n in zip(prompts, news)]
    m = eng.serve(reqs, batch_size=2)
    assert m.tokens_out == sum(news)

    solo = ServeEngine(CFG, params, runtime="retro", gen_headroom=256,
                       max_context=S)
    for p, n, served in zip(prompts, news, reqs):
        ref = Request(prompt=p.copy(), max_new_tokens=n)
        solo.serve([ref], batch_size=1)
        assert ref.out_tokens == served.out_tokens, len(p)
        assert len(served.out_tokens) == n


@pytest.mark.slow
def test_chunked_admission_matches_blocking(setup):
    """Acceptance: chunked (interleaved) admission reproduces blocking
    admission token-for-token on a ragged queue, for both runtimes."""
    params = setup[0]
    rng = np.random.default_rng(3)
    lens = [S, 256, 320, 200]
    news = [20, 6, 41, 12]                  # 41 crosses a flush boundary
    prompts = [rng.integers(0, CFG.vocab, L).astype(np.int32) for L in lens]

    for runtime in ("retro", "full"):
        outs = {}
        for mode in ("blocking", "chunked"):
            eng = ServeEngine(CFG, params, runtime=runtime, gen_headroom=256,
                              max_context=S, admission=mode, prefill_chunk=96)
            reqs = [Request(prompt=p.copy(), max_new_tokens=n)
                    for p, n in zip(prompts, news)]
            m = eng.serve(reqs, batch_size=2)
            assert m.tokens_out == sum(news)
            outs[mode] = [r.out_tokens for r in reqs]
        assert outs["chunked"] == outs["blocking"], runtime


def test_chunked_admission_counts_stage_flushes(setup):
    """``ServeMetrics`` counts the chunk calls and the calls whose staging
    flush ran, as a replay of each prompt's staging buffer predicts; a
    prompt under sink + prefill_segment + local never flushes. The tokens
    are blocking admission's."""
    params = setup[0]
    rx, C = CFG.retro, 96
    lens, news = [S, 90, 200], [4, 3, 5]
    assert min(lens) < rx.sink + rx.prefill_segment + rx.local < max(lens)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, CFG.vocab, L).astype(np.int32) for L in lens]

    outs, metrics = {}, {}
    for mode in ("blocking", "chunked"):
        eng = ServeEngine(CFG, params, runtime="retro", gen_headroom=256,
                          max_context=S, admission=mode, prefill_chunk=C)
        reqs = [Request(prompt=p.copy(), max_new_tokens=n)
                for p, n in zip(prompts, news)]
        metrics[mode] = eng.serve(reqs, batch_size=2)
        outs[mode] = [r.out_tokens for r in reqs]
    assert outs["chunked"] == outs["blocking"]

    chunks = fired = 0
    for L in lens:
        staged = 0
        for t in range(0, L, C):
            n = min(C, L - t)
            staged += n - min(max(rx.sink - t, 0), n)
            flushed = staged >= rx.prefill_segment + rx.local
            while staged >= rx.prefill_segment + rx.local:
                staged -= rx.prefill_segment
            chunks += 1
            fired += flushed
    assert (chunks, fired) == (8, 4)
    m = metrics["chunked"]
    assert (m.prefill_chunks, m.prefill_stage_flushes) == (chunks, fired)
    m = metrics["blocking"]
    assert (m.prefill_chunks, m.prefill_stage_flushes) == (0, 0)


@pytest.mark.slow
def test_fused_attn_impl_matches_jnp(setup):
    """Acceptance: the gather-free fused decode attention reproduces the jnp
    reference token-for-token through the serving engine (ragged queue,
    continuous batching, flush boundaries)."""
    params = setup[0]
    rng = np.random.default_rng(11)
    lens = [S, 256, 320, 200]
    news = [20, 6, 41, 12]                  # 41 crosses a flush boundary
    prompts = [rng.integers(0, CFG.vocab, L).astype(np.int32) for L in lens]

    outs = {}
    for impl in ("jnp", "fused"):
        eng = ServeEngine(CFG, params, runtime="retro", gen_headroom=256,
                          max_context=S, attn_impl=impl)
        assert eng.attn_impl == impl
        reqs = [Request(prompt=p.copy(), max_new_tokens=n)
                for p, n in zip(prompts, news)]
        m = eng.serve(reqs, batch_size=2)
        assert m.tokens_out == sum(news)
        outs[impl] = [r.out_tokens for r in reqs]
    assert outs["fused"] == outs["jnp"]


def test_attn_impl_config_default_and_validation(setup):
    """attn_impl plumbs from RetroConfig through the engine; unknown values
    are rejected up front."""
    import dataclasses
    params = setup[0]
    cfg_f = CFG.replace(retro=dataclasses.replace(RETRO_X, attn_impl="fused"))
    eng = ServeEngine(cfg_f, params, runtime="retro", gen_headroom=256)
    assert eng.attn_impl == "fused"
    with pytest.raises(ValueError, match="attn impl"):
        ServeEngine(CFG, params, attn_impl="nope")


def test_dense_cache_append_active_mask_is_o1():
    """The active mask applies to the per-row write cursor, not the cache:
    inactive rows are untouched and a full row drops its append. That the
    masked append updates the donated cache in place is asserted where it
    matters, on the chip's compiler
    (``test_chip_compile.py::test_dense_cache_append_in_place_for_v5e``);
    the CPU backend's cost model counts the scatter's operands whole."""
    from repro.core.attention import dense_cache_append, init_dense_cache

    B, H, S_max, hd = 2, 2, 4096, 64
    k_new = jnp.ones((B, H, hd), jnp.float32)
    act = jnp.asarray([True, False])

    # semantics: inactive rows untouched, active rows append at their cursor
    c0 = init_dense_cache(B, H, S_max, hd, dtype=jnp.float32)
    c0 = c0._replace(length=jnp.asarray([5, 9], jnp.int32))
    c1 = dense_cache_append(c0, k_new, 2 * k_new, active=act)
    assert c1.length.tolist() == [6, 9]
    np.testing.assert_array_equal(np.asarray(c1.k[0, :, 5]),
                                  np.ones((H, hd), np.float32))
    np.testing.assert_array_equal(np.asarray(c1.k[1]), np.zeros_like(c1.k[1]))
    np.testing.assert_array_equal(np.asarray(c1.v[1]), np.zeros_like(c1.v[1]))

    # at capacity the write is dropped AND the cursor stays put, so length
    # never claims tokens the cache doesn't hold
    c_full = init_dense_cache(B, H, 8, hd, dtype=jnp.float32)._replace(
        length=jnp.asarray([8, 3], jnp.int32))
    c2 = dense_cache_append(c_full, k_new, k_new)
    assert c2.length.tolist() == [8, 4]


def test_chunked_prefill_family_passthrough():
    """encdec/hybrid/ssm pass through: the chunked API refuses and the engine
    falls back to blocking admission for them."""
    assert M.supports_chunked_prefill(CFG)
    for family in ("hybrid", "ssm", "audio"):
        fcfg = CFG.replace(family=family)
        assert not M.supports_chunked_prefill(fcfg)
        with pytest.raises(NotImplementedError, match="blocking"):
            M.apply_prefill_chunk(None, fcfg, {}, None)
        with pytest.raises(NotImplementedError):
            M.make_prefill_chunk_state(fcfg, 1, 64, chunk=16)


def test_serve_metrics_inter_token_latency(setup):
    """ITL / TTFT percentiles are first-class serve metrics: gaps between
    consecutive token deliveries of continuing requests are recorded."""
    params = setup[0]
    eng = ServeEngine(CFG, params, runtime="retro", gen_headroom=256,
                      max_context=S, admission="chunked", prefill_chunk=128)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, CFG.vocab, S).astype(np.int32),
                    max_new_tokens=8) for _ in range(3)]
    m = eng.serve(reqs, batch_size=2)
    assert len(m.step_s) > 0
    assert 0 < m.itl_p50_s <= m.itl_p99_s
    assert 0 < m.ttft_p50_s <= m.ttft_p99_s
    assert m.tokens_out == 3 * 8


def test_engine_runs_across_flush_boundary(setup):
    """Generation longer than update_segment exercises the engine flush."""
    params = setup[0]
    eng = ServeEngine(CFG, params, runtime="retro", gen_headroom=512)
    rng = np.random.default_rng(1)
    n_new = CFG.retro.update_segment + 8
    reqs = [Request(prompt=rng.integers(0, CFG.vocab, S).astype(np.int32),
                    max_new_tokens=n_new) for _ in range(2)]
    m = eng.run_wave(reqs)
    assert m.tokens_out == 2 * n_new
    for r in reqs:
        assert all(0 <= t < CFG.vocab for t in r.out_tokens)


def _serve_case(params, *, offload, frac=0.25, impl="jnp",
                admission="chunked", news=(8, 6, 20), **eng_kw):
    """Shared ragged scenario: 3 requests on 2 slots (slot reuse grafts a new
    request over a retired one), generation crossing no/one flush boundary.
    ``eng_kw`` passes retrofault knobs (fault_profile, fetch_deadline_s, ...)
    straight to the engine."""
    rng = np.random.default_rng(13)
    lens = [S, 256, 320]
    prompts = [rng.integers(0, CFG.vocab, L).astype(np.int32) for L in lens]
    eng = ServeEngine(CFG, params, runtime="retro", gen_headroom=256,
                      max_context=S, admission=admission, prefill_chunk=96,
                      attn_impl=impl, offload=offload, cache_frac=frac,
                      **eng_kw)
    reqs = [Request(prompt=p.copy(), max_new_tokens=n)
            for p, n in zip(prompts, news)]
    m = eng.serve(reqs, batch_size=2)
    return [r.out_tokens for r in reqs], m


def test_offload_serve_matches_direct(setup):
    """Acceptance: host-offload decode (device block cache + cache-slot
    indirection) reproduces the direct-store path token-for-token, and the
    serve metrics record the wave-buffer traffic."""
    params = setup[0]
    ref, m0 = _serve_case(params, offload=False)
    out, m = _serve_case(params, offload=True)
    assert out == ref
    assert m.cache_lookups > 0 and m.bytes_over_link > 0
    assert 0 < m.cache_hit_ratio <= 1
    assert m.effective_cache_hit_ratio >= m.cache_hit_ratio
    # direct path records no cache traffic
    assert m0.cache_lookups == 0 and m0.bytes_over_link == 0


@pytest.mark.slow
@pytest.mark.parametrize("admission", ("chunked", "blocking"))
@pytest.mark.parametrize("impl", ("jnp", "fused"))
def test_offload_serve_parity_matrix(setup, admission, impl):
    """Acceptance: offload == direct token-for-token across admission modes
    and attention impls (generation crosses a flush boundary: the flushed
    segments are appended to the HOST store and retrieved through the
    cache)."""
    params = setup[0]
    news = (8, 6, 41)                   # 41 crosses a flush boundary
    ref, _ = _serve_case(params, offload=False, impl=impl,
                         admission=admission, news=news)
    out, m = _serve_case(params, offload=True, impl=impl,
                         admission=admission, news=news)
    assert out == ref
    assert m.bytes_over_link > 0


def test_offload_cache_coherent_after_flush(setup):
    """Regression: rows with fewer live clusters than plan.r rank dead ids
    (top_k tie-breaks NEG scores to exactly the ids the next flush will
    allocate). Fetching those through the wave buffer would admit all-masked
    payloads that turn into STALE hits once the flush writes real blocks at
    those ids. Dead ids must never touch the buffer: after a flush-crossing
    serve, every cached cluster's payload still equals its host-store row."""
    params = setup[0]
    rng = np.random.default_rng(13)
    # prompts well short of max_context => n_clusters << plan.r every step
    prompts = [rng.integers(0, CFG.vocab, L).astype(np.int32)
               for L in (256, 200)]
    eng = ServeEngine(CFG, params, runtime="retro", gen_headroom=256,
                      max_context=S, cache_frac=0.5, offload=True)
    news = [CFG.retro.update_segment + 9, 6]     # row 0 crosses a flush
    reqs = [Request(prompt=p.copy(), max_new_tokens=n)
            for p, n in zip(prompts, news)]
    eng.serve(reqs, batch_size=2)
    plane = eng._last_plane
    checked = 0
    for per_layer in plane.bufs:
        for b, row in enumerate(per_layer):
            if row is None:
                continue
            for buf in row:
                mapped = np.where(buf.table.cache_slot >= 0)[0]
                # nothing beyond the live cluster count was ever admitted
                assert (mapped < plane.ncl[b]).all()
                for cid in mapped:
                    slot = buf.table.cache_slot[cid]
                    np.testing.assert_array_equal(buf.cache[slot],
                                                  buf.kv_host[cid])
                    checked += 1
    assert checked > 0


def test_offload_eviction_pressure(setup):
    """Cache far smaller than the per-step working set (C << r): every step
    evicts, outputs stay correct, and the link carries real traffic."""
    params = setup[0]
    ref, _ = _serve_case(params, offload=False, news=(6, 5, 8))
    out, m = _serve_case(params, offload=True, frac=0.02, news=(6, 5, 8))
    assert out == ref
    assert m.bytes_over_link > 0
    assert m.cache_hit_ratio < 0.9      # pressure: far from full reuse
    assert m.bytes_from_cache >= 0


def test_offload_zero_rate_fault_profile_is_identity(setup):
    """retrofault acceptance (faults disabled): a FaultyTransport with every
    rate at zero is a pass-through — token-identical to the direct path,
    no degraded steps, no fault counters."""
    params = setup[0]
    ref, _ = _serve_case(params, offload=False)
    out, m = _serve_case(params, offload=True, fault_profile="seed=5",
                         fetch_deadline_s=10.0)
    assert out == ref
    assert m.degraded_steps == 0 and m.dropped_cluster_steps == 0
    assert m.cache_faults == 0 and m.cache_failed_fetches == 0


def test_offload_recoverable_faults_reproduce_outputs(setup):
    """retrofault acceptance (recoverable regime): transient faults with
    ample retries and no deadline are absorbed by the retry loop — outputs
    reproduce the fault-free run exactly, with nonzero fault/retry
    telemetry and zero degraded steps."""
    params = setup[0]
    ref, _ = _serve_case(params, offload=True)
    out, m = _serve_case(params, offload=True,
                         fault_profile="transient=0.3,seed=7",
                         fetch_retries=8)
    assert out == ref
    assert m.cache_faults > 0 and m.cache_retries > 0
    assert m.degraded_steps == 0 and m.cache_failed_fetches == 0


@pytest.mark.chaos
def test_offload_chaos_soak_degrades_without_wedging(setup):
    """retrofault acceptance (degraded regime): a seeded 20%-transient
    schedule with corruption, latency spikes, no retries and a fetch
    deadline tighter than a spike. Every request still completes (no crash,
    no wedge); failed fetches are masked out of the retrieval zone and the
    telemetry records the degradation."""
    params = setup[0]
    news = (8, 6, 20)
    out, m = _serve_case(
        params, offload=True, news=news,
        fault_profile="transient=0.2,corrupt=0.02,spike=0.3,seed=3",
        fetch_retries=0, fetch_deadline_s=0.01)
    assert m.tokens_out == sum(news)
    assert [len(o) for o in out] == list(news)
    assert m.cache_faults > 0 and m.cache_failed_fetches > 0
    assert m.degraded_steps > 0
    assert m.dropped_cluster_steps >= m.degraded_steps


@pytest.mark.chaos
def test_offload_chaos_soak_seed_deterministic(setup):
    """Same seed => same fault schedule => identical outputs and identical
    degradation telemetry across runs."""
    params = setup[0]
    kw = dict(offload=True, fault_profile="transient=0.25,spike=0.3,seed=11",
              fetch_retries=1, fetch_deadline_s=0.01)
    out_a, m_a = _serve_case(params, **kw)
    out_b, m_b = _serve_case(params, **kw)
    assert out_a == out_b
    assert (m_a.cache_faults, m_a.cache_failed_fetches, m_a.degraded_steps,
            m_a.dropped_cluster_steps) == \
           (m_b.cache_faults, m_b.cache_failed_fetches, m_b.degraded_steps,
            m_b.dropped_cluster_steps)


def test_fatal_fault_finishes_request_with_error_status(setup):
    """An unrecoverable link fault poisons only the affected request: it
    finishes with status='error' (structured, no engine-wide quarantine) and
    the serve loop returns normally."""
    params = setup[0]
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, CFG.vocab, L).astype(np.int32)
               for L in (S, 256)]
    eng = ServeEngine(CFG, params, runtime="retro", gen_headroom=256,
                      max_context=S, offload=True, cache_frac=0.25,
                      fault_profile="fatal=1.0,seed=2")
    reqs = [Request(prompt=p.copy(), max_new_tokens=8) for p in prompts]
    m = eng.serve(reqs, batch_size=2)
    assert all(r.status == "error" for r in reqs)
    assert all(len(r.out_tokens) < 8 for r in reqs)
    assert m.steps >= 1                  # the loop ran and unwound cleanly


def test_watchdog_finishes_runaway_request_with_timeout(setup):
    """Per-request decode watchdog: a request that would never finish on its
    own (huge max_new_tokens) is cut off after max_decode_steps with
    status='timeout'; a short request on the same batch stays status='ok'."""
    params = setup[0]
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, CFG.vocab, 256).astype(np.int32)
               for _ in range(2)]
    eng = ServeEngine(CFG, params, runtime="retro", gen_headroom=256,
                      max_context=S, max_decode_steps=6)
    reqs = [Request(prompt=prompts[0], max_new_tokens=200),
            Request(prompt=prompts[1], max_new_tokens=3)]
    eng.serve(reqs, batch_size=2)
    assert reqs[0].status == "timeout"
    assert len(reqs[0].out_tokens) <= 7   # cut at the watchdog, not at 200
    assert reqs[1].status == "ok" and len(reqs[1].out_tokens) == 3


def test_offload_requires_retro_attention(setup):
    params = setup[0]
    with pytest.raises(ValueError, match="offload"):
        ServeEngine(CFG, params, runtime="full", offload=True)
    with pytest.raises(ValueError, match="offload"):
        ServeEngine(CFG.replace(family="ssm"), params, runtime="retro",
                    offload=True)


def test_one_token_requests_excluded_from_request_tps(setup):
    """Regression: a max_new_tokens=1 request decodes zero tokens; its 0.0
    tok/s used to be appended to request_tps, dragging down mean/percentile
    request throughput. The sample is now skipped (TTFT/tokens still count)."""
    params = setup[0]
    eng = ServeEngine(CFG, params, runtime="retro", gen_headroom=256,
                      max_context=S)
    rng = np.random.default_rng(2)
    news = [1, 5, 1]
    reqs = [Request(prompt=rng.integers(0, CFG.vocab, S).astype(np.int32),
                    max_new_tokens=n) for n in news]
    m = eng.serve(reqs, batch_size=2)
    for r, n in zip(reqs, news):
        assert r.done and len(r.out_tokens) == n
    assert m.tokens_out == sum(news)
    assert len(m.ttft_s) == 3
    # only the request that actually decoded contributes a tps sample
    assert len(m.request_tps) == 1
    assert all(t > 0 for t in m.request_tps)
    assert float(np.mean(m.request_tps)) > 0


def test_split_state_decode_matches_monolithic(setup):
    """Hot/cold split decode (§Perf iter 1) is logits-identical."""
    from repro.models.transformer import decode_step_split, split_state
    params, batch, plan, decode, _ = setup
    _, st = M.apply_prefill(params, CFG, batch, runtime="retro", plan=plan,
                            gen_headroom=256)
    tok = jnp.zeros((B,), jnp.int32)
    cold, hot = split_state(st.kv)
    split_fn = jax.jit(lambda p, c, h, t: decode_step_split(
        p, CFG, c, h, t, plan=plan))
    for _ in range(3):
        lg_m, st = decode(params, st, tok, runtime="retro")
        lg_s, hot = split_fn(params, cold, hot, tok)
        np.testing.assert_allclose(np.asarray(lg_m), np.asarray(lg_s),
                                   atol=1e-4, rtol=1e-4)
        tok = jnp.argmax(lg_m, -1).astype(jnp.int32)
