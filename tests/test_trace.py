"""The serve path's tracing: named scopes in the HLO of every jitted serve
stage, and the serve loop's readback counters and per-request times."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AttnConfig, ModelConfig, RetroConfig
from repro.models import model as M
from repro.models.transformer import LIVE_FIELDS
from repro.serving import trace
from repro.serving.engine import Request, ServeEngine

RETRO = RetroConfig(avg_cluster=8, cluster_cap=32, prefill_segment=64,
                    update_segment=32, sink=4, local=32,
                    retrieval_frac=0.25, estimation_frac=0.25, kmeans_iters=2)
CFG = ModelConfig(
    arch_id="trace-tiny", family="dense", n_layers=2, d_model=64, d_ff=128,
    vocab=256, attn=AttnConfig(n_heads=4, n_kv_heads=2, head_dim=16),
    dtype="float32", retro=RETRO)
B, CTX, CHUNK, PATCHES = 2, 192, 64, 24

DECODE_SCOPES = {trace.EMBED, trace.QKV, trace.CACHE_UPDATE, trace.RANK,
                 trace.ATTEND, trace.FFN, trace.UNEMBED}
CHUNK_SCOPES = {trace.EMBED, trace.QKV, trace.CACHE_UPDATE, trace.ATTEND,
                trace.FFN, trace.UNEMBED, trace.INDEX_BUILD}


@pytest.fixture(scope="module")
def engine():
    params = M.init_params(CFG, jax.random.PRNGKey(0))
    return ServeEngine(CFG, params, runtime="retro", gen_headroom=64,
                       max_context=CTX, prefill_chunk=CHUNK)


def _specs(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)


def _lowered(engine, stage):
    """The jitted serve stage ``stage`` lowered at the tiny geometry."""
    params = _specs(engine.params)
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    state = _specs(jax.eval_shape(lambda: M.make_serve_state(
        CFG, B, CTX, runtime="retro", gen_headroom=64, zero_fill=True)))
    cstate = _specs(jax.eval_shape(lambda: M.make_prefill_chunk_state(
        CFG, 1, CTX, runtime="retro", chunk=CHUNK, gen_headroom=64)))
    toks = jax.ShapeDtypeStruct((1, CHUNK), jnp.int32)
    clen = jax.ShapeDtypeStruct((1,), jnp.int32)
    if stage == "decode":
        decode, _ = engine._decode_fns(B, CTX)
        return decode.lower(params, state, jax.ShapeDtypeStruct((B,), jnp.int32),
                            jax.ShapeDtypeStruct((B,), jnp.bool_))
    if stage in ("chunk", "chunk_pe"):
        chunk, chunk_pe = engine._chunk_fns(CTX)
        if stage == "chunk":
            return chunk.lower(params, cstate, toks, clen)
        pe = jax.ShapeDtypeStruct((1, PATCHES, CFG.d_model), jnp.float32)
        return chunk_pe.lower(params, cstate, toks, clen, pe)
    if stage == "fin":
        return engine._finalize_fn(150, CTX).lower(state, cstate, i32)
    # the offload plane's two halves of one decode layer
    from repro.core.zones import plan_zones
    plan = plan_zones(CTX, CFG.retro, 64)
    C, r = 4, max(plan.r, 1)
    _, rank_fn, attend_fn, *_ = engine._offload_fns(B, CTX, C, r)
    lp = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                      params["layers"])
    win = jax.ShapeDtypeStruct((), jnp.float32)
    live = {f: jax.ShapeDtypeStruct(getattr(state.kv, f).shape[1:],
                                    getattr(state.kv, f).dtype)
            for f in LIVE_FIELDS}
    x = jax.ShapeDtypeStruct((B, CFG.d_model), jnp.float32)
    act = jax.ShapeDtypeStruct((B,), jnp.bool_)
    if stage == "rank_fn":
        return rank_fn.lower(lp, win, live, x, act)
    ctx, idx, _ = jax.eval_shape(rank_fn, lp, win, live, x, act)
    H, cap, hd = CFG.n_kv_heads, RETRO.cluster_cap, CFG.head_dim
    blk = jax.ShapeDtypeStruct((B, H, C + r, cap, hd), jnp.float32)
    pos = jax.ShapeDtypeStruct((B, H, C + r, cap), jnp.int32)
    return attend_fn.lower(lp, win, live, x, ctx, blk, blk, pos, idx, idx)


@pytest.mark.parametrize("stage, expected", [
    ("decode", DECODE_SCOPES), ("chunk", CHUNK_SCOPES),
    ("chunk_pe", CHUNK_SCOPES), ("fin", {trace.INDEX_BUILD}),
    ("rank_fn", {trace.QKV, trace.CACHE_UPDATE, trace.RANK}),
    ("attend_fn", {trace.ATTEND, trace.FFN})])
def test_stage_hlo_names_its_scopes(engine, stage, expected):
    """Every vocabulary scope a serve stage runs appears as a component of
    some ``op_name`` in its lowered HLO's metadata (what the profiler
    reports as ``tf_op``; a transformation wraps the name, as in
    ``vmap(index_build)``), and no other vocabulary scope does."""
    text = _lowered(engine, stage).compiler_ir("hlo").get_hlo_module() \
        .to_string()
    parts = {p.rsplit("(", 1)[-1].rstrip(")")
             for path in re.findall(r'op_name="([^"]*)"', text)
             for p in path.split("/")}
    assert expected <= parts, expected - parts
    assert not (parts & set(trace.SCOPES)) - expected


def _serve(offload, lens=(150, 120, 100), news=(5, 3, 7), patches=False):
    params = M.init_params(CFG, jax.random.PRNGKey(1))
    eng = ServeEngine(CFG, params, runtime="retro", gen_headroom=64,
                      max_context=CTX, prefill_chunk=CHUNK, offload=offload)
    rng = np.random.default_rng(3)
    extra = ({"patch_embeds": jnp.ones((1, PATCHES, CFG.d_model))}
             if patches else None)
    reqs = [Request(prompt=rng.integers(0, CFG.vocab, n).astype(np.int32),
                    max_new_tokens=m, extra=extra)
            for n, m in zip(lens, news)]
    return reqs, eng.serve(reqs, batch_size=B)


@pytest.mark.parametrize("offload", [False, True], ids=["direct", "offload"])
def test_readback_waits_and_request_times(offload):
    """The host's readback waits lie inside the phases that hold them;
    each request's time to first token splits into its queue and its
    admission, and queue times follow the FIFO order of admission."""
    reqs, m = _serve(offload)
    assert all(r.status == "ok" and r.done for r in reqs)
    assert m.harvest_wait_s > 0 and m.first_token_wait_s > 0
    waits = m.harvest_wait_s + m.first_token_wait_s
    assert 0 <= waits <= m.prefill_s + m.decode_s
    for r in reqs:
        assert r.queue_s >= 0 and r.admit_s > 0
        assert r.queue_s + r.admit_s == r.ttft_s
    queued = [r.queue_s for r in reqs]
    assert queued == sorted(queued)
    # the third request waits for a slot: its queue time covers a whole
    # admission of one of the first two
    assert reqs[2].queue_s > min(reqs[0].admit_s, reqs[1].admit_s)


def test_spans_are_recorded_on_the_profilers_clock(tmp_path):
    """With a profiler running, each phase of the serve loop leaves its
    host span in the trace (the first request decodes past an index
    update, so the flush runs too)."""
    from jax.profiler import ProfileData
    news = (40, 3, 7)
    _serve(False, news=news)                        # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(False, news=news)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {e.name for p in ProfileData.from_file(str(path)).planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events}
    assert {trace.ADMIT, trace.FIRST_TOKEN, trace.DECODE, trace.HARVEST,
            trace.FLUSH} <= names
