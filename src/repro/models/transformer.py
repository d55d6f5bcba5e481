"""Unified GQA transformer: dense (gemma/minitron), MoE (mixtral/kimi), VLM
(llava backbone). Layers are weight-stacked and scanned (`lax.scan`) so the
compiled HLO stays compact at 61 layers x 512 devices.

Serve-time attention runtime is selectable:
  * "retro" — RetroInfer wave index (the paper's technique)
  * "full"  — dense KV cache, exact attention (the paper's baseline)
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import attention as wa
from repro.core.wave_index import (WaveState, append_token,
                                   init_chunked_prefill, init_wave_state,
                                   maybe_flush, prefill_append_chunk,
                                   prefill_build, prefill_finalize,
                                   scatter_chunk_rows)
from repro.core.zones import ZonePlan, plan_zones
from repro.models import layers as L
from repro.models.moe import init_moe, moe_apply_grouped
from repro.serving import trace

GLOBAL_WINDOW = 1.0e9   # "no sliding window" sentinel (traced-friendly)


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ModelConfig):
    a = cfg.attn
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "ln1": jnp.zeros((cfg.d_model,), _dtype(cfg)),
        "ln2": jnp.zeros((cfg.d_model,), _dtype(cfg)),
        "attn": L.init_attention(k1, cfg.d_model, a.n_heads, a.n_kv_heads,
                                 a.head_dim, _dtype(cfg)),
    }
    if cfg.moe is not None:
        p["moe"] = init_moe(k2, cfg.d_model, cfg.moe, _dtype(cfg))
    else:
        p["mlp"] = L.init_mlp(k3, cfg.d_model, cfg.d_ff, _dtype(cfg))
    return p


def init_transformer(cfg: ModelConfig, key) -> Dict[str, Any]:
    ks = jax.random.split(key, cfg.n_layers + 2)
    layers = jax.vmap(lambda k: init_layer(k, cfg))(ks[: cfg.n_layers])
    window = jnp.asarray(
        [cfg.attn.sliding_window if kind == "l" else GLOBAL_WINDOW
         for kind in cfg.layer_kinds()], jnp.float32)
    params = {
        "embed": L.dense_init(ks[-1], (cfg.vocab, cfg.d_model),
                              scale=cfg.d_model ** -0.5, dtype=_dtype(cfg)),
        "layers": layers,
        "window": window,
        "final_norm": jnp.zeros((cfg.d_model,), _dtype(cfg)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(ks[-2], (cfg.d_model, cfg.vocab),
                                         dtype=_dtype(cfg))
    return params


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------

def embed_tokens(params, cfg: ModelConfig, tokens, patch_embeds=None):
    x = params["embed"][tokens] * math.sqrt(cfg.d_model)
    if patch_embeds is not None:
        npatch = patch_embeds.shape[1]
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x[:, npatch:]], axis=1)
    return x


def unembed(params, cfg: ModelConfig, x):
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T
    else:
        logits = x @ params["lm_head"]
    return logits.astype(jnp.float32)


def _ffn(lp, x, cfg: ModelConfig):
    """x: (..., D) -> (..., D) plus aux loss scalar."""
    if cfg.moe is not None:
        shp = x.shape
        y, aux = moe_apply_grouped(lp["moe"], x.reshape(-1, shp[-1]), cfg.moe,
                                   cfg.act, groups=cfg.moe_dispatch_groups)
        return y.reshape(shp), aux
    return L.mlp_apply(lp["mlp"], x, cfg.act), 0.0


# ---------------------------------------------------------------------------
# training / scoring forward (full attention, chunked online-softmax)
# ---------------------------------------------------------------------------

def forward(params, cfg: ModelConfig, tokens, patch_embeds=None):
    """tokens: (B, T) -> hidden (B, T, D), aux_loss."""
    x = embed_tokens(params, cfg, tokens, patch_embeds)
    B, T, D = x.shape
    positions = jnp.arange(T)
    a = cfg.attn

    @jax.checkpoint
    def layer_fn(carry, xs):
        x, aux = carry
        lp, window = xs
        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = L.attention_qkv(lp["attn"], h, a.n_heads, a.n_kv_heads,
                                  a.head_dim, positions, a.rope_theta)
        o = L.flash_attention_jnp(q, k, v, causal=True, window=window,
                                  softcap=a.softcap)
        x = x + o.reshape(B, T, -1) @ lp["attn"]["wo"]
        h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
        y, aux_l = _ffn(lp, h, cfg)
        return (x + y, aux + aux_l), None

    (x, aux), _ = jax.lax.scan(layer_fn, (x, 0.0),
                               (params["layers"], params["window"]))
    return L.rms_norm(x, params["final_norm"], cfg.norm_eps), aux


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

class ServeState(NamedTuple):
    """Stacked per-layer KV state + scalar step info. kv is either a stacked
    WaveState (retro runtime) or stacked DenseCache (full runtime)."""
    kv: Any


def prefill(params, cfg: ModelConfig, tokens, patch_embeds=None, *,
            runtime: str = "retro", plan: Optional[ZonePlan] = None,
            gen_headroom: int = 4096, lengths: Optional[jax.Array] = None,
            cache_len: Optional[int] = None) -> Tuple[jax.Array, ServeState]:
    """Process the prompt; returns (last-position logits, serve state).

    ``lengths``: optional (B,) int32 true prompt lengths for right-padded
    ragged batches. Causality already keeps real queries blind to pad keys;
    the wave index masks pads out of its stores and the returned logits are
    taken at each row's own last real position.

    ``cache_len``: total dense-cache slots (full runtime) — continuous
    batching sizes every per-slot prefill to the engine-wide capacity so
    states graft into the shared decode batch.
    """
    x = embed_tokens(params, cfg, tokens, patch_embeds)
    B, T, D = x.shape
    positions = jnp.arange(T)
    a = cfg.attn
    retro = cfg.retro
    if plan is None:
        plan = plan_zones(T, retro, gen_headroom)
    lens = None if lengths is None else jnp.asarray(lengths, jnp.int32)
    total = cache_len if cache_len is not None else T + gen_headroom
    assert total >= T, (total, T)

    sp_blocks = cfg.sparse_prefill_blocks
    use_sparse = sp_blocks > 0 and T % 128 == 0

    def layer_fn(x, xs):
        lp, window = xs
        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = L.attention_qkv(lp["attn"], h, a.n_heads, a.n_kv_heads,
                                  a.head_dim, positions, a.rope_theta)
        if use_sparse:
            from repro.core.sparse_prefill import block_sparse_attention
            o = block_sparse_attention(q, k, v, block=128,
                                       topk_blocks=sp_blocks, window=window,
                                       softcap=a.softcap)
        else:
            o = L.flash_attention_jnp(q, k, v, causal=True, window=window,
                                      softcap=a.softcap)
        x = x + o.reshape(B, T, -1) @ lp["attn"]["wo"]
        h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
        y, _ = _ffn(lp, h, cfg)
        x = x + y
        if runtime == "retro":
            st = prefill_build(k, v, retro, plan.m_max, dtype=_dtype(cfg),
                               lengths=lens)
        else:
            st = wa.DenseCache(
                k=jnp.swapaxes(
                    jnp.pad(k, ((0, 0), (0, total - T), (0, 0), (0, 0))), 1, 2
                ).astype(_dtype(cfg)),
                v=jnp.swapaxes(
                    jnp.pad(v, ((0, 0), (0, total - T), (0, 0), (0, 0))), 1, 2
                ).astype(_dtype(cfg)),
                length=(jnp.full((B,), T, jnp.int32) if lens is None else lens))
        return x, st

    x, kv = jax.lax.scan(layer_fn, x, (params["layers"], params["window"]))
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if lens is None:
        last = x[:, -1]
    else:
        last = jnp.take_along_axis(
            x, (lens - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    logits = unembed(params, cfg, last)
    return logits, ServeState(kv=kv)


# ---------------------------------------------------------------------------
# Chunked prefill — admission interleaved with decode (serving engine).
#
# The prompt is consumed a fixed-size chunk at a time: chunk queries attend
# causally to the prior prompt prefix + the chunk itself via an exact
# admission-time dense cache, while the wave index is built incrementally by
# ``prefill_append_chunk``. One compiled shape serves every prompt length
# (the final chunk is right-padded and masked). The admission cache is
# dropped at finalize for the retro runtime; for the dense-cache runtime it
# IS the serve state. Chunk attention is exact — ``sparse_prefill_blocks``
# only applies to the monolithic prefill path.
# ---------------------------------------------------------------------------


class PrefillChunkState(NamedTuple):
    """Admission-time state for chunk-by-chunk prefill. Leaves are stacked
    per-layer (L, ...). ``cache`` holds the exact K/V of the prompt so far;
    ``wave`` is the streaming wave-index build (retro) or None (full)."""
    cache: Any              # stacked DenseCache
    wave: Any               # stacked ChunkedPrefill or None


def init_prefill_chunk_state(cfg: ModelConfig, B: int, max_ctx: int, *,
                             runtime: str = "retro", chunk: int,
                             gen_headroom: int = 4096) -> PrefillChunkState:
    """``max_ctx`` pins the admission geometry to the engine's decode state so
    the finalized state grafts into the shared batch. The dense-runtime cache
    is allocated at full decode capacity (it becomes the serve state); the
    retro admission cache only needs the prompt capacity."""
    a, retro = cfg.attn, cfg.retro
    plan = plan_zones(max_ctx, retro, gen_headroom)
    cache_len = max_ctx if runtime == "retro" else max_ctx + gen_headroom

    def one(_):
        cache = wa.DenseCache(
            jnp.zeros((B, a.n_kv_heads, cache_len, a.head_dim), _dtype(cfg)),
            jnp.zeros((B, a.n_kv_heads, cache_len, a.head_dim), _dtype(cfg)),
            jnp.zeros((B,), jnp.int32))
        if runtime == "retro":
            return cache, init_chunked_prefill(
                B, a.n_kv_heads, a.head_dim, plan.m_max, retro, chunk,
                _dtype(cfg))
        return cache, None

    cache, wave = jax.vmap(one)(jnp.arange(cfg.n_layers))
    return PrefillChunkState(cache=cache, wave=wave)


def _cache_append_chunk(cache: wa.DenseCache, k, v, clens) -> wa.DenseCache:
    """Append a (B, C, Hkv, hd) chunk at each row's cursor. Only the valid
    prefix of each row's chunk is written (dropped scatter — a padded final
    chunk near capacity must not clamp into earlier entries)."""
    B, C = k.shape[:2]
    cap = cache.k.shape[2]
    j = jnp.arange(C, dtype=jnp.int32)[None, :]
    clens = jnp.asarray(clens, jnp.int32)
    idx = jnp.where(j < clens[:, None], cache.length[:, None] + j, cap)
    return wa.DenseCache(
        scatter_chunk_rows(cache.k, jnp.swapaxes(k, 1, 2), idx),
        scatter_chunk_rows(cache.v, jnp.swapaxes(v, 1, 2), idx),
        cache.length + clens)


def _chunk_attention(q, cache: wa.DenseCache, t0, clens, *, window=None,
                     softcap=None):
    """Exact causal attention of chunk queries against the admission cache
    (which already holds the chunk). q: (B, C, Hq, hd); t0: (B,) absolute
    position of q[:, 0]; keys beyond each row's filled prefix are masked."""
    B, C, Hq, hd = q.shape
    Hkv = cache.k.shape[1]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, C, Hkv, G, hd)
    s = jnp.einsum("bchgd,bhtd->bhgct", qg.astype(jnp.float32),
                   cache.k.astype(jnp.float32)) * scale
    s = L.soft_cap(s, softcap)
    kpos = jnp.arange(cache.k.shape[2])
    q_abs = t0[:, None] + jnp.arange(C)                     # (B, C)
    ok = (kpos[None, None, :] <= q_abs[:, :, None]) \
        & (kpos[None, None, :] < (t0 + clens)[:, None, None])
    if window is not None:
        ok = ok & (kpos[None, None, :] > q_abs[:, :, None] - window)
    s = jnp.where(ok[:, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgct,bhtd->bhgcd", p, cache.v.astype(jnp.float32))
    return jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(B, C, Hq, hd).astype(q.dtype)


def prefill_chunk(params, cfg: ModelConfig, tokens, state: PrefillChunkState,
                  *, runtime: str = "retro", chunk_lens=None,
                  patch_embeds=None) -> Tuple[jax.Array, PrefillChunkState]:
    """Process the next prompt chunk. tokens: (B, C) right-padded; returns
    (logits at each row's last valid chunk position, new state).

    ``chunk_lens``: optional (B,) valid prefix per row (None = full chunk).
    ``patch_embeds``: full (B, P, D) vlm patch embeddings — the slice
    overlapping this chunk's absolute positions replaces the token embeds.
    """
    a, retro = cfg.attn, cfg.retro
    B, C = tokens.shape
    clens = jnp.full((B,), C, jnp.int32) if chunk_lens is None \
        else jnp.asarray(chunk_lens, jnp.int32)
    t0 = state.cache.length[0]                              # (B,) shared by layers
    positions = t0[:, None] + jnp.arange(C)                 # (B, C) per-row
    with trace.scope(trace.EMBED):
        x = params["embed"][tokens] * math.sqrt(cfg.d_model)
        if patch_embeds is not None:
            P = patch_embeds.shape[1]
            pe = jnp.take_along_axis(patch_embeds,
                                     jnp.clip(positions, 0, P - 1)[..., None],
                                     axis=1)
            x = jnp.where((positions < P)[..., None], pe.astype(x.dtype), x)

    def layer_fn(x, xs):
        lp, cache_l, wave_l, window = xs
        with trace.scope(trace.QKV):
            h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = L.attention_qkv(lp["attn"], h, a.n_heads, a.n_kv_heads,
                                      a.head_dim, positions, a.rope_theta)
        with trace.scope(trace.CACHE_UPDATE):
            cache_l = _cache_append_chunk(cache_l, k, v, clens)
        with trace.scope(trace.ATTEND):
            o = _chunk_attention(q, cache_l, t0, clens, window=window,
                                 softcap=a.softcap)
            x = x + o.reshape(B, C, -1) @ lp["attn"]["wo"]
        with trace.scope(trace.FFN):
            h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
            y, _ = _ffn(lp, h, cfg)
            x = x + y
        if runtime == "retro":
            wave_l = prefill_append_chunk(wave_l, k, v, retro, clens)
        return x, (cache_l, wave_l)

    x, (cache, wave) = jax.lax.scan(
        layer_fn, x,
        (params["layers"], state.cache, state.wave, params["window"]))
    with trace.scope(trace.UNEMBED):
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        last = jnp.take_along_axis(
            x, jnp.maximum(clens - 1, 0)[:, None, None].astype(jnp.int32),
            axis=1)[:, 0]
        logits = unembed(params, cfg, last)
    return logits, PrefillChunkState(cache=cache, wave=wave)


def finalize_prefill_chunk(cfg: ModelConfig, state: PrefillChunkState, *,
                           runtime: str = "retro", total_len: int) -> ServeState:
    """Close a chunked admission: retro clusters the tail + installs the local
    window (bit-identical wave state to ``prefill_build``); the dense runtime's
    admission cache is the serve state as-is."""
    if runtime != "retro":
        return ServeState(kv=state.cache)
    kv = jax.vmap(
        lambda w: prefill_finalize(w, cfg.retro, total_len))(state.wave)
    return ServeState(kv=kv)


def decode_step(params, cfg: ModelConfig, state: ServeState, token, *,
                runtime: str = "retro", plan: ZonePlan,
                inline_flush: bool = False,
                active: Optional[jax.Array] = None,
                attn_impl: Optional[str] = None) -> Tuple[jax.Array, ServeState]:
    """One generation step. token: (B,) int32 -> logits (B, V).

    ``attn_impl``: wave-attention implementation — "jnp" (reference) or
    "fused" (gather-free paged Pallas kernel); None defers to
    ``cfg.retro.attn_impl``.

    ``inline_flush=False`` keeps the segmented-clustering index update OFF the
    hot path (the paper amortizes it to ~0.2% of decode latency by running it
    asynchronously every 1K tokens); the serving engine calls
    ``model.flush_state`` when the staging buffer fills. ``inline_flush=True``
    folds it into the step (self-contained, used by some tests).

    ``active``: optional (B,) bool continuous-batching slot mask — rows whose
    slot is free skip the KV append so their counters never drift; their
    logits are computed but discarded by the scheduler. Rows are at their OWN
    positions: RoPE uses each row's length."""
    a = cfg.attn
    retro = cfg.retro
    impl = wa.resolve_attn_impl(attn_impl or retro.attn_impl)
    with trace.scope(trace.EMBED):
        x = params["embed"][token] * math.sqrt(cfg.d_model)     # (B, D)
    B = x.shape[0]

    def layer_fn(x, xs):
        lp, lstate, window = xs
        pos = lstate.length                                  # (B,) new token position
        with trace.scope(trace.QKV):
            h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = L.attention_qkv(
                lp["attn"], h[:, None, :], a.n_heads, a.n_kv_heads,
                a.head_dim, pos[:, None], a.rope_theta)
            q, k, v = q[:, 0], k[:, 0], v[:, 0]              # (B, H*, hd)
        if runtime == "retro":
            # append_token, the rank and the attend half name themselves
            lstate = append_token(lstate, k, v, active=active)
            out = wa.wave_attention_decode(q, lstate, retro, plan,
                                           window=window, softcap=a.softcap,
                                           impl=impl)
            if inline_flush:
                lstate = maybe_flush(lstate, retro)
            o = out.out
        else:
            with trace.scope(trace.CACHE_UPDATE):
                lstate = wa.dense_cache_append(lstate, k, v, active=active)
            with trace.scope(trace.ATTEND):
                o = wa.full_attention_decode(q, lstate, window=window,
                                             softcap=a.softcap)
        with trace.scope(trace.ATTEND):
            x = x + o.reshape(B, -1) @ lp["attn"]["wo"]
        with trace.scope(trace.FFN):
            h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
            y, _ = _ffn(lp, h, cfg)
            x = x + y
        return x, lstate

    x, kv = jax.lax.scan(layer_fn, x,
                         (params["layers"], state.kv, params["window"]))
    with trace.scope(trace.UNEMBED):
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = unembed(params, cfg, x)
    return logits, ServeState(kv=kv)


# ---------------------------------------------------------------------------
# Hot/cold state split (§Perf iteration 1, EXPERIMENTS.md)
#
# The monolithic serve step threads the full wave state through the layer
# scan; unchanged cluster stores then appear in the scan's ys and the step's
# outputs, which the compiled HLO materializes as full-store copies (and the
# cost analysis charges as memory traffic). Decode only MUTATES the staging
# buffers + counters ("hot"); the cluster stores/meta index ("cold") change
# only at the 1K-token flush. Splitting them keeps the cold stores out of the
# step's dataflow entirely.
# ---------------------------------------------------------------------------

COLD_FIELDS = ("k_store", "v_store", "pos_store", "centroid", "vsum", "size",
               "stored", "max_pos", "n_clusters")
HOT_FIELDS = ("sink_k", "sink_v", "local_k", "local_v", "local_len", "length")


def split_state(kv: WaveState):
    cold = {f: getattr(kv, f) for f in COLD_FIELDS}
    hot = {f: getattr(kv, f) for f in HOT_FIELDS}
    return cold, hot


def join_state(cold, hot) -> WaveState:
    return WaveState(**cold, **hot)


def decode_step_split(params, cfg: ModelConfig, cold, hot, token, *,
                      plan: ZonePlan, unroll: bool = False, mesh=None,
                      attn_impl: Optional[str] = None):
    """Retro decode with the hot/cold split: returns (logits, new_hot).

    ``cold``/``hot`` are dicts of stacked (L, ...) leaves as produced by
    ``split_state`` applied to ``ServeState.kv``.

    ``unroll=True`` replaces the layer scan with an unrolled loop (§Perf
    iteration): lax.scan bundles its xs — including the read-only cluster
    stores — into the while-loop tuple, which buffer assignment materializes
    as a full-store temp copy; unrolling reads the stores in place.

    ``attn_impl``: as in ``decode_step`` ("fused" composes with the split:
    the paged kernel reads the cold stores in place)."""
    a, retro = cfg.attn, cfg.retro
    impl = wa.resolve_attn_impl(attn_impl or retro.attn_impl)
    x = params["embed"][token] * math.sqrt(cfg.d_model)
    B = x.shape[0]

    def layer_fn(x, xs):
        lp, cold_i, hot_i, window = xs
        lstate = join_state(cold_i, hot_i)
        pos = lstate.length                                  # (B,) per-row
        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = L.attention_qkv(
            lp["attn"], h[:, None, :], a.n_heads, a.n_kv_heads, a.head_dim,
            pos[:, None], a.rope_theta)
        q, k, v = q[:, 0], k[:, 0], v[:, 0]
        lstate = append_token(lstate, k, v)
        if mesh is not None:
            from repro.core.distributed import distributed_wave_attention
            o = distributed_wave_attention(q, lstate, retro, plan, mesh,
                                           window=window, softcap=a.softcap)
        else:
            o = wa.wave_attention_decode(q, lstate, retro, plan,
                                         window=window, softcap=a.softcap,
                                         impl=impl).out
        x = x + o.reshape(B, -1) @ lp["attn"]["wo"]
        h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
        y, _ = _ffn(lp, h, cfg)
        new_hot = {f: getattr(lstate, f) for f in HOT_FIELDS}
        return x + y, new_hot

    if unroll:
        # cold may be a stacked dict of (L, ...) leaves or a per-layer list
        # (separate args => no per-layer slices of the stacked store, which
        # the HLO cost model charges at full-operand size; see EXPERIMENTS).
        per_layer_cold = isinstance(cold, (list, tuple))
        hots = []
        kinds = cfg.layer_kinds()
        for i in range(cfg.n_layers):
            sl = lambda t: jax.tree.map(lambda a_: a_[i], t)
            cold_i = cold[i] if per_layer_cold else sl(cold)
            # static per-layer window in the unrolled path
            win = jnp.float32(a.sliding_window if kinds[i] == "l"
                              else GLOBAL_WINDOW)
            x, nh = layer_fn(x, (sl(params["layers"]), cold_i, sl(hot), win))
            hots.append(nh)
        new_hot = jax.tree.map(lambda *xs: jnp.stack(xs), *hots)
    else:
        assert mesh is None, "distributed retrieval requires unroll=True"
        x, new_hot = jax.lax.scan(
            layer_fn, x, (params["layers"], cold, hot, params["window"]))
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return unembed(params, cfg, x), new_hot


# ---------------------------------------------------------------------------
# Host-offload decode (paper Sec. 4.3: wave buffer in the serve loop)
#
# The cluster PAYLOAD stores (k_store/v_store/pos_store) live host-side; the
# device keeps the meta index + steady zones ("live" fields) plus a block
# cache. One decode step is two jitted halves per layer with the control
# plane (cluster-id -> cache-slot translation, miss fetch, deferred
# admissions) in between:
#
#   rank:   qkv + local append + centroid ranking + estimation build
#           -> retrieved cluster ids (the only per-layer host sync)
#   attend: paged attention over [device block cache | miss staging buffer]
#           via translated cache slots, then output proj + FFN
#
# Identical math to ``decode_step`` — cache placement is accuracy-agnostic.
# ---------------------------------------------------------------------------

PAYLOAD_FIELDS = ("k_store", "v_store", "pos_store")
LIVE_FIELDS = tuple(f for f in WaveState._fields if f not in PAYLOAD_FIELDS)


def live_wave_state(live: Dict[str, jax.Array]) -> WaveState:
    """WaveState view over the device-resident fields of the host-offload
    configuration — the payload stores are host-side, so they are ``None``
    here; rank/estimation/steady-zone code never touches them."""
    return WaveState(k_store=None, v_store=None, pos_store=None, **live)


def decode_embed(params, cfg: ModelConfig, token):
    """token: (B,) int32 -> (B, D) embedded decode input."""
    with trace.scope(trace.EMBED):
        return params["embed"][token] * math.sqrt(cfg.d_model)


def decode_unembed(params, cfg: ModelConfig, x):
    """(B, D) final hidden -> (B, V) logits (final norm + unembed)."""
    with trace.scope(trace.UNEMBED):
        return unembed(params, cfg, L.rms_norm(x, params["final_norm"],
                                               cfg.norm_eps))


def offload_decode_rank(lp, window, cfg: ModelConfig, live: Dict, x, *,
                        plan: ZonePlan, active: Optional[jax.Array] = None):
    """Control-plane half of one offload decode layer. Returns
    ``(ctx, idx_r, new_live)`` — ``idx_r`` (B, Hkv, r) are the retrieved
    cluster ids the engine translates into cache slots; ``ctx`` carries the
    query + estimation tensors into :func:`offload_decode_attend`."""
    a, retro = cfg.attn, cfg.retro
    B = x.shape[0]
    lstate = live_wave_state(live)
    pos = lstate.length                                  # (B,) new token pos
    with trace.scope(trace.QKV):
        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = L.attention_qkv(
            lp["attn"], h[:, None, :], a.n_heads, a.n_kv_heads, a.head_dim,
            pos[:, None], a.rope_theta)
        q, k, v = q[:, 0], k[:, 0], v[:, 0]              # (B, H*, hd)
    lstate = append_token(lstate, k, v, active=active)
    G = a.n_heads // a.n_kv_heads
    qg = q.reshape(B, a.n_kv_heads, G, a.head_dim)
    idx_r, est_logit, cs_e, vs_e, cover = wa.wave_decode_rank(
        qg, lstate, retro, plan, window=window, softcap=a.softcap,
        with_cover=True)
    ctx = (q, est_logit, cs_e, vs_e, cover)
    return ctx, idx_r, {f: getattr(lstate, f) for f in LIVE_FIELDS}


def offload_decode_attend(lp, window, cfg: ModelConfig, live: Dict, x, ctx,
                          cache_k, cache_v, cache_pos, idx_slots, valid, *,
                          plan: ZonePlan, attn_impl: Optional[str] = None):
    """Data-plane half: attention over the steady zone + the slot-addressed
    blocks of the device cache (hits) / miss staging tail (misses), then
    output projection + FFN. Returns the next hidden state.

    ``valid``: (B, Hkv, r) int32 per-cluster validity mask from the control
    plane's translate — 0 marks a cluster whose fetch failed its deadline
    this step; it is masked out of the retrieval zone and covered by the
    estimation zone via ``ctx``'s cover triple (degraded decode). All-ones
    is bit-identical to the pre-fault path."""
    a, retro = cfg.attn, cfg.retro
    impl = wa.resolve_attn_impl(attn_impl or retro.attn_impl)
    B = x.shape[0]
    lstate = live_wave_state(live)
    q, est_logit, cs_e, vs_e, cover = ctx
    out = wa.wave_attention_attend(
        q, lstate, retro, plan, idx_slots, est_logit, cs_e, vs_e,
        kv_src=(cache_k, cache_v, cache_pos), window=window,
        softcap=a.softcap, impl=impl, valid=valid, cover=cover).out
    with trace.scope(trace.ATTEND):
        x = x + out.reshape(B, -1) @ lp["attn"]["wo"]
    with trace.scope(trace.FFN):
        h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
        y, _ = _ffn(lp, h, cfg)
        return x + y


def offload_flush(cfg: ModelConfig, live_stacked: Dict, rows):
    """Index update for the offload serve path: per layer, cluster the oldest
    update segment into META entries on device and return the payload blocks
    (stacked (L, B, H, k_new, cap, ...)) for the host store. ``rows``: (B,)
    bool — rows to flush (the engine's staging-full mirror); unflushed rows
    pass through bit-unchanged and their returned blocks must be ignored."""
    from repro.core.wave_index import flush_segment_offload

    def one(lv):
        st, res = flush_segment_offload(live_wave_state(lv), cfg.retro,
                                        rows=rows)
        return {f: getattr(st, f) for f in LIVE_FIELDS}, res

    return jax.vmap(one)(live_stacked)


def init_serve_state(cfg: ModelConfig, B: int, seq_len: int, *,
                     runtime: str = "retro", gen_headroom: int = 4096,
                     zero_fill: bool = False) -> ServeState:
    """Zero-initialized serve state with the same structure/shape the prefill
    produces — used for dry-run lowering of serve_step without a real prefill.

    ``zero_fill=True`` leaves every per-row counter at zero (an all-free
    continuous-batching batch awaiting per-slot prefill grafts) instead of
    pretending each row holds a full ``seq_len`` context."""
    a, retro = cfg.attn, cfg.retro
    plan = plan_zones(seq_len, retro, gen_headroom)

    def one_layer(_):
        if runtime == "retro":
            st = init_wave_state(B, a.n_kv_heads, a.head_dim, plan.m_max,
                                 retro, _dtype(cfg))
            if not zero_fill:
                st = st._replace(
                    length=jnp.full((B,), seq_len, jnp.int32),
                    local_len=jnp.full((B,), retro.local, jnp.int32),
                    n_clusters=jnp.full((B,), plan.m_max, jnp.int32))
            return st
        return wa.DenseCache(
            jnp.zeros((B, a.n_kv_heads, seq_len + gen_headroom, a.head_dim),
                      _dtype(cfg)),
            jnp.zeros((B, a.n_kv_heads, seq_len + gen_headroom, a.head_dim),
                      _dtype(cfg)),
            jnp.full((B,), 0 if zero_fill else seq_len, jnp.int32))

    kv = jax.vmap(one_layer)(jnp.arange(cfg.n_layers))
    return ServeState(kv=kv)
