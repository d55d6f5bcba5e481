"""Continuous-batching serving engine with chunked admission and a sync-free
decode loop.

The decode loop runs a fixed number of SLOTS (the decode batch). Each slot
holds at most one in-flight request; finished requests free their slot and
queued requests are admitted mid-stream. Per-request wave-index bookkeeping
(``length``/``local_len``/``n_clusters`` are (B,) arrays) lets ragged
requests sit at different positions in one batch; staging-buffer flushes are
per-row masked, so rows flush on their own schedule.

Admission (``admission="chunked"``, the default where the family supports
it): a request's prompt is consumed one fixed-size chunk per scheduler
iteration, interleaved between decode steps, so in-flight decodes never stall
longer than one chunk. One compiled chunk shape (the final chunk is
right-padded and masked) replaces the per-bucket prefill jit cache; the wave
index is built incrementally (``prefill_append_chunk``) and finalized
bit-identically to the monolithic build. ``admission="blocking"`` keeps the
monolithic per-slot prefill (bucketed/jit-cached) for comparison and for the
pass-through families (encdec/hybrid/ssm), which fall back automatically.

The decode loop issues NO host sync between consecutive decode dispatches:
tokens are sampled on device and fed device-to-device into the next step; the
ids of step t are read back (the loop's only sync) only after step t+1 has
been dispatched. Completion is therefore detected one step late — the extra
speculative token of a just-finished request is dropped on harvest, and its
slot's state is overwritten by the next admission graft. First tokens of all
requests admitted in the same iteration are sampled with ONE coalesced
device->host readback.

Host-offload mode (``offload=True``, paper Sec. 4.3): the cluster payload
stores live host-side behind per-(layer, slot, kv-head) ``WaveBuffer``s and
decode attention reads a per-layer device block cache through cache-slot
indirection — hits from the cache store, misses fetched over the link into a
per-step staging tail — with cache admissions deferred off the hot path.
Token-for-token identical to the direct-store path; the decode loop then
syncs retrieved ids once per layer (the paper's CPU control plane), trading
the sync-free loop for bounded device memory. See ``_OffloadPlane``.

Metrics are per-request (TTFT, decode tok/s) plus engine-level slot occupancy,
aggregate throughput, and inter-token latency (p50/p99 over gaps between
consecutive token deliveries of continuing requests — the decode-interference
signal chunked admission exists to shrink). Only real requests count: free
slots produce logits that are never sampled, so padding can't inflate
``tokens_out``. Offload serving adds the wave-buffer counters (hit ratio,
bytes over the link / from cache / from pending, pending hits) aggregated
over every per-row block cache.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.wave_buffer import (BufferStats, FatalTransportError,
                                    FaultProfile, FaultyTransport,
                                    LinkTransport, WaveBuffer)
from repro.core.wave_index import local_buffer_size
from repro.core.zones import plan_zones
from repro.models import model as M
from repro.models.model import ATTN_FAMILIES
from repro.models.transformer import HOT_FIELDS, LIVE_FIELDS
from repro.serving import trace


@dataclass
class Request:
    prompt: np.ndarray                  # (S,) int32
    max_new_tokens: int
    out_tokens: List[int] = field(default_factory=list)
    done: bool = False
    extra: Optional[Dict] = None        # per-request prefill extras (e.g. vlm)
    # ---- filled by the engine ----
    ttft_s: float = 0.0                 # enqueue -> first token (queue + admit)
    queue_s: float = 0.0                # enqueue -> its first chunk dispatched
    admit_s: float = 0.0                # first chunk dispatched -> first token
    decode_tps: float = 0.0             # this request's decode tokens/s
    # "ok" | "timeout" (max-decode-steps watchdog) | "error" (unrecoverable
    # transport fault) — structured per-request completion status; non-ok
    # requests still free their slot and the scheduler keeps serving
    status: str = "ok"


@dataclass
class ServeMetrics:
    """Aggregate serve metrics. Padding/free slots never contribute: only
    sampled tokens of real requests are counted."""
    prefill_s: float = 0.0
    decode_s: float = 0.0
    # host time blocked in the loop's two readbacks: the lagged id harvest
    # (inside decode_s) and the coalesced first-token sample (inside
    # prefill_s) — high while the device sets the pace, low when the host does
    harvest_wait_s: float = 0.0
    first_token_wait_s: float = 0.0
    tokens_out: int = 0
    steps: int = 0                      # decode steps executed
    flushes: int = 0                    # decode-time index updates run
    occupied_slot_steps: int = 0        # sum over steps of active slots
    n_slots: int = 0
    ttft_s: List[float] = field(default_factory=list)
    request_tps: List[float] = field(default_factory=list)
    # gaps between consecutive token deliveries of continuing requests —
    # includes any admission work scheduled in between (the interference term)
    step_s: List[float] = field(default_factory=list)
    # host-offload wave-buffer counters (Fig. 16 at serve level; zero unless
    # the engine runs with offload=True) — aggregated over every per-row
    # block cache, including caches retired when their slot was re-admitted
    cache: "BufferStats" = field(default_factory=BufferStats)
    # degraded decode (retrofault): steps whose attend ran with >= 1 cluster
    # masked out of the retrieval zone (fetch failed its deadline/retries,
    # mass covered by the estimation zone), and the cluster·step drop count
    degraded_steps: int = 0
    dropped_cluster_steps: int = 0

    @property
    def decode_tps(self) -> float:
        return self.tokens_out / max(self.decode_s, 1e-9)

    # -- delegated wave-buffer counters (single source of truth: BufferStats)
    @property
    def cache_lookups(self) -> int:
        return self.cache.lookups

    @property
    def cache_hits(self) -> int:
        return self.cache.hits

    @property
    def cache_pending_hits(self) -> int:
        return self.cache.pending_hits

    @property
    def bytes_over_link(self) -> int:
        return self.cache.bytes_over_link

    @property
    def bytes_from_cache(self) -> int:
        return self.cache.bytes_from_cache

    @property
    def bytes_from_pending(self) -> int:
        return self.cache.bytes_from_pending

    # -- fault/retry aggregates (retrofault; zero on a clean link)
    @property
    def cache_faults(self) -> int:
        return self.cache.faults

    @property
    def cache_retries(self) -> int:
        return self.cache.retries

    @property
    def cache_corrupt_fetches(self) -> int:
        return self.cache.corrupt_fetches

    @property
    def cache_failed_fetches(self) -> int:
        return self.cache.failed_fetches

    @property
    def cache_hit_ratio(self) -> float:
        return self.cache.hit_ratio

    @property
    def effective_cache_hit_ratio(self) -> float:
        """Includes pending hits (repeat misses served without a second link
        transfer) — the traffic-relevant hit rate."""
        return self.cache.effective_hit_ratio

    @property
    def slot_occupancy(self) -> float:
        return self.occupied_slot_steps / max(self.steps * self.n_slots, 1)

    @property
    def itl_p50_s(self) -> float:
        return float(np.percentile(self.step_s, 50)) if self.step_s else 0.0

    @property
    def itl_p99_s(self) -> float:
        return float(np.percentile(self.step_s, 99)) if self.step_s else 0.0

    @property
    def ttft_p50_s(self) -> float:
        return float(np.percentile(self.ttft_s, 50)) if self.ttft_s else 0.0

    @property
    def ttft_p99_s(self) -> float:
        return float(np.percentile(self.ttft_s, 99)) if self.ttft_s else 0.0


# back-compat alias (pre-continuous engines returned per-wave metrics)
WaveMetrics = ServeMetrics


# ---------------------------------------------------------------------------
# Stage contract, consumed by the retrolint jaxpr checker (repro.analysis).
#
# Every jitted serve stage is registered here by its function __name__ with
# the donations it MUST declare (and which must lower to true output aliases
# — rule RL102) and its compile budget over a serve run (rule RL103):
#   * "per_geometry":      compiles exactly once per engine geometry
#   * "per_prompt_len":    once per distinct admitted prompt length
#   * "per_prompt_bucket": once per distinct bucketed prompt length
#                          (blocking admission only)
# Adding a jitted stage to the engine without registering it here fails the
# lint gate, which is the point: the contract is the reviewable artifact.
#
# PR 7 (retrosched) extends every entry with its EFFECTS — the abstract
# buffers the stage reads / writes / donates / passes (donated-and-carried:
# the output aliases the input unchanged) — and the memory ``space`` it runs
# in. Buffer names come from ``analysis.schedule_model.BUFFER_SPACE``;
# ``[l]`` means the event's layer instance, ``[*]`` every layer. Host
# control-plane ops of the offload decode step (``space="host"``,
# ``budget="host"``: not jitted, so no compile budget or donation lowering
# applies) are registered in the same table so the whole schedule contract
# is one reviewable artifact; the happens-before checker (RL301-RL305)
# resolves recorded schedule events against these declarations. How to
# declare effects for a new stage: src/repro/analysis/README.md.
# ---------------------------------------------------------------------------
SERVE_STAGES: Dict[str, Dict[str, Any]] = {
    # engine-lifetime jits (built in __init__)
    "graft":           dict(donate=(0,), budget="per_geometry",
                            space="device",
                            effects=dict(reads=("serve_state", "slot_state"),
                                         writes=("serve_state",),
                                         donates=("serve_state",))),
    "argmax_ids":      dict(donate=(), budget="per_geometry", space="device",
                            effects=dict(reads=("logits",),
                                         writes=("tokens",))),
    "categorical_ids": dict(donate=(), budget="per_geometry", space="device",
                            effects=dict(reads=("logits",),
                                         writes=("tokens",))),
    "merge_tokens":    dict(donate=(), budget="per_geometry", space="device",
                            effects=dict(reads=("tokens",),
                                         writes=("tokens",))),
    # admission
    "prefill":         dict(donate=(), budget="per_prompt_bucket",
                            space="device",
                            effects=dict(reads=("prompt",),
                                         writes=("slot_state",))),
    "chunk":           dict(donate=(1,), budget="per_geometry",
                            space="device",
                            effects=dict(reads=("prompt", "chunk_state"),
                                         writes=("chunk_state",),
                                         donates=("chunk_state",))),
    "chunk_pe":        dict(donate=(1,), budget="per_geometry",
                            space="device",
                            effects=dict(reads=("prompt", "chunk_state"),
                                         writes=("chunk_state",),
                                         donates=("chunk_state",))),
    # fin's chunk state (arg 1) stays un-donated on purpose: finalize
    # TRANSFORMS the staged tail (clustering) rather than updating it in
    # place, so most leaves cannot alias an output and a donation would
    # silently degrade to copies (RL102 would rightly fail); copy_ok
    # records the exemption for the RL104 missed-donation advice
    "fin":             dict(donate=(0,), budget="per_prompt_len",
                            copy_ok=(1,), space="device",
                            effects=dict(reads=("serve_state",
                                                "chunk_state"),
                                         writes=("serve_state",
                                                 "slot_state"),
                                         donates=("serve_state",))),
    # direct-store decode
    "decode":          dict(donate=(1,), budget="per_geometry",
                            space="device",
                            effects=dict(reads=("tokens", "serve_state"),
                                         writes=("logits", "serve_state"),
                                         donates=("serve_state",))),
    "flush":           dict(donate=(0,), budget="per_geometry",
                            space="device",
                            effects=dict(reads=("serve_state",),
                                         writes=("serve_state",),
                                         donates=("serve_state",))),
    # host-offload decode plane (device stream)
    "embed_tokens":    dict(donate=(), budget="per_geometry", space="device",
                            effects=dict(reads=("tokens",),
                                         writes=("hidden",))),
    "rank_fn":         dict(donate=(2,), budget="per_geometry",
                            space="device",
                            effects=dict(reads=("hidden", "live[l]"),
                                         writes=("ctx[l]", "ids[l]",
                                                 "live[l]"),
                                         donates=("live[l]",))),
    "attend_fn":       dict(donate=(), budget="per_geometry", space="device",
                            effects=dict(reads=("hidden", "ctx[l]",
                                                "live[l]", "cache_body[l]",
                                                "cache_tail[l]", "slots[l]",
                                                "valid[l]"),
                                         writes=("hidden",))),
    "unembed_logits":  dict(donate=(), budget="per_geometry", space="device",
                            effects=dict(reads=("hidden",),
                                         writes=("logits",))),
    "cache_upd":       dict(donate=(0, 1, 2), budget="per_geometry",
                            space="device",
                            # the staging tail is overwritten wholesale (all
                            # r slots restaged every step), so it is not a
                            # data read; the body IS (scatter preserves
                            # un-admitted slots)
                            effects=dict(reads=("cache_body[l]",
                                                "adm_queue[l]", "miss[l]"),
                                         writes=("cache_body[l]",
                                                 "cache_tail[l]"),
                                         donates=("cache_body[l]",
                                                  "cache_tail[l]"))),
    # cache_stage donates the whole cache array but only WRITES the staging
    # tail — the body rides through as an aliased output (``passes``), which
    # is what keeps RL305 from treating the body as clobbered
    "cache_stage":     dict(donate=(0, 1, 2), budget="per_geometry",
                            space="device",
                            effects=dict(reads=("miss[l]",),
                                         writes=("cache_tail[l]",),
                                         donates=("cache_body[l]",
                                                  "cache_tail[l]"),
                                         passes=("cache_body[l]",))),
    "offload_flush":   dict(donate=(0,), budget="per_geometry",
                            space="device",
                            effects=dict(reads=("live[*]",),
                                         writes=("live[*]", "flush_blocks"),
                                         donates=("live[*]",))),
    # host control plane of the offload decode step (not jitted; traced as
    # schedule events via _OffloadPlane.trace)
    "readback_start":  dict(donate=(), budget="host", space="host",
                            effects=dict(reads=("ids[l]",))),
    "readback_ids":    dict(donate=(), budget="host", space="host",
                            effects=dict(reads=("ids[l]",),
                                         writes=("ids_host[l]",))),
    # translate additionally builds the per-cluster validity mask (valid[l],
    # link space): 0 marks a miss whose fetch failed its retry/deadline
    # budget this step — attend masks it out of the retrieval zone and the
    # estimation zone covers its mass (degraded decode, retrofault)
    "translate":       dict(donate=(), budget="host", space="host",
                            effects=dict(reads=("ids_host[l]", "cmt[l]",
                                                "host_store[l]",
                                                "pending[l]"),
                                         writes=("slots[l]", "miss[l]",
                                                 "valid[l]", "pending[l]",
                                                 "cmt[l]"))),
    "drain_admissions": dict(donate=(), budget="host", space="host",
                             effects=dict(reads=("pending[l]",
                                                 "host_store[l]"),
                                          writes=("cmt[l]", "pending[l]",
                                                  "adm_queue[l]"))),
    "readback_flush":  dict(donate=(), budget="host", space="host",
                            effects=dict(reads=("flush_blocks",))),
    "host_flush":      dict(donate=(), budget="host", space="host",
                            effects=dict(writes=("host_store[*]",))),
    "admit_slot":      dict(donate=(), budget="host", space="host",
                            effects=dict(writes=("host_store[*]", "cmt[*]",
                                                 "pending[*]",
                                                 "adm_queue[*]"))),
}

# retronum (PR 10): the per-stage NUMERICS contract, checked by the
# precision-flow pass (rules RL401-RL406, ``analysis/numerics_check.py``)
# over every recorded stage trace. Schema (``analysis/README.md``):
#   softmax — dtype floor for exp/log/LSE-chain transcendentals (RL401)
#   accum   — dtype floor for dot_general accumulation       (RL402)
#   narrow  — "output-only": the final astype(q.dtype) and same-dtype
#             storage writes are the ONLY sanctioned narrowings
#             (RL403/RL404); "free" opts a stage out
# Every device stage runs under the default f32 contract; a stage needing
# a different floor declares its own ``numerics=`` inline (setdefault
# below respects it). Host control-plane steps hold no traced math, so
# they carry no contract.
NUMERICS_F32: Dict[str, str] = dict(softmax="float32", accum="float32",
                                    narrow="output-only")
for _contract in SERVE_STAGES.values():
    if _contract["space"] == "device":
        _contract.setdefault("numerics", NUMERICS_F32)
del _contract


@dataclass
class _Admission:
    """One slot's in-progress chunked admission (or a just-finished blocking
    prefill awaiting its coalesced first-token sample)."""
    req: Request
    cstate: Any = None                  # PrefillChunkState (chunked mode)
    consumed: int = 0
    logits: Any = None                  # device logits of the last chunk


class _OffloadPlane:
    """Host control plane of one offload serve() call (paper Sec. 4.3).

    The cluster PAYLOAD stores live host-side, one ``WaveBuffer`` per
    (layer, slot, kv-head) row over PACKED per-cluster payload rows
    ``[K | V | positions]`` (f32 — exact for bf16/f32 stores and integer
    positions, so cache placement is bit-transparent). The device keeps, per
    layer, a block-cache store of ``C + r`` slots: slots [0, C) mirror each
    row's ``WaveBuffer.cache`` and the tail r slots are the per-step miss
    staging buffer. Each decode step runs per layer:

      rank (jit) -> ids readback -> translate ids through the mapping tables
      (hits -> cache slots, misses -> staging slots; misses fetched from the
      host store) -> cache update (jit: previous step's deferred admissions +
      this step's staged misses) -> attend (jit, slot-indirected paged
      kernel) -> ``apply_updates`` (host, OFF the hot path; admissions mirror
      into the device cache at the NEXT step's cache update).

    The loop is LAYER-PIPELINED (retrosched's RL304 report, PR 7): right
    after layer l's attend is dispatched, layer l+1's rank is dispatched and
    its id readback STARTED (``copy_to_host_async``); only then does layer
    l's deferred-admission drain run on the host. Layer l+1's blocking id
    sync therefore overlaps the drain and the device's cache-update + attend
    + rank work instead of idling behind them. Every dispatch / host op /
    sync calls ``trace`` (a no-op hooked by
    ``analysis.schedule_model.ScheduleRecorder``), and the recorded schedule
    is model-checked against the SERVE_STAGES effects declarations by
    RL301-RL305 in CI — the pipeline ships as a checked refactor, not a
    leap of faith.
    """

    def trace(self, op: str, layer: int, kind: str, step: int,
              **extras) -> None:
        """Schedule-event hook, one call per dispatch / host op / sync in
        program order. A no-op in production; ``ScheduleRecorder`` patches
        it at class level to record the happens-before event stream."""

    def __init__(self, engine: "ServeEngine", B: int, max_ctx: int):
        cfg = engine.cfg
        self.cfg = cfg
        self.params = engine.params
        self.plan = plan_zones(max_ctx, cfg.retro, engine.gen_headroom)
        self.L, self.B, self.H = cfg.n_layers, B, cfg.n_kv_heads
        self.hd, self.cap, self.M = cfg.head_dim, cfg.retro.cluster_cap, \
            self.plan.m_max
        self.r = max(self.plan.r, 1)        # staging tail (dead slot if r=0)
        self.C = engine._resolve_cache_clusters(self.M)
        self.policy = engine.cache_policy
        self.dtype = jnp.dtype(cfg.dtype)
        C, r, cap, hd = self.C, self.r, self.cap, self.hd
        self.cache_k = [jnp.zeros((B, self.H, C + r, cap, hd), self.dtype)
                        for _ in range(self.L)]
        self.cache_v = [jnp.zeros((B, self.H, C + r, cap, hd), self.dtype)
                        for _ in range(self.L)]
        self.cache_p = [jnp.full((B, self.H, C + r, cap), -1, jnp.int32)
                        for _ in range(self.L)]
        # per (layer, slot, head) host buffer; None until the slot is admitted
        self.bufs: List[List[Optional[List[WaveBuffer]]]] = [
            [None] * B for _ in range(self.L)]
        # per-layer queued device-cache mirror of deferred admissions;
        # None = nothing admitted (the mirror transfer + scatter is skipped)
        self.pending_adm: List[Optional[Tuple[np.ndarray, ...]]] = \
            [None] * self.L
        self.ncl = np.zeros(B, np.int64)    # host mirror of n_clusters
        self.retired = BufferStats()        # stats of replaced slot caches
        self._step = -1                     # schedule epoch for trace events
        # retrofault: ONE transport per plane, shared by every per-row wave
        # buffer — the control plane is single-threaded, so a seeded
        # FaultyTransport yields one reproducible fault schedule per serve
        self.transport = (FaultyTransport(engine.fault_profile)
                          if engine.fault_profile is not None
                          else LinkTransport())
        self.fetch_retries = engine.fetch_retries
        self.fetch_backoff_s = engine.fetch_backoff_s
        self.fetch_deadline_s = engine.fetch_deadline_s
        self.degraded_steps = 0             # steps with >= 1 masked cluster
        self.dropped_cluster_steps = 0      # cluster·step masked count
        self.failed_slots: Dict[int, str] = {}   # slot -> fatal fault message
        (self._embed, self._rank, self._attend, self._unembed,
         self._cache_upd, self._cache_stage, self._flush) = \
            engine._offload_fns(B, max_ctx, self.C, self.r)
        self._layers = [jax.tree.map(lambda a, i=i: a[i], engine.params["layers"])
                        for i in range(self.L)]
        self._windows = [engine.params["window"][i] for i in range(self.L)]

    # ------------------------------------------------------------- packing
    def _pack(self, k, v, p) -> np.ndarray:
        """(M', cap, hd) x2 + (M', cap) -> (M', D) packed f32 payload rows."""
        m = k.shape[0]
        return np.concatenate([
            np.asarray(k, np.float32).reshape(m, -1),
            np.asarray(v, np.float32).reshape(m, -1),
            np.asarray(p, np.float32)], axis=1)

    def _unpack(self, rows: np.ndarray):
        """(n, D) packed rows -> k/v (n, cap, hd) f32 + pos (n, cap) int32."""
        n, cap, hd = rows.shape[0], self.cap, self.hd
        k = rows[:, :cap * hd].reshape(n, cap, hd)
        v = rows[:, cap * hd:2 * cap * hd].reshape(n, cap, hd)
        p = rows[:, 2 * cap * hd:].astype(np.int32)
        return k, v, p

    # ----------------------------------------------------------- admission
    def admit_slot(self, i: int, st1) -> None:      # retrolint: hot
        """Offload a freshly admitted request's cluster stores: device->host
        transfer of slot ``i``'s payload blocks, fresh mapping tables (the
        previous occupant's cache entries die with it; its stats are retired
        into the engine aggregate)."""
        self._step += 1
        self.trace("admit_slot", -1, "host", self._step)
        # sanctioned syncs: the admission-time device->host store transfer IS
        # the offload (one per admitted request, amortized over its decode)
        k_all = np.asarray(  # retrolint: sync(admission store offload)
            st1.kv.k_store)[:, 0]                       # (L, H, M, cap, hd)
        v_all = np.asarray(  # retrolint: sync(admission store offload)
            st1.kv.v_store)[:, 0]
        p_all = np.asarray(  # retrolint: sync(admission store offload)
            st1.kv.pos_store)[:, 0]
        self.ncl[i] = int(
            np.asarray(  # retrolint: sync(admission cluster-count mirror)
                st1.kv.n_clusters)[0, 0])
        for l in range(self.L):
            old = self.bufs[l][i]
            if old is not None:
                for buf in old:
                    self.retired.merge(buf.stats)
            self.bufs[l][i] = [
                WaveBuffer(self._pack(k_all[l, h], v_all[l, h], p_all[l, h]),
                           cache_clusters=self.C, policy=self.policy,
                           transport=self.transport,
                           max_retries=self.fetch_retries,
                           backoff_s=self.fetch_backoff_s)
                for h in range(self.H)]
            # drop pending admissions aimed at the replaced slot's caches
            if self.pending_adm[l] is not None:
                slots, ak, av, ap = self.pending_adm[l]
                slots = slots.copy()
                slots[i] = self.C + self.r              # OOB => dropped write
                self.pending_adm[l] = (slots, ak, av, ap)

    # ------------------------------------------------------- control plane
    def _translate(self, l, ids, active):           # retrolint: hot
        """Cluster ids -> combined cache-slot ids; fetch miss payloads.

        Ids of not-yet-live clusters (>= the row's ``n_clusters`` mirror —
        ``top_k`` tie-breaks the NEG-masked dead scores to exactly the ids
        the next flush will allocate) NEVER touch the wave buffer: fetching
        them would admit an all-masked payload that would later be served as
        a STALE hit once the flush writes the real blocks at those ids. They
        map to their staging slot instead, whose default ``pos = -1`` payload
        reproduces the direct path's dead-block masking bit-for-bit.

        Also returns the per-cluster validity mask ``valid`` (B, H, r)
        int32 (retrofault): 0 marks a LIVE cluster whose miss fetch failed
        its retry/deadline budget this step — its staging slot holds the
        self-masking default payload and the attend covers its mass with the
        estimation zone. Dead ids stay valid=1 (their pos=-1 staging payload
        already reproduces the direct path bit-for-bit, and masking them
        would diverge from it). A :class:`FatalTransportError` marks the
        whole slot failed (``failed_slots``) — the serve loop finishes that
        request with ``status="error"`` after the step; remaining slots are
        untouched (no engine-wide quarantine).
        """
        B, H, r = ids.shape
        cap, hd = self.cap, self.hd
        idx_slots = np.zeros((B, H, r), np.int32)
        valid = np.ones((B, H, r), np.int32)
        miss_k = np.zeros((B, H, self.r, cap, hd), np.float32)
        miss_v = np.zeros((B, H, self.r, cap, hd), np.float32)
        miss_p = np.full((B, H, self.r, cap), -1, np.int32)
        if r == 0:      # steady-zone-only plan: attend pads its own dead slot
            return idx_slots, valid, miss_k, miss_v, miss_p
        stage = self.C + np.arange(r)
        for b in range(B):
            if not active[b] or self.bufs[l][b] is None \
                    or b in self.failed_slots:
                continue
            dead = ids[b] >= self.ncl[b]                    # (H, r)
            for h in range(H):
                buf = self.bufs[l][b][h]
                live_j = np.where(~dead[h])[0]
                idx_slots[b, h] = stage                     # default: staging
                if len(live_j) == 0:
                    continue
                try:
                    slot, hit, payload, ok = buf.translate(
                        ids[b, h, live_j], deadline_s=self.fetch_deadline_s)
                except FatalTransportError as e:
                    # kill only this slot; partial per-head state for the
                    # step is harmless (staged defaults self-mask) because
                    # the request is finished before its token is harvested
                    self.failed_slots[b] = str(e)
                    break
                idx_slots[b, h, live_j] = np.where(
                    hit, slot, stage[live_j]).astype(np.int32)
                valid[b, h, live_j[~ok]] = 0
                self.dropped_cluster_steps += int((~ok).sum())
                miss_j = live_j[~hit & ok]
                if len(miss_j):
                    mk, mv, mp = self._unpack(payload[~hit & ok])
                    miss_k[b, h, miss_j] = mk
                    miss_v[b, h, miss_j] = mv
                    miss_p[b, h, miss_j] = mp
        return idx_slots, valid, miss_k, miss_v, miss_p

    def _drain_admissions(self, l, active) -> bool:  # retrolint: hot
        """Apply deferred WaveBuffer admissions (off the attend hot path) and
        queue their device-cache mirror for the next step's cache update.
        A warm-cache step with zero admissions queues None — the next cache
        update then skips the mirror transfer + scatter entirely. Returns
        whether anything was queued (the RL302 mirror-edge trace bit)."""
        B, H, r = self.B, self.H, self.r
        queued = None
        for b in range(B):
            if not active[b] or self.bufs[l][b] is None:
                continue
            for h in range(H):
                n = 0
                for vict, _ids, payload in self.bufs[l][b][h].apply_updates():
                    if queued is None:
                        queued = (
                            np.full((B, H, r), self.C + r, np.int32),  # OOB
                            np.zeros((B, H, r, self.cap, self.hd),
                                     np.float32),
                            np.zeros((B, H, r, self.cap, self.hd),
                                     np.float32),
                            np.full((B, H, r, self.cap), -1, np.int32))
                    slots, ak, av, ap = queued
                    m = len(vict)
                    pk, pv, pp = self._unpack(payload)
                    slots[b, h, n:n + m] = vict
                    ak[b, h, n:n + m] = pk
                    av[b, h, n:n + m] = pv
                    ap[b, h, n:n + m] = pp
                    n += m
        self.pending_adm[l] = queued
        return queued is not None

    # ------------------------------------------------------------- decode
    def _launch_rank(self, l, kv, x, act_dev, t):   # retrolint: hot
        """Dispatch layer ``l``'s rank and START its retrieved-id readback
        (``copy_to_host_async`` — non-blocking; the transfer overlaps
        whatever the host and device do next). The matching blocking sync
        happens at this layer's loop iteration in ``decode_step``."""
        live = {f: getattr(kv, f)[l] for f in LIVE_FIELDS}
        self.trace("rank_fn", l, "dispatch", t)
        ctx, idx_r, live = self._rank(self._layers[l], self._windows[l],
                                      live, x, act_dev)
        self.trace("readback_start", l, "host", t)
        idx_r.copy_to_host_async()
        return ctx, idx_r, live

    def decode_step(self, state, tokens_dev, active):  # retrolint: hot
        """One decode step over the slot batch, layer-pipelined: layer l+1's
        rank is dispatched and its id readback started BEFORE layer l's
        deferred-admission drain runs, so the per-layer id sync overlaps the
        drain and the device's cache-update/attend/rank work (see the class
        docstring; retrosched certifies the order). Returns (device logits,
        new state)."""
        self._step += 1
        t = self._step
        drops_before = self.dropped_cluster_steps
        self.trace("embed_tokens", -1, "dispatch", t)
        x = self._embed(self.params, tokens_dev)
        act_dev = jnp.asarray(active)
        kv = state.kv
        new_hot: List[Dict[str, jax.Array]] = []
        nxt = self._launch_rank(0, kv, x, act_dev, t)
        for l in range(self.L):
            ctx, idx_r, live = nxt
            # the paper's CPU control plane: translating retrieved cluster
            # ids through the cache mapping tables needs them on host. The
            # readback was started asynchronously at dispatch time, so this
            # waits only for the transfer remainder.
            self.trace("readback_ids", l, "sync", t)
            ids = np.asarray(idx_r)  # retrolint: sync(per-layer id readback)
            self.trace("translate", l, "host", t)
            with trace.span(trace.TRANSLATE):
                idx_slots, valid, mk, mv, mp = self._translate(l, ids, active)
            if self.pending_adm[l] is None:     # warm cache: staging only
                self.trace("cache_stage", l, "dispatch", t)
                self.cache_k[l], self.cache_v[l], self.cache_p[l] = \
                    self._cache_stage(self.cache_k[l], self.cache_v[l],
                                      self.cache_p[l], jnp.asarray(mk),
                                      jnp.asarray(mv), jnp.asarray(mp))
            else:
                adm_slots, adm_k, adm_v, adm_p = self.pending_adm[l]
                self.trace("cache_upd", l, "dispatch", t)
                self.cache_k[l], self.cache_v[l], self.cache_p[l] = \
                    self._cache_upd(self.cache_k[l], self.cache_v[l],
                                    self.cache_p[l], jnp.asarray(adm_slots),
                                    jnp.asarray(adm_k), jnp.asarray(adm_v),
                                    jnp.asarray(adm_p), jnp.asarray(mk),
                                    jnp.asarray(mv), jnp.asarray(mp))
            self.trace("attend_fn", l, "dispatch", t)
            x = self._attend(self._layers[l], self._windows[l], live, x, ctx,
                             self.cache_k[l], self.cache_v[l],
                             self.cache_p[l], jnp.asarray(idx_slots),
                             jnp.asarray(valid))
            new_hot.append(live)
            if l + 1 < self.L:      # pipeline: next rank before this drain
                nxt = self._launch_rank(l + 1, kv, x, act_dev, t)
            with trace.span(trace.DRAIN_ADMISSIONS):    # off the hot path
                queued = self._drain_admissions(l, active)
            self.trace("drain_admissions", l, "host", t, queued=queued)
        self.trace("unembed_logits", -1, "dispatch", t)
        logits = self._unembed(self.params, x)
        if self.dropped_cluster_steps > drops_before:
            self.degraded_steps += 1
        kv = kv._replace(**{f: jnp.stack([h[f] for h in new_hot])
                            for f in HOT_FIELDS})
        return logits, state._replace(kv=kv)

    # -------------------------------------------------------------- flush
    def flush(self, state, rows):               # retrolint: hot
        """Decode-time index update: meta entries on device, payload blocks
        appended to the host stores at each flushed row's cluster offset."""
        self._step += 1                 # own schedule epoch (between steps)
        kv = state.kv
        live = {f: getattr(kv, f) for f in LIVE_FIELDS}
        self.trace("offload_flush", -1, "dispatch", self._step)
        new_live, res = self._flush(live, jnp.asarray(rows))
        # sanctioned syncs: flushed payload blocks append to the HOST stores,
        # once per update_segment decoded tokens, not per step
        self.trace("readback_flush", -1, "sync", self._step)
        rk = np.asarray(res.k_store)  # retrolint: sync(flush block readback)
        rv = np.asarray(res.v_store)  # retrolint: sync(flush block readback)
        rp = np.asarray(res.pos_store)  # retrolint: sync(flush block readback)
        self.trace("host_flush", -1, "host", self._step)
        k_new = rk.shape[3]
        for b in np.where(rows)[0]:
            off = int(self.ncl[b])
            for l in range(self.L):
                if self.bufs[l][b] is None:
                    continue
                for h in range(self.H):
                    # store_rows, not a raw slice write: the flush must
                    # refresh the per-row crc32s or every later fetch of
                    # these clusters would read back as corruption
                    self.bufs[l][b][h].store_rows(
                        off, self._pack(rk[l, b, h], rv[l, b, h], rp[l, b, h]))
            self.ncl[b] += k_new
        return state._replace(kv=kv._replace(**new_live))

    # ------------------------------------------------------------- stats
    def export_stats(self, metrics: "ServeMetrics") -> None:
        metrics.cache.merge(self.retired)
        for per_layer in self.bufs:
            for row in per_layer:
                if row is not None:
                    for buf in row:
                        metrics.cache.merge(buf.stats)
        metrics.degraded_steps += self.degraded_steps
        metrics.dropped_cluster_steps += self.dropped_cluster_steps


class ServeEngine:
    """``serve(requests, batch_size)`` — continuous scheduler over a slot
    batch. ``max_context`` pins the decode geometry (zone plan / cluster-store
    capacity); all requests served by one engine share it, so a request's
    outputs are independent of what else shares the batch (a solo run at
    batch_size=1 reproduces them token-for-token, under either admission
    mode). ``prefill_chunk`` sets the chunked-admission chunk size;
    ``prefill_bucket`` > 1 right-pads blocking-mode prompts up to a multiple,
    trading a masked prefill for fewer compiled shapes. ``attn_impl`` selects
    the retro decode-attention implementation ("jnp" reference or "fused"
    gather-free paged kernel); None defers to ``cfg.retro.attn_impl``."""

    def __init__(self, cfg: ModelConfig, params, *, runtime: str = "retro",
                 gen_headroom: int = 1024, temperature: float = 0.0,
                 max_context: Optional[int] = None, prefill_bucket: int = 1,
                 admission: str = "chunked", prefill_chunk: int = 256,
                 attn_impl: Optional[str] = None,
                 offload: Optional[bool] = None,
                 cache_clusters: Optional[int] = None,
                 cache_frac: Optional[float] = None,
                 cache_policy: Optional[str] = None,
                 fault_profile: Optional[Any] = None,
                 fetch_deadline_s: Optional[float] = None,
                 fetch_retries: int = 2,
                 fetch_backoff_s: float = 1e-3,
                 max_decode_steps: Optional[int] = None):
        if admission not in ("chunked", "blocking"):
            raise ValueError(f"unknown admission mode {admission!r}")
        from repro.core.attention import resolve_attn_impl
        self.attn_impl = resolve_attn_impl(attn_impl or cfg.retro.attn_impl)
        self.cfg = cfg
        self.params = params
        self.runtime = runtime
        self.gen_headroom = gen_headroom
        self.temperature = temperature
        self.max_context = max_context
        self.prefill_bucket = max(1, prefill_bucket)
        self.admission = admission
        self.prefill_chunk = max(1, prefill_chunk)
        retro = cfg.retro
        self.offload = retro.offload if offload is None else offload
        if self.offload and not M.supports_offload(cfg, runtime):
            raise ValueError(
                "host-offload serving requires the retro runtime on an "
                f"attention family, got runtime={runtime!r} "
                f"family={cfg.family!r}")
        self.cache_clusters = retro.cache_clusters if cache_clusters is None \
            else cache_clusters
        self.cache_frac = retro.cache_frac if cache_frac is None \
            else cache_frac
        self.cache_policy = cache_policy or retro.cache_policy
        # retrofault knobs (offload data plane; inert on the direct path):
        # fault_profile accepts a FaultProfile or a "transient=0.2,seed=3"
        # CLI spec string; fetch_deadline_s is the per-translate-call virtual
        # budget; max_decode_steps is the per-request watchdog (any path)
        if isinstance(fault_profile, str):
            fault_profile = FaultProfile.parse(fault_profile)
        self.fault_profile = fault_profile
        self.fetch_deadline_s = fetch_deadline_s
        self.fetch_retries = fetch_retries
        self.fetch_backoff_s = fetch_backoff_s
        self.max_decode_steps = max_decode_steps
        self._prefill_jit: Dict[Any, Any] = {}
        self._decode_jit: Dict[Any, Any] = {}
        self._chunk_jit: Dict[Any, Any] = {}
        self._finalize_jit: Dict[Any, Any] = {}
        self._offload_jit: Dict[Any, Any] = {}
        def graft(big, small, slot):
            return jax.tree.map(
                lambda b, s: jax.lax.dynamic_update_slice_in_dim(
                    b, s.astype(b.dtype), slot, axis=1), big, small)

        # sample ON DEVICE: the decode loop only ever moves (B,) token ids to
        # host, never the (B, vocab) logits (at production vocab sizes that
        # transfer would dominate the step).
        def argmax_ids(lg):
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)

        def categorical_ids(key, lg, temp):
            return jax.random.categorical(key, lg / temp).astype(jnp.int32)

        # scatter freshly admitted first tokens into the device token vector
        def merge_tokens(toks, upd, mask):
            return jnp.where(mask, upd, toks)

        self._graft = jax.jit(graft, donate_argnums=(0,))
        self._argmax = jax.jit(argmax_ids)
        self._categorical = jax.jit(categorical_ids)
        self._merge_tokens = jax.jit(merge_tokens)

    # ------------------------------------------------------------- compiled fns
    def _bucket(self, L: int) -> int:
        retro = self.cfg.retro
        if self.cfg.family not in ATTN_FAMILIES:
            return L        # recurrent prefills consume pads: compile exact
        if L < retro.sink + retro.local:
            return L        # too short to mask a ragged tail; compile exact
        b = self.prefill_bucket
        return L if b <= 1 else ((L + b - 1) // b) * b

    def _prefill_fn(self, seq_len: int, max_ctx: int):
        key = (seq_len, max_ctx)
        if key not in self._prefill_jit:
            cfg, rt, gh = self.cfg, self.runtime, self.gen_headroom
            plan = plan_zones(max_ctx, cfg.retro, gh) \
                if cfg.family != "ssm" else None
            ragged = cfg.family in ATTN_FAMILIES

            @jax.jit
            def prefill(params, batch, lengths):
                return M.apply_prefill(params, cfg, batch, runtime=rt,
                                       plan=plan, gen_headroom=gh,
                                       lengths=lengths if ragged else None,
                                       cache_len=max_ctx + gh)

            self._prefill_jit[key] = prefill
        return self._prefill_jit[key]

    def _chunk_fns(self, max_ctx: int):
        """ONE compiled prefill shape per engine geometry: every prompt is
        consumed as right-padded (1, prefill_chunk) chunks. The vlm variant
        additionally threads the request's patch embeddings (one compile per
        distinct patch shape)."""
        if max_ctx not in self._chunk_jit:
            cfg, rt = self.cfg, self.runtime

            @partial(jax.jit, donate_argnums=(1,))
            def chunk(params, cstate, toks, clen):
                return M.apply_prefill_chunk(params, cfg, {"tokens": toks},
                                             cstate, runtime=rt,
                                             chunk_lens=clen)

            @partial(jax.jit, donate_argnums=(1,))
            def chunk_pe(params, cstate, toks, clen, pe):
                return M.apply_prefill_chunk(
                    params, cfg, {"tokens": toks, "patch_embeds": pe},
                    cstate, runtime=rt, chunk_lens=clen)

            self._chunk_jit[max_ctx] = (chunk, chunk_pe)
        return self._chunk_jit[max_ctx]

    def _finalize_fn(self, total_len: int, max_ctx: int):
        """Finalize + graft one admitted slot. Per-prompt-length entries are
        cheap (tail clustering + scatter) — the expensive compiled shape, the
        chunk forward, is shared. In offload mode the finalized single-slot
        state is ALSO returned: it is the source of the slot's device->host
        store transfer (``_OffloadPlane.admit_slot``)."""
        key = (total_len, max_ctx, self.offload)
        if key not in self._finalize_jit:
            cfg, rt = self.cfg, self.runtime
            with_st1 = self.offload

            @partial(jax.jit, donate_argnums=(0,))
            def fin(big, cstate, slot):
                st1 = M.finalize_prefill_chunk(cfg, cstate, runtime=rt,
                                               total_len=total_len)
                big = jax.tree.map(
                    lambda b, s: jax.lax.dynamic_update_slice_in_dim(
                        b, s.astype(b.dtype), slot, axis=1), big, st1)
                return (big, st1) if with_st1 else big

            self._finalize_jit[key] = fin
        return self._finalize_jit[key]

    def _resolve_cache_clusters(self, m_max: int) -> int:
        """Device block-cache slots: absolute override or a fraction of the
        cluster-store size — clamped to [1, m_max] (tiny ``int(frac * n)``
        configs must round up to a one-slot cache, never zero)."""
        c = self.cache_clusters if self.cache_clusters > 0 \
            else int(self.cache_frac * m_max)
        return max(1, min(c, m_max))

    def _offload_fns(self, B: int, max_ctx: int, C: int, r: int):
        """Compiled pieces of the offload decode step, cached per engine
        geometry: (embed, rank, attend, unembed, cache_update, flush)."""
        key = (B, max_ctx, C, r)
        if key not in self._offload_jit:
            cfg = self.cfg
            plan = plan_zones(max_ctx, cfg.retro, self.gen_headroom)
            impl = self.attn_impl
            (embed, rank, attend, unembed, flush) = M.offload_decode_fns(cfg)

            def embed_tokens(p, t):
                return embed(p, cfg, t)

            # ``live`` is donated: the caller rebinds it from the result
            # (decode_step), so the per-layer hot fields update in place
            # instead of paying a defensive copy every step/layer
            @partial(jax.jit, donate_argnums=(2,))
            def rank_fn(lp, window, live, x, active):
                return rank(lp, window, cfg, live, x, plan=plan,
                            active=active)

            @jax.jit
            def attend_fn(lp, window, live, x, ctx, ck, cv, cp, idx, valid):
                return attend(lp, window, cfg, live, x, ctx, ck, cv, cp, idx,
                              valid, plan=plan, attn_impl=impl)

            def unembed_logits(p, x):
                return unembed(p, cfg, x)

            def cache_stage(ck, cv, cp, miss_k, miss_v, miss_p):
                # this step's misses stage into the tail [C, C + r)
                def stage(c, m):
                    return jax.lax.dynamic_update_slice(
                        c, m.astype(c.dtype), (C,) + (0,) * (m.ndim - 1))
                ss = jax.vmap(jax.vmap(stage))
                return ss(ck, miss_k), ss(cv, miss_v), ss(cp, miss_p)

            @partial(jax.jit, donate_argnums=(0, 1, 2))
            def cache_upd(ck, cv, cp, adm_slots, adm_k, adm_v, adm_p,
                          miss_k, miss_v, miss_p):
                # previous step's deferred admissions mirror into [0, C)
                # (OOB-padded slot ids are dropped writes)
                def row(c, s, pay):
                    return c.at[s].set(pay.astype(c.dtype), mode="drop")
                rr = jax.vmap(jax.vmap(row))
                ck, cv, cp = rr(ck, adm_slots, adm_k), \
                    rr(cv, adm_slots, adm_v), rr(cp, adm_slots, adm_p)
                return cache_stage(ck, cv, cp, miss_k, miss_v, miss_p)

            # the stacked live fields are donated: flush's caller replaces
            # them wholesale (``kv._replace(**new_live)``) and never touches
            # the old references again
            @partial(jax.jit, donate_argnums=(0,))
            def offload_flush(live_stacked, rows):
                return flush(cfg, live_stacked, rows)

            self._offload_jit[key] = (
                jax.jit(embed_tokens),
                rank_fn,
                attend_fn,
                jax.jit(unembed_logits),
                cache_upd,
                # warm-cache fast path: no admissions queued, staging only
                jax.jit(cache_stage, donate_argnums=(0, 1, 2)),
                offload_flush)
        return self._offload_jit[key]

    def _decode_fns(self, batch_size: int, max_ctx: int):
        key = (batch_size, max_ctx)
        if key not in self._decode_jit:
            cfg, rt, gh = self.cfg, self.runtime, self.gen_headroom
            impl = self.attn_impl
            plan = plan_zones(max_ctx, cfg.retro, gh) \
                if cfg.family != "ssm" else None

            @partial(jax.jit, donate_argnums=(1,))
            def decode(params, state, token, active):
                return M.apply_decode(params, cfg, state, token, runtime=rt,
                                      plan=plan, seq_len=max_ctx,
                                      gen_headroom=gh, active=active,
                                      attn_impl=impl)

            @partial(jax.jit, donate_argnums=(0,))
            def flush(state):
                return M.flush_state(cfg, state, runtime=rt)

            self._decode_jit[key] = (decode, flush)
        return self._decode_jit[key]

    # ---------------------------------------------------------------- serving
    def _sample_dev(self, logits, key):
        """Device logits -> device (B,) token ids (no host transfer)."""
        if self.temperature <= 0:
            return self._argmax(logits)
        return self._categorical(key, logits, jnp.float32(self.temperature))

    def _sample(self, logits, key) -> np.ndarray:   # retrolint: hot
        """Device logits -> host (B,) token ids (blocks until ready). Used
        only for coalesced first-token sampling: ONE readback per admission
        round; the decode loop samples with ``_sample_dev`` (no sync)."""
        return np.asarray(  # retrolint: sync(coalesced first-token readback)
            self._sample_dev(logits, key)).astype(np.int64)

    def serve(self, requests: List[Request], batch_size: int,  # retrolint: hot
              seed: int = 0) -> ServeMetrics:
        """Serve a FIFO queue through ``batch_size`` continuous slots."""
        cfg, rt = self.cfg, self.runtime
        assert requests
        max_ctx = self.max_context or max(
            self._bucket(len(r.prompt)) for r in requests)
        min_len = cfg.retro.sink + 1 \
            if rt == "retro" and cfg.family != "ssm" else 1
        for r in requests:
            if not min_len <= len(r.prompt) <= max_ctx:
                raise ValueError(
                    f"prompt length {len(r.prompt)} outside "
                    f"[{min_len}, {max_ctx}]")
        B = batch_size
        # chunk attention is exact: configs that opt into block-sparse
        # prefill keep the monolithic (sparse) admission path
        chunked = self.admission == "chunked" \
            and M.supports_chunked_prefill(cfg, rt) \
            and cfg.sparse_prefill_blocks == 0
        plane = _OffloadPlane(self, B, max_ctx) if self.offload else None
        decode, flush = (None, None) if self.offload \
            else self._decode_fns(B, max_ctx)
        state = M.make_serve_state(cfg, B, max_ctx, runtime=rt,
                                   gen_headroom=self.gen_headroom,
                                   zero_fill=True)
        lbuf = local_buffer_size(cfg.retro)
        use_flush = rt == "retro" and cfg.family != "ssm"

        queue = deque(requests)
        slots: List[Optional[Request]] = [None] * B
        admitting: List[Optional[_Admission]] = [None] * B
        active = np.zeros(B, bool)
        staged = np.zeros(B, np.int64)      # host mirror of local_len (retro)
        slot_steps = np.zeros(B, np.int64)  # watchdog: decode steps per slot
        admit_t = np.zeros(B, float)
        tokens_dev = jnp.zeros((B,), jnp.int32)     # device-resident ids
        prev_sampled = None                 # step t's device ids (unsynced)
        prev_snapshot: List[Optional[Request]] = [None] * B
        last_deliver_t: Optional[float] = None
        last_deliver: set = set()
        metrics = ServeMetrics(n_slots=B)
        key = jax.random.PRNGKey(seed)
        t_start = time.perf_counter()

        def finish(i: int, req: Request, status: str = "ok"):
            req.done = True
            req.status = status
            dt = time.perf_counter() - admit_t[i]
            n_decode = len(req.out_tokens) - 1   # first token is prefill's
            req.decode_tps = n_decode / dt if dt > 0 and n_decode > 0 else 0.0
            # a max_new_tokens=1 request decodes ZERO tokens — recording its
            # 0.0 tok/s would drag down mean/percentile request throughput,
            # so the sample is skipped (the request still counts everywhere
            # else: TTFT, tokens_out)
            if n_decode > 0:
                metrics.request_tps.append(req.decode_tps)
            slots[i] = None
            active[i] = False

        while queue or active.any() or any(a is not None for a in admitting) \
                or prev_sampled is not None:
            # ---- admission: one prefill chunk per admitting slot ----------
            t0 = time.perf_counter()
            with trace.span(trace.ADMIT):
                completed: List[Tuple[int, _Admission]] = []
                for i in range(B):
                    if not chunked:
                        if active[i] or slots[i] is not None or not queue:
                            continue
                        req = queue.popleft()
                        L = len(req.prompt)
                        S_b = min(self._bucket(L), max_ctx)
                        assert S_b >= L
                        toks = np.zeros((1, S_b), np.int32)
                        toks[0, :L] = req.prompt
                        batch = {"tokens": jnp.asarray(toks)}
                        if req.extra:
                            batch.update(req.extra)
                        prefill = self._prefill_fn(S_b, max_ctx)
                        req.queue_s = time.perf_counter() - t_start
                        logits, st1 = prefill(self.params, batch,
                                              jnp.asarray([L], jnp.int32))
                        state = self._graft(state, st1, jnp.asarray(i, jnp.int32))
                        if plane is not None:   # device->host store offload
                            plane.admit_slot(i, st1)
                        completed.append((i, _Admission(req=req, logits=logits,
                                                        consumed=L)))
                        continue
                    if admitting[i] is None and not active[i] \
                            and slots[i] is None and queue:
                        req = queue.popleft()
                        admitting[i] = _Admission(
                            req=req,
                            cstate=M.make_prefill_chunk_state(
                                cfg, 1, max_ctx, runtime=rt,
                                chunk=self.prefill_chunk,
                                gen_headroom=self.gen_headroom))
                    adm = admitting[i]
                    if adm is None:
                        continue
                    L, C = len(adm.req.prompt), self.prefill_chunk
                    n = min(C, L - adm.consumed)
                    toks = np.zeros((1, C), np.int32)
                    toks[0, :n] = adm.req.prompt[adm.consumed:adm.consumed + n]
                    chunk, chunk_pe = self._chunk_fns(max_ctx)
                    extra = adm.req.extra or {}
                    if adm.consumed == 0:
                        adm.req.queue_s = time.perf_counter() - t_start
                    if set(extra) == {"patch_embeds"}:
                        adm.logits, adm.cstate = chunk_pe(
                            self.params, adm.cstate, jnp.asarray(toks),
                            jnp.asarray([n], jnp.int32), extra["patch_embeds"])
                    elif extra:     # uncompiled fallback for exotic extras
                        adm.logits, adm.cstate = M.apply_prefill_chunk(
                            self.params, cfg,
                            {"tokens": jnp.asarray(toks), **extra},
                            adm.cstate, runtime=rt,
                            chunk_lens=jnp.asarray([n], jnp.int32))
                    else:
                        adm.logits, adm.cstate = chunk(
                            self.params, adm.cstate, jnp.asarray(toks),
                            jnp.asarray([n], jnp.int32))
                    adm.consumed += n
                    if adm.consumed >= L:
                        fin = self._finalize_fn(L, max_ctx)
                        if plane is not None:
                            state, st1 = fin(state, adm.cstate,
                                             jnp.asarray(i, jnp.int32))
                            plane.admit_slot(i, st1)    # device->host offload
                        else:
                            state = fin(state, adm.cstate,
                                        jnp.asarray(i, jnp.int32))
                        adm.cstate = None
                        admitting[i] = None
                        completed.append((i, adm))

                if completed:
                    # coalesced first-token sampling: ONE host sync for every
                    # request admitted this iteration
                    key, sub = jax.random.split(key)
                    stacked = jnp.concatenate([a.logits for _, a in completed], 0)
                    t_wait = time.perf_counter()
                    with trace.span(trace.FIRST_TOKEN):
                        first = self._sample(stacked, sub)  # blocks until ready
                    now = time.perf_counter()
                    metrics.first_token_wait_s += now - t_wait
                    upd = np.zeros(B, np.int32)
                    mask = np.zeros(B, bool)
                    for (i, adm), tok in zip(completed, first):
                        req = adm.req
                        req.admit_s = now - t_start - req.queue_s
                        req.ttft_s = req.queue_s + req.admit_s
                        req.out_tokens.append(int(tok))
                        metrics.tokens_out += 1
                        metrics.ttft_s.append(req.ttft_s)
                        admit_t[i] = now
                        slots[i] = req
                        active[i] = True
                        slot_steps[i] = 0
                        upd[i], mask[i] = tok, True
                        # device local_len after admission: chunked finalize uses
                        # the true length; a padded blocking prefill uses S_b, but
                        # _bucket only pads prompts with L >= sink + local, where
                        # both give exactly ``local`` — the mirror matches either
                        staged[i] = min(cfg.retro.local,
                                        max(adm.consumed - cfg.retro.sink, 0))
                        if len(req.out_tokens) >= req.max_new_tokens:
                            finish(i, req)
                    tokens_dev = self._merge_tokens(tokens_dev, jnp.asarray(upd),
                                                    jnp.asarray(mask))
            metrics.prefill_s += time.perf_counter() - t0

            # ---- one decode step over the whole slot batch -----------------
            # Dispatch step t+1 BEFORE syncing step t's ids: sampling stays on
            # device and the ids ride back one step late (the loop's only
            # decode-path host sync).
            t0 = time.perf_counter()
            did_decode = False
            if active.any():
                key, sub = jax.random.split(key)
                with trace.span(trace.DECODE):
                    if plane is not None:
                        logits, state = plane.decode_step(state, tokens_dev,
                                                          active)
                    else:
                        logits, state = decode(self.params, state, tokens_dev,
                                               jnp.asarray(active))
                    new_sampled = self._sample_dev(logits, sub)  # no sync
                snapshot = [slots[i] if active[i] else None for i in range(B)]
                metrics.steps += 1
                metrics.occupied_slot_steps += int(active.sum())
                staged[active] += 1
                slot_steps[active] += 1
                did_decode = True
                # unrecoverable transport fault: finish ONLY the affected
                # requests with a structured error status — no engine-wide
                # quarantine, the remaining slots keep serving. The killed
                # request's in-flight token is dropped by the lagged harvest
                # below (slots[i] no longer holds it).
                if plane is not None and plane.failed_slots:
                    for i in sorted(plane.failed_slots):
                        if slots[i] is not None:
                            finish(i, slots[i], status="error")
                    plane.failed_slots.clear()
                # per-request watchdog: a request whose stop condition never
                # triggers cannot occupy a slot forever
                if self.max_decode_steps is not None:
                    for i in range(B):
                        if active[i] and slot_steps[i] >= self.max_decode_steps:
                            finish(i, slots[i], status="timeout")

            # ---- harvest step t's ids (one step lagged) --------------------
            if prev_sampled is not None:
                # the decode loop's ONLY sync: step t's ids, harvested one
                # step late (step t+1 is already dispatched above)
                t_wait = time.perf_counter()
                with trace.span(trace.HARVEST):
                    ids = np.asarray(prev_sampled)  # retrolint: sync(lagged id harvest)
                now = time.perf_counter()
                metrics.harvest_wait_s += now - t_wait
                delivered = set()
                for i, req in enumerate(prev_snapshot):
                    if req is None or slots[i] is not req or req.done:
                        continue        # freed/re-admitted: speculative token
                    delivered.add(id(req))
                    req.out_tokens.append(int(ids[i]))
                    metrics.tokens_out += 1
                    if len(req.out_tokens) >= req.max_new_tokens:
                        finish(i, req)
                if delivered:
                    if last_deliver_t is not None and (delivered
                                                       & last_deliver):
                        metrics.step_s.append(now - last_deliver_t)
                    last_deliver_t, last_deliver = now, delivered
            if did_decode:
                prev_sampled, prev_snapshot = new_sampled, snapshot
                tokens_dev = new_sampled
            else:
                prev_sampled, prev_snapshot = None, [None] * B
            metrics.decode_s += time.perf_counter() - t0

            # ---- per-row masked index update (off the per-step hot path) ---
            if use_flush and (staged >= lbuf).any():
                rows = staged >= lbuf
                with trace.span(trace.FLUSH):
                    if plane is not None:
                        state = plane.flush(state, rows)
                    else:
                        state = flush(state)
                metrics.flushes += 1
                staged[rows] -= cfg.retro.update_segment
        if plane is not None:
            plane.export_stats(metrics)
            self._last_plane = plane        # inspection hook (tests)
        return metrics

    def run_wave(self, requests: List[Request],
                 extra_batch: Optional[Dict] = None,
                 seed: int = 0) -> ServeMetrics:
        """Back-compat: serve one batch of requests with one slot each."""
        if extra_batch:
            for i, r in enumerate(requests):
                r.extra = {k: v[i:i + 1] for k, v in extra_batch.items()}
        return self.serve(requests, batch_size=len(requests), seed=seed)
