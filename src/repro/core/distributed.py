"""Distributed wave attention — beyond-paper sharded retrieval (DESIGN §6).

Baseline (paper-faithful under pjit): the cluster stores are sharded over the
'model' axis, the global top-r gather crosses shards, and XLA materializes the
retrieved KV blocks with all-gather/all-reduce collectives whose payload is
O(r · cap · hd) *KV bytes* per head per step.

This module replaces that with LOCAL retrieval: every shard ranks only its
local clusters, retrieves its local top-⌈r/n⌉ (+ local estimation zone), and
computes a partial flash merge (num, den, m). Shards then combine with one
pmax + psum whose payload is O(B · H · G · (hd + 2)) floats — independent of
r and cap. The steady zone is contributed by shard 0 only.

Quality note: the union of per-shard top-⌈r/n⌉ is not exactly the global
top-r; segmented clustering spreads hot clusters across shards (cluster ids
are segment-major), and the estimation zone covers stragglers — measured in
tests/test_distributed.py.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import RetroConfig
from repro.core.attention import wave_attention_decode
from repro.core.wave_index import WaveState
from repro.core.zones import ZonePlan


def local_plan(plan: ZonePlan, n_shards: int) -> ZonePlan:
    return plan._replace(r=max(1, math.ceil(plan.r / n_shards)),
                         e=max(1, math.ceil(plan.e / n_shards)))


def shard_wave_attention(q, state: WaveState, retro: RetroConfig,
                         plan: ZonePlan, *, axis: str = "model",
                         window=None, softcap=None, shard_id=None):
    """Body function — must run under shard_map with the cluster axis of
    ``state`` sharded over ``axis``. q: (B, Hq, hd) replicated over ``axis``.
    Returns (B, Hq, hd) replicated over ``axis``.

    ``shard_id``: (1,) int32 operand sharded over ``axis`` (an arange split
    across shards). Used instead of lax.axis_index, which lowers to a
    PartitionId op that SPMD can't partition when other mesh axes stay auto.
    """
    B, Hq, hd = q.shape
    n_sh = jax.lax.axis_size(axis)
    ax = shard_id[0] if shard_id is not None else jax.lax.axis_index(axis)
    m_loc = state.centroid.shape[2]
    lp = local_plan(plan, n_sh)
    # clamp to the local shard's cluster count (full-coverage case)
    r_loc = min(lp.r, m_loc)
    e_loc = min(lp.e, m_loc - r_loc)
    lp = lp._replace(r=r_loc, e=e_loc)
    num, den, m, _ = wave_attention_decode(
        q, state, retro, lp, window=window, softcap=softcap,
        cluster_offset=ax * m_loc, include_steady=(ax == 0),
        return_parts=True)
    m_glob = jax.lax.pmax(m, axis)
    scale = jnp.exp(m - m_glob)
    num = jax.lax.psum(num * scale[..., None], axis)
    den = jax.lax.psum(den * scale, axis)
    out = num / jnp.maximum(den, 1e-30)[..., None]
    return out.reshape(B, Hq, hd).astype(q.dtype)


def state_specs_cluster_sharded(state: WaveState, axis: str = "model"):
    """PartitionSpecs for a per-layer WaveState with the cluster axis sharded
    (per-layer leaves: (B, H, M, ...))."""
    def spec(name, leaf):
        nd = leaf.ndim
        if name in ("k_store", "v_store", "pos_store", "centroid", "vsum",
                    "size", "stored", "max_pos"):
            s = [None] * nd
            s[2] = axis
            return P(*s)
        return P(*([None] * nd))

    return WaveState(*[spec(f, getattr(state, f))
                       for f in WaveState._fields])


def distributed_wave_attention(q, state: WaveState, retro: RetroConfig,
                               plan: ZonePlan, mesh, *, axis: str = "model",
                               window=None, softcap=None):
    """shard_map wrapper: q replicated on ``axis``, state cluster-sharded.

    ``window`` may be a traced scalar — passed as an explicit (replicated)
    shard_map operand rather than captured in the closure."""
    manual = frozenset({axis})
    # the body leaves every axis but ``axis`` to the partitioner, which needs
    # Auto axis types; jax.make_mesh builds Explicit ones by default
    mesh = jax.sharding.Mesh(mesh.devices, mesh.axis_names)
    state_specs = state_specs_cluster_sharded(state, axis)
    n_sh = mesh.shape[axis]
    shard_ids = jnp.arange(n_sh, dtype=jnp.int32)

    if window is not None:
        def body(q, s, sid, w):
            return shard_wave_attention(q, s, retro, plan, axis=axis,
                                        window=w, softcap=softcap,
                                        shard_id=sid)
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(), state_specs, P(axis), P()),
                           out_specs=P(), axis_names=manual, check_vma=False)
        return fn(q, state, shard_ids, jnp.asarray(window, jnp.float32))

    def body(q, s, sid):
        return shard_wave_attention(q, s, retro, plan, axis=axis,
                                    window=None, softcap=softcap,
                                    shard_id=sid)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), state_specs, P(axis)),
                       out_specs=P(), axis_names=manual, check_vma=False)
    return fn(q, state, shard_ids)
