"""The serve path's tracing vocabulary: named scopes on device code and host
spans on the profiler's clock.

``scope(name)`` is ``jax.named_scope``: it only adds ``name`` to the
``op_name`` metadata of the operations traced inside it, so it costs nothing
at run time. xprof's op profile groups device time by these names (the
``tf_op`` statistic of an ``XLA Ops`` event carries the ``op_name`` path),
and ``bench/scopes.py`` sums a trace by them. The innermost scope of an
operation names it; an operation under none of them is the layer loop's own
work (scan slicing, carries, copies).

``span(name)`` is ``jax.profiler.TraceAnnotation``: a host event recorded
only while a profiler runs, on the clock of the device trace, so an idle gap
on the device can be laid against what the serve loop was doing.
"""
from __future__ import annotations

import jax

# ---- device scopes (model step, wave index) --------------------------------
EMBED = "embed"                 # token (and patch) embedding
QKV = "qkv"                     # pre-attention norm, q/k/v projections, RoPE
CACHE_UPDATE = "cache_update"   # append_token; a chunk's cache append, staging
RANK = "rank"                   # centroid scoring, top-k, estimation zone
ATTEND = "attend"               # the attention itself (paged kernel) and wo
FFN = "ffn"                     # post-attention norm and the MLP
UNEMBED = "unembed"             # final norm and the head
INDEX_BUILD = "index_build"     # prompt clustering: append_chunk, finalize

SCOPES = (EMBED, QKV, CACHE_UPDATE, RANK, ATTEND, FFN, UNEMBED, INDEX_BUILD)

# ---- host spans of ServeEngine.serve (one per phase of an iteration) -------
ADMIT = "serve.admit"               # chunk dispatches, finalize, first tokens
FIRST_TOKEN = "serve.first_token"   # the coalesced first-token readback
DECODE = "serve.decode"             # decode-step dispatch
HARVEST = "serve.harvest"           # the lagged id readback
FLUSH = "serve.flush"               # decode-time index update
# offload control plane, named as its stages in engine.SERVE_STAGES
TRANSLATE = "translate"
DRAIN_ADMISSIONS = "drain_admissions"


def scope(name: str):
    """Name the device operations traced inside (``jax.named_scope``)."""
    return jax.named_scope(name)


def span(name: str):
    """Host span, recorded while a profiler runs
    (``jax.profiler.TraceAnnotation``)."""
    return jax.profiler.TraceAnnotation(name)
