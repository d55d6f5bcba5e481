"""Device time per chunked-admission call (``jit_chunk`` and
``jit_chunk_pe``) under the scope ``attend`` (the chunk's exact attention
over the admission cache and ``wo``), in ms."""
from bench.metrics._programs import scope_ms_per_call


def read(run):
    return scope_ms_per_call(run, ("chunk", "chunk_pe"), "attend")
