"""Device time per chunked-admission call (``jit_chunk`` and
``jit_chunk_pe``) under the scope ``index_build`` (the staging flush: the
gated segment clustering and the block write), in ms."""
from bench.metrics._programs import scope_ms_per_call


def read(run):
    return scope_ms_per_call(run, ("chunk", "chunk_pe"), "index_build")
