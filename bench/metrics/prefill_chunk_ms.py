"""Device time of one chunked-admission program call (``jit_chunk`` or,
with image patches, ``jit_chunk_pe``), in ms."""
from bench.metrics._programs import ms_per_call


def read(run):
    return ms_per_call(run, ("chunk", "chunk_pe"))
