"""Device time per ``jit_decode`` call under the scope ``rank`` (the wave
index's centroid scores, top-k and estimation zone), in ms."""
from bench.metrics._programs import scope_ms_per_call


def read(run):
    return scope_ms_per_call(run, ("decode",), "rank")
