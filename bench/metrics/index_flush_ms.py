"""Device time of one decode-time wave-index update (``jit_flush``: the
segmented clustering of ``update_segment`` staged tokens in every layer),
in ms."""
from bench.metrics._programs import ms_per_call


def read(run):
    return ms_per_call(run, ("flush",))
