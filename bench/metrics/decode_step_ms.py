"""Device time of the engine's jitted ``decode`` program per call, in ms
(trace line ``XLA Modules``, programs named ``jit_decode``)."""
from bench.metrics._programs import ms_per_call


def read(run):
    return ms_per_call(run, ("decode",))
