"""Device time per ``jit_decode`` call under no named scope (``loop``: the
layer loop's own slicing, carries and copies), in ms."""
from bench.metrics._programs import scope_ms_per_call


def read(run):
    return scope_ms_per_call(run, ("decode",), "loop")
