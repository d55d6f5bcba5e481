"""Share of the window the scheduler spent in admission: the engine's own
``ServeMetrics.prefill_s`` span over the window's wall time, in %."""


def read(run):
    return 100.0 * run.serve.prefill_s / run.wall_s
