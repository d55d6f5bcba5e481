"""Share of the window's wall time the serve loop's host spent blocked in
its two readbacks, in %: ``ServeMetrics.harvest_wait_s`` (the lagged id
harvest) plus ``ServeMetrics.first_token_wait_s`` (the coalesced
first-token sample), over the window's wall time. High while the device
sets the pace, falling when the host does; a faster device also lowers it,
so it is read beside ``decode_step_ms``. None where the program keeps no
such counters."""


def read(run):
    waits = [getattr(run.serve, name, None)
             for name in ("harvest_wait_s", "first_token_wait_s")]
    if None in waits:
        return None
    return 100.0 * sum(waits) / run.wall_s
