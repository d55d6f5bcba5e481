"""Device time per call of compiled programs, from the reduced trace."""


def ms_per_call(run, names):
    if run.trace is None:
        return None
    calls = secs = 0
    for name in names:
        c, s = run.trace["modules"].get(name, (0, 0.0))
        calls, secs = calls + c, secs + s
    return 1e3 * secs / calls if calls else None
