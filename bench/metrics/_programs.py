"""Device time per call of compiled programs, from the reduced trace: whole
programs (``modules``), or the part under one named scope (``scopes``,
``bench/scopes.py``)."""


def ms_per_call(run, names):
    if run.trace is None:
        return None
    calls = secs = 0
    for name in names:
        c, s = run.trace["modules"].get(name, (0, 0.0))
        calls, secs = calls + c, secs + s
    return 1e3 * secs / calls if calls else None


def scope_ms_per_call(run, names, scope):
    """Device ms per call of the programs ``names`` under ``scope``; None
    where the trace holds no scope times, none of the programs ran, or no
    operation of theirs lies under ``scope``."""
    if run.trace is None or "scopes" not in run.trace:
        return None
    calls = ops = secs = 0
    for name in names:
        calls += run.trace["modules"].get(name, (0, 0.0))[0]
        n, s = run.trace["scopes"].get(name, {}).get(scope, (0, 0.0))
        ops, secs = ops + n, secs + s
    return 1e3 * secs / calls if calls and ops else None
