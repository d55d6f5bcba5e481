"""Share of the traced window in which the device idled while the serve
loop's host waited in a readback (``sync_idle_s`` of ``bench/scopes.py``:
idle gaps whose innermost ``serve.*`` span is ``serve.harvest`` or
``serve.first_token``), in %."""


def read(run):
    if run.trace is None or "sync_idle_s" not in run.trace:
        return None
    return 100.0 * run.trace["sync_idle_s"] / run.trace["window_s"]
