"""Peak device memory in use over the run (``memory_stats()
["peak_bytes_in_use"]``, read right after the window), in GiB."""


def read(run):
    peak = run.device.get("memory_peak_bytes", 0)
    return peak / 2 ** 30 if peak else None
