"""95th percentile of every token-delivery gap of the window
(``ServeMetrics.step_s``, host clock), in ms."""
import numpy as np


def read(run):
    if not run.serve.step_s:
        return None
    return float(np.percentile(run.serve.step_s, 95)) * 1e3
