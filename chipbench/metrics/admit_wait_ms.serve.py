"""Median, over the window's requests, of the time from when a request
was due to when the harness first saw it admitted."""

import numpy as np


def read(run):
    waits = run.data["admit_wait"]
    return float(np.median(waits)) * 1e3 if waits else None
