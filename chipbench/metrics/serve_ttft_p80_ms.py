"""80th percentile, over every request due in the window, of the time
from when it was due to its first token on the host (numpy's linear
interpolation between order statistics)."""

import numpy as np


def read(run):
    return float(np.percentile(run.data["ttft"], 80)) * 1e3
