"""95th percentile of every gap between consecutive output tokens of a
request whose later token came inside the window."""

import numpy as np


def read(run):
    return float(np.percentile(run.data["itl"], 95)) * 1e3
