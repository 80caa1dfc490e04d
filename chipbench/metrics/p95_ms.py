"""95th percentile of every request's latency in the window, in ms: host
clock from the call to ``block_until_ready`` of its ids and distances."""

import numpy as np


def read(run):
    return float(np.percentile(run.latencies_s, 95)) * 1e3
