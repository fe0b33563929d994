"""95th percentile of request latency, put to fetch, over every request of the window."""
import numpy as np


def read(rec):
    return 1e3 * float(np.percentile(rec["latencies_s"], 95))
