"""95th percentile of pair latency, from each request's scheduled
send, over every pair sent in the window."""
import numpy as np

from bench.metrics._common import latencies_ms


def read(rec):
    lat = latencies_ms(rec, "pair")
    return None if lat is None else float(np.percentile(lat, 95))
