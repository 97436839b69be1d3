"""Median host time of one ``QueryEngine.pairs`` call in the window,
from the benchmark's span around it."""
import numpy as np


def read(rec):
    spans = rec["spans"].get("engine.pairs")
    if not spans:
        return None
    return float(np.median([(e - s) * 1e3 for s, e, _ in spans]))
