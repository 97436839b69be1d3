"""99th percentile of how late the generator sent each pair after its
scheduled time, in ms."""
import numpy as np


def read(rec):
    if rec["mix"]["kind"] != "pair" or not rec["requests"]:
        return None
    late = [(r["sent"] - r["sched"]) * 1e3 for r in rec["requests"]]
    return float(np.percentile(late, 99))
