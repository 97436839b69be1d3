"""Share of the traced window in which no operation ran on the
device, in a top-k cell."""
from bench.metrics._common import idle_share


def read(rec):
    return idle_share(rec, "topk")
