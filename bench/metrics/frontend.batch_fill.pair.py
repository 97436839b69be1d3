"""Mean size/cap of the pair batches the frontend closed in the
window (``ServeFrontend.batch_log``)."""
from bench.metrics._common import batch_fill


def read(rec):
    return batch_fill(rec, "pair")
