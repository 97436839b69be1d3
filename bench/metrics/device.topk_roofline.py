"""Share of the HBM roofline reached by the top-k program: least bytes
of one execution (``bench/peaks.topk_least_bytes``, with the batch B
read from the dispatched shape) over the peak bandwidth, divided by
its device time per execution."""
from bench import peaks
from bench.metrics._common import module_ms


def dispatched_batch(rec):
    """B of the engine's top-k shape tags ``("topk", B, bucket, ...)``."""
    bs = {int(s[1]) for s in rec["shapes"] if s and s[0] == "topk"}
    return bs.pop() if len(bs) == 1 else None


def read(rec):
    ms = module_ms(rec, "topk")
    batch = dispatched_batch(rec)
    if ms is None or batch is None or not rec.get("peaks"):
        return None
    ix = rec["index"]
    least = peaks.topk_least_bytes(batch, ix["n"], ix["m"], ix["l_max"])
    return 100.0 * least / rec["peaks"]["hbm_bytes_per_s"] / (ms * 1e-3)
