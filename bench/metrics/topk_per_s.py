"""Top-k answers completed in the window, over the time from the
window's start to the last completion inside it."""


def read(rec):
    if rec["mix"]["kind"] != "topk":
        return None
    done = [r["done"] for r in rec["requests"] if r["done"] is not None]
    if not done:
        return None
    return len(done) / (max(done) - rec["window"]["t0"])
