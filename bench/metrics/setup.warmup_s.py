"""Host seconds from the loaded artifact to a warm frontend: engine
install (dequantize, pad, upload) and one request of the cell's kind."""


def read(rec):
    return rec["setup"].get("warmup_s")
