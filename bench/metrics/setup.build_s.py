"""Host seconds of ``build_index_scale``: diagonal, HP propagation,
packing and the artifact write."""


def read(rec):
    return rec["setup"].get("build_s")
