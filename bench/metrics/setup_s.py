"""Process start to window start: imports, graph generation, index
build and write, mmap load, engine upload and warm-up."""


def read(rec):
    return rec["setup_s"]
