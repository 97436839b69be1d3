"""Device ms per execution of the pair-join program, from the trace."""
from bench.metrics._common import module_ms


def read(rec):
    return module_ms(rec, "pair")
