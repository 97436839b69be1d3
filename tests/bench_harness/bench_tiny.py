"""A test-only configuration for driving the harness on the CPU at a
tiny size. It is registered here, never in BENCHMARK.json."""
from __future__ import annotations

import contextlib
import copy
import functools
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {
    "name": "tiny",
    "graph": {"n": 240, "m": 1900, "gamma_in": 2.1, "gamma_out": 2.72,
              "max_in_degree": 50, "max_out_degree": 50,
              "structure_seed": 0},
    "plan": {"eps": 0.2, "c": 0.6},
}

# the real cell whose mix, limits and metrics each tiny cell borrows
SIBLING = {"topk-closed": "wikivote-e0.025.topk-closed",
           "pair-open": "wikivote-e0.025.pair-open"}


def tiny_cell(mix: str) -> dict:
    from bench import harness
    cell = copy.deepcopy(harness.load_cell(SIBLING[mix]))
    cell["name"] = f"tiny.{mix}"
    cell["config"] = copy.deepcopy(TINY)
    cell["limits"]["simrank_err"] = TINY["plan"]["eps"]
    if mix == "topk-closed":
        cell["mix"]["clients"] = 16
        cell["mix"]["check_samples"] = 8
    else:
        cell["mix"]["rate_per_s"] = 300
        cell["mix"]["check_samples"] = 200
    return cell


def run_tiny(mix: str, seed: int = 2**31 + 11, seconds: float = 1.0,
             trace: bool = False, keep=None, writes=None):
    """One harness run of the tiny cell on the CPU, with the mix's
    ``writes`` where given; returns (cell, record, result line)."""
    import jax

    from bench import harness
    cell = tiny_cell(mix)
    if writes is not None:
        cell["mix"]["writes"] = writes
    # the harness turns on JAX's persistent cache; give the other
    # tests of this process their settings back
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in names}
    try:
        rec = harness.run(cell, seed, seconds, trace,
                          t_start=time.monotonic(), require_tpu=False,
                          keep=keep)
    finally:
        from jax.experimental.compilation_cache import compilation_cache
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    return cell, rec, harness.result_line(cell, rec, trace)


@contextlib.contextmanager
def fp32_artifact():
    """While open, ``build_index_scale`` writes float32 values (no
    quantization) and ``SlingIndex.load`` reads the file into memory
    (no mmap): the only artifact the program's ``update_index``
    repairs."""
    from repro.core import build
    from repro.core.index import SlingIndex
    real_build, real_load = build.build_index_scale, SlingIndex.__dict__["load"]

    def load(path, mmap=False, validate=None):
        return real_load.__func__(path, mmap=False, validate=validate)

    build.build_index_scale = functools.partial(real_build, quantize=None)
    SlingIndex.load = staticmethod(load)
    try:
        yield
    finally:
        build.build_index_scale = real_build
        SlingIndex.load = real_load


def write_trial(workload: str, seed: int, seconds: float, writes: dict,
                fp32: bool = False) -> None:
    """One run on the chip of the BENCHMARK.json cell ``workload`` with
    its mix's ``writes`` set, the cell so made registered nowhere;
    ``fp32`` serves the artifact of :func:`fp32_artifact`. Prints as
    ``bench/run.py`` does. From the checkout's root:

        python3 -c "import sys; sys.path.insert(0, 'tests/bench_harness');
            import bench_tiny; bench_tiny.write_trial('<cell>', <seed>, 30,
            {'hold_back': 100, 'batches': 2, 'first_at_s': 2, 'every_s': 10},
            fp32=True)"
    """
    from bench import harness
    cell = harness.load_cell(workload)
    cell["mix"]["writes"] = writes
    with fp32_artifact() if fp32 else contextlib.nullcontext():
        rec = harness.run(cell, seed, seconds, False,
                          t_start=time.monotonic())
    harness.print_result(harness.result_line(cell, rec, False), rec)
