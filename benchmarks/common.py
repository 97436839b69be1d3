"""Shared benchmark helpers: timing, CSV rows, and the versioned
structured-row store behind ``BENCH_<mode>.json`` + ``run.py
--compare`` (EXPERIMENTS.md "Perf trajectory")."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

import numpy as np


def timeit(fn, repeat: int = 3, number: int = 1):
    """Median wall time of fn() in microseconds."""
    times = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        for _ in range(number):
            fn()
        times.append((time.perf_counter() - t0) / number)
    return 1e6 * float(np.median(times))


ROWS: list[tuple[str, float, str]] = []


# ----------------------------------------------------------------------
# structured rows: the machine-readable twin of emit(), collected into
# a versioned BENCH_<mode>.json by run.py so results survive as data,
# not just CSV stdout. EVERY emit() records one -- benches that only
# print CSV still land in the JSON (their n/backend/mesh fields are
# parsed out of the row name) -- so --compare covers every bench mode,
# not just the backend-comparison benches that call emit_row directly.
# ----------------------------------------------------------------------
BENCH_SCHEMA_VERSION = 2

JROWS: list[dict] = []

_NAME_FIELDS = (("n", re.compile(r"/n=(\d+)(?=/|$)"), int, 0),
                ("backend", re.compile(r"/backend=(\w+)(?=/|$)"), str,
                 "host"),
                ("mesh", re.compile(r"/mesh=(\d+)(?=/|$)"), int, 1))


def _row_from_name(name: str, us: float, derived: str) -> dict:
    """Best-effort structured row parsed from a CSV row name: the
    ``/n=300``-style segments become fields and are stripped from the
    bench id so keys line up across runs and graph sizes stay a field,
    not part of the identity string."""
    bench = name
    fields = {}
    for key, rx, typ, default in _NAME_FIELDS:
        m = rx.search(bench)
        if m:
            fields[key] = typ(m.group(1))
            bench = rx.sub("", bench, count=1)
        else:
            fields[key] = default
    return {
        "schema": BENCH_SCHEMA_VERSION,
        "bench": bench,
        **fields,
        "wall": None if us != us else float(us),
        "throughput": None,
        "derived": derived,
    }


def emit(name: str, us: float, derived: str = "", *,
         structured: bool = True) -> None:
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}")
    if structured:
        JROWS.append(_row_from_name(name, us, derived))


def emit_row(bench: str, *, n: int, backend: str, mesh: int,
             wall_us: float, throughput: float | None = None,
             derived: str = "", **extra) -> None:
    """Record one structured benchmark row and print its CSV twin.

    Schema (BENCH_SCHEMA_VERSION): ``bench`` (measurement id), ``n``
    (graph size), ``backend`` ("lax" | "pallas" | "host"), ``mesh``
    (shard count, 1 = single device), ``wall`` (microseconds, NaN for
    trace-only rows), ``throughput`` (per-second rate, None when the
    row has no natural rate). Extra keys ride along unvalidated
    (bench_serve uses them for p50/p99/shed_rate/occupancy).
    """
    row = {
        "schema": BENCH_SCHEMA_VERSION,
        "bench": str(bench),
        "n": int(n),
        "backend": str(backend),
        "mesh": int(mesh),
        # trace-only rows pass NaN -> stored as null (strict JSON)
        "wall": None if wall_us != wall_us else float(wall_us),
        "throughput": None if throughput is None else float(throughput),
    }
    row.update(extra)
    JROWS.append(row)
    if not derived and throughput is not None:
        derived = f"{throughput:.0f}/s"
    emit(f"{bench}/backend={backend}/mesh={mesh}/n={n}", wall_us,
         derived, structured=False)


def write_json(mode: str, path: str | None = None,
               rows: list[dict] | None = None) -> str:
    """Write structured rows (default: all accumulated) to
    ``BENCH_<mode>.json``."""
    if path is None:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        path = os.path.join(repo, f"BENCH_{mode}.json")
    rows = JROWS if rows is None else rows
    doc = {"schema": BENCH_SCHEMA_VERSION, "mode": mode, "rows": rows}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# wrote {len(rows)} structured rows -> {path}")
    return path


def cpu_mesh_subprocess(module: str, args: list[str], ok_marker: str,
                        row_prefix: str, mesh: int) -> None:
    """Run a forced-host-device sharding check in a child process.

    The child is pinned to the CPU (``JAX_PLATFORMS=cpu``) with
    ``mesh`` forced host devices: the parent may hold the accelerator,
    and a chip belongs to one process at a time. Its ``row_prefix``
    rows are re-emitted here under ``/platform=cpu``, so no reader
    takes them for device numbers.
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={mesh}"
    env.setdefault("PYTHONPATH", "src")
    r = subprocess.run([sys.executable, "-m", module, *args],
                       capture_output=True, text=True, timeout=900,
                       env=env)
    assert ok_marker in r.stdout, r.stdout + r.stderr
    for line in r.stdout.splitlines():
        if line.startswith(row_prefix):
            name, us, derived = line.split(",", 2)
            emit(f"{name}/platform=cpu", float(us), derived)


# ----------------------------------------------------------------------
# cross-PR regression compare (run.py --compare OLD.json)
# ----------------------------------------------------------------------
def _row_key(row: dict) -> tuple:
    return (row.get("bench"), row.get("n"), row.get("backend"),
            row.get("mesh"))


def _index_rows(rows: list[dict], label: str) -> dict:
    """Key rows by identity, warning on collapse: a bench that emits
    two rows with the same (bench, n, backend, mesh) would otherwise
    silently hide all but the last from the regression gate."""
    out: dict[tuple, dict] = {}
    for r in rows:
        key = _row_key(r)
        if key in out:
            print(f"# compare WARNING: duplicate identity {key} in "
                  f"{label} rows; keeping the last -- earlier rows "
                  f"are invisible to the regression gate")
        out[key] = r
    return out


def compare_rows(old_rows: list[dict], new_rows: list[dict],
                 slow_ratio: float = 1.5) -> list[dict]:
    """Diff two row sets on the (bench, n, backend, mesh) identity.

    For every identity present in both, compares ``wall`` (lower is
    better) and ``throughput`` (higher is better); a ``wall`` ratio
    above ``slow_ratio`` -- or a throughput ratio below its inverse --
    marks the row REGRESSED. Returns the regressed comparison records;
    prints the full diff table as ``# compare`` CSV lines (identity,
    old, new, ratio, status) plus a summary with new/vanished
    identities. Micro-benchmark walls jitter, hence the generous
    default ratio -- this is a trajectory guard, not a 5% gate.
    """
    old = _index_rows(old_rows, "old")
    new = _index_rows(new_rows, "new")
    regressed: list[dict] = []
    compared = 0
    for key in new:
        if key not in old:
            continue
        o, nrow = old[key], new[key]
        for field, higher_is_better in (("wall", False),
                                        ("throughput", True)):
            ov, nv = o.get(field), nrow.get(field)
            if ov is None or nv is None or ov <= 0 or nv <= 0:
                continue
            compared += 1
            ratio = nv / ov
            bad = (ratio < 1.0 / slow_ratio if higher_is_better
                   else ratio > slow_ratio)
            status = ("REGRESSED" if bad else
                      ("improved" if (ratio > 1.0) == higher_is_better
                       and abs(ratio - 1.0) > 0.05 else "ok"))
            print(f"# compare,{key[0]},n={key[1]},backend={key[2]},"
                  f"mesh={key[3]},{field},{ov:.1f},{nv:.1f},"
                  f"x{ratio:.2f},{status}")
            if bad:
                regressed.append({"key": key, "field": field,
                                  "old": ov, "new": nv, "ratio": ratio})
    only_new = len(set(new) - set(old))
    vanished = len(set(old) - set(new))
    print(f"# compare summary: {compared} measurements diffed, "
          f"{len(regressed)} regressed (> x{slow_ratio:g}), "
          f"{only_new} new identities, {vanished} vanished")
    return regressed


def compare_json(old_path: str, new_rows: list[dict] | None = None,
                 slow_ratio: float = 1.5) -> list[dict]:
    """Load a prior ``BENCH_<mode>.json`` and diff against ``new_rows``
    (default: this process's accumulated rows). Refuses rows written
    by a *future* schema (same forward-compat rule as the index
    artifacts); older schemas compare fine -- the identity fields have
    existed since version 1."""
    with open(old_path) as f:
        doc = json.load(f)
    if doc.get("schema", 0) > BENCH_SCHEMA_VERSION:
        raise ValueError(
            f"{old_path} was written by schema {doc['schema']}, this "
            f"build understands <= {BENCH_SCHEMA_VERSION}")
    return compare_rows(doc.get("rows", []),
                        JROWS if new_rows is None else new_rows,
                        slow_ratio=slow_ratio)
