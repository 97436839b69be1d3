"""The harness end to end on the CPU at the tiny test-only size: the
result line carries exactly the contract's keys, the compared numbers
last, and the metrics the cell declares."""
import json

import pytest
from bench_tiny import run_tiny

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("mix,trace", [("topk-closed", False),
                                       ("pair-open", True)])
def test_result_line_keys(mix, trace, capsys):
    from bench import harness
    cell, rec, line = run_tiny(mix, trace=trace)
    harness.print_result(line, rec)
    out = capsys.readouterr()
    last = json.loads(out.out.strip().splitlines()[-1])
    want = KEYS[:-1] + (["breakdown"] if trace else []) + KEYS[-1:]
    assert list(last) == want
    assert last["correct"] is True, last["checks"]
    assert last["failed"] == 0 and last["attempted"] > 0
    dev = last["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
    specs = cell["per_layer"] if trace else cell["end_to_end"]
    declared = {m["name"]: m["unit"] for m in specs}
    assert set(last["metrics"]) <= set(declared)
    for name, m in last["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == declared[name]
    if not trace:
        # host-clock metrics exist on any platform
        assert "setup_s" in last["metrics"]
        assert len(last["metrics"]) == len(declared)
    # the compared numbers, each beside its limit, end standard error
    err = [l for l in out.err.strip().splitlines() if l.startswith("check ")]
    assert [l.split()[1] for l in err] == list(last["checks"])


def test_open_loop_counts_every_request_sent():
    cell, rec, line = run_tiny("pair-open", seconds=1.0)
    assert line["attempted"] == round(cell["mix"]["rate_per_s"] * 1.0)
    assert all(r["done"] is not None for r in rec["requests"])
