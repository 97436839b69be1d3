"""The control at a size a test run holds: the plain reference put in
the program's place and computed in bfloat16 reads above the cell's
limits, where the program reads below them. The limit on the gap to
exact SimRank is the eps that the configuration states."""
import json

import pytest
from bench_tiny import run_tiny, tiny_cell


@pytest.mark.parametrize("mix,number", [("topk-closed", "topk_score_err"),
                                        ("pair-open", "pair_abs_err")])
def test_control_fails_the_limit_the_program_meets(mix, number):
    from bench import control
    got = {}

    def keep(rec, art, edges, exact):
        got["control"] = control.control_numbers(rec, art, edges, exact,
                                                 2**31 + 11)

    cell, rec, line = run_tiny(mix, keep=keep)
    limit = tiny_cell(mix)["limits"][number]
    assert line["correct"] is True, line["checks"]
    assert rec["checks"][number]["value"] <= limit
    assert got["control"][number] > limit


def test_simrank_limit_is_the_configured_eps():
    from bench import harness
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell["limits"]["simrank_err"] == cell["config"]["plan"]["eps"]
