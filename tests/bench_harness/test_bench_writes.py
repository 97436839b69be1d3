"""A mix with graph writes on the CPU at the tiny size: the writer
applies the held-back edges through ``update_index`` and the
frontend's swap during the window, and each answer is checked against
the index and graph of the epoch that served it. The program repairs
only an unquantized index held in memory, so the runs that must come
out correct serve one (``fp32_artifact``); the artifact every cell
serves, int16 and mmap'd, is refused by the update, and that run is
not correct."""
import collections

import pytest
from bench_tiny import fp32_artifact, run_tiny

from bench import check, traffic

# two batches of 100 of the tiny graph's 1,900 edges, inside a 6-s
# window; on the CPU an update takes 1-2 s
WRITES = {"hold_back": 200, "batches": 2, "first_at_s": 0.3, "every_s": 1.5}
SECONDS = 6.0


def epochs_answered(rec) -> collections.Counter:
    swaps = check.swap_calls(rec["writes"])
    return collections.Counter(
        check.epochs_of(r["done"], swaps) for r in rec["requests"]
        if r["done"] is not None)


@pytest.mark.parametrize("mix", ["pair-open", "topk-closed"])
def test_writes_are_checked_against_their_epoch(mix):
    with fp32_artifact():
        _, rec, line = run_tiny(mix, seconds=SECONDS, writes=WRITES)
    assert line["correct"] is True, (line["checks"], rec["writes"])
    assert [w["applied"] for w in rec["writes"]] == [True, True]
    assert all(w["report"]["touched"] > 0 for w in rec["writes"])
    assert rec["checks"]["write_failed"] == {"value": 0, "limit": 0}
    # answers were served, and checked, on the built epoch and a later one
    served = {lo for lo, hi in epochs_answered(rec) if lo == hi}
    assert 0 in served and len(served) >= 2, epochs_answered(rec)
    assert len(rec["epoch_check_s"]) >= 2


def test_served_artifact_refuses_writes_and_is_not_correct():
    _, rec, line = run_tiny("pair-open", seconds=2.0,
                            writes=dict(WRITES, every_s=1.0))
    assert line["correct"] is False
    assert rec["checks"]["write_failed"]["value"] == 2
    assert "ValueError" in rec["writes"][0]["error"]
    assert rec["checks"]["lost"]["value"] == 0
    assert not any(w["applied"] for w in rec["writes"])


@pytest.mark.parametrize("mix", ["pair-open", "topk-closed"])
def test_static_run_starts_no_writer(monkeypatch, mix):
    from bench import harness

    def no_writer(*a, **kw):
        raise AssertionError("a static mix started a writer")
    monkeypatch.setattr(harness.Writer, "start", no_writer)
    _, rec, line = run_tiny(mix)
    assert line["correct"] is True, line["checks"]
    assert not {"writes", "answers_at_swap", "epoch_check_s"} & set(rec)
    assert "write_failed" not in line["checks"]


M = 1900


@pytest.mark.parametrize("writes,why", [
    ({"hold_back": 200, "batches": 5, "first_at_s": 0, "every_s": 1}, "batches"),
    ({"hold_back": M, "batches": 2, "first_at_s": 0, "every_s": 1}, "hold_back"),
    ({"hold_back": 0, "batches": 1, "first_at_s": 0, "every_s": 1}, "hold_back"),
    ({"hold_back": 201, "batches": 2, "first_at_s": 0, "every_s": 1}, "hold_back"),
    ({"hold_back": 200, "batches": 2, "first_at_s": 1, "every_s": 5}, "window"),
    ({"hold_back": 200, "batches": 1, "first_at_s": 6, "every_s": 1}, "window"),
    ({"hold_back": 200, "batches": 2, "first_at_s": 1, "every_s": 0}, "every_s"),
])
def test_loading_refuses_a_bad_write_mix(writes, why):
    with pytest.raises(ValueError, match=why):
        traffic.write_batches({"writes": writes}, M, 6.0)


def test_write_batches_split_the_held_back_tail():
    got = traffic.write_batches({"writes": WRITES}, M, SECONDS)
    assert got == [(0.3, 1700, 1800), (1.8, 1800, 1900)]
    assert traffic.write_batches({}, M, SECONDS) == []


def test_a_refused_write_mix_stops_the_run_before_set_up():
    with pytest.raises(ValueError, match="batches"):
        run_tiny("pair-open", writes=dict(WRITES, batches=8))


@pytest.mark.parametrize("done,want", [
    (0.5, (0, 0)), (1.0, (0, 1)), (1.5, (0, 1)), (2.0, (0, 1)),
    (2.5, (1, 1)), (3.0, (1, 2)), (3.2, (2, 2))])
def test_epoch_of_an_answer(done, want):
    assert check.epochs_of(done, [(1.0, 2.0), (3.0, 3.1)]) == want
