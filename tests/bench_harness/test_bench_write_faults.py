"""The per-epoch check fails a write mix whose swap is broken
underneath: the swap skipped, so the built epoch keeps serving; the
swap handed a copy of the index from before the update; and the
check's epoch of each answer shifted by one. Every batch still
applies, so ``write_failed`` stays 0 and only the answers give the
fault away."""
import copy

import pytest
from bench_tiny import fp32_artifact, run_tiny
from test_bench_writes import SECONDS, WRITES


SERVED = {"pair-open": "pair_abs_err", "topk-closed": "topk_score_err"}


def _plant(monkeypatch, fault):
    from bench import check
    from repro.core import build
    from repro.serve import ServeFrontend
    real_swap = ServeFrontend.swap_index
    if fault == "swap_skipped":
        monkeypatch.setattr(ServeFrontend, "swap_index",
                            lambda self, index, g, affected=None: {})
    elif fault == "swap_of_old_copy":
        real_update = build.update_index
        before = []

        def update(idx, g, delta, **kw):
            before.append(copy.deepcopy(idx))
            return real_update(idx, g, delta, **kw)

        def swap(self, index, g, affected=None):
            return real_swap(self, before[-1], g, affected)
        monkeypatch.setattr(build, "update_index", update)
        monkeypatch.setattr(ServeFrontend, "swap_index", swap)
    else:                               # epoch_shifted
        real_epochs = check.epochs_of
        monkeypatch.setattr(check, "epochs_of", lambda done, swaps: tuple(
            max(0, e - 1) for e in real_epochs(done, swaps)))


@pytest.mark.parametrize("mix", ["pair-open", "topk-closed"])
@pytest.mark.parametrize("fault", ["swap_skipped", "swap_of_old_copy",
                                   "epoch_shifted"])
def test_write_fault_is_not_correct(monkeypatch, mix, fault):
    _plant(monkeypatch, fault)
    with fp32_artifact():
        _, rec, line = run_tiny(mix, seconds=SECONDS, writes=WRITES)
    checks = line["checks"]
    assert [w["applied"] for w in rec["writes"]] == [True, True]
    assert checks["write_failed"]["value"] == 0
    assert line["correct"] is False, checks
    assert checks[SERVED[mix]]["value"] > checks[SERVED[mix]]["limit"]
