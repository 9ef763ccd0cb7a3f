"""``correct`` comes out true on a sound run and false when the timed
path is broken underneath, and the precision control fails the limits.
Runs on the CPU at 32x32 with the look for a chip skipped."""
import numpy as np
import pytest

from chipbench import check, spec
from chipbench.tests.conftest import run_cell

ARGS = ("--seed", "3000000019", "--seconds", "1", "--trace", "0")


def _break(monkeypatch, alter):
    """Alter what the program's compute step produces for every batch."""
    from repro.runtime import cnn_server

    real = cnn_server._BucketedCompute.classify

    def classify(self, images, uids=()):
        labels, probs, logits = real(self, images, uids)
        return labels, probs, alter(np.array(logits), uids)

    monkeypatch.setattr(cnn_server._BucketedCompute, "classify", classify)


@pytest.mark.parametrize("workload", ["mobilenetv1-224.closed",
                                      "resnet50-224.open"])
def test_a_sound_run_is_correct(small_copy, no_chip_check, capsys, workload):
    rc, last, err = run_cell(small_copy, capsys, "--workload", workload,
                             *ARGS)
    assert rc == 0 and last["correct"], err
    assert last["failed"] == 0 and last["attempted"] > 0
    assert list(last)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check logit_err_rms")


def _negate_first(logits, uids):
    logits[0] = -logits[0]
    return logits


def _reverse_lanes(logits, uids):
    return logits[::-1].copy()


def _shift_one(logits, uids):
    logits[-1, 0] += 10.0 * np.abs(logits[-1]).max()
    return logits


@pytest.mark.parametrize("alter", [_negate_first, _reverse_lanes,
                                   _shift_one])
def test_an_answer_altered_where_it_is_produced_is_caught(
        small_copy, no_chip_check, capsys, monkeypatch, alter):
    _break(monkeypatch, alter)
    rc, last, err = run_cell(small_copy, capsys, "--workload",
                             "mobilenetv1-224.closed", *ARGS)
    assert rc == 0 and last["correct"] is False, err


def test_a_request_that_never_gets_an_answer_is_caught(
        small_copy, no_chip_check, capsys, monkeypatch):
    from repro.runtime import cnn_server

    real = cnn_server._BucketedCompute.classify

    def classify(self, images, uids=()):
        if 5 in uids:
            raise RuntimeError("lost")
        return real(self, images, uids)

    monkeypatch.setattr(cnn_server._BucketedCompute, "classify", classify)
    rc, last, err = run_cell(small_copy, capsys, "--workload",
                             "mobilenetv1-224.closed", *ARGS)
    assert rc == 0 and last["correct"] is False
    assert last["failed"] >= 1


@pytest.mark.parametrize("workload", ["resnet50-224.closed",
                                      "mobilenetv1-224.closed"])
def test_the_int4_control_fails_the_limits(small_copy, workload):
    from chipbench.control import control_numbers

    cell = spec.load_cell(workload, root=small_copy)
    cell.limits = spec.load_cell(workload).limits  # the real cell's limits
    got = control_numbers(cell, seed=7)
    checks = {k: {"value": got[k], "limit": cell.limits[k]}
              for k in check.ORDER}
    assert not check.passed(checks), checks
