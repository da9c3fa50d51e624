"""A run of the LFM2 cell with the timed path broken underneath comes
out not correct, once for each fault it can have
(``bench/faults_lfm2.py``, and ``bench/faults.py``'s half batch), driven on the CPU at a small size
(``test_bench_rl_lfm2.plan_run``) and judged by the cell's limits."""
import pytest

from test_bench_rl_lfm2 import LFM2, plan_run
from bench import correct, faults, faults_lfm2


@pytest.mark.parametrize("fault", ["dropped_at_capacity", "bias_ignored",
                                   "conv_state_reset", "half_batch"])
def test_fault_is_not_correct(monkeypatch, fault):
    {**faults.FAULTS, **faults_lfm2.FAULTS}[fault](monkeypatch.setattr)
    res = plan_run(monkeypatch)
    limits = correct.load_limits(LFM2)
    assert not correct.judge(res["_record"]["readings"], limits)[0], \
        res["checks"]
