"""The control and the planted faults of control.py, at the program's
reduced size on the CPU.  On the chip they run at each cell's own size
(PERF.md gives those readings); here they show that the control path
runs and reads far from the program, and that every fault fails the
cell's limits."""

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import compare, control    # noqa: E402
from chipbench.tests import tiny          # noqa: E402


@pytest.mark.parametrize("workload,cfg", [
    ("gpt2s.train.paper", "gpt2-small"),
    ("neo125.train.paper", "gpt-neo-125m")])
def test_training_control_and_faults(tmp_path, workload, cfg):
    out = tmp_path / "readings.json"
    control.main(["--workload", workload, "--seeds", "3", "--seconds", "1",
                  "--out", str(out)], require_tpu=False,
                 overrides={"cfg": tiny.tiny_cfg(cfg),
                            "traffic": tiny.tiny_train_traffic()})
    (row,) = json.loads(out.read_text())
    prog, ctrl = row["program"], row["control_bf16"]
    assert ctrl["loss_gap"] > 10 * prog["loss_gap"]
    assert ctrl["grad_gap"] > 10 * prog["grad_gap"]
    limits = compare.load_limits(workload)
    for fault in ("fault_half_batch", "fault_state_unchanged"):
        assert not compare.judge(row[fault], limits)[0], (fault, row[fault])


def test_serving_control_and_fault(tmp_path):
    out = tmp_path / "readings.json"
    control.main(["--workload", "gpt2s.serve.steady", "--seeds", "3",
                  "--seconds", "1", "--out", str(out)], require_tpu=False,
                 overrides={"cfg": tiny.tiny_cfg(),
                            "traffic": tiny.tiny_serve_traffic(),
                            "bench": tiny.bench_with_later()})
    (row,) = json.loads(out.read_text())
    assert row["control_bf16"]["served_gap"] >= \
        row["program"]["served_gap"]
    limits = compare.load_limits("gpt2s.serve.steady")
    assert not compare.judge(row["fault_token_altered"], limits)[0]
