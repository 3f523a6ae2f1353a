"""A whole run, past the look for a chip, at the program's reduced size
on the CPU: sound, it reads correct; with the timed path broken under
it, correct comes out false, once for each fault the cell can have."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import run                 # noqa: E402
from chipbench.tests import tiny          # noqa: E402

CELLS = {
    "gpt2s.train.paper": (tiny.tiny_cfg, tiny.tiny_train_traffic,
                          ("state_unchanged", "half_batch")),
    "neo125.train.paper": (lambda: tiny.tiny_cfg("gpt-neo-125m"),
                           tiny.tiny_train_traffic,
                           ("state_unchanged", "half_batch")),
    "gpt2s.serve.steady": (tiny.tiny_cfg, tiny.tiny_serve_traffic,
                           ("token_altered",)),
}
CASES = [(w, None) for w in CELLS] + [(w, f) for w, c in CELLS.items()
                                      for f in c[2]]


def _result(capsys, workload, fault, seconds=1):
    cfg, traffic, _ = CELLS[workload]
    rc = run.main(["--workload", workload, "--seed", str(2 ** 31 + 99),
                   "--seconds", str(seconds), "--trace", "0"],
                  require_tpu=False,
                  overrides={"cfg": cfg(), "traffic": traffic(),
                             "bench": tiny.bench_with_later(),
                             "fault": fault})
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,fault", CASES)
def test_correct_sees_the_fault(capsys, workload, fault):
    res = _result(capsys, workload, fault)
    assert res["correct"] is (fault is None), res["check"]
    assert list(res)[-1] == "check"


FLEET = """
import json, sys
sys.path.insert(0, {root!r})
from chipbench import run
from chipbench.tests import tiny
tr = dict(tiny.tiny_train_traffic(), clients=4, mesh_data=4)
rc = run.main(["--workload", "gpt2s.train.fleet4", "--seed", "5",
               "--seconds", "1", "--trace", "0"], require_tpu=False,
              overrides={{"cfg": tiny.tiny_cfg(), "traffic": tr,
                          "bench": tiny.bench_with_later(),
                          "fault": {fault!r}}})
sys.exit(rc)
"""


@pytest.mark.parametrize("fault", [None, "no_exchange", "state_unchanged",
                                   "half_batch"])
def test_four_chip_cell_sees_the_fault(fault):
    """The sharded cell on four virtual CPU devices (its own process: the
    device count is fixed when JAX starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c",
                        FLEET.format(root=str(ROOT), fault=fault)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is (fault is None), res["check"]
    assert res["device"]["count"] == 4


def test_no_result_without_the_chip(capsys):
    rc = run.main(["--workload", "gpt2s.train.paper", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""
