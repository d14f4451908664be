"""What decides ``correct`` has to fail: under the control (the
configuration's lower-precision path) and under each fault the timed path
can have. Each case runs the harness at a tiny size on the CPU."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = ("bigann-int8.sat",)


_RUNS = {}


def _run(cell, case):
    if cell not in _RUNS:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        p = subprocess.run([sys.executable,
                            os.path.join(HERE, "fault_run.py"), cell],
                           env=env, capture_output=True, text=True,
                           timeout=900)
        assert p.returncode == 0, p.stderr[-3000:]
        _RUNS[cell] = json.loads(p.stdout.strip().splitlines()[-1])
    return _RUNS[cell][case][0]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    assert _run(cell, "none")["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    out = _run(cell, "control")
    assert not out["correct"]
    c = out["checks"]["max_excess"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("fault", ["drop_half", "empty_half", "alter",
                                   "no_work"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault):
    assert not _run(cell, fault)["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_recall_loss_catches_half_the_batch_left_out(cell):
    # the answers all come back and hold only true matches: recall alone
    # tells this run from a sound one
    c = _run(cell, "empty_half")["checks"]
    assert c["unanswered"]["value"] == 0 and c["max_excess"]["value"] == 0
    assert c["recall_loss"]["value"] > c["recall_loss"]["limit"]
    sound = _run(cell, "none")["checks"]["recall_loss"]
    assert sound["value"] <= sound["limit"]
