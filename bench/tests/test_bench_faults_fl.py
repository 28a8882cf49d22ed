"""A FedVeca run end to end on the CPU at a test size, past the harness's
look for a chip: sound, it comes out correct; with a fault planted in
the timed path, correct comes out false."""
import time

import pytest

from bench_fixtures import fixture_cell

from bench import faults, harness

CELL = "cnn-tiny.fl-tiny"
SEED = 2**31 + 99


def run(fault=None):
    cell = fixture_cell(CELL)
    if fault is None:
        return cell.kind().run(cell, SEED, 0.5, False, time.time())
    with faults.FAULTS[fault]():
        return cell.kind().run(cell, SEED, 0.5, False, time.time())


def test_sound_run_is_correct():
    out = run()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["counters"]["compiles_in_window"] == 0
    assert set(out["e2e"]) == {"round_ms", "setup_s"}


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_fault_is_caught(fault):
    out = run(fault)
    assert not out["correct"], out["checks"]


def test_control_fails_the_limits():
    """The reference in bfloat16 in the program's place fails a limit."""
    cell = fixture_cell(CELL)
    out = cell.kind().control(cell, SEED, 0.5)
    assert out["correct"], out["checks"]
    assert not harness.passed(harness.judge(out["control"], cell.limits)), \
        out["control"]


_SHARDED = """
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[2]]
from bench_fixtures import fixture_cell
from bench import faults
cell = fixture_cell("cnn-tiny.fl-tiny-c8")
for fault in (None, "no_exchange"):
    if fault is None:
        out = cell.kind().run(cell, %d, 0.5, False, time.time())
    else:
        with faults.FAULTS[fault]():
            out = cell.kind().run(cell, %d, 0.5, False, time.time())
    print(json.dumps(dict(fault=fault, correct=out["correct"],
                          count=out["device"]["count"],
                          checks=out["checks"]), default=float))
""" % (SEED, SEED)


def test_sharded_run_and_missing_exchange():
    """On four (host) devices the round shards its clients over the
    chips: sound, it comes out correct; with the psum between chips left
    out, correct comes out false. Its own process, since the device
    count is fixed when JAX starts."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    here = Path(__file__).resolve().parent
    root = here.parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(root / "src"))
    proc = subprocess.run([sys.executable, "-c", _SHARDED, str(here),
                           str(root)], env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    sound, broken = [json.loads(l) for l in proc.stdout.splitlines()[-2:]]
    assert sound["count"] == 4
    assert sound["correct"], sound["checks"]
    assert not broken["correct"], broken["checks"]
