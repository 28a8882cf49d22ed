"""A serving run end to end on the CPU at a test size, past the harness's
look for a chip: sound, it comes out correct; with each served token
altered where it is produced, correct comes out false."""
import time

from bench_fixtures import fixture_cell

from bench import faults, harness

SEED = 2**31 + 7


def run(cell_name, fault=None):
    cell = fixture_cell(cell_name)
    if fault is None:
        return cell.kind().run(cell, SEED, 1.5, False, time.time())
    with faults.FAULTS[fault]():
        return cell.kind().run(cell, SEED, 1.5, False, time.time())


def test_sound_open_loop_run_is_correct():
    out = run("decoder-tiny.chat-tiny")
    assert out["correct"], out["checks"]
    assert out["readings"]["checked_tokens"] >= 20
    assert out["counters"]["compiles_in_window"] == 0
    assert set(out["e2e"]) == {"serve_tok_s", "itl_p95_ms", "ttft_p90_ms",
                               "setup_s"}


def test_altered_tokens_are_caught():
    out = run("decoder-tiny.backlog-tiny", "token_altered")
    assert not out["correct"], out["checks"]


def test_int8_control_fails_the_limit():
    """On the same finished requests of one window, the served tokens
    stay under the limit and the int8 control's picks do not (CPU,
    readings over 4 seeds: served 0.0011-0.0045, control 0.0050-0.025;
    this seed reads 0.0042 and 0.025)."""
    cell = fixture_cell("decoder-small.longer-tiny")
    out = cell.kind().control(cell, 2, 4.0)
    assert out["readings"]["checked_tokens"] >= 200
    assert out["correct"], out["checks"]
    assert not harness.passed(harness.judge(out["control"], cell.limits)), \
        out["control"]
