"""The lowered allreduce's service file on four virtual CPU devices at
small sizes: set-up ties the fan-out and the lowered call to the
reference, a window of lowered calls is clean, and what must make the
cell's ``correct`` false."""

import pytest

from bench_testlib import last_line, run_cell

CELL = "collective_allreduce.collective_4mb_d1"


@pytest.fixture
def deployment():
    import jax

    from benchmark.lib.loader import Cell, load_module
    from benchmark.lib.stamps import Stamps
    from benchmark.run import Context
    from brpc_tpu.butil.flags import flag, set_flag
    from brpc_tpu.transport import device_stats  # noqa: F401 - the flag

    old = flag("device_stats_enabled")
    set_flag("device_stats_enabled", True)
    cell = Cell(CELL, rehearse=True)
    dep = load_module("services", cell.config["service"]).build(
        Context(cell, 2**31 + 17, jax.devices()[:4], Stamps(trace=False),
                ""))
    try:
        dep.prepare()
        dep.start()
        yield dep
    finally:
        dep.close()
        set_flag("device_stats_enabled", old)


def _window(dep, calls):
    for seq in range(dep.first_seq, dep.first_seq + calls):
        cntl = dep.call_sync(seq)
        arrs = dep.response_arrays(seq, cntl)
        dep.verify(seq, cntl, arrs)


def test_set_up_ties_both_paths_and_a_lowered_window_is_clean(deployment):
    dep = deployment
    assert all(r.committed and r.devices() == {dep.devices[0]}
               for r in dep.requests)
    # every pooled request fanned out (the handlers ran, block i on
    # chip i) and then lowered, each against the reference
    assert dep.warm() == 2 * dep.pool
    assert len(dep.stamps.handlers) == dep.n * dep.pool
    assert dep.misplaced == []
    combo = dep.fabric.combo
    assert (combo.collective_fused, combo.collective_fallbacks) == \
        (dep.pool, 0)
    _window(dep, 9)
    assert len(dep.stamps.handlers) == dep.n * dep.pool     # no message
    assert dep.finish() == 0 and dep.problems == []
    said = dep.describe()
    assert said["window_counters"]["fused"] == said["calls_since_mark"] == 9
    assert said["window_counters"]["fallbacks"] == 0
    assert said["lanes"] == ["local-d2d"] * 4


def test_a_fall_back_in_the_window_is_not_correct(deployment):
    from benchmark.services.allreduce_collective import METHOD, SERVICE

    dep = deployment
    dep.warm()

    def broken(s):
        raise RuntimeError("no such program")
    dep.fabric.combo._collective_fns[(SERVICE, METHOD)] = broken
    cntl = dep.call_sync(dep.first_seq)     # fans out: still an answer
    assert not cntl.failed()
    with pytest.raises(AssertionError, match="not lowered"):
        dep.response_arrays(dep.first_seq, cntl)
    assert dep.finish() >= 1
    assert "the program counted" in dep.problems[0]
    assert dep.describe()["window_counters"]["fallbacks"] == 1


def test_corrupted_response_is_not_correct():
    proc = run_cell(CELL, "--inject", "corrupt_response")
    res = last_line(proc)
    assert res["correct"] is False and res["failed"] > 0
    assert proc.returncode != 0
    assert "differ from the reference" in proc.stdout
