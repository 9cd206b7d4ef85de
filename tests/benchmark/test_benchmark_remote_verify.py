"""``remote_caller``'s guarantee is a payload EQUAL to the reference for
its request: the client's verifier sorts a response into bit exact, within
the tolerance alone, or wrong; and a run whose responses pass by the
tolerance alone while the program, run again, still gives its bytes of
set-up is not ``correct`` (the bytes were altered on the way)."""

import types

import numpy as np

import bench_testlib  # noqa: F401 - puts the repo on sys.path
from benchmark.lib.loader import load_module


def _bf16(values):
    import ml_dtypes
    return np.asarray(values, dtype=np.float32).astype(ml_dtypes.bfloat16)


def test_the_verifier_sorts_exact_near_and_wrong():
    child = load_module("drivers", "remote_child")
    produced = _bf16(np.linspace(-4, 4, 64).reshape(8, 8))
    client = types.SimpleNamespace(
        index_of=lambda seq: 0, produced=[produced],
        expected=[produced.astype(np.float32)], atol=2 ** -4,
        reference=load_module("reference", "remote_caller"))
    v = child.Verifier(client)
    v.start()
    near = produced.copy()
    near.view(np.uint16)[0, 0] ^= 1          # one low mantissa bit
    wrong = produced + produced.dtype.type(1)
    for seq, arr in ((10, produced.copy()), (11, near), (12, wrong),
                     (13, produced[:4])):
        v.put(seq, arr)
    assert v.drain(10.0)
    assert (v.checked, v.fast, v.soft) == (4, 1, [11])
    assert [seq for seq, _why in v.bad] == [12, 13]


class _Stamps:
    def __init__(self):
        self.failures = []

    def fail(self, seq, reason):
        self.failures.append((seq, reason))


def _deployment(step, tolerance_only):
    service = load_module("services", "remote_caller")
    dep = object.__new__(service.RemoteCallerDeployment)
    x = _bf16(np.ones((4, 4)))
    dep.reference = load_module("reference", "remote_caller")
    dep.pool = dep.layers = 2
    dep.xs, dep.w_in, dep.w_out = [x, x + x], [x, x], [x, x]
    dep.step = step
    dep.produced = [np.asarray(step(dep.xs[i], x, x)) for i in range(2)]
    dep.stamps, dep.first_seq = _Stamps(), 4093
    dep.placement_violations, dep.bad = 0, []
    dep.child_pid, dep.child_lane = 1, "staged-dcn"
    calls = [(4093 + i, 0, 1) for i in range(5)]
    dep.window_reply = {
        "calls": calls, "checked": 5, "fast_path": 5 - len(tolerance_only),
        "tolerance_only": tolerance_only, "bad": [], "cpu_s": 1.0,
        "verify_cpu_s": 0.1, "issue_cpu_s": 0.2}
    report = {"jax_loaded": False, "tpud_put_fallbacks": 0,
              "unbalanced": [], "cells": {}, "spans": [], "counters": {}}
    dep.child = types.SimpleNamespace(ask=lambda **_cmd: report)
    return dep


def test_near_but_not_equal_fails_the_run_where_the_program_repeats_itself(
        capsys, monkeypatch):
    # the counter is the process's, and another test of this worker may
    # have made a device_put fail on purpose
    from brpc_tpu.transport import syscall_stats
    monkeypatch.setattr(syscall_stats, "snapshot",
                        lambda: {"tpud_put_fallbacks": 0})
    steady = lambda x, w_in, w_out: x + w_in          # noqa: E731
    dep = _deployment(steady, [4095, 4096])
    assert dep.finish() == 0
    (failure,) = dep.stamps.failures
    assert "2 responses" in failure[1] and "altered on the way" in failure[1]
    assert '"verified_by_tolerance_alone": 2' in capsys.readouterr().out
    # every response bit exact: nothing to hold against the program
    dep = _deployment(steady, [])
    assert dep.finish() == 0 and dep.stamps.failures == []
    # a program that does not repeat its own bytes: the tolerance is all
    # there is to hold a response to, and it held
    turn = iter(range(100))
    drifting = lambda x, w_in, w_out: (           # noqa: E731
        x + w_in if next(turn) < 2 else x + w_in + w_in)
    dep = _deployment(drifting, [4095])
    assert dep.finish() == 0 and dep.stamps.failures == []
