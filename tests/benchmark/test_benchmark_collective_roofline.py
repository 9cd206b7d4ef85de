"""``collective_roofline`` counts the scatter by what the deployment
does, not by the name of an op: the request is committed to chip 0 and
the lowered program scatters it itself, so the least time holds the
scatter whether the compiler emits a ``collective-permute`` or an
``all-to-all`` for it (it emitted each, in two builds of one program).
A test file of its own, so that the schedule of the larger files stays
as it was (``test_benchmark_contract.py``)."""

from types import SimpleNamespace

import pytest

import bench_testlib  # noqa: F401 - puts the repo on sys.path

V5E = {"ici_bits_per_s": 1600e9, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("scatter_op", ["collective-permute.3",
                                        "all-to-all.1"])
def test_the_roofline_counts_the_scatter_whatever_op_carries_it(
        scatter_op):
    from benchmark.layer_metrics import collective_roofline
    from benchmark.lib.trace_reduce import MODULES_LINE, OPS_LINE, Trace

    def device(i):
        t0 = 1e6 * i
        return {OPS_LINE: [(scatter_op, t0, 200e3),
                           ("all-reduce.2", t0 + 200e3, 100e3)],
                MODULES_LINE: [("jit_collective_Mesh_Shard(7)", t0, d)
                               for d in (310e3, 315e3, 320e3)]}

    run = SimpleNamespace(
        trace=Trace({i: device(i) for i in range(4)}, []),
        trace_devices=[0, 1, 2, 3], peaks=lambda: V5E,
        cell=SimpleNamespace(chips=4, sizes={"shard_block": [2048, 1024],
                                             "dtype": "bfloat16"}))
    # 18,874,368 B at 200 GB/s over the median program of 315 us
    assert collective_roofline.read(run) == pytest.approx(
        100 * 94.3718 / 315, 1e-4)
