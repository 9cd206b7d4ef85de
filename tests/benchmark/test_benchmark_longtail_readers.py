"""The three ``longtail_echo`` readers on made-up stamps, spans and
counters: what each computes, and that each reports nothing where its
source is missing (an older program, a run without spans)."""

import types

import pytest

import bench_testlib  # noqa: F401 - puts the repo on sys.path
from benchmark.layer_metrics import (short_behind_long_us, worker_held_share,
                                     worker_wait_p95_us)
from benchmark.lib.stats import percentile

MS = 1_000_000


def test_short_behind_long_is_the_difference_of_two_medians():
    # one long handler from 100 to 105 ms (shard 1 marks it); 30 shorts
    # arrive inside it and take 3 ms, 30 arrive clear of it and take 2 ms;
    # a short handler's stamp (shard 0) holds nobody
    handlers = {1: [(1, 100 * MS, 105 * MS)], 2: [(0, 300 * MS, 900 * MS)]}
    behind = [(10 + i, 100 * MS + i * 100_000,
               100 * MS + i * 100_000 + 3 * MS) for i in range(30)]
    clear = [(50 + i, 200 * MS + i * 10 * MS, 202 * MS + i * 10 * MS)
             for i in range(30)]
    run = types.SimpleNamespace(calls=behind + clear, handlers=handlers)
    assert short_behind_long_us.read(run) == pytest.approx(1000.0)
    # under 20 calls on a side, or no long handler at all: nothing
    few = types.SimpleNamespace(calls=behind[:19] + clear, handlers=handlers)
    assert short_behind_long_us.read(few) is None
    none = types.SimpleNamespace(calls=behind + clear, handlers={})
    assert short_behind_long_us.read(none) is None


def _span(side, sid, parent=0, **kw):
    base = dict(side=side, method="Echo", trace_id=7, span_id=sid,
                parent_span_id=parent, error_code=0, start_us=0,
                received_us=0, worker_us=0)
    return types.SimpleNamespace(**dict(base, **kw))


def test_worker_wait_joins_the_spans_and_takes_the_p95(monkeypatch):
    spans = []
    for i in range(400):
        spans.append(_span("client", 1000 + i, start_us=5000 + i))
        spans.append(_span("server", 5000 + i, parent=1000 + i,
                           received_us=6000 + i,
                           worker_us=6000 + i + (i % 100)))
    # none of these is a sample: another method, a failed call, a call
    # from before the window, a server span without its client
    spans += [_span("client", 1, method="SlowStep", start_us=5000),
              _span("server", 2, parent=1, method="SlowStep",
                    received_us=1, worker_us=90000),
              _span("client", 3, start_us=5000),
              _span("server", 4, parent=3, error_code=1008,
                    received_us=1, worker_us=90000),
              _span("client", 5, start_us=10),
              _span("server", 6, parent=5, received_us=1, worker_us=90000),
              _span("server", 8, parent=9, received_us=1, worker_us=90000)]
    monkeypatch.setattr(worker_wait_p95_us, "program_spans", lambda: spans)
    run = types.SimpleNamespace(_win_start_ns=1000 * 1000, window_s=1.0)
    want = percentile([i % 100 for i in range(400)], 0.95)
    assert worker_wait_p95_us.read(run) == pytest.approx(want)
    # under 200 samples no ten lie beyond the p95; spans of a program
    # that stamps no worker_us give no sample at all
    monkeypatch.setattr(worker_wait_p95_us, "program_spans",
                        lambda: spans[:300])
    assert worker_wait_p95_us.read(run) is None
    for s in spans:
        if s.side == "server":
            del s.worker_us
    monkeypatch.setattr(worker_wait_p95_us, "program_spans", lambda: spans)
    assert worker_wait_p95_us.read(run) is None


def test_worker_held_share_is_held_time_over_the_pool(monkeypatch):
    from brpc_tpu.transport import syscall_stats
    monkeypatch.setattr(syscall_stats, "snapshot",
                        lambda: {"fiber_workers": 13})
    run = types.SimpleNamespace(
        counters={"syscalls": {"usercode_held_us": 260_000}}, window_s=20.0)
    assert worker_held_share.read(run) == pytest.approx(0.1)
    older = types.SimpleNamespace(counters={"syscalls": {}}, window_s=20.0)
    assert worker_held_share.read(older) is None
    monkeypatch.setattr(syscall_stats, "snapshot", lambda: {})
    assert worker_held_share.read(run) is None
