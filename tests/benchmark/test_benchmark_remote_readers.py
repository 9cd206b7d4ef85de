"""The six per-layer readers of ``remote_caller.step_2mb_d8_tpud`` on
recorded data: what each divides by what, which cells and spans it
takes, and that each reads nothing (never 0, never an error) where its
source is missing."""

import sys
import types

import bench_testlib  # noqa: F401 - puts the repo on sys.path
from benchmark.lib.loader import load_module

SERVICE = "benchmark.services.remote_caller"


class _Run:
    """As much of run.py's RunData as the readers touch."""

    def __init__(self, cells=None, cpu_s=0.0, verified=0, syscalls=None):
        self.counters = {"cells": cells or {}, "cpu_s": cpu_s,
                         "syscalls": {"tpud_batches_out": 1}
                         if syscalls is None else syscalls}
        self.verified_calls = verified
        self.cell = types.SimpleNamespace(traffic={"method": "Step"})


def _reader(name):
    return load_module("layer_metrics", name)


def _with_service(monkeypatch, last):
    monkeypatch.setitem(sys.modules, SERVICE,
                        types.SimpleNamespace(LAST=last))


def _cell(**kw):
    row = dict.fromkeys(("transfers", "completed", "failed", "bytes_out",
                         "bytes_in", "leaked_bytes", "recv_transfers",
                         "staged_fallbacks", "stage_us_sum", "wire_us_sum",
                         "ack_us_sum", "recv_us_sum"), 0)
    row.update(kw)
    return row


def test_cpu_a_call_of_each_process(monkeypatch):
    run = _Run(cpu_s=12.0, verified=4000)
    assert _reader("remote_server_cpu_us_per_call").read(run) == 3000.0
    assert _reader("remote_server_cpu_us_per_call").read(_Run()) is None
    client = _reader("remote_client_cpu_us_per_call")
    # no such deployment in the process, or no report: nothing
    monkeypatch.delitem(sys.modules, SERVICE, raising=False)
    assert client.read(run) is None
    _with_service(monkeypatch, {})
    assert client.read(run) is None
    # the child's CPU less its verifier's, over the parent's count
    _with_service(monkeypatch, {"window": {"cpu_s": 9.0,
                                           "verify_cpu_s": 1.0}})
    assert client.read(run) == 2000.0
    assert client.read(_Run(cpu_s=1.0, verified=0)) is None
    _with_service(monkeypatch, {"window": {"cpu_s": 1.0,
                                           "verify_cpu_s": 1.0}})
    assert client.read(run) is None         # never 0 by construction


def test_the_staged_lane_cells_alone_are_read():
    cells = {
        "tcp://127.0.0.1:5|staged-dcn": _cell(
            transfers=100, completed=100, recv_transfers=50,
            stage_us_sum=300000.0, wire_us_sum=100000.0, ack_us_sum=7.0,
            recv_us_sum=60000.0),
        "tcp://127.0.0.1:6|staged-dcn": _cell(
            transfers=100, completed=100, recv_transfers=150,
            stage_us_sum=100000.0, wire_us_sum=100000.0,
            recv_us_sum=140000.0),
        # another lane's cell is not this metric's
        "ici://127.0.0.1:7|local-d2d": _cell(
            transfers=1000, stage_us_sum=9e9, recv_transfers=1000,
            recv_us_sum=9e9)}
    run = _Run(cells)
    assert _reader("staged_send_us").read(run) == 3000.0    # no ack leg
    assert _reader("staged_take_us").read(run) == 1000.0
    # a reply's transit: the wire leg of the same cells, alone
    assert _reader("remote_response_wire_us").read(run) == 1000.0
    other = _Run({"ici://h:1|local-d2d": cells["ici://127.0.0.1:7|local-d2d"]})
    names = ("staged_send_us", "staged_take_us", "remote_response_wire_us")
    assert all(_reader(n).read(other) is None for n in names)
    idle = _Run({"tcp://h:1|staged-dcn": _cell()})
    assert all(_reader(n).read(idle) is None for n in names)
    # a program that tracks no staged batch (PR 37's parent: no tpud_*
    # counters, and its cells close stage and wire in one instant): the
    # transit is left out, and so is a wire leg of 0
    assert _reader("remote_response_wire_us").read(
        _Run(cells, syscalls={"recv": 5})) is None
    unstamped = _Run({"tcp://h:1|staged-dcn": _cell(
        transfers=10, completed=10, stage_us_sum=50.0)})
    assert _reader("remote_response_wire_us").read(unstamped) is None


def _client(i, **kw):
    d = {"trace_id": f"{7:016x}", "span_id": f"{i:016x}",
         "parent_span_id": f"{0:016x}", "method": "Step", "error_code": 0,
         "start_us": 1000 * i, "write_done_us": 1000 * i + 100,
         "first_byte_us": 1000 * i + 900, "end_us": 1000 * i + 950}
    d.update(kw)
    return d


def _server(i, **kw):
    d = {"trace_id": f"{7:016x}", "span_id": f"{1000 + i:016x}",
         "parent_span_id": f"{i:016x}", "side": "server", "method": "Step",
         "error_code": 0, "received_us": 1000 * i + 100 + 300 + i,
         "flushed_us": 1000 * i + 900 - 200 - i}
    d.update(kw)
    return d


def test_span_pairs_join_by_the_ids_and_drop_what_is_not_whole():
    request = _reader("remote_request_wire_us")
    clients = [_client(i) for i in range(1, 8)]
    servers = [_server(i) for i in range(1, 8)]
    servers[0]["error_code"] = 1008                 # a failed call
    servers[1]["flushed_us"] = 0                    # a missing stamp
    servers[2]["trace_id"] = f"{8:016x}"            # another trace
    clients[3]["first_byte_us"] = 0
    servers.append(_server(99))                     # no client half
    joined = request.pairs(clients, servers)
    assert [int(c["span_id"], 16) for c, _s in joined] == [5, 6, 7]
    assert all(s["parent_span_id"] == c["span_id"] for c, s in joined)


def test_request_wire_median_and_nothing_without_one_clock(
        monkeypatch):
    from brpc_tpu.rpc import span as span_mod

    request = _reader("remote_request_wire_us")
    n = 41
    spans = []
    for i in range(1, n + 1):
        s = _server(i)
        obj = span_mod.Span(
            trace_id=7, span_id=1000 + i, parent_span_id=i, side="server",
            method="Step", received_us=s["received_us"],
            flushed_us=s["flushed_us"])
        spans.append(obj)
    monkeypatch.setattr(span_mod.global_collector, "recent",
                        lambda _n=0: spans)
    last = {"clocks_agree": True,
            "report": {"spans": [_client(i) for i in range(1, n + 1)]}}
    _with_service(monkeypatch, last)
    # gaps are 300 + i: the median sits at i = 21
    assert request.read(_Run()) == 321
    # clocks that disagreed in set-up: nothing is compared
    _with_service(monkeypatch, dict(last, clocks_agree=False))
    assert request.read(_Run()) is None
    # too few pairs, or no client process that reported: nothing
    few = {"clocks_agree": True,
           "report": {"spans": [_client(i) for i in range(1, 11)]}}
    _with_service(monkeypatch, few)
    assert request.read(_Run()) is None
    _with_service(monkeypatch, {"clocks_agree": True})
    assert request.read(_Run()) is None
    monkeypatch.delitem(sys.modules, SERVICE, raising=False)
    assert request.read(_Run()) is None
