"""The remote caller's entries in BENCHMARK.json, found by name and by
membership, never by position or count: one configuration, one cell,
the cell's name listed once under ``call_p50_us``, six per-layer entries
standing together after what was there, and the cell's name listed under
the two accepted readers that move ``call_p50_us`` and find their source
in the cell. A later cell appends after all of them and edits none of
this."""

import bench_testlib
from bench_testlib import ROOT, read_json

CELL = "remote_caller.step_2mb_d8_tpud"
THE_SIX = [
    ("remote_server_cpu_us_per_call", "us/call", "program_counter",
     "entry and dispatch"),
    ("remote_client_cpu_us_per_call", "us/call", "program_counter",
     "entry and dispatch"),
    ("staged_send_us", "us", "program_counter", "device lane"),
    ("staged_take_us", "us", "program_counter", "device lane"),
    ("remote_request_wire_us", "us", "program_span", "socket and framing"),
    ("remote_response_wire_us", "us", "program_counter",
     "socket and framing"),
]
APPENDED_TO = ("fabric_overhead_us", "call_p99_traced_us")


def test_the_cell_reports_the_median_and_the_rate_cells_are_pr29s():
    from test_benchmark_thread_roles import CELLS

    e2e = {e["name"]: e for e in bench_testlib.bench()["end_to_end"]}
    # a closed loop at depth 8: the median is depth / rate. The role
    # cells report the rate, among any cell listed there later
    assert set(CELLS) <= set(e2e["calls_per_s"]["workloads"])
    assert e2e["call_p50_us"]["workloads"].count(CELL) == 1
    assert CELL not in e2e["call_p99_us"]["workloads"]  # spread over 3%
    assert "workloads" not in e2e["setup_s"]
    assert (e2e["call_p50_us"]["bound"], e2e["calls_per_s"]["bound"]) == \
        (0.14, 0.13)


def test_the_six_new_entries_stand_together_after_what_was_there():
    per_layer = bench_testlib.bench()["per_layer"]
    names = [m["name"] for m in per_layer]
    first = names.index(THE_SIX[0][0])
    assert per_layer[first:first + len(THE_SIX)] == [
        {"name": name, "unit": unit, "better": "lower", "source": source,
         "layer": layer, "moves": "call_p50_us", "workloads": [CELL]}
        for name, unit, source, layer in THE_SIX]
    # after PR 35's four shares of the loop's awake time, the list's end
    # at the parent. Of the entries that were there, two list the new
    # cell, once each: both move the metric the cell reports
    assert names[first - 1] == "dispatcher_process_share"
    listing = [m for m in per_layer[:first] if CELL in m["workloads"]]
    assert sorted(m["name"] for m in listing) == sorted(APPENDED_TO)
    assert all(m["workloads"].count(CELL) == 1
               and m["moves"] == "call_p50_us" for m in listing)
    layers = {m["layer"] for m in per_layer[:first]}
    assert {layer for *_r, layer in THE_SIX} <= layers   # no new layer


def test_the_cell_and_its_configuration_as_the_issue_names_them():
    bench = bench_testlib.bench()
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    # the deployment runs on chip 0; the cell holds a four-chip host for
    # steadiness alone, and says so (PERF.md section 6)
    assert cell["chips"] == 4
    assert "steadiness" in cell["why"]
    assert cell["config"] == "remote_caller" \
        and cell["traffic"] == "step_2mb_d8_tpud"
    (conf,) = [c for c in bench["configs"] if c["name"] == "remote_caller"]
    assert conf["reduced"] == ["hosts"]
    assert len(conf["source"]) <= 200 and "rdma_performance" in conf["source"]
    body = read_json(ROOT, conf["file"])
    assert body["architecture"] is None and body["service"] == "remote_caller"
    assert body["chips"] == 1 and "steadiness" in body["host"]
    assert body["layout"]["lane"] == "staged-dcn"
    assert body["layout"]["servers"] == ["tpud://127.0.0.1:0#device=0"]
    assert {"client_off_chip", "placement", "no_silent_staging",
            "every_call_verified", "lane", "device_cells_balance",
            "step"} <= set(body["guarantees"])
    perf = read_json(ROOT, "benchmark", "configs", "tpu_performance.json")
    # the server side is tpu_performance's to the letter
    assert body["sizes"] == perf["sizes"]
    assert body["rehearse_sizes"] == perf["rehearse_sizes"]
    assert body["layout"]["channel_options"] == \
        perf["layout"]["channel_options"]


def test_the_traffic_is_step_2mb_d8s_with_the_client_outside():
    mine = read_json(ROOT, "benchmark", "traffic", "step_2mb_d8_tpud.json")
    its = read_json(ROOT, "benchmark", "traffic", "step_2mb_d8.json")
    assert mine["driver"] == "remote_closed_loop"
    for key in ("method", "depth", "pool"):
        assert mine[key] == its[key], key
    assert (mine["method"], mine["depth"], mine["pool"]) == ("Step", 8, 8)
