"""Traffic engine (ISSUE 11): production capture into .brpccap corpora
through both dispatch lanes, torn-corpus degradation, time-warped
open-loop replay fidelity, priority-tag wire round trip, postfork
per-file hygiene, the /capture control page, and capture-under-chaos
leak checks."""

import json
import os
import subprocess
import sys
import tempfile
import time

import pytest

from brpc_tpu import chaos
from brpc_tpu.chaos import Fault, FaultPlan
from brpc_tpu.butil.flags import flag, set_flag
from brpc_tpu.rpc import Channel, ChannelOptions, Server, ServerOptions, \
    Service
from brpc_tpu.rpc.controller import Controller
from brpc_tpu.traffic import capture
from brpc_tpu.traffic.corpus import (CorpusReader, CorpusWriter,
                                     corpus_files, merge_corpora,
                                     read_corpus)
from brpc_tpu.traffic.replay import (PaceSpec, merge_reports,
                                     parse_mix, run_open_loop,
                                     synthesize_records)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def recorder_off():
    """Every test leaves the process-wide recorder stopped."""
    yield
    capture.stop_capture()


def _serve(extra=None):
    hits = {}
    server = Server(ServerOptions(enable_builtin_services=False))
    svc = Service("T")

    @svc.method()
    async def Echo(cntl, request):
        k = f"prio{cntl.request_priority}"
        hits[k] = hits.get(k, 0) + 1
        hits["Echo"] = hits.get("Echo", 0) + 1
        return request

    @svc.method()
    def Boom(cntl, request):
        hits["Boom"] = hits.get("Boom", 0) + 1
        raise RuntimeError("handler exploded")

    if extra is not None:
        extra(svc)
    server.add_service(svc)
    ep = server.start("tcp://127.0.0.1:0")
    return server, f"tcp://{ep.host}:{ep.port}", hits


# ------------------------------------------------------------- corpus
class TestCorpus:
    def test_roundtrip_and_sidecar_index(self, tmp_path):
        recs = synthesize_records(
            40, parse_mix("8:0.5,256:0.5"), parse_mix("1:0.5,9:0.5"),
            qps=500.0, seed=3, service="T", method="Echo",
            timeout_ms=750)
        p = str(tmp_path / "c.brpccap")
        w = CorpusWriter(p)
        for r in recs:
            w.write(r)
        w.close()
        assert CorpusReader(p).records() == recs
        idx = CorpusReader(p).index()
        assert idx["source"] == "sidecar"
        assert idx["records"] == 40
        assert idx["methods"] == {"T.Echo": 40}
        assert set(idx["priorities"]) == {"1", "9"}

    def test_torn_tail_loses_one_record_and_index_rescans(
            self, tmp_path):
        recs = synthesize_records(20, [(64, 1.0)], [(0, 1.0)],
                                  qps=500.0, seed=5)
        p = str(tmp_path / "torn.brpccap")
        w = CorpusWriter(p)
        for r in recs:
            w.write(r)
        w.close()
        raw = open(p, "rb").read()
        open(p, "wb").write(raw[:-7])     # torn final write
        r = CorpusReader(p)
        assert len(r.records()) == 19
        # the sidecar no longer matches the file: index must fall back
        # to a scan instead of reporting 20 records that aren't there
        idx = CorpusReader(p).index()
        assert idx["source"] == "scan" and idx["records"] == 19

    def test_mid_file_corruption_resyncs(self, tmp_path):
        recs = synthesize_records(10, [(32, 1.0)], [(0, 1.0)],
                                  qps=500.0, seed=6)
        p = str(tmp_path / "corrupt.brpccap")
        w = CorpusWriter(p)
        for r in recs:
            w.write(r)
        w.close()
        raw = bytearray(open(p, "rb").read())
        raw[len(raw) // 2] ^= 0xFF        # flip a byte mid-file
        open(p, "wb").write(bytes(raw))
        got = CorpusReader(p).records()
        # exactly one record is lost to the corruption; the reader
        # resyncs to the next magic and keeps going
        assert len(got) == 9

    def test_bad_index_sidecar_is_ignored(self, tmp_path):
        recs = synthesize_records(5, [(16, 1.0)], [(0, 1.0)],
                                  qps=100.0, seed=7)
        p = str(tmp_path / "badidx.brpccap")
        w = CorpusWriter(p)
        for r in recs:
            w.write(r)
        w.close()
        open(p + ".idx", "w").write("{not json")
        idx = CorpusReader(p).index()
        assert idx["source"] == "scan" and idx["records"] == 5

    def test_merge_corpora_orders_by_arrival(self, tmp_path):
        a = synthesize_records(6, [(8, 1.0)], [(1, 1.0)], qps=100.0,
                               seed=1)
        b = synthesize_records(6, [(8, 1.0)], [(2, 1.0)], qps=130.0,
                               seed=2)
        for name, rs in (("a", a), ("b", b)):
            w = CorpusWriter(str(tmp_path / f"{name}.brpccap"))
            for r in rs:
                w.write(r)
            w.close()
        out = str(tmp_path / "merged.brpccap")
        idx = merge_corpora([str(tmp_path / "a.brpccap"),
                             str(tmp_path / "b.brpccap")], out)
        assert idx["records"] == 12
        stamps = [r.arrival_mono_ns for r in CorpusReader(out)]
        assert stamps == sorted(stamps)


# ------------------------------------------------------------ capture
class TestCapture:
    def test_both_lanes_record_with_status_and_latency(
            self, tmp_path, recorder_off):
        server, addr, hits = _serve()
        try:
            capture.start_capture(dir=str(tmp_path), max_per_second=0)
            # classic lane: timeout-bearing metas defer to it by
            # construction (the native walker's judge-or-defer)
            ch = Channel(addr, ChannelOptions(timeout_ms=2000))
            for i in range(6):
                assert not ch.call_sync("T", "Echo",
                                        b"c%d" % i).failed()
            # turbo lane: timeout-less + priority-less requests ride
            # the scan lane, which must record in-line
            ch2 = Channel(addr, ChannelOptions(timeout_ms=None))
            for i in range(4):
                assert not ch2.call_sync("T", "Echo",
                                         b"t%d" % i).failed()
            # failed handler: the record carries the verdict
            c = ch.call_sync("T", "Boom", b"x")
            assert c.failed()
            snap = capture.stop_capture()
            assert snap["pending"] == 0
            recs = read_corpus(str(tmp_path))
            assert len(recs) == 11
            by_status = [r for r in recs if r.status != 0]
            assert len(by_status) == 1 and \
                by_status[0].method_key == "T.Boom"
            ok = [r for r in recs if r.status == 0]
            assert all(r.latency_us > 0 for r in recs)
            assert {r.payload for r in ok} == \
                {b"c%d" % i for i in range(6)} \
                | {b"t%d" % i for i in range(4)}
            # classic-lane records carry the wire deadline budget
            classic = [r for r in recs if r.payload.startswith(b"c")]
            assert all(r.timeout_ms == 2000 for r in classic)
            ch.close()
            ch2.close()
        finally:
            server.stop()
            server.join(2)

    def test_priority_tag_wire_roundtrip_and_capture(
            self, tmp_path, recorder_off):
        server, addr, hits = _serve()
        try:
            capture.start_capture(dir=str(tmp_path), max_per_second=0)
            ch = Channel(addr, ChannelOptions(timeout_ms=2000))
            cntl = Controller()
            cntl.request_priority = 7
            cntl.request_attachment.append(b"ATT")
            assert not ch.call_sync("T", "Echo", b"p", cntl=cntl).failed()
            # reuse resets the tag: the next call is default-absent
            assert not ch.call_sync("T", "Echo", b"q",
                                    cntl=cntl).failed()
            capture.stop_capture()
            assert hits["prio7"] == 1 and hits["prio0"] == 1
            recs = sorted(read_corpus(str(tmp_path)),
                          key=lambda r: r.arrival_mono_ns)
            assert [r.priority for r in recs] == [7, 0]
            assert recs[0].attachment == b"ATT"
            ch.close()
        finally:
            server.stop()
            server.join(2)

    def test_per_method_sampling_rates(self, tmp_path, recorder_off):
        server, addr, hits = _serve()
        try:
            capture.start_capture(
                dir=str(tmp_path), max_per_second=0,
                method_rates={"T.Echo": 0.0}, default_rate=1.0)
            ch = Channel(addr, ChannelOptions(timeout_ms=2000))
            for i in range(5):
                assert not ch.call_sync("T", "Echo", b"x").failed()
            ch.call_sync("T", "Boom", b"y")
            capture.stop_capture()
            recs = read_corpus(str(tmp_path))
            # Echo rate 0 = never sampled; Boom rides the default rate
            assert [r.method_key for r in recs] == ["T.Boom"]
            ch.close()
        finally:
            server.stop()
            server.join(2)

    def test_rotation_and_disk_budget(self, tmp_path, recorder_off):
        rec = capture.global_recorder()
        cfg = capture.CaptureConfig(
            dir=str(tmp_path), default_rate=1.0, max_per_second=0,
            rotate_bytes=4096, disk_budget_bytes=12288)
        rec.start(cfg)
        payload = b"R" * 512
        for i in range(64):
            r = rec.sample_request("T.Rot", "T", "Rot", payload, None,
                                   time.monotonic_ns(), 0.0, i, 0)
            rec.record_complete(r, 0, 10.0)
        capture.stop_capture()
        assert rec.rotations >= 2, rec.rotations
        assert rec.deleted_files >= 1, rec.deleted_files
        total = sum(os.path.getsize(p)
                    for p in corpus_files(str(tmp_path)))
        # budget enforcement runs at rotation: bounded by budget + one
        # active file's rotate size
        assert total <= 12288 + 4096 + 1024

    def test_capture_under_chaos_leaks_nothing(self, tmp_path,
                                               recorder_off):
        """Seeded delay/corrupt faults while capturing: every call
        reaches a verdict, the recorder's queue drains to zero, and
        the corpus stays readable (no torn records from the chaos)."""
        server, addr, hits = _serve()
        try:
            capture.start_capture(dir=str(tmp_path), max_per_second=0)
            plan = (FaultPlan(seed=9)
                    .at(addr, 1, Fault("corrupt", at_byte=6))
                    .at(addr, 2, Fault("delay", at_byte=4,
                                       delay_ms=120)))
            chaos.install(plan)
            try:
                outcomes = []
                for i in range(8):
                    ch = Channel(addr, ChannelOptions(
                        timeout_ms=400, max_retry=1,
                        share_connections=False))
                    c = ch.call_sync("T", "Echo", b"z%d" % i)
                    outcomes.append(c.error_code)
                    ch.close()
            finally:
                chaos.uninstall()
            snap = capture.stop_capture()
            assert snap["pending"] == 0
            assert snap["dropped_queue"] == 0
            recs = read_corpus(str(tmp_path))
            r = CorpusReader(corpus_files(str(tmp_path))[0])
            list(r)
            assert r.bad_records == 0 and r.skipped_bytes == 0
            # the server saw at most the calls that got through; every
            # record it captured completed with a verdict
            assert len(recs) <= len(outcomes) + 2   # retries add calls
        finally:
            server.stop()
            server.join(2)

    def test_postfork_child_records_to_own_file(self, tmp_path,
                                                recorder_off):
        capture.start_capture(dir=str(tmp_path), max_per_second=0)
        rec = capture.global_recorder()
        r = rec.sample_request("T.P", "T", "P", b"parent", None,
                               time.monotonic_ns(), 0.0, 1, 0)
        rec.record_complete(r, 0, 5.0)
        parent_pid = os.getpid()
        rd, wr = os.pipe()
        pid = os.fork()
        if pid == 0:
            try:
                crec = capture.global_recorder()
                msg = "OK"
                if crec._q:
                    msg = "child inherited parent queue"
                elif not crec.capturing():
                    msg = "child lost active capture state"
                else:
                    x = crec.sample_request(
                        "T.P", "T", "P", b"child", None,
                        time.monotonic_ns(), 0.0, 2, 0)
                    crec.record_complete(x, 0, 5.0)
                    crec.stop()
                    names = [os.path.basename(p)
                             for p in crec.corpus_paths()]
                    if not any(f"capture-{os.getpid()}-" in n
                               for n in names):
                        msg = f"no child-pid file in {names}"
                os.write(wr, msg.encode())
            except BaseException as e:  # noqa: BLE001
                os.write(wr, f"EXC:{e}".encode())
            finally:
                os._exit(0)
        os.close(wr)
        out = b""
        while True:
            b = os.read(rd, 4096)
            if not b:
                break
            out += b
        os.close(rd)
        os.waitpid(pid, 0)
        assert out == b"OK", out
        capture.stop_capture()
        # the parent's record landed in the parent-pid file, untouched
        mine = [p for p in corpus_files(str(tmp_path))
                if f"capture-{parent_pid}-" in p]
        assert mine and any(r.payload == b"parent"
                            for r in CorpusReader(mine[0]))

    def test_legacy_rpc_dump_flag_alias(self, tmp_path, recorder_off):
        server, addr, hits = _serve()
        old = flag("rpc_dump_dir")
        try:
            set_flag("rpc_dump_dir", str(tmp_path))
            ch = Channel(addr, ChannelOptions(timeout_ms=2000))
            for i in range(3):
                assert not ch.call_sync("T", "Echo", b"l%d" % i).failed()
            rec = capture.global_recorder()
            assert rec.capturing() and rec.snapshot()["legacy"]
            # legacy budget alias applies when capture_max_per_second
            # keeps its (nonzero) default
            assert rec._cfg.max_per_second in (
                flag("rpc_dump_max_requests_per_second"),
                flag("capture_max_per_second"))
            set_flag("rpc_dump_dir", "")
            ch.call_sync("T", "Echo", b"post")   # notices the clear
            assert not rec.capturing()
            # load_dump reads the corpus through the old API. The
            # clear was noticed on the dispatch path, which stops the
            # recorder without waiting (flush_s=0): the writer thread
            # drains the queue on its own, so give it its moment
            from brpc_tpu.rpc.rpc_dump import load_dump
            deadline = time.monotonic() + 5.0
            while True:
                got = []
                for p in corpus_files(str(tmp_path)):
                    got.extend(load_dump(p))
                payloads = {g[2] for g in got}
                if {b"l0", b"l1", b"l2"} <= payloads \
                        or time.monotonic() > deadline:
                    break
                time.sleep(0.02)
            assert {b"l0", b"l1", b"l2"} <= payloads
            assert all(g[0] == "T" and g[1] == "Echo" for g in got)
            ch.close()
        finally:
            set_flag("rpc_dump_dir", old)
            server.stop()
            server.join(2)


# ------------------------------------------------------------- replay
class TestReplay:
    def test_warped_replay_reproduces_counts_and_profile(
            self, recorder_off):
        server, addr, hits = _serve()
        try:
            def attempt(seed):
                # per-attempt hit deltas so a retry's accounting does
                # not inherit the first run's counts
                base1 = hits.get("prio1", 0)
                base9 = hits.get("prio9", 0)
                recs = synthesize_records(
                    80, parse_mix("8:0.7,512:0.3"),
                    parse_mix("1:0.7,9:0.3"), qps=400.0,
                    mode="poisson", seed=seed, service="T",
                    method="Echo", timeout_ms=1500)
                rep = run_open_loop(
                    recs, addr, PaceSpec("recorded", warp=2.0),
                    conns=3)
                assert rep["ok"] == 80 and rep["fail"] == 0
                # 80 records at ~400/s recorded, 2x warp -> ~0.1s
                assert rep["elapsed_s"] <= 0.35, rep["elapsed_s"]
                # priorities preserved end to end
                d1 = hits["prio1"] - base1
                d9 = hits["prio9"] - base9
                assert d1 + d9 == 80
                per_prio = rep["per_priority"]
                assert per_prio["1"]["ok"] == d1
                assert per_prio["9"]["ok"] == d9
                return rep

            # cumulative retry ladder (the overhead gates' pattern):
            # inter-send gaps here are ~2.5ms, so a busy box's
            # scheduler jitter alone can shave a point or two off
            # fidelity (observed 88.75 under parallel test load). A
            # NEAR miss (>=85) earns the next seed; a real pacing
            # regression lands far below 85 and fails on the first
            # attempt. loadavg is NOT part of the near-miss gate — it
            # is a lagging 1-minute average, and a parallel-suite
            # burst can finish before it crosses any threshold (the
            # old `load > 0.5` conjunction was itself the flake).
            def near_miss(r):
                assert r["fidelity_pct"] >= 85, r["fidelity_pct"]

            def fidelity_floor():
                # load-aware window, PINNED: 90 standalone; a visibly
                # loaded box earns exactly two points, never more —
                # the 88 floor stays above every regression mode we
                # have seen (they land below 85)
                load = os.getloadavg()[0] / (os.cpu_count() or 1)
                return 88.0 if load > 0.5 else 90.0

            rep = attempt(13)
            for seed in (14, 15, 16, 17):
                if rep["fidelity_pct"] >= 90:
                    break
                near_miss(rep)
                rep = attempt(seed)
            if rep["fidelity_pct"] < fidelity_floor() \
                    and not os.environ.get("_BRPC_TPU_WARP_RETRY"):
                # last resort after the in-test seeds: ONE subprocess
                # retry in a fresh interpreter (the flake passes
                # standalone) — the guard env stops recursion, and the
                # retry applies the same pinned load-aware floor
                near_miss(rep)
                import subprocess
                import sys
                env = dict(os.environ, _BRPC_TPU_WARP_RETRY="1")
                r = subprocess.run(
                    [sys.executable, "-m", "pytest", "-q", "-x",
                     "-p", "no:cacheprovider",
                     __file__ + "::TestReplay::"
                     "test_warped_replay_reproduces_counts_and_profile"],
                    capture_output=True, text=True, timeout=240,
                    env=env)
                assert r.returncode == 0, r.stdout + r.stderr
                return
            assert rep["fidelity_pct"] >= fidelity_floor(), \
                (rep["fidelity_pct"], os.getloadavg()[0])
        finally:
            server.stop()
            server.join(2)

    def test_qps_and_poisson_pacing(self, recorder_off):
        server, addr, hits = _serve()
        try:
            recs = synthesize_records(40, [(16, 1.0)], [(0, 1.0)],
                                      qps=100.0, seed=2, service="T",
                                      method="Echo")
            for mode in ("qps", "poisson"):
                rep = run_open_loop(
                    recs, addr, PaceSpec(mode, qps=400.0, seed=4),
                    conns=2)
                assert rep["ok"] == 40, rep
                assert rep["fidelity_pct"] >= 85, (mode, rep)
        finally:
            server.stop()
            server.join(2)

    def test_merge_reports_pools_classes(self):
        recs = synthesize_records(10, [(8, 1.0)], [(2, 1.0)],
                                  qps=100.0, seed=3)
        # two synthetic worker reports via the real engine shape
        r = {"records": 10, "issued": 10, "ok": 9, "fail": 1,
             "elapsed_s": 1.0, "behind_ms_max": 2.0,
             "bucket_width_s": 0.1, "sched_hist": [5, 5],
             "issue_hist": [5, 5], "pace": {"mode": "qps"},
             "classes": {"T.Echo|p2": {
                 "ok": 9, "fail": 1, "error_codes": {"1008": 1},
                 "lat_ms_samples": [1.0, 2.0, 3.0]}}}
        m = merge_reports([r, json.loads(json.dumps(r))])
        assert m["ok"] == 18 and m["fail"] == 2
        cls = m["classes"]["T.Echo|p2"]
        assert cls["error_codes"]["1008"] == 2
        assert cls["p50_ms"] is not None
        assert m["fidelity_pct"] == 100.0
        assert m["per_priority"]["2"]["ok"] == 18

    def test_deadline_rederivation_from_recorded_budget(
            self, recorder_off):
        """A record with a tiny recorded budget replays with that
        budget: against a slow handler it times out, while records
        without budgets ride the default."""
        def extra(svc):
            @svc.method()
            async def Slow(cntl, request):
                from brpc_tpu import fiber
                await fiber.sleep(0.25)
                return request

        server, addr, hits = _serve(extra)
        try:
            from brpc_tpu.traffic.corpus import CapturedRequest
            recs = [CapturedRequest(
                "T.Slow", "T", "Slow", b"s", b"", 1000, 0, 80.0, 0, 1,
                0, 0.0)]
            rep = run_open_loop(recs, addr, PaceSpec("recorded"),
                                conns=1)
            assert rep["fail"] == 1 and rep["ok"] == 0
            codes = rep["classes"]["T.Slow|p0"]["error_codes"]
            from brpc_tpu.rpc import errno_codes as berr
            assert str(berr.ERPCTIMEDOUT) in codes, codes
        finally:
            server.stop()
            server.join(2)


# -------------------------------------------------------- /capture page
class TestCapturePage:
    def test_http_start_stop_download_e2e(self, tmp_path, recorder_off):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        from spawn_util import http_get_local
        server = Server(ServerOptions(enable_builtin_services=True))
        svc = Service("T")

        @svc.method()
        async def Echo(cntl, request):
            return request

        server.add_service(svc)
        ep = server.start("tcp://127.0.0.1:0")
        try:
            st, body = http_get_local(
                ep.port, f"/capture?action=start&dir={tmp_path}"
                         "&max_per_second=0")
            assert st == 200, body
            assert json.loads(body)["active"]
            ch = Channel(f"tcp://{ep.host}:{ep.port}",
                         ChannelOptions(timeout_ms=2000))
            for i in range(7):
                assert not ch.call_sync("T", "Echo",
                                        b"h%d" % i).failed()
            st, body = http_get_local(ep.port, "/capture?action=stop")
            assert st == 200
            snap = json.loads(body)
            assert not snap["active"] and snap["written"] == 7
            st, body = http_get_local(ep.port, "/capture")
            assert st == 200 and json.loads(body)["written"] == 7
            st, body = http_get_local(ep.port,
                                      "/capture?action=download")
            assert st == 200 and body[:4] == b"RIO1"
            dl = str(tmp_path / "dl.brpccap")
            open(dl, "wb").write(body)
            assert len(CorpusReader(dl).records()) == 7
            st, _ = http_get_local(ep.port, "/capture?action=bogus")
            assert st == 400
            # builtin RPC twin serves the same payload
            c = ch.call_sync("builtin", "capture", b"")
            assert not c.failed()
            assert json.loads(
                c.response_payload.to_bytes())["written"] == 7
            ch.close()
        finally:
            server.stop()
            server.join(2)


# --------------------------------------------------------------- tools
class TestTools:
    def test_rpc_view_summary_on_corpus(self, tmp_path):
        recs = synthesize_records(
            30, parse_mix("16:0.5,1024:0.5"), parse_mix("1:0.5,9:0.5"),
            qps=300.0, seed=21, service="V", method="M")
        p = str(tmp_path / "v.brpccap")
        w = CorpusWriter(p)
        for r in recs:
            w.write(r)
        w.close()
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "rpc_view.py"),
             p, "--summary", "--json"],
            capture_output=True, text=True, cwd=REPO, timeout=60)
        assert r.returncode == 0, r.stderr
        s = json.loads(r.stdout.strip().splitlines()[-1])
        assert s["records"] == 30
        assert s["methods"] == {"V.M": 30}
        assert set(s["priorities"]) == {"1", "9"}
        assert s["interarrival"]["avg_qps"] > 100
        # priority filter narrows
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "rpc_view.py"),
             p, "--summary", "--json", "--priority", "9"],
            capture_output=True, text=True, cwd=REPO, timeout=60)
        s = json.loads(r.stdout.strip().splitlines()[-1])
        assert set(s["priorities"]) == {"9"}

    def test_rpc_press_synthetic_mixed_press(self, recorder_off):
        server, addr, hits = _serve()
        try:
            r = subprocess.run(
                [sys.executable,
                 os.path.join(REPO, "tools", "rpc_press.py"), addr,
                 "T", "Echo", "--qps", "300", "--duration", "0.8",
                 "--size-mix", "16:0.7,512:0.3",
                 "--priority-mix", "1:0.5,9:0.5", "--json"],
                capture_output=True, text=True, cwd=REPO, timeout=90)
            assert r.returncode == 0, r.stderr[-500:]
            rep = json.loads(r.stdout.strip().splitlines()[-1])
            assert rep["ok"] == rep["records"] > 0
            assert rep["fail"] == 0
            assert set(rep["per_priority"]) == {"1", "9"}
            assert hits["prio1"] + hits["prio9"] == rep["ok"]
        finally:
            server.stop()
            server.join(2)

    def test_rpc_replay_cli_time_warp(self, tmp_path, recorder_off):
        server, addr, hits = _serve()
        try:
            recs = synthesize_records(
                40, [(32, 1.0)], [(3, 1.0)], qps=100.0, seed=17,
                service="T", method="Echo")
            p = str(tmp_path / "cli.brpccap")
            w = CorpusWriter(p)
            for r in recs:
                w.write(r)
            w.close()
            r = subprocess.run(
                [sys.executable,
                 os.path.join(REPO, "tools", "rpc_replay.py"), p, addr,
                 "--warp", "4", "--json"],
                capture_output=True, text=True, cwd=REPO, timeout=90)
            assert r.returncode == 0, r.stderr[-500:] + r.stdout[-300:]
            rep = json.loads(r.stdout.strip().splitlines()[-1])
            assert rep["ok"] == 40 and rep["fail"] == 0
            # 40 records spanning ~0.4s at 4x warp -> ~0.1s
            assert rep["elapsed_s"] <= 0.4, rep["elapsed_s"]
            assert hits["prio3"] == 40
        finally:
            server.stop()
            server.join(2)
