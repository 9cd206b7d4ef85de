"""graftlint's own tests: every rule must detect its seeded fixture
violation (tests/graftlint_fixtures/), the clean fixture must produce
zero findings (the false-positive budget is 0), waivers must suppress
only with a reason, and the repo itself must lint clean — the same
gate tools/preflight.py --gate enforces.

The fixtures are real checked-in modules so a rule regression shows up
as a diffable test failure, not a silent loss of coverage.
"""

import json
import os
import subprocess
import sys

from brpc_tpu.analysis.core import Analyzer

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO_ROOT, "tests", "graftlint_fixtures")


def _lint(*names):
    paths = [os.path.join(FIXTURES, n) for n in names]
    return Analyzer().run(paths)


class TestSeededViolations:
    def test_fiber_blocking_direct_and_via_helper(self):
        active, _ = _lint("bad_fiber_blocking.py")
        rules = [f.rule for f in active]
        assert rules == ["fiber-blocking"] * 2, active
        msgs = " | ".join(f.message for f in active)
        assert "time.sleep" in msgs
        # context propagation: the helper's block is attributed to the
        # fiber root that reaches it
        assert "reached via" in msgs

    def test_iobuf_mutation_after_handoff(self):
        active, _ = _lint("bad_iobuf_aliasing.py")
        assert [f.rule for f in active] == ["iobuf-aliasing"] * 2, active
        assert all("handed off via 'write'" in f.message
                   for f in active)
        # the loop-carried case: iteration N's handoff poisons the
        # append at the top of iteration N+1
        src = open(os.path.join(
            FIXTURES, "bad_iobuf_aliasing.py")).read().splitlines()
        assert any("iteration N's write" in src[f.line - 1]
                   for f in active), [f.format() for f in active]

    def test_fiber_blocking_helper_defined_below_caller(self):
        # forward call edge: the fixture's helper is defined BELOW the
        # fiber root; the 'reached via' finding (asserted above) only
        # exists if call resolution sees the complete def table
        src = open(os.path.join(
            FIXTURES, "bad_fiber_blocking.py")).read()
        assert src.index("async def fiber_entry") \
            < src.index("def _helper_that_blocks")

    def test_fast_lane_without_defer_exit(self):
        active, _ = _lint("bad_judge_defer.py")
        assert [f.rule for f in active] == ["judge-defer"] * 2, active
        msgs = " | ".join(f.message for f in active)
        assert "turbo_dispatch" in msgs and "defer" in msgs
        # a defer exit inside a NESTED def must not satisfy the
        # enclosing fast lane's contract
        assert "turbo_nested_decoy" in msgs

    def test_lock_order_cycle(self):
        # v2: the with-nesting AB/BA cycle is now reported by the lock
        # model's whole-program lock-cycle rule (lock-order's successor)
        active, _ = _lint("bad_lock_order.py")
        assert [f.rule for f in active] == ["lock-cycle"], active
        assert "_io_lock" in active[0].message
        assert "_state_lock" in active[0].message

    def test_incomplete_registered_protocol(self):
        # rule level: every deficiency is individually detected (the
        # analyzer dedups same-location findings to one, asserted below)
        from brpc_tpu.analysis.core import Context, iter_source_files
        from brpc_tpu.analysis.rules.registry_complete import (
            RegistryCompleteRule,
        )
        files = iter_source_files(
            [os.path.join(FIXTURES, "bad_registry.py")])
        findings = list(RegistryCompleteRule().check(
            files[0], Context(files)))
        assert len(findings) == 3, [f.format() for f in findings]
        msgs = " | ".join(f.message for f in findings)
        assert "process" in msgs          # no dispatch surface
        assert "pack/" in msgs            # no client encoding surface
        assert "maps errors to nothing" in msgs
        # parse() IS concrete on the fixture: must not be flagged
        assert "no concrete parse" not in msgs
        # analyzer level: the call site surfaces as one active finding
        active, _ = _lint("bad_registry.py")
        assert [f.rule for f in active] == ["registry-complete"], active

    def test_incomplete_limiter_in_spec_parser(self):
        # limiter clause: new_limiter constructing a class whose
        # on_responded/max_concurrency are still the base's raising
        # stubs must fire (rule level shows each missing member)
        from brpc_tpu.analysis.core import Context, iter_source_files
        from brpc_tpu.analysis.rules.registry_complete import (
            RegistryCompleteRule,
        )
        files = iter_source_files(
            [os.path.join(FIXTURES, "bad_limiter_registry.py")])
        findings = list(RegistryCompleteRule().check(
            files[0], Context(files)))
        msgs = " | ".join(f.message for f in findings)
        assert len(findings) == 2, [f.format() for f in findings]
        assert "on_responded" in msgs and "max_concurrency" in msgs
        # on_requested IS concrete on the fixture: must not be flagged
        assert "no concrete on_requested" not in msgs
        active, _ = _lint("bad_limiter_registry.py")
        assert [f.rule for f in active] == ["registry-complete"], active

    def test_complete_limiter_parser_is_clean(self):
        active, _ = _lint("good_limiter_registry.py")
        assert active == [], [f.format() for f in active]

    def test_real_limiter_parser_passes_and_mutation_fires(self, tmp_path):
        """The real rpc/concurrency_limiter.py must lint clean — and a
        mutation replacing AutoLimiter.on_responded with the raising
        stub must fire, pinning that the clause actually reads the real
        parser's classes (not just the fixture's)."""
        real = os.path.join(REPO_ROOT, "brpc_tpu", "rpc",
                            "concurrency_limiter.py")
        active, _ = Analyzer().run([real])
        assert [f for f in active if f.rule == "registry-complete"] \
            == [], [f.format() for f in active]
        src = open(real).read()
        # ConstantLimiter's whole on_responded (anchored by the
        # @property that follows it, so the AutoLimiter method with the
        # same first lines cannot match)
        needle = ("    def on_responded(self, latency_us, failed,"
                  " cost: float = 1.0):\n"
                  "        with self._lock:\n"
                  "            self._inflight = max(0.0,"
                  " self._inflight - cost)\n"
                  "\n"
                  "    @property\n")
        assert needle in src, "ConstantLimiter.on_responded shape moved"
        mutated = src.replace(
            needle,
            "    def on_responded(self, latency_us, failed,"
            " cost: float = 1.0):\n"
            "        raise NotImplementedError\n"
            "\n"
            "    @property\n", 1)
        mut = tmp_path / "concurrency_limiter.py"
        mut.write_text(mutated)
        active, _ = Analyzer().run([str(mut)])
        hits = [f for f in active if f.rule == "registry-complete"
                and "ConstantLimiter" in f.message]
        assert hits, [f.format() for f in active]

    def test_cxx_walker_unbounded_int32_and_dropped_read(self):
        # the fixture's comments deliberately name INT32_MAX /
        # 0x7FFFFFFF and the dropped local: a bound or use that exists
        # only in a comment must not satisfy the rule
        active, _ = _lint("cxx")
        assert [f.rule for f in active] == ["judge-defer"] * 3, active
        msgs = " | ".join(f.message for f in active)
        assert "StreamSettings.credits" in msgs and "INT32_MAX" in msgs
        assert "StreamSettings.need_feedback" in msgs \
            and "dropped" in msgs
        # deadline propagation: a lane reading timeout_ms without
        # enforcing or deferring fires (the read guard's own
        # `return false` — and the one in the fixture's comment —
        # must not satisfy the check)
        assert "RpcRequestMeta.timeout_ms" in msgs \
            and "enforcing or deferring" in msgs
        # the correctly bounded walk_meta attachment_size stays silent
        assert "attachment_size" not in msgs

    def test_cxx_rule_survives_timeout_gate_removal_in_real_fastcore(
            self, tmp_path):
        """Mutation pin for the deadline clause: strip the defer gate
        off walk_request_meta's timeout_ms case in the real fastcore.cc
        (keeping its comments, which mention defer and the classic
        lane) — the rule must fire, so the lane can never silently go
        back to serving requests the classic lane sheds."""
        src = open(os.path.join(
            REPO_ROOT, "brpc_tpu", "native", "src", "fastcore.cc")).read()
        gate = [ln for ln in src.splitlines()
                if "m->defer_timeout && m->timeout_ms != 0" in ln]
        assert len(gate) == 1, gate
        mutated = src.replace(gate[0] + "\n", "")
        native = tmp_path / "native"
        native.mkdir()
        (native / "fastcore.cc").write_text(mutated)
        proto_dir = tmp_path / "protocol" / "proto"
        proto_dir.mkdir(parents=True)
        proto_src = os.path.join(REPO_ROOT, "brpc_tpu", "protocol",
                                 "proto", "tpu_rpc_meta.proto")
        (proto_dir / "tpu_rpc_meta.proto").write_text(
            open(proto_src).read())
        active, _ = Analyzer().run([str(tmp_path)])
        msgs = " | ".join(f.message for f in active)
        assert "RpcRequestMeta.timeout_ms" in msgs, msgs

    def test_cxx_rule_survives_guard_removal_in_real_fastcore(self, tmp_path):
        """Mutation pin: strip the actual credits guard out of the real
        fastcore.cc (keeping its explanatory comments, which mention
        INT32_MAX) — the rule must fire, i.e. the static gate really
        does block reintroduction of ADVICE finding 1."""
        src = open(os.path.join(
            REPO_ROOT, "brpc_tpu", "native", "src", "fastcore.cc")).read()
        guard = [ln for ln in src.splitlines()
                 if "s_credits > 0x7FFFFFFFull" in ln]
        assert len(guard) == 1, guard
        mutated = src.replace(guard[0] + "\n", "")
        native = tmp_path / "native"
        native.mkdir()
        (native / "fastcore.cc").write_text(mutated)
        proto_dir = tmp_path / "protocol" / "proto"
        proto_dir.mkdir(parents=True)
        proto_src = os.path.join(REPO_ROOT, "brpc_tpu", "protocol",
                                 "proto", "tpu_rpc_meta.proto")
        (proto_dir / "tpu_rpc_meta.proto").write_text(
            open(proto_src).read())
        active, _ = Analyzer().run([str(tmp_path)])
        msgs = " | ".join(f.message for f in active)
        assert any(f.rule == "judge-defer" for f in active), active
        assert "StreamSettings.credits" in msgs, msgs


class TestSpanFinish:
    def test_leaky_exits_detected(self):
        active, _ = _lint("bad_span_finish.py")
        assert [f.rule for f in active] == ["span-finish"] * 3, \
            [f.format() for f in active]
        msgs = " | ".join(f.message for f in active)
        assert "returns" in msgs and "raises" in msgs
        # the violations anchor on the leaky exits, not the start calls
        src = open(os.path.join(
            FIXTURES, "bad_span_finish.py")).read().splitlines()
        for f in active:
            assert "return" in src[f.line - 1] or "raise" in src[f.line - 1]
        # the loop case: a span started per iteration leaks even though
        # an earlier (different) span in the same function WAS finished
        assert any("len(items)" in src[f.line - 1] for f in active), \
            [f.format() for f in active]

    def test_finishing_patterns_accepted(self):
        # the fixture pair's clean half: direct finish on early exits,
        # try/finally coverage, the deferred completion-hook idiom, and
        # the branch-gated null-span alias — zero findings
        active, waived = _lint("good_span_finish.py")
        assert active == [] and waived == [], \
            [f.format() for f in active + waived]

    def test_mutation_deleting_finish_fires_on_real_dispatch(self):
        """Mutation pin: delete the shed path's finish_span from the
        real server_dispatch.py — the rule must fire, so a future edit
        can never silently drop shed spans from /rpcz again."""
        from brpc_tpu.analysis.core import Context, SourceFile
        from brpc_tpu.analysis.rules.span_finish import SpanFinishRule
        path = os.path.join(REPO_ROOT, "brpc_tpu", "rpc",
                            "server_dispatch.py")
        src = open(path).read()
        target = [ln for ln in src.splitlines()
                  if "finish_span(span, cntl)" in ln
                  and "shed load" in ln]
        assert len(target) == 1, target
        sf = SourceFile(path, "brpc_tpu/rpc/server_dispatch.py",
                        src.replace(target[0] + "\n", ""))
        found = list(SpanFinishRule().check(sf, Context([sf])))
        assert any(f.rule == "span-finish" for f in found), found
        # and the unmutated file stays clean
        sf_ok = SourceFile(path, "brpc_tpu/rpc/server_dispatch.py", src)
        assert list(SpanFinishRule().check(sf_ok, Context([sf_ok]))) == []


class TestBlockRecycle:
    def test_seeded_violations(self):
        active, _ = _lint("bad_block_recycle.py")
        assert [f.rule for f in active] == ["block-recycle"] * 3, \
            [f.format() for f in active]
        msgs = " | ".join(f.message for f in active)
        assert "pooled blocks" in msgs and "recycled" in msgs
        # the loop-carried case: a pop late in iteration N stales the
        # window read at the top of iteration N+1
        src = open(os.path.join(
            FIXTURES, "bad_block_recycle.py")).read().splitlines()
        assert any("BAD on pass 2" in src[f.line - 1] for f in active), \
            [f.format() for f in active]

    def test_good_fixture_zero_false_positives(self):
        active, waived = _lint("good_block_recycle.py")
        assert active == [] and waived == [], \
            [f.format() for f in active + waived]

    def test_mutation_pop_before_scan_fires_on_real_pool_code(self):
        """Mutation pin on the REAL scan lane: reorder turbo_scan's
        portal.pop_front(consumed) to before the native scan reads the
        window — the rule must fire, so the slice-then-pop discipline
        that keeps pooled blocks safe to recycle cannot be silently
        reordered away."""
        from brpc_tpu.analysis.core import Context, SourceFile
        from brpc_tpu.analysis.rules.block_recycle import BlockRecycleRule
        path = os.path.join(REPO_ROOT, "brpc_tpu", "protocol",
                            "tpu_std.py")
        src = open(path).read()
        scan_line = "        consumed, recs = scan(win, MAGIC, SMALL_FRAME_MAX, 128,\n"
        pop_line = "        portal.pop_front(consumed)\n"
        assert scan_line in src and pop_line in src
        mutated = src.replace(pop_line, "").replace(
            scan_line, "        portal.pop_front(12)\n" + scan_line)
        sf = SourceFile(path, "brpc_tpu/protocol/tpu_std.py", mutated)
        found = list(BlockRecycleRule().check(sf, Context([sf])))
        assert any(f.rule == "block-recycle" and "'win'" in f.message
                   for f in found), [f.format() for f in found]
        # and the unmutated file stays clean
        sf_ok = SourceFile(path, "brpc_tpu/protocol/tpu_std.py", src)
        assert list(BlockRecycleRule().check(sf_ok, Context([sf_ok]))) \
            == []


class TestCleanFixture:
    def test_zero_false_positives(self):
        active, waived = _lint("clean.py")
        assert active == [] and waived == [], \
            [f.format() for f in active + waived]


class TestWaivers:
    def test_reasoned_waiver_suppresses_and_bare_waiver_reports(self):
        active, waived = _lint("bad_waiver.py")
        # the four waived violations...
        assert sorted(f.rule for f in waived) == ["fiber-blocking"] * 4
        reasons = {f.reason for f in waived}
        assert any("reasoned waivers suppress" in (r or "")
                   for r in reasons)
        # a reason wrapping onto the next comment line is recorded whole
        assert any("recorded whole" in (r or "") for r in reasons), \
            reasons
        # ...while the reasonless waiver is reported, and an inline
        # waiver must NOT leak onto the same rule's violation one line
        # below it
        assert sorted(f.rule for f in active) == \
            ["fiber-blocking", "waiver-reason"], \
            [f.format() for f in active]
        leak = [f for f in active if f.rule == "fiber-blocking"]
        src = open(os.path.join(FIXTURES, "bad_waiver.py")).read()
        line = src.splitlines()[leak[0].line - 1]
        assert "must NOT leak" in line, line


class TestPostforkReset:
    def test_seeded_violations(self):
        active, _ = _lint("bad_postfork.py")
        assert [f.rule for f in active] == ["postfork-reset"] * 2, \
            [f.format() for f in active]
        msgs = " | ".join(f.message for f in active)
        assert "global_loop" in msgs and "'cache'" in msgs
        # the findings anchor on the accessor def and the singleton
        # assignment, not on the classes
        src = open(os.path.join(
            FIXTURES, "bad_postfork.py")).read().splitlines()
        anchors = [src[f.line - 1] for f in active]
        assert any("def global_loop" in a for a in anchors), anchors
        assert any("cache = BufferCache()" in a for a in anchors), anchors

    def test_good_fixture_zero_false_positives(self):
        # registered accessor, plain-data module singletons, compiled
        # regexes: zero findings under the FULL analyzer
        active, waived = _lint("good_postfork.py")
        assert active == [] and waived == [], \
            [f.format() for f in active + waived]

    def test_protocol_registrar_exempt_on_real_module(self):
        """ensure_registered() in protocol/tpu_std.py is the lazy
        accessor shape but hands the instance to register_protocol —
        the protocol table is fork-safe codec data, so the rule must
        stay silent there (and the module carries no waiver)."""
        from brpc_tpu.analysis.core import Context, SourceFile
        from brpc_tpu.analysis.rules.postfork_reset import PostforkResetRule
        path = os.path.join(REPO_ROOT, "brpc_tpu", "protocol", "tpu_std.py")
        src = open(path).read()
        assert "def ensure_registered" in src and \
            "postfork" not in src  # no registration, no waiver
        sf = SourceFile(path, "brpc_tpu/protocol/tpu_std.py", src)
        found = list(PostforkResetRule().check(sf, Context([sf])))
        assert found == [], [f.format() for f in found]

    def test_statcell_fixture_violations(self):
        """The stat-cell registry shape (rpc/backend_stats.py idiom):
        a lazy cell-registry accessor and a freelist-bearing ring
        store, unregistered — both must fire."""
        active, _ = _lint("bad_postfork_statcells.py")
        assert [f.rule for f in active] == ["postfork-reset"] * 2, \
            [f.format() for f in active]
        msgs = " | ".join(f.message for f in active)
        assert "global_cells" in msgs and "'rings'" in msgs

    def test_statcell_good_fixture_zero_false_positives(self):
        active, waived = _lint("good_postfork_statcells.py")
        assert active == [] and waived == [], \
            [f.format() for f in active + waived]

    def test_mutation_dropping_registration_fires_on_real_backend_stats(
            self):
        """Mutation pin: strip the postfork.register line from the real
        rpc/backend_stats.py — the rule must fire on global_stats(), so
        the stat-cell registry can never silently lose its fork reset
        (a forked shard would serve the parent's per-backend cells)."""
        from brpc_tpu.analysis.core import Context, SourceFile
        from brpc_tpu.analysis.rules.postfork_reset import PostforkResetRule
        path = os.path.join(REPO_ROOT, "brpc_tpu", "rpc",
                            "backend_stats.py")
        src = open(path).read()
        target = [ln for ln in src.splitlines()
                  if "postfork.register(" in ln]
        assert len(target) == 1, target
        mutated = src.replace(target[0] + "\n", "")
        sf = SourceFile(path, "brpc_tpu/rpc/backend_stats.py", mutated)
        found = list(PostforkResetRule().check(sf, Context([sf])))
        assert any(f.rule == "postfork-reset"
                   and "global_stats" in f.message
                   for f in found), [f.format() for f in found]
        # and the unmutated module stays clean
        sf_ok = SourceFile(path, "brpc_tpu/rpc/backend_stats.py", src)
        assert list(PostforkResetRule().check(sf_ok, Context([sf_ok]))) \
            == []

    def test_mutation_dropping_registration_fires_on_device_stats(self):
        """Mutation pin: strip the postfork.register line from the real
        transport/device_stats.py — the rule must fire on
        global_device_stats(), so the device-cell registry can never
        silently lose its fork reset (a forked shard would report the
        parent's transfer cells and a conn weak-set pointing into the
        parent's transport)."""
        from brpc_tpu.analysis.core import Context, SourceFile
        from brpc_tpu.analysis.rules.postfork_reset import PostforkResetRule
        path = os.path.join(REPO_ROOT, "brpc_tpu", "transport",
                            "device_stats.py")
        src = open(path).read()
        target = [ln for ln in src.splitlines()
                  if "postfork.register(" in ln]
        assert len(target) == 1, target
        mutated = src.replace(target[0] + "\n", "")
        sf = SourceFile(path, "brpc_tpu/transport/device_stats.py",
                        mutated)
        found = list(PostforkResetRule().check(sf, Context([sf])))
        assert any(f.rule == "postfork-reset"
                   and "global_device_stats" in f.message
                   for f in found), [f.format() for f in found]
        # and the unmutated module stays clean
        sf_ok = SourceFile(path, "brpc_tpu/transport/device_stats.py",
                           src)
        assert list(PostforkResetRule().check(sf_ok, Context([sf_ok]))) \
            == []

    def test_registry_fixture_violation(self):
        """The object-registry registrar shape (fiber/worker_module.py
        idiom): a register* function appending its bare parameter into
        a module-level list, unregistered — must fire."""
        active, _ = _lint("bad_postfork_registry.py")
        assert [f.rule for f in active] == ["postfork-reset"], \
            [f.format() for f in active]
        assert "register_engine" in active[0].message
        src = open(os.path.join(
            FIXTURES, "bad_postfork_registry.py")).read().splitlines()
        assert "def register_engine" in src[active[0].line - 1]

    def test_registry_good_fixture_zero_false_positives(self):
        active, waived = _lint("good_postfork_registry.py")
        assert active == [] and waived == [], \
            [f.format() for f in active + waived]

    def test_register_protocol_registry_exempt_on_real_module(self):
        """protocol/registry.py's register_protocol appends its bare
        parameter into the module-level protocol list — exactly the
        registry shape — but the protocol table is fork-safe codec
        data: the rule must stay silent there without a waiver."""
        from brpc_tpu.analysis.core import Context, SourceFile
        from brpc_tpu.analysis.rules.postfork_reset import PostforkResetRule
        path = os.path.join(REPO_ROOT, "brpc_tpu", "protocol",
                            "registry.py")
        src = open(path).read()
        assert "_protocols.append(p)" in src and "postfork" not in src
        sf = SourceFile(path, "brpc_tpu/protocol/registry.py", src)
        found = list(PostforkResetRule().check(sf, Context([sf])))
        assert found == [], [f.format() for f in found]

    def test_mutation_dropping_registration_fires_on_worker_module(self):
        """Mutation pin: strip the postfork.register line from the real
        fiber/worker_module.py — the rule must fire on register_module,
        so the worker-module registry can never silently lose its fork
        reset (a forked shard's workers would double-run the parent's
        serving engine)."""
        from brpc_tpu.analysis.core import Context, SourceFile
        from brpc_tpu.analysis.rules.postfork_reset import PostforkResetRule
        path = os.path.join(REPO_ROOT, "brpc_tpu", "fiber",
                            "worker_module.py")
        src = open(path).read()
        target = [ln for ln in src.splitlines()
                  if "postfork.register(" in ln]
        assert len(target) == 1, target
        mutated = src.replace(target[0] + "\n", "")
        sf = SourceFile(path, "brpc_tpu/fiber/worker_module.py", mutated)
        found = list(PostforkResetRule().check(sf, Context([sf])))
        assert any(f.rule == "postfork-reset"
                   and "register_module" in f.message
                   for f in found), [f.format() for f in found]
        # and the unmutated module stays clean
        sf_ok = SourceFile(path, "brpc_tpu/fiber/worker_module.py", src)
        assert list(PostforkResetRule().check(sf_ok, Context([sf_ok]))) \
            == []

    def test_mutation_dropping_registration_fires_on_real_dispatcher(self):
        """Mutation pin: strip the postfork.register line from the real
        transport/event_dispatcher.py — the rule must fire, so the
        dispatcher singleton can never silently lose its fork reset
        (a forked shard would EPOLL_CTL the parent's epoll set)."""
        from brpc_tpu.analysis.core import Context, SourceFile
        from brpc_tpu.analysis.rules.postfork_reset import PostforkResetRule
        path = os.path.join(REPO_ROOT, "brpc_tpu", "transport",
                            "event_dispatcher.py")
        src = open(path).read()
        target = [ln for ln in src.splitlines()
                  if "postfork.register(" in ln]
        assert len(target) == 1, target
        mutated = src.replace(target[0] + "\n", "")
        sf = SourceFile(path, "brpc_tpu/transport/event_dispatcher.py",
                        mutated)
        found = list(PostforkResetRule().check(sf, Context([sf])))
        assert any(f.rule == "postfork-reset"
                   and "global_dispatcher" in f.message
                   for f in found), [f.format() for f in found]
        # and the unmutated module stays clean
        sf_ok = SourceFile(path, "brpc_tpu/transport/event_dispatcher.py",
                           src)
        assert list(PostforkResetRule().check(sf_ok, Context([sf_ok]))) \
            == []


class TestCli:
    def _run(self, *args):
        return subprocess.run(
            [sys.executable, "-m", "brpc_tpu.analysis", *args],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)

    def test_exit_code_is_unwaived_finding_count(self):
        # the CI contract: exit code == number of unwaived findings
        # (0 = clean), pinned here so scripts can rely on it
        bad = self._run(os.path.join(FIXTURES, "bad_iobuf_aliasing.py"))
        assert bad.returncode == 2 and "iobuf-aliasing" in bad.stdout
        four = self._run(os.path.join(FIXTURES,
                                      "bad_memoryview_release.py"))
        assert four.returncode == 4, four.stdout + four.stderr
        clean = self._run(os.path.join(FIXTURES, "clean.py"))
        assert clean.returncode == 0, clean.stdout + clean.stderr

    def test_unknown_rule_is_usage_error(self):
        proc = self._run("--rules", "no-such-rule",
                         os.path.join(FIXTURES, "clean.py"))
        assert proc.returncode == 120 and "unknown rules" in proc.stderr

    def test_list_rules_names_the_v2_pack(self):
        proc = self._run("--list-rules")
        assert proc.returncode == 0
        for rule in ("lock-cycle", "callback-under-lock",
                     "blocking-under-lock", "sampler-no-lazy-import",
                     "event-wait-not-sleep", "memoryview-release",
                     "fiber-blocking", "postfork-reset"):
            assert rule in proc.stdout, proc.stdout

    def test_format_json(self):
        proc = self._run("--format=json",
                         os.path.join(FIXTURES, "bad_lock_cycle.py"))
        report = json.loads(proc.stdout)
        assert proc.returncode == len(report["active"]) == 1
        assert report["active"][0]["rule"] == "lock-cycle"

    def test_format_sarif_is_valid_2_1_0(self):
        proc = self._run(
            "--format=sarif",
            os.path.join(FIXTURES, "bad_memoryview_release.py"))
        sarif = json.loads(proc.stdout)
        assert sarif["version"] == "2.1.0"
        run = sarif["runs"][0]
        assert run["tool"]["driver"]["name"] == "graftlint"
        rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
        results = run["results"]
        assert len(results) == 4 and proc.returncode == 4
        for r in results:
            assert r["ruleId"] in rule_ids
            loc = r["locations"][0]["physicalLocation"]
            assert loc["artifactLocation"]["uri"].endswith(
                "bad_memoryview_release.py")
            assert loc["region"]["startLine"] >= 1
        # waived findings ride along as suppressed results
        waived = self._run("--format=sarif",
                           os.path.join(REPO_ROOT, "brpc_tpu", "rpc",
                                        "progressive.py"))
        wsarif = json.loads(waived.stdout)
        sup = [r for r in wsarif["runs"][0]["results"]
               if r.get("suppressions")]
        assert sup and all(s["suppressions"][0]["justification"]
                           for s in sup)

    def test_show_waivers_audits_reasons_and_usage(self):
        proc = self._run("--show-waivers",
                         os.path.join(REPO_ROOT, "brpc_tpu"))
        assert proc.returncode == 0
        # every in-force waiver is listed with its reason, and the
        # real-tree waivers all suppress something (no stale rows)
        assert "disable=callback-under-lock" in proc.stdout
        assert "disable=judge-defer" in proc.stdout
        assert "UNUSED" not in proc.stdout, proc.stdout
        js = self._run("--show-waivers", "--format=json",
                       os.path.join(REPO_ROOT, "brpc_tpu"))
        rows = json.loads(js.stdout)["waivers"]
        assert rows and all(w["reason"] for w in rows)
        assert all(w["used"] for w in rows)

    def test_changed_filters_to_git_diff(self, tmp_path):
        # a scratch git repo: one clean file committed, one bad file
        # added after — --changed must report ONLY the bad file's
        # findings even though both are analyzed
        import shutil
        repo = tmp_path / "repo"
        repo.mkdir()
        shutil.copy(os.path.join(FIXTURES, "clean.py"),
                    repo / "settled.py")

        def git(*a):
            return subprocess.run(["git", *a], cwd=repo,
                                  capture_output=True, text=True,
                                  timeout=60)

        git("init", "-q")
        git("-c", "user.email=t@t", "-c", "user.name=t", "add", "-A")
        git("-c", "user.email=t@t", "-c", "user.name=t",
            "commit", "-qm", "seed")
        shutil.copy(os.path.join(FIXTURES, "bad_lock_cycle.py"),
                    repo / "fresh.py")
        proc = subprocess.run(
            [sys.executable, "-m", "brpc_tpu.analysis", "--changed",
             "HEAD", "--format=json", str(repo)],
            cwd=repo, capture_output=True, text=True, timeout=120,
            env={**os.environ, "PYTHONPATH": REPO_ROOT})
        report = json.loads(proc.stdout)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert [f["rule"] for f in report["active"]] == ["lock-cycle"]
        assert report["active"][0]["path"].endswith("fresh.py")


class TestRepoIsClean:
    def test_package_lints_clean(self):
        """The acceptance gate: brpc_tpu/ has no unwaived findings, and
        every waiver carries a reason."""
        active, waived = Analyzer().run(
            [os.path.join(REPO_ROOT, "brpc_tpu")])
        assert active == [], [f.format() for f in active]
        assert all(f.reason for f in waived), \
            [f.format() for f in waived]


def _ctx_for(path, relpath, src):
    from brpc_tpu.analysis.core import Context, SourceFile
    sf = SourceFile(path, relpath, src)
    return sf, Context([sf])


class TestLockModelSnapshot:
    """The discovered whole-program lock graph is a pinned artifact:
    the model must keep finding the real locks, and its edge count only
    grows DELIBERATELY (update the pin with the docs registry when a
    new nesting ships)."""

    # update deliberately, together with docs/invariants.md
    # (36: +Controller._arb_lock -> RetryBudget._lock — the retry
    # token bucket drains inside _retry_taken_call's arb hold)
    # (44: +IciConn._flush_lock/_pump_lock -> DeviceCell._lock — the
    # device-transfer stage trackers stamp AND settle their leaf cells
    # from the ici flush/ack legs (stamps hold the cell lock so the
    # settle latch fully serializes span access). The model also mints
    # receiver-inferred twin nodes (device_stats:cell._lock and
    # device_stats:?._lock) for the same physical lock, x2 each, plus
    # -> _ReducerBase._lock x2. DeviceCell._lock is a LOCK_ORDER leaf,
    # see racelane.py)
    #
    # 44 -> 40 (ISSUE 15): return-annotation receiver typing resolves
    # global_dispatcher().pause_read() to EventDispatcher by its
    # declared return type, not by a unique method name (the four
    # Socket._nevent_lock / SslConn._ssl_lock -> dispatcher edges),
    # and blocklisting notify/notify_all from the unique-method
    # fallback removed four edges that were never real: stdlib
    # threading.Condition notifies in fiber/timer.py and
    # fiber/scheduler.py had been misresolved to FiberCondition,
    # fabricating Butex/timer chains under PeriodicTask._lock,
    # Controller._arb_lock and Butex._lock.
    #
    # 40 -> 42 with guardlint (ISSUE 16): fluent-chain receiver
    # typing (`ndropped_queue = Adder().expose(...)` now types the
    # module var) resolves bvar .add() calls under Recorder._lock
    # (capture.py record_complete) and Socket._handoff_lock (the
    # handoff accounting), adding the two held-lock ->
    # _ReducerBase._lock leaf edges that were always executed but
    # previously invisible. _ReducerBase._lock is an acquire-last
    # leaf everywhere, so no LOCK_ORDER change.
    #
    # 42 stays 42 without the ring lane (ISSUE 28): its dispatcher's
    # lock was a leaf (only native calls ran under it), so deleting
    # the lane removed one lock (134 -> 133 discovered) and no edge;
    # the socket -> EventDispatcher edges were already typed by
    # annotation, not by the twin's absence.
    PINNED_EDGE_COUNT = 42

    def _model(self):
        from brpc_tpu.analysis.core import Context, iter_source_files
        from brpc_tpu.analysis.lockmodel import get_lock_model
        files = iter_source_files([os.path.join(REPO_ROOT, "brpc_tpu")])
        return get_lock_model(Context(files))

    def test_discovers_the_known_real_locks(self):
        m = self._model()
        names = set(m.locks)
        for known in ("Controller._arb_lock", "Controller._lb_lock",
                      "ContinuousBatcher._lock", "FlightRecorder._lock",
                      "Channel._socket_lock", "Channel._pool_lock",
                      "Socket.pending_lock", "ServingEngine._decode_lock",
                      "EventDispatcher._lock", "BackendCell._lock"):
            assert known in names, f"lock model lost {known}"
        # the acceptance floor: >= 15 real locks across the package
        assert len(names) >= 15, sorted(names)

    def test_lazy_dict_locks_resolve_through_foreign_receivers(self):
        # Controller's _LAZY dict declares _arb_lock as an RLock; the
        # acquisition `with cntl._arb_lock:` in backend_stats.py must
        # land on the Controller node, not an anonymous one
        m = self._model()
        assert m.locks["Controller._arb_lock"].kind == "RLock"
        fkeys = [k for k in m.funcs
                 if "backend_stats" in k and "attempt" in k.lower()]
        hit = any("Controller._arb_lock" in
                  {a for a, _ in m.funcs[k].acquires} for k in fkeys)
        assert hit, fkeys

    def test_edge_count_grows_only_deliberately(self):
        m = self._model()
        assert len(m.edges) == self.PINNED_EDGE_COUNT, (
            f"lock graph has {len(m.edges)} edges, pinned "
            f"{self.PINNED_EDGE_COUNT}: a new lock nesting shipped — "
            "re-run the lock-cycle rule, extend the LOCK_ORDER "
            "registry in analysis/racelane.py + docs/invariants.md, "
            "then update this pin", sorted(m.edges))

    def test_acquisition_graph_is_cycle_free(self):
        m = self._model()
        assert m.cycles() == []


class TestLockCycle:
    def test_interprocedural_cycle_detected_with_witness(self):
        active, _ = _lint("bad_lock_cycle.py")
        assert [f.rule for f in active] == ["lock-cycle"], \
            [f.format() for f in active]
        msg = active[0].message
        # both hops of the witness are named with their call chains —
        # neither function nests the locks syntactically
        assert "Journal._journal_lock" in msg
        assert "Index._index_lock" in msg
        assert "via Journal.flush->Index.touch" in msg
        assert "via Index.rebuild->Journal.record_entry" in msg

    def test_consistent_order_is_clean(self):
        active, waived = _lint("good_lock_cycle.py")
        assert active == [] and waived == [], \
            [f.format() for f in active + waived]

    def test_mutation_arb_lb_inversion_on_real_modules(self):
        """Mutation pin for the PR 7 bug class: the tree keeps
        `_arb_lock`/`_lb_lock` strictly sequential (controller releases
        arb before taking lb; the cluster channel calls the arb-taking
        super()._on_attempt_failed AFTER its lb hold closes).
        Re-nesting both — arb around lb in _reset_for_call, super()
        inside the lb hold — closes the AB/BA cycle and the rule must
        fire; the unmutated pair is cycle-free."""
        from brpc_tpu.analysis.core import Context, SourceFile
        from brpc_tpu.analysis.rules.lock_graph import LockCycleRule
        cpath = os.path.join(REPO_ROOT, "brpc_tpu", "rpc",
                             "controller.py")
        clpath = os.path.join(REPO_ROOT, "brpc_tpu", "rpc",
                              "cluster_channel.py")
        chpath = os.path.join(REPO_ROOT, "brpc_tpu", "rpc",
                              "channel.py")
        csrc, clsrc = open(cpath).read(), open(clpath).read()
        chsrc = open(chpath).read()
        # hop 1: controller nests arb around lb
        seq = "            with self._lb_lock:"
        assert seq in csrc
        cmut = csrc.replace(
            seq, "            with self._arb_lock, self._lb_lock:")
        # hop 2: cluster channel calls the arb-taking base hook while
        # still holding the lb lock
        tail = ("                cntl._lb_fed.append(ep)\n"
                "        # backend stat cells + attempt spans (base "
                "hook) see the same\n"
                "        # resolved endpoint the LB/breaker feedback "
                "uses\n"
                "        super()._on_attempt_failed(cntl, code, text, "
                "ep)\n")
        assert tail in clsrc
        clmut = clsrc.replace(
            tail, "                cntl._lb_fed.append(ep)\n"
                  "                super()._on_attempt_failed("
                  "cntl, code, text, ep)\n")

        def run(ctrl_src, clus_src):
            files = [
                SourceFile(cpath, "brpc_tpu/rpc/controller.py",
                           ctrl_src),
                SourceFile(clpath, "brpc_tpu/rpc/cluster_channel.py",
                           clus_src),
                SourceFile(chpath, "brpc_tpu/rpc/channel.py", chsrc),
            ]
            return list(LockCycleRule().finalize(Context(files)))

        found = run(cmut, clmut)
        assert any(f.rule == "lock-cycle"
                   and "Controller._arb_lock" in f.message
                   and "Controller._lb_lock" in f.message
                   for f in found), [f.format() for f in found]
        assert run(csrc, clsrc) == []       # the real pair stays clean


class TestCallbackUnderLock:
    def test_seeded_violations(self):
        active, _ = _lint("bad_callback_under_lock.py")
        assert [f.rule for f in active] == ["callback-under-lock"] * 2, \
            [f.format() for f in active]
        msgs = " | ".join(f.message for f in active)
        assert "on_token" in msgs and "while holding" in msgs
        # the helper case carries the witness chain
        assert "on_finish" in msgs and "reached under" in msgs \
            and "MiniBatcher.retire_all -> MiniBatcher._emit_done" in msgs

    def test_collect_then_fire_is_clean(self):
        active, waived = _lint("good_callback_under_lock.py")
        assert active == [] and waived == [], \
            [f.format() for f in active + waived]

    def test_mutation_firing_inside_lock_on_real_batcher(self):
        """Mutation pin on the REAL serving batcher: re-indenting the
        final _fire into the `with self._lock:` block reintroduces the
        PR 8 bug (callbacks fired under the batcher lock) — the rule
        must fire, and the unmutated module must stay clean."""
        from brpc_tpu.analysis.rules.lock_graph import (
            CallbackUnderLockRule,
        )
        path = os.path.join(REPO_ROOT, "brpc_tpu", "serving",
                            "batcher.py")
        src = open(path).read()
        tail = "        self._fire(emits, done)\n        if stats_on:"
        assert tail in src
        mutated = src.replace(
            tail, "            self._fire(emits, done)\n"
                  "        if stats_on:")
        sf, ctx = _ctx_for(path, "brpc_tpu/serving/batcher.py", mutated)
        found = list(CallbackUnderLockRule().finalize(ctx))
        assert any(f.rule == "callback-under-lock"
                   and "on_token" in f.message for f in found), \
            [f.format() for f in found]
        sf_ok, ctx_ok = _ctx_for(path, "brpc_tpu/serving/batcher.py",
                                 src)
        assert list(CallbackUnderLockRule().finalize(ctx_ok)) == []


class TestBlockingUnderLock:
    def test_seeded_violations(self):
        active, _ = _lint("bad_blocking_under_lock.py")
        assert [f.rule for f in active] == ["blocking-under-lock"] * 2, \
            [f.format() for f in active]
        msgs = " | ".join(f.message for f in active)
        assert "time.sleep()" in msgs and "while holding" in msgs
        assert "Event.wait" in msgs and "reached under" in msgs

    def test_waits_outside_and_condvar_idiom_clean(self):
        active, waived = _lint("good_blocking_under_lock.py")
        assert active == [] and waived == [], \
            [f.format() for f in active + waived]

    def test_mutation_sleeping_under_recorder_lock(self):
        """Mutation pin on the REAL flight recorder: pulling the loop's
        interruptible sleep under self._lock stalls every /hotspots
        reader for the nap — the rule must fire; unmutated stays
        clean."""
        from brpc_tpu.analysis.rules.lock_graph import (
            BlockingUnderLockRule,
        )
        path = os.path.join(REPO_ROOT, "brpc_tpu", "builtin",
                            "flight_recorder.py")
        src = open(path).read()
        line = "                self._sleep(0.05)\n"
        assert line in src
        mutated = src.replace(
            line, "                with self._lock:\n"
                  "                    self._sleep(0.05)\n", 1)
        sf, ctx = _ctx_for(path, "brpc_tpu/builtin/flight_recorder.py",
                           mutated)
        found = list(BlockingUnderLockRule().finalize(ctx))
        assert any(f.rule == "blocking-under-lock"
                   and "FlightRecorder._lock" in f.message
                   for f in found), [f.format() for f in found]
        sf_ok, ctx_ok = _ctx_for(
            path, "brpc_tpu/builtin/flight_recorder.py", src)
        assert list(BlockingUnderLockRule().finalize(ctx_ok)) == []


class TestSamplerNoLazyImport:
    def test_seeded_violations(self):
        active, _ = _lint("bad_sampler_import.py")
        assert [f.rule for f in active] == \
            ["sampler-no-lazy-import"] * 2, \
            [f.format() for f in active]
        msgs = " | ".join(f.message for f in active)
        assert "StackSampler._loop" in msgs
        assert "reached via StackSampler._loop -> " \
            "StackSampler._attribute" in msgs

    def test_bind_before_start_is_clean(self):
        active, waived = _lint("good_sampler_import.py")
        assert active == [] and waived == [], \
            [f.format() for f in active + waived]

    def test_mutation_lazy_import_in_real_attribution_path(self):
        """Mutation pin on the REAL flight recorder: re-introducing the
        PR 8 lazy import inside _attribute (the fd-churn flake) must
        fire the rule; the fixed module stays clean."""
        from brpc_tpu.analysis.rules.sampler_import import (
            SamplerNoLazyImportRule,
        )
        path = os.path.join(REPO_ROOT, "brpc_tpu", "builtin",
                            "flight_recorder.py")
        src = open(path).read()
        target = "                cntl = _serving_cntl.peek(fiber)\n"
        assert target in src
        mutated = src.replace(
            target,
            "                from brpc_tpu.rpc.server_dispatch import "
            "_serving_cntl as sc\n"
            "                cntl = sc.peek(fiber)\n", 1)
        sf, ctx = _ctx_for(path, "brpc_tpu/builtin/flight_recorder.py",
                           mutated)
        found = list(SamplerNoLazyImportRule().finalize(ctx))
        assert any(f.rule == "sampler-no-lazy-import"
                   and "_attribute" in f.message for f in found), \
            [f.format() for f in found]
        sf_ok, ctx_ok = _ctx_for(
            path, "brpc_tpu/builtin/flight_recorder.py", src)
        assert list(SamplerNoLazyImportRule().finalize(ctx_ok)) == []


class TestEventWaitNotSleep:
    def test_seeded_violations(self):
        active, _ = _lint("bad_event_wait.py")
        assert [f.rule for f in active] == ["event-wait-not-sleep"] * 2, \
            [f.format() for f in active]
        msgs = " | ".join(f.message for f in active)
        assert "Monitor._watch" in msgs and "_pacer" in msgs

    def test_event_parked_loop_is_clean(self):
        active, waived = _lint("good_event_wait.py")
        assert active == [] and waived == [], \
            [f.format() for f in active + waived]

    def test_mutation_sleep_in_real_shard_monitor(self):
        """Mutation pin on the REAL shard supervisor: swapping the
        monitor loop's Event-parked tick back to time.sleep (the exact
        pre-PR 6 shape) must fire the rule; unmutated stays clean."""
        from brpc_tpu.analysis.rules.event_wait import (
            EventWaitNotSleepRule,
        )
        path = os.path.join(REPO_ROOT, "brpc_tpu", "rpc",
                            "shard_group.py")
        src = open(path).read()
        waits = [ln for ln in src.splitlines()
                 if "park.wait(" in ln]
        assert len(waits) == 1, waits
        mutated = src.replace(
            waits[0],
            waits[0].replace("park.wait(", "time.sleep("))
        sf, ctx = _ctx_for(path, "brpc_tpu/rpc/shard_group.py", mutated)
        found = list(EventWaitNotSleepRule().finalize(ctx))
        assert any(f.rule == "event-wait-not-sleep"
                   and "_monitor_loop" in f.message for f in found), \
            [f.format() for f in found]
        sf_ok, ctx_ok = _ctx_for(path, "brpc_tpu/rpc/shard_group.py",
                                 src)
        assert list(EventWaitNotSleepRule().finalize(ctx_ok)) == []


class TestTrafficCaptureLint:
    """ISSUE 11 pins on the traffic recorder: the capture subsystem's
    fork hygiene, its never-block-the-dispatch-path lock discipline,
    and its writer thread's no-lazy-import rule must all be enforced
    by the analyzers — each pin mutates the REAL module and asserts
    the rule fires (and that the shipped module stays clean)."""

    PATH = os.path.join(REPO_ROOT, "brpc_tpu", "traffic", "capture.py")
    REL = "brpc_tpu/traffic/capture.py"

    def test_mutation_dropping_postfork_registration_fires(self):
        """Strip the postfork.register line: a forked shard inheriting
        the parent's recorder queue/writer-fd would interleave into
        the parent-pid corpus through the shared file offset — the
        postfork-reset rule must keep that registration unloseable."""
        from brpc_tpu.analysis.core import Context, SourceFile
        from brpc_tpu.analysis.rules.postfork_reset import PostforkResetRule
        src = open(self.PATH).read()
        target = [ln for ln in src.splitlines()
                  if "postfork.register(" in ln]
        assert len(target) == 1, target
        mutated = src.replace(target[0] + "\n", "")
        sf = SourceFile(self.PATH, self.REL, mutated)
        found = list(PostforkResetRule().check(sf, Context([sf])))
        assert any(f.rule == "postfork-reset"
                   and "_recorder" in f.message for f in found), \
            [f.format() for f in found]
        sf_ok = SourceFile(self.PATH, self.REL, src)
        assert list(PostforkResetRule().check(sf_ok,
                                              Context([sf_ok]))) == []

    def test_mutation_waiting_under_recorder_lock_fires(self):
        """Pull the writer's parked wait under Recorder._lock: every
        request completing on the dispatch side enqueues under that
        lock, so a wait inside it stalls the dispatch path for the
        whole tick — the blocking-under-lock rule must fire. (Disk
        writes live outside the lock by the same discipline; the
        queue-swap drain keeps the hold O(1).)"""
        from brpc_tpu.analysis.rules.lock_graph import (
            BlockingUnderLockRule,
        )
        src = open(self.PATH).read()
        line = "            self._wake.wait(0.1)\n"
        assert line in src
        mutated = src.replace(
            line, "            with self._lock:\n"
                  "                self._wake.wait(0.1)\n", 1)
        sf, ctx = _ctx_for(self.PATH, self.REL, mutated)
        found = list(BlockingUnderLockRule().finalize(ctx))
        assert any(f.rule == "blocking-under-lock"
                   and "Recorder._lock" in f.message
                   for f in found), [f.format() for f in found]
        sf_ok, ctx_ok = _ctx_for(self.PATH, self.REL, src)
        assert list(BlockingUnderLockRule().finalize(ctx_ok)) == []

    def test_mutation_lazy_import_in_writer_loop_fires(self):
        """Introduce a lazy import inside _record_writer_loop: the
        capture writer is recorder-thread code (the rule's 'record'
        marker matches it by construction), and a lazy import there
        opens module files on that thread at drain time — the PR 8
        fd-churn flake's shape. The rule must fire; the shipped module
        binds everything at module load and stays clean."""
        from brpc_tpu.analysis.rules.sampler_import import (
            SamplerNoLazyImportRule,
        )
        src = open(self.PATH).read()
        needle = ("            self._wake.wait(0.1)\n"
                  "            self._wake.clear()\n")
        assert needle in src
        mutated = src.replace(
            needle, needle + "            from brpc_tpu.rpc import "
                             "server_dispatch as _sd\n", 1)
        sf, ctx = _ctx_for(self.PATH, self.REL, mutated)
        found = list(SamplerNoLazyImportRule().finalize(ctx))
        assert any(f.rule == "sampler-no-lazy-import"
                   and "_record_writer_loop" in f.message
                   for f in found), [f.format() for f in found]
        sf_ok, ctx_ok = _ctx_for(self.PATH, self.REL, src)
        assert list(SamplerNoLazyImportRule().finalize(ctx_ok)) == []

    def test_recorder_lock_ranked_in_lock_order(self):
        """The recorder lock is a declared LEAF in the racelane's
        LOCK_ORDER registry (and docs table row 34): dispatch-side
        enqueues take it bare, and nothing may nest inside it."""
        from brpc_tpu.analysis.racelane import LOCK_ORDER
        names = [n for n, _ in LOCK_ORDER]
        assert "Recorder._lock" in names
        # trailing leaf block: nothing this codebase ranks may nest
        # inside the recorder lock — only the ISSUE-13 sampler-tick
        # leaves (series rings, anomaly watchdog) and the ISSUE-14
        # admission leaves rank below it, and those are leaves
        # themselves
        below = names[names.index("Recorder._lock") + 1:]
        assert below == ["SeriesCollector._lock",
                         "AnomalyWatchdog._lock",
                         "AdmissionController._lock",
                         "retry_policy:_group_lock",
                         "IncidentManager._lock",
                         "ServingCell._cell_lock",
                         "ServingStats._ring_lock"], below


class TestDeviceObsLint:
    """ISSUE 12 pins on the device observatory: the device cell lock's
    place in the runtime lock order, and the uniqueness of the
    recorder-hook verbs (the lock model's unique-method fallback minted
    a FALSE edge from a shared `on_complete` name in PR 11 — the
    device hooks must never collide the same way)."""

    def test_device_cell_lock_ranked_after_ici_locks(self):
        """DeviceCell._lock is a declared LEAF acquired under the ici
        flush/pump holds (BatchTracker settle paths): it must rank
        AFTER every IciConn lock in LOCK_ORDER + docs table row 28."""
        from brpc_tpu.analysis.racelane import LOCK_ORDER
        names = [n for n, _ in LOCK_ORDER]
        assert "DeviceCell._lock" in names
        for ici_lock in ("IciConn._pump_lock", "IciConn._flush_lock",
                         "IciConn._lock"):
            assert names.index(ici_lock) < \
                names.index("DeviceCell._lock"), ici_lock

    def test_device_hook_verbs_are_unique(self):
        """Every device-stats hook/stamp verb is defined exactly once
        across the package — a second definer would re-open the
        unique-method-fallback false-edge hazard."""
        import re
        verbs = ("stamp_device_thread", "unstamp_device_thread",
                 "device_thread_label", "lane_encoded", "lane_flushed",
                 "lane_acked", "lane_failed", "note_open", "note_done",
                 "note_recv", "open_transfer",
                 "lane_introspection", "take_device_payload_with_recv",
                 "device_page_payload", "merge_device_payloads")
        counts = {v: 0 for v in verbs}
        pkg = os.path.join(REPO_ROOT, "brpc_tpu")
        for dirpath, _dirs, files in os.walk(pkg):
            for fn in files:
                if not fn.endswith(".py"):
                    continue
                src = open(os.path.join(dirpath, fn),
                           encoding="utf-8").read()
                for v in verbs:
                    counts[v] += len(
                        re.findall(rf"\bdef {v}\b", src))
        dupes = {v: n for v, n in counts.items() if n != 1}
        assert not dupes, dupes


class TestMemoryviewRelease:
    def test_seeded_violations(self):
        active, _ = _lint("bad_memoryview_release.py")
        assert [f.rule for f in active] == ["memoryview-release"] * 4, \
            [f.format() for f in active]
        src = open(os.path.join(
            FIXTURES, "bad_memoryview_release.py")).read().splitlines()
        # findings anchor on the RESIZE; the conditional-release decoy
        # (released on one path only) and the branch-local view leaking
        # into an unconditional resize both fire
        assert any("VIOLATION 2" in src[f.line - 1] for f in active)
        assert any("VIOLATION 4" in src[f.line - 1] for f in active)

    def test_release_disciplines_are_clean(self):
        active, waived = _lint("good_memoryview_release.py")
        assert active == [] and waived == [], \
            [f.format() for f in active + waived]

    def test_mutation_dropping_release_in_real_ici_flush(self):
        """Mutation pin on the REAL ici transport: deleting the
        `finally: mv.release()` from _flush reintroduces the PR 6
        BufferError (frame-pinning sampler vs `del wirebuf[:n]`) — the
        rule must fire; the fixed module stays clean."""
        from brpc_tpu.analysis.rules.memoryview_release import (
            MemoryviewReleaseRule,
        )
        path = os.path.join(REPO_ROOT, "brpc_tpu", "transport", "ici.py")
        src = open(path).read()
        guard = ("                    finally:\n"
                 "                        mv.release()\n")
        assert guard in src
        mutated = src.replace(guard, "")
        sf, ctx = _ctx_for(path, "brpc_tpu/transport/ici.py", mutated)
        found = list(MemoryviewReleaseRule().check(sf, ctx))
        assert any(f.rule == "memoryview-release"
                   and "_wirebuf" in f.message for f in found), \
            [f.format() for f in found]
        sf_ok, ctx_ok = _ctx_for(path, "brpc_tpu/transport/ici.py", src)
        assert list(MemoryviewReleaseRule().check(sf_ok, ctx_ok)) == []


class TestTimelineLint:
    """ISSUE 13 pins on the telemetry time machine: the series
    registry's fork hygiene, the anomaly watchdog's sampler-thread
    import discipline (it runs on the bvar sampler tick — the PR 8
    fd-hazard rule reaches it through the marker-named cross-module
    recursion), the uniqueness of the watchdog verbs, and the new
    leaf rows in the runtime lock order."""

    SERIES = os.path.join(REPO_ROOT, "brpc_tpu", "bvar", "series.py")
    ANOMALY = os.path.join(REPO_ROOT, "brpc_tpu", "bvar", "anomaly.py")

    def _files_with(self, relpath, content):
        from brpc_tpu.analysis.core import SourceFile, iter_source_files
        out = []
        for f in iter_source_files([os.path.join(REPO_ROOT, "brpc_tpu")]):
            if f.relpath == relpath:
                out.append(SourceFile(f.path, relpath, content))
            else:
                out.append(f)
        return out

    def test_mutation_dropping_series_postfork_registration_fires(self):
        """Strip the postfork.register line from the REAL series
        module: a forked shard inheriting the parent's rings would
        serve the PARENT's history as its own /timeline (and the leaf
        lock may be mid-hold at fork) — the postfork-reset rule must
        keep that registration unloseable."""
        from brpc_tpu.analysis.core import Context, SourceFile
        from brpc_tpu.analysis.rules.postfork_reset import PostforkResetRule
        src = open(self.SERIES).read()
        target = [ln for ln in src.splitlines()
                  if "postfork.register(" in ln]
        assert len(target) == 1, target
        mutated = src.replace(target[0] + "\n", "")
        sf = SourceFile(self.SERIES, "brpc_tpu/bvar/series.py", mutated)
        found = list(PostforkResetRule().check(sf, Context([sf])))
        assert any(f.rule == "postfork-reset"
                   and "global_series" in f.message for f in found), \
            [f.format() for f in found]
        sf_ok = SourceFile(self.SERIES, "brpc_tpu/bvar/series.py", src)
        assert list(PostforkResetRule().check(sf_ok,
                                              Context([sf_ok]))) == []

    def test_mutation_lazy_import_in_watchdog_pass_fires(self):
        """Introduce a lazy import inside AnomalyWatchdog.watchdog_pass:
        the watchdog runs on the bvar sampler's tick thread (window
        Sampler._run -> series_sample_tick -> watchdog_sample_pass,
        each hop marker-named), and a lazy import there opens module
        files on that thread at sample time — the PR 8 fd-churn flake's
        shape. The cross-module recursion must root the rule into
        anomaly.py; the shipped module binds at module load and stays
        clean."""
        from brpc_tpu.analysis.core import Context
        from brpc_tpu.analysis.rules.sampler_import import (
            SamplerNoLazyImportRule,
        )
        src = open(self.ANOMALY).read()
        needle = "        opened: Optional[Incident] = None\n"
        assert needle in src
        mutated = src.replace(
            needle, needle + "        from brpc_tpu.butil import "
                             "timekeeping as _tk\n", 1)
        found = list(SamplerNoLazyImportRule().finalize(Context(
            self._files_with("brpc_tpu/bvar/anomaly.py", mutated))))
        assert any(f.rule == "sampler-no-lazy-import"
                   and "watchdog_pass" in f.message
                   and f.path == "brpc_tpu/bvar/anomaly.py"
                   for f in found), [f.format() for f in found]
        clean = list(SamplerNoLazyImportRule().finalize(Context(
            self._files_with("brpc_tpu/bvar/anomaly.py", src))))
        assert [f for f in clean
                if f.path.startswith("brpc_tpu/bvar/")] == [], \
            [f.format() for f in clean]

    def test_mutation_lazy_import_in_series_store_fires(self):
        """Same pin one hop earlier: a lazy import inside the series
        engine's store path (reached from the tick) must fire."""
        from brpc_tpu.analysis.core import Context
        from brpc_tpu.analysis.rules.sampler_import import (
            SamplerNoLazyImportRule,
        )
        src = open(self.SERIES).read()
        needle = "        points: Dict[str, float] = {}\n"
        assert needle in src
        mutated = src.replace(
            needle, needle + "        import json as _json\n", 1)
        found = list(SamplerNoLazyImportRule().finalize(Context(
            self._files_with("brpc_tpu/bvar/series.py", mutated))))
        assert any(f.rule == "sampler-no-lazy-import"
                   and f.path == "brpc_tpu/bvar/series.py"
                   for f in found), [f.format() for f in found]

    def test_watchdog_verbs_are_unique(self):
        """Every watchdog/series hook verb is defined exactly once
        across the package — a second definer would re-open the
        unique-method-fallback false-edge hazard (the PR 11 lesson;
        never on_*/enabled names on sampler-reachable objects)."""
        import re
        verbs = ("watchdog_pass", "watchdog_sample_pass",
                 "series_sample_tick", "incident_snapshot",
                 "note_incident", "store_readings", "collect_readings",
                 "declare_series_kind", "bind_watchdog_imports",
                 "merge_timeline_states")
        counts = {v: 0 for v in verbs}
        pkg = os.path.join(REPO_ROOT, "brpc_tpu")
        for dirpath, _dirs, files in os.walk(pkg):
            for fn in files:
                if not fn.endswith(".py"):
                    continue
                src = open(os.path.join(dirpath, fn)).read()
                for v in verbs:
                    counts[v] += len(re.findall(
                        rf"def {v}\(", src))
        assert all(c == 1 for c in counts.values()), counts

    def test_series_locks_ranked_as_trailing_leaves(self):
        """SeriesCollector._lock and AnomalyWatchdog._lock lead the
        trailing leaf block of LOCK_ORDER (docs table rows 36-39,
        closed by the ISSUE-14 admission leaves): settled on the
        sampler tick thread, never wrapping another acquisition — and
        the lock model must DISCOVER both (a silent rename would
        un-rank them without failing)."""
        from brpc_tpu.analysis.core import Context, iter_source_files
        from brpc_tpu.analysis.lockmodel import get_lock_model
        from brpc_tpu.analysis.racelane import LOCK_ORDER
        names = [n for n, _ in LOCK_ORDER]
        assert names[-7:] == ["SeriesCollector._lock",
                              "AnomalyWatchdog._lock",
                              "AdmissionController._lock",
                              "retry_policy:_group_lock",
                              "IncidentManager._lock",
                              "ServingCell._cell_lock",
                              "ServingStats._ring_lock"]
        m = get_lock_model(Context(iter_source_files(
            [os.path.join(REPO_ROOT, "brpc_tpu")])))
        assert "SeriesCollector._lock" in m.locks
        assert "AnomalyWatchdog._lock" in m.locks
        assert "AdmissionController._lock" in m.locks
        assert "IncidentManager._lock" in m.locks
        # leaves: none may be the HELD side of any lock-graph edge
        for a, _b in m.edges:
            assert a not in ("SeriesCollector._lock",
                             "AnomalyWatchdog._lock",
                             "AdmissionController._lock",
                             "IncidentManager._lock"), m.edges
