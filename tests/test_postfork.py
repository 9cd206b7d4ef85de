"""The postfork-reset registry (butil/postfork.py): a forked child
must rebuild every process-global singleton privately — fresh
dispatcher (the inherited epoll fd is the PARENT's kernel object),
fresh TaskControl (worker threads exist only in the parent), fresh
timer/socket-map/pools — and the parent must be completely untouched.
These are the invariants shard-group serving stands on."""

import os
import sys

from brpc_tpu.butil import postfork


def _run_in_fork(check) -> str:
    """Fork, run ``check()`` in the child, return its report string.
    The child exits through os._exit so pytest machinery never runs
    twice."""
    r, w = os.pipe()
    pid = os.fork()
    if pid == 0:
        try:
            msg = check() or "OK"
        except BaseException as e:  # noqa: BLE001 - report, don't raise
            msg = f"EXC:{type(e).__name__}:{e}"
        try:
            os.write(w, str(msg).encode()[:4096])
        finally:
            os._exit(0)
    os.close(w)
    chunks = []
    while True:
        b = os.read(r, 4096)
        if not b:
            break
        chunks.append(b)
    os.close(r)
    os.waitpid(pid, 0)
    return b"".join(chunks).decode()


class TestRegistry:
    def test_canonical_singletons_are_registered(self):
        # IMPORTING a singleton-caching module must register its reset
        # (the graftlint postfork-reset rule enforces the source side;
        # this pins the runtime side). Registration-at-import is the
        # load-bearing property: whatever was imported before a fork
        # has, by construction, registered before that fork.
        import brpc_tpu.rpc  # noqa: F401
        import brpc_tpu.rpc.span  # noqa: F401
        import brpc_tpu.transport.event_dispatcher  # noqa: F401
        import brpc_tpu.transport.socket_map  # noqa: F401
        names = set(postfork.registered_names())
        for expected in ("transport.event_dispatcher", "fiber.scheduler",
                         "fiber.timer", "transport.socket_map",
                         "transport.socket", "butil.iobuf",
                         "bvar.window", "bvar.variable", "rpc.span",
                         "rpc.controller", "transport.input_messenger"):
            assert expected in names, (expected, sorted(names))

    def test_worker_module_registry_resets_in_child(self):
        """The worker-module registry must NOT survive fork: a forked
        shard whose fresh worker loops polled the parent's modules
        would double-run the parent's serving engine. The parent keeps
        its registration."""
        from brpc_tpu.fiber import worker_module as wm

        class Probe(wm.WorkerModule):
            pass

        probe = Probe()
        wm.register_module(probe)
        try:
            def check():
                mods = wm.registered_modules()
                if mods:
                    return f"child inherited {len(mods)} modules"
                # the child-side registry must be USABLE (fresh lock)
                p2 = Probe()
                wm.register_module(p2)
                if wm.registered_modules() != [p2]:
                    return "child re-registration broken"
                return "OK"

            assert _run_in_fork(check) == "OK"
            # parent untouched
            assert probe in wm.registered_modules()
        finally:
            wm.unregister_module(probe)

    def test_reregistering_a_name_replaces_not_stacks(self):
        calls = []
        postfork.register("test.dup", lambda: calls.append(1))
        postfork.register("test.dup", lambda: calls.append(2))
        assert postfork.registered_names().count("test.dup") == 1

    def test_generation_zero_in_parent(self):
        assert postfork.generation() == 0


class TestForkResets:
    def test_child_rebuilds_singletons_parent_untouched(self):
        from brpc_tpu.butil.iobuf import pool
        from brpc_tpu.fiber.scheduler import global_control
        from brpc_tpu.fiber.timer import global_timer
        from brpc_tpu.transport.event_dispatcher import global_dispatcher
        from brpc_tpu.transport.socket_map import global_socket_map

        # the objects, not their ids: a singleton the child dropped is
        # freed there, and its successor may be given the same address
        # ("socket_map inherited", about one whole run in ten)
        parent = {
            "dispatcher": global_dispatcher(),
            "control": global_control(),
            "timer": global_timer(),
            "socket_map": global_socket_map(),
        }
        before_misses = pool.misses

        def check():
            problems = []
            if global_dispatcher() is parent["dispatcher"]:
                problems.append("dispatcher inherited")
            if global_control() is parent["control"]:
                problems.append("control inherited")
            if global_timer() is parent["timer"]:
                problems.append("timer inherited")
            if global_socket_map() is parent["socket_map"]:
                problems.append("socket_map inherited")
            if pool.misses != 0 or pool.hits != 0:
                problems.append("iobuf pool stats inherited")
            if postfork.generation() != 1:
                problems.append(f"generation {postfork.generation()}")
            if postfork.reset_errors():
                problems.append("reset errors: "
                                + ";".join(postfork.reset_errors()))
            return "; ".join(problems) or "OK"

        assert _run_in_fork(check) == "OK"
        # the PARENT's singletons and stats are untouched
        assert global_dispatcher() is parent["dispatcher"]
        assert global_control() is parent["control"]
        assert pool.misses == before_misses
        assert postfork.generation() == 0

    def test_child_can_serve_rpc_after_fork(self):
        """The whole point: a forked child builds a working private
        stack — spawn a fiber, run a timer sleep, allocate pooled
        blocks — with zero inherited machinery."""

        def check():
            import time as _time

            from brpc_tpu.butil.iobuf import IOBuf
            from brpc_tpu.fiber import global_control
            from brpc_tpu.fiber.timer import global_timer

            box = {}

            def work():
                box["ran"] = True

            f = global_control().spawn(work)
            if not f.join(5) or not box.get("ran"):
                return "fiber never ran in child"
            fired = []
            global_timer().schedule_after(0.05, lambda: fired.append(1))
            deadline = _time.monotonic() + 5
            while not fired and _time.monotonic() < deadline:
                _time.sleep(0.01)
            if not fired:
                return "timer never fired in child"
            buf = IOBuf()
            buf.append(b"x" * 8192)
            if buf.to_bytes() != b"x" * 8192:
                return "iobuf broken in child"
            return "OK"

        assert _run_in_fork(check) == "OK"

    def test_subprocess_spawn_does_not_reset(self):
        """fork+exec tools (subprocess.Popen) must NOT trigger the
        reset handlers — only real os.fork children (shard workers)
        pay them. A spawned interpreter starts at generation 0 by
        construction; this pins that the PARENT-side registry stays
        quiet across Popen."""
        import subprocess

        gen0 = postfork.generation()
        proc = subprocess.run(
            [sys.executable, "-c", "print('spawned')"],
            capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0
        assert postfork.generation() == gen0
