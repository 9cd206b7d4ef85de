"""Bring-up guards (PR 21): nothing on the chip path hides the device.

CPU tier-1 checks of what ``chip_smoke.py`` relies on: an endpoint's
``#device=K`` out of range is an error, the compile cache lands where
the environment says, a fork after the transfer server started leaves a
live child, the native artifacts are keyed by content, and the smoke
itself runs in rehearsal (and refuses the CPU without ``--rehearse``).
"""

import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(argv, env=None, timeout=300):
    full_env = dict(os.environ)
    full_env.update(env or {})
    return subprocess.run([sys.executable, *argv], cwd=REPO, env=full_env,
                          capture_output=True, text=True, timeout=timeout)


# ------------------------------------------------------- device ordinals

class TestDeviceOrdinalOutOfRange:
    def test_ici_listen_and_connect_refuse(self):
        from brpc_tpu.rpc import Channel, Server, ServerOptions

        server = Server(ServerOptions(enable_builtin_services=False))
        with pytest.raises(ValueError, match="names device 64"):
            server.start("ici://127.0.0.1:0#device=64")
        ep = server.start("ici://127.0.0.1:0#device=0")
        try:
            ch = Channel(f"ici://127.0.0.1:{ep.port}#reply_device=64")
            cntl = ch.call_sync("Nope", "Nope", b"")
            assert cntl.failed()
            assert "names device 64" in cntl.error_text
        finally:
            server.stop()
            server.join(2)

    def test_tpu_listen_and_connect_refuse(self):
        from brpc_tpu.butil.endpoint import str2endpoint
        from brpc_tpu.transport.tpu import TpuTransport

        t = TpuTransport()
        with pytest.raises(ValueError, match="names device 64"):
            t.listen(str2endpoint("tpu://oor:1#device=64"), lambda c: None)
        lst = t.listen(str2endpoint("tpu://oor:1#device=0"), lambda c: None)
        try:
            with pytest.raises(ValueError, match="names device 64"):
                t.connect(str2endpoint("tpu://oor:1#device=64"))
            with pytest.raises(ValueError, match="names device 64"):
                t.connect(str2endpoint(
                    "tpu://oor:1#device=0&reply_device=64"))
        finally:
            lst.stop()

    def test_in_range_ordinal_is_that_device(self):
        import jax
        from brpc_tpu.butil.jax_runtime import local_device

        assert local_device(3) == jax.devices()[3]
        assert local_device(None) == jax.devices()[0]


# --------------------------------------------------------- compile cache

_CACHE_PROBE = (
    "import json, sys; sys.path.insert(0, %r); import jax; "
    "from brpc_tpu.butil import jax_runtime as r; "
    "p = r.ensure_compile_cache(); "
    "print(json.dumps({'ret': p, 'default': r.DEFAULT_CACHE_DIR, "
    "'cfg': jax.config.jax_compilation_cache_dir, "
    "'min_s': jax.config.jax_persistent_cache_min_compile_time_secs, "
    "'env': __import__('os').environ.get('JAX_COMPILATION_CACHE_DIR')}))"
    % REPO)


class TestCompileCachePlacement:
    """Each case in its own interpreter: the function edits process-wide
    JAX config, and never initializes a backend."""

    def _probe(self, env):
        clean = {k: v for k, v in os.environ.items()
                 if not k.startswith("JAX_")}
        clean.update(env)
        p = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=clean,
                           capture_output=True, text=True, timeout=120)
        assert p.returncode == 0, p.stderr[-2000:]
        return json.loads(p.stdout.strip().splitlines()[-1])

    def test_env_is_honoured_and_no_other_dir_is_set(self, tmp_path):
        d = str(tmp_path / "cc")
        got = self._probe({"JAX_COMPILATION_CACHE_DIR": d})
        assert got["ret"] == d and got["cfg"] == d and got["env"] == d
        assert got["min_s"] == 0.0

    def test_unset_gives_the_fixed_path_and_exports_it(self):
        got = self._probe({})
        assert got["default"] == os.path.join(REPO, ".jax_cache")
        assert got["ret"] == got["cfg"] == got["env"] == got["default"]

    def test_cpu_pinned_process_gets_no_default_cache(self):
        got = self._probe({"JAX_PLATFORMS": "cpu"})
        assert got["ret"] is None and got["cfg"] is None
        assert got["env"] is None

    def test_operator_threshold_is_left_alone(self, tmp_path):
        got = self._probe({
            "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc"),
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "2.5"})
        assert got["min_s"] == 2.5

    def test_no_cache_path_from_tempfile_pid_or_clock(self):
        src = open(os.path.join(REPO, "brpc_tpu", "butil",
                                "jax_runtime.py")).read()
        for word in ("tempfile", "getpid", "time."):
            assert word not in src


# ------------------------------------------------------------------ fork

_FORK_PROBE = """
import os, sys, time
sys.path.insert(0, %r)
from brpc_tpu.transport import ici
from brpc_tpu.butil import postfork
ici._get_transfer_server()
assert ici.transfer_lane_status() == "up", ici.transfer_lane_status()
pid = os.fork()
if pid == 0:
    # register_at_fork already ran reset_all() in this child
    ok = postfork.generation() == 1 and not postfork.reset_errors() \\
        and ici.transfer_lane_status() == "not started"
    postfork.reset_all()          # and it is safe to run again
    # shard workers leave through os._exit: a forked child of ANY
    # jax-initialized process crashes in jax's own atexit otherwise
    os._exit(0 if ok else 3)
deadline = time.monotonic() + 30
while time.monotonic() < deadline:
    done, status = os.waitpid(pid, os.WNOHANG)
    if done:
        print("CHILD", status)
        sys.exit(0)
    time.sleep(0.05)
os.kill(pid, 9)
os.waitpid(pid, 0)
print("CHILD hung")
""" % REPO


def test_fork_after_transfer_server_leaves_a_live_child():
    """Regression (jaxlib 0.9): the child's postfork reset dropped the
    parent's TransferServer handle and its destructor crashed or hung
    the child — the product path Server.start(num_shards=N) in any
    process that had used ici://."""
    p = subprocess.run([sys.executable, "-c", _FORK_PROBE],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip().splitlines()[-1] == "CHILD 0", p.stdout


# ---------------------------------------------------- native content hash

class TestNativeKeyedByContent:
    def _compile(self, src, out):
        from brpc_tpu.native import build
        return build._compile("test", [build.CXX, "-shared", "-fPIC"],
                              [str(src)], str(out), False)

    def test_mtime_alone_does_not_rebuild_a_byte_does(self, tmp_path):
        from brpc_tpu.native import build
        import shutil
        if shutil.which(build.CXX) is None:
            pytest.skip("no compiler")
        src = tmp_path / "t.cc"
        out = tmp_path / "t.so"
        src.write_text('extern "C" int answer() { return 41; }\n')
        self._compile(src, out)
        first = os.stat(out)
        tag = open(str(out) + ".tag").read()
        assert len(tag) == 64

        # newer mtime, same bytes: current
        later = time.time() + 100
        os.utime(src, (later, later))
        self._compile(src, out)
        assert os.stat(out).st_ino == first.st_ino
        assert os.stat(out).st_mtime_ns == first.st_mtime_ns

        # older artifact mtime would have looked "fresh"; one byte
        # changed: rebuilt, new tag
        src.write_text('extern "C" int answer() { return 42; }\n')
        os.utime(src, (1, 1))
        self._compile(src, out)
        assert open(str(out) + ".tag").read() != tag
        import ctypes
        assert ctypes.CDLL(str(out)).answer() == 42

    def test_flags_are_part_of_the_key(self, tmp_path):
        from brpc_tpu.native import build
        src = tmp_path / "t.cc"
        src.write_text("int x;\n")
        a = build._build_key(["g++", "-O2"], [str(src)])
        b = build._build_key(["g++", "-O2", "-fsanitize=address"],
                             [str(src)])
        assert a != b

    def test_loaded_artifacts_match_the_sources_on_disk(self):
        from brpc_tpu import native
        from brpc_tpu.native import build, fastcore
        if not (native.available() and fastcore.available()):
            pytest.skip("native lane unavailable")
        key = build._build_key([build.CXX, *build._cxxflags(())],
                               build.sources())
        assert open(build.LIB_PATH + ".tag").read() == key


# ------------------------------------------------------------ chip_smoke

def _summary(proc):
    """The report line; the verdict after it must agree and carry the
    driver's keys, no others."""
    report, verdict = map(json.loads, proc.stdout.strip().splitlines()[-2:])
    assert list(verdict) == ["ok", "device"]
    assert list(verdict["device"]) == ["platform", "kind", "count"]
    assert verdict == {"ok": report["ok"], "device": report["device"]}
    assert isinstance(verdict["ok"], bool)
    assert isinstance(verdict["device"]["count"], int)
    return report


class TestChipSmoke:
    def test_rehearsal_runs_every_phase(self):
        p = _run([SMOKE, "--rehearse"])
        assert p.returncode == 0, p.stderr[-3000:]
        doc = _summary(p)
        assert doc["ok"] is True and doc["rehearsal"] is True
        assert doc["device"]["platform"] == "cpu"
        assert doc["claim"] is None and list(doc)[-1] == "claim"
        assert set(doc["phases"]) == {"fabric", "serving", "kernel",
                                      "four_chips"}
        assert all(ph["ok"] is True for ph in doc["phases"].values())
        assert doc["native"] and doc["fastcore"]
        fab = doc["phases"]["fabric"]
        assert fab["transports"]["ici"]["lane_kind"] == "local-d2d"
        assert fab["transfer_lane"] == "up"
        assert fab["device_cells_final"]["failed"] == 0
        assert fab["stager"]["staged_count"] > 0
        assert doc["phases"]["kernel"]["backend"] == "pallas_interpret"
        four = doc["phases"]["four_chips"]
        assert four["collective_fused"] == 3
        assert four["collective_fallbacks"] == 0
        assert len(set(four["responses_landed_on"])) == 4

    def test_cpu_without_rehearse_is_a_failure_with_no_result(self):
        p = _run([SMOKE], env={"JAX_PLATFORMS": "cpu"})
        assert p.returncode != 0
        assert p.stdout.strip() == ""
        assert "not a TPU" in p.stderr

    def test_require_chips_is_enforced(self):
        p = _run([SMOKE, "--rehearse", "--require-chips", "64",
                  "--only", "kernel"])
        assert p.returncode != 0 and p.stdout.strip() == ""

    @pytest.mark.parametrize("phase", ["fabric", "serving", "kernel",
                                       "four_chips"])
    def test_a_failed_phase_fails_the_run(self, phase):
        p = _run([SMOKE, "--rehearse", "--only", phase,
                  "--inject-failure", phase])
        assert p.returncode == 1
        doc = _summary(p)
        assert doc["ok"] is False and doc["problems"] == [phase]
        assert doc["phases"][phase]["ok"] is False

    def test_compile_cache_lands_where_the_environment_says(self, tmp_path):
        d = tmp_path / "cc"
        env = {"JAX_COMPILATION_CACHE_DIR": str(d)}
        first = _summary(_run([SMOKE, "--rehearse", "--only", "serving"],
                              env=env))
        assert first["ok"] and first["compile_cache"]["dir"] == str(d)
        assert first["compile_cache"]["misses"] > 0
        assert first["compile_cache"]["entries_after"] > 0
        assert os.listdir(d)
        second = _summary(_run([SMOKE, "--rehearse", "--only", "serving"],
                               env=env))
        assert second["ok"] and second["compile_cache"]["hits"] > 0
        assert not os.path.exists(os.path.join(REPO, ".jax_cache"))

    def test_alone_in_a_directory_it_fails(self, tmp_path):
        import shutil
        shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
        p = subprocess.run(
            [sys.executable, "chip_smoke.py", "--rehearse"], cwd=tmp_path,
            env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
            capture_output=True, text=True, timeout=120)
        assert p.returncode != 0 and p.stdout.strip() == ""
