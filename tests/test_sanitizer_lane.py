"""Tier-2 sanitizer lane: rebuild the native artifacts under
ASan/UBSan and re-run the differential fuzzers against them.

The fast lanes are C++ fed by attacker-controlled bytes; the pure
fuzzers prove *semantic* robustness but memory errors that do not
change observable behavior (one-byte overreads, uninitialized loads,
UB the optimizer tolerates today) ship silently. This lane rebuilds
``libbrpc_tpu_native.san.so`` / ``_brpc_fastcore.san.so`` with
``-fsanitize=address,undefined`` and re-runs the decoder fuzz,
protocol fuzz and native suites in a subprocess whose interpreter
preloads the sanitizer runtimes — any diagnosis aborts the child and
fails here with the report in the assertion message.

Marked ``slow`` (tier-2): the rebuild + instrumented run costs tens of
seconds and tier-1 must stay fast. Run directly with:
    python -m pytest tests/test_sanitizer_lane.py -m slow
or via the preflight gate's smoke-build (tools/preflight.py --gate).
"""

import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SAN = ("address", "undefined")

# the differential fuzz surface the ISSUE pins to this lane (the
# hotpath class covers scan_frames' materialize mode — the batched
# scan's payload/attachment slicing runs in C and must fuzz
# instrumented)
FUZZ_TARGETS = ["tests/test_decoder_fuzz.py", "tests/test_protocol_fuzz.py",
                "tests/test_native.py",
                "tests/test_hotpath_batching.py::TestBatchedScanDifferential"]
# engagement/wiring assertions that are timing-sensitive under the
# sanitizers' ~2-10x slowdown (burst accumulation); they are perf-path
# wiring checks, not memory-safety differentials — tier-1 covers them
# uninstrumented
DESELECT = ["tests/test_native.py::TestBatchParseWired::"
            "test_burst_correctness_with_batch_parse"]


def _toolchain_ready():
    from brpc_tpu.native.build import sanitizer_toolchain_missing
    return not sanitizer_toolchain_missing(SAN)


@pytest.mark.slow
@pytest.mark.sanitize
def test_differential_fuzzers_pass_under_asan_ubsan():
    from brpc_tpu.native.build import build, build_fastcore, sanitizer_env
    if not _toolchain_ready():
        pytest.skip("no g++/libasan/libubsan toolchain")
    # build both artifacts instrumented (separate .san.so cache — the
    # plain lane's artifacts stay untouched)
    lib = build(sanitize=SAN)
    fast = build_fastcore(sanitize=SAN)
    assert lib.endswith(".san.so") and os.path.exists(lib)
    assert fast.endswith(".san.so") and os.path.exists(fast)

    env = dict(os.environ)
    env.update(sanitizer_env(SAN))
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "pytest", *FUZZ_TARGETS, "-q",
           "-p", "no:cacheprovider", "-p", "no:randomly"]
    for d in DESELECT:
        cmd += ["--deselect", d]
    proc = subprocess.run(cmd, cwd=REPO_ROOT, env=env,
                          capture_output=True, text=True, timeout=540)
    tail = (proc.stdout + proc.stderr)[-4000:]
    assert proc.returncode == 0, \
        f"differential fuzzers failed under {','.join(SAN)}:\n{tail}"
    # the child must have actually exercised the sanitized artifacts
    # (a missing extension would silently fall back to pure Python and
    # prove nothing)
    probe = subprocess.run(
        [sys.executable, "-c",
         "from brpc_tpu.native import fastcore; m = fastcore.get(); "
         "print(getattr(m, '__file__', ''))"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert ".san.so" in probe.stdout, \
        f"sanitized extension not loaded:\n{probe.stdout}\n{probe.stderr}"


def test_sanitize_mode_parsing_and_artifact_paths():
    """Cheap invariants of the lane plumbing (no build, no subprocess:
    safe for any tier)."""
    from brpc_tpu.native.build import (FASTCORE_PATH, LIB_PATH, _san_path,
                                       sanitize_mode)
    assert sanitize_mode("") == ()
    assert sanitize_mode("address") == ("address",)
    assert sanitize_mode("address, undefined") == ("address", "undefined")
    assert sanitize_mode("undefined,address,undefined") == \
        ("undefined", "address")
    with pytest.raises(ValueError):
        sanitize_mode("adress")   # typo must not silently drop coverage
    assert _san_path(LIB_PATH, ()) == LIB_PATH
    assert _san_path(LIB_PATH, ("address",)).endswith(
        "libbrpc_tpu_native.san.so")
    assert _san_path(FASTCORE_PATH, SAN).endswith("_brpc_fastcore.san.so")


def test_sanitize_typo_raises_on_every_loader_call():
    """A misspelled BRPC_TPU_SANITIZE must raise from the native
    loaders on EVERY call — never latch into the silent pure-Python
    fallback while the run claims sanitizer coverage."""
    code = (
        "import os; os.environ['BRPC_TPU_SANITIZE'] = 'adress'\n"
        "from brpc_tpu.native import fastcore\n"
        "import brpc_tpu.native as native\n"
        "for loader in (fastcore.get, fastcore.get, native.lib):\n"
        "    try:\n"
        "        loader()\n"
        "    except ValueError:\n"
        "        continue\n"
        "    raise SystemExit('typo swallowed by ' + repr(loader))\n"
        "print('ok')\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0 and "ok" in proc.stdout, \
        proc.stdout + proc.stderr


def test_sanitize_env_change_after_latch_raises():
    """Setting BRPC_TPU_SANITIZE after the loaders have latched their
    plain-lane cache must raise on the next call — the cached
    uninstrumented artifact must never be served as sanitized."""
    code = (
        "import os\n"
        "from brpc_tpu.native import fastcore\n"
        "import brpc_tpu.native as native\n"
        "fastcore.get(); native.lib()\n"   # latch the plain lane
        "os.environ['BRPC_TPU_SANITIZE'] = 'address'\n"
        "for loader in (fastcore.get, fastcore.get, native.lib):\n"
        "    try:\n"
        "        loader()\n"
        "    except RuntimeError as e:\n"
        "        assert 'changed' in str(e), e\n"
        "        continue\n"
        "    raise SystemExit('stale cache served by ' + repr(loader))\n"
        "print('ok')\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0 and "ok" in proc.stdout, \
        proc.stdout + proc.stderr


def test_no_native_plus_sanitize_is_a_loud_conflict():
    """BRPC_TPU_NO_NATIVE must not short-circuit past sanitize
    enforcement: disabling the native lane while BRPC_TPU_SANITIZE is
    set would run pure Python under a sanitized-looking env."""
    code = (
        "import os\n"
        "os.environ['BRPC_TPU_SANITIZE'] = 'address'\n"
        "os.environ['BRPC_TPU_NO_NATIVE'] = '1'\n"
        "from brpc_tpu.native import fastcore\n"
        "import brpc_tpu.native as native\n"
        "for loader in (fastcore.get, native.lib, native.lib):\n"
        "    try:\n"
        "        loader()\n"
        "    except RuntimeError as e:\n"
        "        assert 'BRPC_TPU_NO_NATIVE' in str(e), e\n"
        "        continue\n"
        "    raise SystemExit('silent fallback in ' + repr(loader))\n"
        "print('ok')\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0 and "ok" in proc.stdout, \
        proc.stdout + proc.stderr


def test_sanitized_load_failure_raises_not_silent_fallback():
    """A VALID sanitize mode whose artifact fails to build or load must
    raise from the loaders on every call — the uninstrumented
    pure-Python fallback would pass the run off as sanitized with zero
    coverage (the classic failure mode: .san.so built but the sanitizer
    runtime is not LD_PRELOADed into a stock interpreter)."""
    code = (
        "import os; os.environ['BRPC_TPU_SANITIZE'] = 'address'\n"
        "import brpc_tpu.native.build as b\n"
        "def boom(*a, **k): raise OSError('sabotaged build')\n"
        "b.build = b.build_fastcore = boom\n"
        "from brpc_tpu.native import fastcore\n"
        "import brpc_tpu.native as native\n"
        "for loader in (fastcore.get, fastcore.get, native.lib,\n"
        "               native.lib):\n"
        "    try:\n"
        "        loader()\n"
        "    except RuntimeError as e:\n"
        "        assert 'BRPC_TPU_SANITIZE' in str(e), e\n"
        "        continue\n"
        "    raise SystemExit('silent fallback in ' + repr(loader))\n"
        "print('ok')\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0 and "ok" in proc.stdout, \
        proc.stdout + proc.stderr
