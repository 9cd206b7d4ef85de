"""Example-rot guard: the fast examples run inside the suite (conftest
already forces the 8-device CPU mesh), imported as modules and driven
with small parameters — the reference uses example/multi_threaded_echo
as its own smoke test (SURVEY.md §4)."""

import importlib.util
import os
import sys

import pytest

_EXAMPLES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples")


def _load(name):
    path = os.path.join(_EXAMPLES, name, "main.py")
    spec = importlib.util.spec_from_file_location(f"example_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_multi_threaded_echo_example():
    _load("multi_threaded_echo").main(n_fibers=4, seconds=0.5)


def test_http_progressive_example():
    _load("http_progressive").main(total_mb=1)


def test_parallel_allreduce_example(capsys):
    _load("parallel_allreduce").main()
    out = capsys.readouterr().out
    assert "sum=65536" in out


def test_long_context_example():
    _load("long_context").main(seq=256)


def test_auth_example():
    _load("auth").main()


def test_backup_request_example():
    _load("backup_request").main()


@pytest.mark.parametrize("device_frames", ["", "device_frames"])
def test_streaming_echo_example(device_frames, capsys):
    _load("streaming_echo").main(n_frames=5, device_frames=device_frames)
    out = capsys.readouterr().out
    assert "got 5 echoes" in out
    assert ("5 device frames came back" in out) == bool(device_frames)


def test_inference_serving_example(capsys):
    _load("inference_serving").main(max_tokens=6)
    out = capsys.readouterr().out
    assert "[done: 6 tokens]" in out


def _run_serving_example(name, monkeypatch, **kw):
    """Examples that end in run_until_asked_to_quit(): stub the serve
    loop so the rot guard exercises their full setup + self-drive and
    returns (their own clients already ran by that point)."""
    from brpc_tpu.rpc.server import Server

    stopped = []

    def fake_serve(self):
        self.stop()
        self.join(2)
        stopped.append(True)

    monkeypatch.setattr(Server, "run_until_asked_to_quit", fake_serve)
    _load(name).main(**kw)
    assert stopped


def test_redis_kv_example(monkeypatch, capsys):
    _run_serving_example("redis_kv", monkeypatch,
                         addr="tcp://127.0.0.1:0")
    out = capsys.readouterr().out
    assert "GET greeting       -> b'hello'" in out or "hello" in out


def test_thrift_echo_example(monkeypatch, capsys):
    _run_serving_example("thrift_echo", monkeypatch,
                         addr="tcp://127.0.0.1:0")
    assert b"hello thrift".decode() in capsys.readouterr().out


def test_rtmp_relay_example(capsys):
    _load("rtmp_relay").main(addr="tcp://127.0.0.1:0")
    assert "player received" in capsys.readouterr().out
