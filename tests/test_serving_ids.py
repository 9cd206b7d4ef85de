"""Token ids past the byte vocabulary on every Generate transport: a
request may give ``tokens``; the unary reply carries ids (``text`` only
in the byte vocabulary); a streamed or HTTP-chunked token is its id as
4 little-endian bytes where the vocabulary exceeds 256."""

import http.client
import json
import struct
import time

import pytest

from brpc_tpu.rpc import Channel, Server, ServerOptions
from brpc_tpu.rpc import errno_codes as berr
from brpc_tpu.rpc.controller import Controller
from brpc_tpu.rpc.stream import StreamOptions
from brpc_tpu.serving import (TinyDecoder, TinyDecoderConfig,
                              add_generate_service)

PROMPT = [300, 7, 511, 256, 42]


@pytest.fixture(scope="module")
def wide():
    """A server whose model speaks 512 ids, and the model's oracle."""
    model = TinyDecoder(TinyDecoderConfig(vocab=512, cache_len=96))
    server = Server(ServerOptions(enable_builtin_services=False))
    gs = add_generate_service(server, model=model, warmup=True)
    ep = server.start("tcp://127.0.0.1:0")
    try:
        yield gs, ep, model.generate(PROMPT, 24)
    finally:
        server.stop()
        server.join(2)


def _unary(ep, body: dict, timeout_ms: int = 20000):
    ch = Channel(str(ep))
    try:
        cntl = Controller()
        cntl.timeout_ms = timeout_ms
        return ch.call_sync("GenerateService", "Generate",
                            json.dumps(body).encode(), cntl=cntl)
    finally:
        ch.close()


def test_unary_reply_carries_ids_past_255(wide):
    gs, ep, oracle = wide
    assert any(t > 255 for t in oracle)
    cntl = _unary(ep, {"tokens": PROMPT, "max_tokens": 24})
    assert not cntl.failed(), cntl.error_text
    doc = json.loads(cntl.response_payload.to_bytes())
    assert doc == {"status": "completed", "n": 24, "tokens": oracle}
    # an id the vocabulary does not hold is refused at the door
    bad = _unary(ep, {"tokens": [5, 512], "max_tokens": 2})
    assert bad.failed() and bad.error_code == berr.EREQUEST


def test_streamed_token_is_four_little_endian_bytes(wide):
    gs, ep, oracle = wide
    frames, done = [], []

    def on_frame(_s, msg):
        p = msg.payload.to_bytes()
        (frames if p[:1] == b"t" else done).append(p)

    ch = Channel(str(ep))
    try:
        cntl = Controller()
        cntl.timeout_ms = 20000
        cntl = ch.call_sync(
            "GenerateService", "Generate",
            json.dumps({"tokens": PROMPT, "max_tokens": 24}).encode(),
            cntl=cntl, stream_options=StreamOptions(on_received=on_frame))
        assert not cntl.failed(), cntl.error_text
        deadline = time.monotonic() + 15
        while not done and time.monotonic() < deadline:
            time.sleep(0.005)
    finally:
        ch.close()
    assert done and done[0][:1] == b"d"
    assert all(len(f) == 5 for f in frames)
    assert [struct.unpack("<I", f[1:])[0] for f in frames] == oracle


def test_http_chunked_token_is_four_little_endian_bytes(wide):
    gs, ep, oracle = wide
    conn = http.client.HTTPConnection(ep.host, ep.port, timeout=15)
    try:
        conn.request("POST", "/GenerateService/Generate",
                     body=json.dumps({"tokens": PROMPT, "max_tokens": 24}))
        resp = conn.getresponse()
        assert resp.status == 200
        body = resp.read()
    finally:
        conn.close()
    payload, _, footer = body.rpartition(b"\n#")
    assert footer == b"completed n=24"
    assert list(struct.unpack(f"<{len(payload) // 4}I", payload)) == oracle


def test_byte_vocabulary_keeps_text_and_one_byte_frames():
    """The toy of the byte vocabulary answers as before: ``text`` beside
    the ids, and ``tokens`` in a request are ids like the prompt's
    bytes."""
    server = Server(ServerOptions(enable_builtin_services=False))
    add_generate_service(server, cache_len=96, warmup=True)
    ep = server.start("tcp://127.0.0.1:0")
    try:
        by_text = _unary(ep, {"prompt": "ids", "max_tokens": 6})
        by_ids = _unary(ep, {"tokens": list(b"ids"), "max_tokens": 6})
        for cntl in (by_text, by_ids):
            assert not cntl.failed(), cntl.error_text
        a = json.loads(by_text.response_payload.to_bytes())
        b = json.loads(by_ids.response_payload.to_bytes())
        assert a == b and "text" in a and len(a["tokens"]) == 6
    finally:
        server.stop()
        server.join(2)


@pytest.mark.parametrize("ids", ["int32", "float32"])
def test_a_prompt_attached_as_one_array(ids):
    """A call over the device lane may attach its prompt as one integer
    array (the JSON body then gives the budget): its ids are the prompt.
    An array of another kind is refused at the door."""
    import numpy as np

    model = TinyDecoder(TinyDecoderConfig(vocab=512, cache_len=96))
    server = Server(ServerOptions(enable_builtin_services=False))
    add_generate_service(server, model=model, warmup=True)
    ep = server.start("ici://127.0.0.1:0#device=0")
    ch = Channel(f"ici://127.0.0.1:{ep.port}#reply_device=0")
    try:
        cntl = Controller()
        cntl.timeout_ms = 20000
        cntl = ch.call_sync(
            "GenerateService", "Generate",
            json.dumps({"max_tokens": 24}).encode(), cntl=cntl,
            request_device_arrays=[np.asarray(PROMPT, getattr(np, ids))])
    finally:
        ch.close()
        server.stop()
        server.join(2)
    if ids == "float32":
        assert cntl.failed() and cntl.error_code == berr.EREQUEST
        return
    assert not cntl.failed(), cntl.error_text
    doc = json.loads(cntl.response_payload.to_bytes())
    assert doc["tokens"] == model.generate(PROMPT, 24)
