"""GenerateService: the streaming front-end of the serving lane.

One registered method, three transports, one batcher behind them all:

  * **tpu_std streaming** — the client attaches a Stream to the
    Generate call; the handler admits the request and returns
    ``b"accepted"`` immediately. Tokens ride back as credit-controlled
    stream frames AS THEY DECODE (time-to-first-token = the prefill in
    the first step after admission, not batch completion). Frame
    payloads are tagged: ``t<id>`` one token (the id as one byte where
    the model's vocabulary is the byte one, else as 4 little-endian
    bytes), ``d<json>`` done summary, ``e<errno>`` terminal error
    (deadline eviction sends ``e1008``);
  * **HTTP** — the same method over ``POST /GenerateService/Generate``
    streams tokens as chunked-transfer bytes (one a token, or 4
    little-endian a token past the byte vocabulary) through a
    ProgressiveAttachment, with a trailing ``\\n#<state> ...`` status
    line (chunked bodies cannot carry a late status code). A dead peer
    flips ``pa.write()`` to False — the feeder cancels the sequence
    and the KV slot frees (the progressive dead-peer fix exists for
    exactly this loop);
  * **unary** — a plain tpu_std call parks its handler fiber until the
    sequence retires and returns every token in one JSON response:
    ``tokens`` as ids, and ``text`` where the vocabulary is the byte
    one (deadline eviction fails the call with ``ERPCTIMEDOUT``).

Request body: JSON ``{"prompt": str, "max_tokens": int,
"stop_token": int?}`` (prompt bytes ARE the tokens), or ``{"tokens":
[int, ...], ...}`` (token ids below the model's vocabulary), or a bare
byte string treated as the prompt with the default token budget. A
call that carries a device array (``request_device_arrays``, one
integer vector) takes its prompt ids from it, the JSON body then giving
only the budget: ids born on the device stay ids.

The model: ``GenerateService(model=...)`` takes a model (the interface
in ``serving/model.py``) or a factory that makes one; each server start
builds its replica from it. The default is the TinyDecoder the
``serving_*`` flags shape.

Wiring: ``add_generate_service(server)`` registers the service and
arms the engine lifecycle — ``Server.start`` builds a FRESH
model/batcher/engine and registers it as a WorkerModule (in a shard
group each forked worker does this post-fork, so every shard owns a
private replica), ``Server.stop`` unregisters and drains it.
"""

from __future__ import annotations

import json
import struct
import threading
import time
from collections import deque
from typing import List, Optional, Tuple

from brpc_tpu.butil.flags import define_flag, flag
from brpc_tpu.fiber.sync import FiberEvent
from brpc_tpu.fiber.worker_module import register_module, unregister_module
from brpc_tpu.rpc import errno_codes as berr
from brpc_tpu.rpc.service import Service
from brpc_tpu.rpc.stream import StreamOptions, stream_accept

from . import serving_stats as _sstats
from .batcher import (CANCELED, COMPLETED, EVICTED, ContinuousBatcher,
                      GenRequest, RequestTooLong, expose_serving_vars)
from .engine import ServingEngine
from .model import TinyDecoder, TinyDecoderConfig

define_flag("serving_max_batch", 8,
            "KV slots per serving engine replica (the continuous "
            "batch's max size)")
define_flag("serving_cache_len", 160,
            "tokens of KV capacity per slot (prompt + generation)")
define_flag("serving_max_waiting", 32,
            "bounded admission queue behind the KV slots; submits past "
            "this shed immediately (ELIMIT)")
define_flag("serving_default_max_tokens", 32,
            "token budget for requests that don't name one")
define_flag("serving_warmup", True,
            "run one throwaway decode step at server start so the "
            "first request's TTFT measures scheduling, not XLA compile")

_ID = struct.Struct("<I")

# pending-frame cap for a stream consumer that stopped granting
# credits: past this the sequence is canceled (a slow reader must not
# pin a KV slot forever)
_MAX_PENDING_FRAMES = 512


def _parse_request(body, vocab: int = 256, device_tokens=None
                   ) -> Tuple[List[int], int, Optional[int]]:
    """(prompt ids, token budget, stop token) of a request; ValueError
    for one the service refuses. ``device_tokens`` are the ids the call
    carried as a device array."""
    raw = bytes(body) if not isinstance(body, bytes) else body
    max_tokens = int(flag("serving_default_max_tokens"))
    stop_token = None
    tokens = device_tokens
    if raw[:1] == b"{":
        try:
            doc = json.loads(raw.decode("utf-8"))
        except (ValueError, UnicodeDecodeError) as e:
            raise ValueError(f"bad request json: {e}")
        if tokens is not None:
            pass
        elif "tokens" in doc:
            tokens = doc["tokens"]
            if not isinstance(tokens, list) or not all(
                    isinstance(t, int) and not isinstance(t, bool)
                    for t in tokens):
                raise ValueError("'tokens' must be a list of ints")
        else:
            prompt = doc.get("prompt", "")
            if not isinstance(prompt, str) or not prompt:
                raise ValueError(
                    "request needs a non-empty 'prompt' string or "
                    "'tokens' list")
            tokens = list(prompt.encode("utf-8"))
        if "max_tokens" in doc:
            max_tokens = int(doc["max_tokens"])
        if doc.get("stop_token") is not None:
            stop_token = int(doc["stop_token"])
    elif tokens is None:
        tokens = list(raw)
    if not tokens:
        raise ValueError("empty prompt")
    if min(tokens) < 0 or max(tokens) >= vocab:
        raise ValueError(f"token ids must lie in [0, {vocab})")
    if max_tokens < 1:
        raise ValueError("max_tokens must be >= 1")
    return tokens, max_tokens, stop_token


def _device_tokens(cntl) -> Optional[List[int]]:
    """The prompt ids a call carried as its one device array (None
    where it carried none)."""
    arrs = getattr(cntl, "request_device_arrays", None)
    if not arrs:
        return None
    import numpy as np
    ids = np.asarray(arrs[0])
    if len(arrs) != 1 or ids.ndim != 1 \
            or not np.issubdtype(ids.dtype, np.integer):
        raise ValueError("a prompt on the device is one integer vector")
    return ids.tolist()


def token_bytes(tok: int, vocab: int) -> bytes:
    """A token on the wire: one byte in the byte vocabulary, else its id
    as 4 little-endian bytes."""
    if vocab <= 256:
        return bytes((tok & 0xFF,))
    return _ID.pack(tok)


class _StreamSender:
    """Token emitter for the stream path. Runs on the engine's worker
    thread: write_nowait only (never parks a decode slice on credits);
    frames the window can't take queue up and flush before the next
    frame, and a consumer that stops draining past the cap cancels the
    sequence."""

    def __init__(self, stream, batcher: ContinuousBatcher):
        self.stream = stream
        self.batcher = batcher
        self.vocab = batcher.model.vocab
        self.req: Optional[GenRequest] = None   # set right after ctor
        self._pending: deque = deque()
        self._lock = threading.Lock()

    def _dead(self) -> bool:
        return self.stream.closed or self.stream.remote_closed

    def _push(self, payload: bytes) -> bool:
        """Queue + flush under one lock (token order must survive a
        racing finish); False once the stream is unwritable."""
        with self._lock:
            self._pending.append(payload)
            while self._pending:
                if self._dead():
                    return False
                # graftlint: disable=callback-under-lock -- write_nowait
                # never parks (credit check + queue only) and holding
                # _lock here IS the token-order guarantee; the failure
                # path (batcher.cancel) just flips a lock-free flag
                if not self.stream.write_nowait(self._pending[0]):
                    # out of credits (or just died — next call notices)
                    break
                self._pending.popleft()
            return len(self._pending) <= _MAX_PENDING_FRAMES

    def token(self, req: GenRequest, tok: int) -> None:
        if not self._push(b"t" + token_bytes(tok, self.vocab)):
            self.batcher.cancel(req)

    def finish(self, req: GenRequest, state: str) -> None:
        if state == COMPLETED:
            self._push(b"d" + json.dumps(
                {"n": req.ntokens, "status": "completed"}).encode())
        elif state == EVICTED:
            self._push(b"e%d" % req.error_code)
        # CANCELED: the peer is gone — nothing to tell it
        with self._lock:
            leftover = bool(self._pending) and not self._dead()
        if not leftover:
            self.stream.close()
            return
        # the credit window closed on the tail of the stream: this is
        # the LAST push, so nothing will retry the pending frames —
        # without them the client never learns its verdict (the d/e
        # frame is in there). Hand the tail to a fiber that parks on
        # the credit butex properly, then closes.
        from brpc_tpu import fiber

        async def drain_then_close():
            while True:
                with self._lock:
                    if not self._pending or self._dead():
                        break
                    frame = self._pending[0]
                if not await self.stream.write(frame, timeout_s=10.0):
                    break
                with self._lock:
                    if self._pending and self._pending[0] is frame:
                        self._pending.popleft()
            self.stream.close()

        fiber.spawn(drain_then_close)


class _HttpSender:
    """Token emitter for the HTTP chunked path: raw token bytes, then a
    ``\\n#<state>`` status footer (the only way chunked transfer can
    report a post-headers outcome). A dead peer turns pa.write() False
    and cancels the sequence — freeing the KV slot is the whole point
    of observing the disconnect."""

    def __init__(self, pa, batcher: ContinuousBatcher):
        self.pa = pa
        self.batcher = batcher
        self.vocab = batcher.model.vocab

    def token(self, req: GenRequest, tok: int) -> None:
        if not self.pa.write(token_bytes(tok, self.vocab)):
            self.batcher.cancel(req)

    def finish(self, req: GenRequest, state: str) -> None:
        if state != CANCELED:
            footer = f"\n#{state} n={req.ntokens}"
            if req.error_code:
                footer += f" err={req.error_code}"
            self.pa.write(footer.encode())
        self.pa.close()


class GenerateService:
    """Owner of the serving stack on one server: builds the Service to
    register, and the per-start engine lifecycle Server.start/stop
    drive (fresh replica per start — in a shard group that means per
    forked worker, after the postfork registry cleared the parent's
    module registrations)."""

    def __init__(self, max_batch: Optional[int] = None,
                 cache_len: Optional[int] = None,
                 max_waiting: Optional[int] = None,
                 model_seed: Optional[int] = None,
                 warmup: Optional[bool] = None,
                 name: str = "GenerateService", model=None):
        self.name = name
        # a model instance (shared by every start) or a factory called
        # at each start; None: the TinyDecoder of the flags below
        self._model = model
        self._max_batch = max_batch
        self._cache_len = cache_len
        self._max_waiting = max_waiting
        self._model_seed = model_seed
        self._warmup = warmup
        self.batcher: Optional[ContinuousBatcher] = None
        self.engine: Optional[ServingEngine] = None

    # ----------------------------------------------------------- lifecycle
    def _build_model(self):
        if self._model is None:
            cfg = TinyDecoderConfig(
                cache_len=int(self._cache_len
                              if self._cache_len is not None
                              else flag("serving_cache_len")))
            if self._model_seed is not None:
                cfg.seed = self._model_seed
            return TinyDecoder(cfg)
        if hasattr(self._model, "decode"):
            return self._model
        return self._model()

    def on_server_start(self, server) -> None:
        self.batcher = ContinuousBatcher(
            self._build_model(),
            max_batch=int(self._max_batch if self._max_batch is not None
                          else flag("serving_max_batch")),
            max_waiting=int(self._max_waiting
                            if self._max_waiting is not None
                            else flag("serving_max_waiting")),
            wake=server._control.parking_lot.signal)
        self.engine = ServingEngine(self.batcher,
                                    label=f"{self.name}.Generate")
        expose_serving_vars()
        warm = self._warmup if self._warmup is not None \
            else bool(flag("serving_warmup"))
        if warm:
            self.engine.warm_up()
        register_module(self.engine)

    def on_server_stop(self, server) -> None:
        if self.engine is not None:
            unregister_module(self.engine)
        if self.batcher is not None:
            self.batcher.stop()

    # ------------------------------------------------------------- service
    def build_service(self) -> Service:
        svc = Service(self.name)
        svc.register_method("Generate", self._generate)
        svc.register_method("Stats", self._stats)
        return svc

    def _stats(self, cntl, request) -> bytes:
        if self.batcher is None:
            return json.dumps({"enabled": False}).encode()
        return json.dumps(self._payload(), default=str).encode()

    def _payload(self) -> dict:
        out = {"enabled": True, "service": self.name}
        out.update(self.batcher.stats_snapshot())
        out["engine"] = self.engine.snapshot() if self.engine else {}
        # the flight-deck panes (per-method token table, TTFT/TPOT
        # percentiles + pooled reservoirs, step ring) ride the SAME
        # builder — HTTP route, builtin twin and shard dump all read
        # serving_page_payload, so the views cannot diverge
        out["stats"] = _sstats.serving_obs_pane()
        return out

    async def _generate(self, cntl, request):
        batcher = self.batcher
        if batcher is None or batcher.stopped:
            cntl.set_failed(berr.ELOGOFF, "serving engine not running")
            return b""
        try:
            prompt, max_tokens, stop_token = _parse_request(
                request, batcher.model.vocab, _device_tokens(cntl))
        except ValueError as e:
            cntl.set_failed(berr.EREQUEST, str(e))
            return b""
        if getattr(cntl, "_peer_stream_id", 0):
            return self._generate_stream(cntl, batcher, prompt,
                                         max_tokens, stop_token)
        if getattr(cntl, "_server_socket", None) is None:
            return self._generate_http(cntl, batcher, prompt,
                                       max_tokens, stop_token)
        return await self._generate_unary(cntl, batcher, prompt,
                                          max_tokens, stop_token)

    def _submit(self, cntl, batcher, req) -> bool:
        """Shared shed/too-long handling; True when admitted. The
        flight-deck tracker attaches HERE (one flag check per request);
        a request refused at the door settles immediately with its
        cause — everything it spent lands in queue_us."""
        req.tracker = _sstats.open_generation(
            self.name, "Generate", cntl, created_ns=req.created_ns)
        try:
            ok = batcher.submit(req)
        except RequestTooLong as e:
            cntl.set_failed(berr.EREQUEST, str(e))
            if req.tracker is not None:
                req.tracker.gen_settled("rejected",
                                        cause="prompt_too_long",
                                        error_code=berr.EREQUEST)
            return False
        if not ok:
            cntl.set_failed(berr.ELIMIT, "serving queue full (shed)")
            if req.tracker is not None:
                req.tracker.gen_settled(
                    "shed", cause="queue_full",
                    finished_ns=req.finished_ns,
                    error_code=req.error_code or berr.ELIMIT)
            return False
        return True

    def _generate_stream(self, cntl, batcher, prompt, max_tokens,
                         stop_token):
        st = stream_accept(cntl, StreamOptions())
        sender = _StreamSender(st, batcher)
        req = GenRequest(prompt, max_tokens, cntl=cntl,
                         on_token=sender.token, on_finish=sender.finish,
                         stop_token=stop_token)
        sender.req = req
        # client vanished mid-generation (close frame or socket death):
        # free the KV slot at the next step boundary
        st.on_close(lambda _s: batcher.cancel(req))
        if not self._submit(cntl, batcher, req):
            st.close()
            return b""
        return b"accepted"

    def _generate_http(self, cntl, batcher, prompt, max_tokens,
                       stop_token):
        pa = cntl.create_progressive_attachment("application/octet-stream")
        sender = _HttpSender(pa, batcher)
        req = GenRequest(prompt, max_tokens, cntl=cntl,
                         on_token=sender.token, on_finish=sender.finish,
                         stop_token=stop_token)
        if not self._submit(cntl, batcher, req):
            return b""          # cntl failed -> plain HTTP error reply
        return None             # body streams through the attachment

    async def _generate_unary(self, cntl, batcher, prompt, max_tokens,
                              stop_token):
        ev = FiberEvent()
        outcome = {}

        def on_finish(req_, state):
            outcome["state"] = state
            ev.set()

        req = GenRequest(prompt, max_tokens, cntl=cntl,
                         on_finish=on_finish, stop_token=stop_token)
        if not self._submit(cntl, batcher, req):
            return b""
        # the batcher's eviction sweep owns deadline enforcement; the
        # extra 30s is a backstop against a wedged engine, not a budget
        rem = cntl.remaining_ms()
        budget = 30.0 if rem is None else rem / 1e3 + 30.0
        if not await ev.wait(budget):
            batcher.cancel(req)
            cntl.set_failed(berr.EINTERNAL, "serving engine wedged")
            return b""
        state = outcome.get("state")
        if state == EVICTED:
            cntl.set_failed(berr.ERPCTIMEDOUT,
                            "evicted mid-generation (deadline)")
            return b""
        if state != COMPLETED:
            cntl.set_failed(berr.EINTERNAL, f"generation {state}")
            return b""
        reply = {"status": "completed", "n": req.ntokens,
                 "tokens": req.tokens}
        if batcher.model.vocab <= 256:
            reply["text"] = bytes(req.tokens).decode("utf-8", "replace")
        return json.dumps(reply).encode()


def add_generate_service(server, **kwargs) -> GenerateService:
    """Register a GenerateService on ``server`` and arm the engine
    lifecycle (Server.start builds + registers the replica; stop drains
    it). Returns the GenerateService handle."""
    gs = GenerateService(**kwargs)
    server.add_service(gs.build_service())
    server._serving = gs
    return gs


def serving_page_payload(server) -> dict:
    """The /serving payload: batcher + engine state for this server.
    ONE builder shared by the RPC builtin service and the HTTP handler,
    so the two views cannot diverge."""
    gs = getattr(server, "_serving", None)
    if gs is None or gs.batcher is None:
        return {"enabled": False}
    return gs._payload()
