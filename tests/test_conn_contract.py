"""The contract between a Socket and its conn (transport/base.py::Conn).

Every name Socket, Channel or the server reads off a conn is declared
on ``Conn`` with its default, and nothing under transport/, rpc/ or
chaos/ asks a conn by string any more (ISSUE 28). A declared name is
FOUND on the class, so a wrapper's ``__getattr__`` never fires for it:
these tests hold every conn class, wrappers included, to answering each
name itself, and the two wrappers to handing over exactly what they
mean to.
"""

import inspect
import os
import re
import socket as pysocket
import ssl as pyssl
import time

import pytest

from brpc_tpu.butil.endpoint import str2endpoint
from brpc_tpu.chaos.inject import ChaosConn
from brpc_tpu.chaos.plan import FaultPlan
from brpc_tpu.transport import ici
from brpc_tpu.transport.base import OPTIONAL_NAMES, Conn
from brpc_tpu.transport.mem import MemConn, _MemPipe
from brpc_tpu.transport.ssl import SslConn
from brpc_tpu.transport.tcp import TcpConn
from brpc_tpu.transport.tpu import TpuConn
from brpc_tpu.transport.tpud import TpudConn

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the optional part of the contract, read off the class that declares
# it: a name added to Conn is held to these tests without an edit here
OPTIONAL = {n: getattr(Conn, n) for n in OPTIONAL_NAMES}
FLAGS = {n for n, v in OPTIONAL.items() if v is False}
METHODS = {n for n, v in OPTIONAL.items() if v is None} - {"lane_kind"}


# ------------------------------------------------------------- live conns
def _tcp_pair():
    lis = pysocket.socket()
    lis.bind(("127.0.0.1", 0))
    lis.listen(1)
    port = lis.getsockname()[1]
    a = pysocket.create_connection(("127.0.0.1", port))
    b, _ = lis.accept()
    lis.close()
    return a, b, str2endpoint(f"tcp://127.0.0.1:{port}")


def _tcp():
    a, b, ep = _tcp_pair()
    return TcpConn(a, ep, ep), [b]


def _mem():
    ep = str2endpoint("mem://conn-contract")
    return MemConn(_MemPipe(), _MemPipe(), ep, ep), []


def _tpu():
    ep = str2endpoint("tpu://conn-contract")
    return TpuConn(_MemPipe(), _MemPipe(), ep, ep, None, "contract"), []


def _tpud():
    a, b, ep = _tcp_pair()
    return TpudConn(TcpConn(a, ep, ep), ep, ep, None), [b]


def _ici():
    a, b, ep = _tcp_pair()
    return ici.IciConn(TcpConn(a, ep, ep), ep, ep), [b]


def _ssl():
    a, b, ep = _tcp_pair()
    ctx = pyssl.SSLContext(pyssl.PROTOCOL_TLS_CLIENT)
    ctx.check_hostname = False
    ctx.verify_mode = pyssl.CERT_NONE
    s = ctx.wrap_socket(a, do_handshake_on_connect=False)
    return SslConn(s, ep, ep), [b]


def _chaos(make_inner):
    def make():
        inner, far = make_inner()
        return ChaosConn(inner, None, FaultPlan(seed=1), "contract", 0), far
    return make


CONNS = {
    "TcpConn": _tcp, "MemConn": _mem, "TpuConn": _tpu,
    "TpudConn": _tpud, "IciConn": _ici, "SslConn": _ssl,
    "ChaosConn(TcpConn)": _chaos(_tcp),
    "ChaosConn(IciConn)": _chaos(_ici),
}


@pytest.fixture
def live():
    made = []

    def make(kind):
        conn, far = CONNS[kind]()
        made.append((conn, far))
        return conn

    yield make
    for conn, far in made:
        try:
            conn.close()
        except Exception:
            pass
        for s in far:
            s.close()


@pytest.mark.parametrize("kind", list(CONNS))
def test_every_conn_answers_every_name_itself(kind, live):
    """(a) Each name of the contract is found on the conn without its
    ``__getattr__`` (a lookup that falls through to one is how a
    capability gets lost in silence), and is what Conn declares it to
    be: a flag a bool, an optional method None or callable."""
    conn = live(kind)
    for name in OPTIONAL:
        # raises AttributeError where only __getattr__ would answer
        inspect.getattr_static(conn, name)
        value = getattr(conn, name)
        if name in FLAGS:
            assert isinstance(value, bool), (kind, name, value)
        elif name in METHODS:
            assert value is None or callable(value), (kind, name, value)
        elif name == "lane_kind":
            assert value is None or isinstance(value, str), (kind, value)
        else:
            assert name == "peer_info"
            assert value is True or value is None \
                or isinstance(value, dict), (kind, value)
    # a conn that drains by pending_bytes has to have it
    if conn.drain_all_reads:
        assert callable(conn.pending_bytes)
    if conn.level_triggered:
        assert callable(conn.pause_read_events)
        assert callable(conn.resume_read_events)


def test_no_wrapper_left_to_answer_by_accident():
    assert "__getattr__" not in vars(ici.IciConn)
    # ChaosConn keeps one for what lies outside the contract; no name
    # of the contract may reach it
    for name in OPTIONAL:
        assert name in vars(ChaosConn), name


@pytest.mark.parametrize("name", ["level_triggered", "pause_read_events",
                                  "resume_read_events", "pluck_fd"])
def test_ici_conn_hands_over_its_inner_conns_answer(name, live):
    """(b) The four names IciConn delegates: what its TCP conn says of
    the fd, it says. Losing pluck_fd here would cost echo_small_d1 the
    pluck lane and show in no test of behaviour."""
    conn = live("IciConn")
    inner = conn._inner
    assert isinstance(inner, TcpConn)
    mine, its = getattr(conn, name), getattr(inner, name)
    assert mine == its and mine is not None
    if name == "pluck_fd":
        assert conn.pluck_fd() == inner.pluck_fd()
    # and the two it must NOT hand over (PERF.md section 6, PR 27)
    assert conn.stream_fd is None and inner.stream_fd is not None
    assert conn.short_read_drained is False and inner.short_read_drained


@pytest.mark.parametrize("name", ["level_triggered", "pause_read_events",
                                  "resume_read_events"])
def test_tpud_conn_hands_over_its_inner_conns_answer(name, live):
    """The fd under a tpud:// conn is its TCP conn's: what that says of
    it as an event source, this conn says (without it a busy period
    with data arriving re-fires the level-triggered fd for its whole
    length, and nothing pauses it)."""
    conn = live("TpudConn")
    inner = conn._inner
    assert isinstance(inner, TcpConn)
    mine, its = getattr(conn, name), getattr(inner, name)
    assert mine == its and mine is not None


def test_tpud_conn_declares_what_is_true_of_it_and_no_more(live):
    """(b2) Flag by flag and method by method: the tracker it takes,
    the fd it may not hand out (its bytes are enveloped and buffered
    above it), and the gathered write it could take and does not (it
    lost a third of the rate at 2 MB a batch: PERF.md section 6,
    PR 37)."""
    conn = live("TpudConn")
    assert conn.supports_device_lane and conn.supports_device_tracker
    assert conn.lane_kind == "staged-dcn"
    assert callable(conn.take_device_payload)
    assert {"tracker", "flush"} <= set(
        inspect.signature(conn.write_device_payload).parameters)
    assert conn.inline_write_ok is False and conn.flush is None
    for name in ("stream_fd", "pluck_fd", "awaits_peer_frame", "writev",
                 "read_into_v", "read_chunks", "pending_bytes"):
        assert getattr(conn, name) is None, name
    assert conn.short_read_drained is False and conn.drain_all_reads is False
    assert conn._inner.stream_fd is not None and conn._inner.pluck_fd
    assert conn.write(memoryview(b"abc")) == 3
    # peek_closed: the inner conn's FIN probe, and nothing undelivered
    assert conn.peek_closed() is False
    other, far = _tpud()
    try:
        far[0].close()
        for _ in range(500):
            if other.peek_closed():
                break
            time.sleep(0.01)
        assert other.peek_closed() is True
        other._appbuf += b"read, not yet delivered"
        assert other.peek_closed() is False
    finally:
        other.close()


def test_chaos_conn_lacks_writev_and_passes_the_read_side(live):
    """(c) Every outbound byte has to cross the fault script, so the
    gather write is hidden; the read side and the fd pass through."""
    conn = live("ChaosConn(TcpConn)")
    inner = conn._inner
    assert inner.writev is not None and conn.writev is None
    assert conn.pluck_fd() == inner.pluck_fd()
    assert conn.stream_fd() == inner.stream_fd()
    assert conn.peek_closed == inner.peek_closed
    assert conn.peek_closed() is False
    assert conn.read_chunks is None          # TcpConn lacks it
    assert conn.level_triggered and conn.short_read_drained \
        and conn.inline_write_ok
    over_mem = ChaosConn(live("MemConn"), None, FaultPlan(seed=1), "m", 0)
    assert over_mem.read_chunks == over_mem._inner.read_chunks
    assert over_mem.read_chunks() == ((), False)
    assert over_mem.drain_all_reads and over_mem.pending_bytes() == 0
    # what changes when a hello lands is asked each time, not copied
    over_ici = live("ChaosConn(IciConn)")
    assert over_ici.peer_info is None
    over_ici._inner.peer_info = {"proc": "elsewhere"}
    assert over_ici.peer_info == {"proc": "elsewhere"}
    assert over_ici.lane_kind == over_ici._inner.lane_kind == "staged"
    # outside the contract: still forwarded by __getattr__
    assert over_ici.outstanding_batches == 0


# ----------------------------------------------------------------- source
_CONN_GETATTR = re.compile(
    r"""getattr\(\s*(?:conn|self\.conn|sock\.conn|socket\.conn|"""
    r"""self\._inner)\s*,\s*["']""")


def test_no_conn_is_asked_by_string():
    """(d) A capability read as getattr(conn, "name", default) fails
    silently into the slow path when the name is misspelt or dropped
    from a wrapper; read as conn.name it cannot."""
    hits = []
    for sub in ("transport", "rpc", "chaos"):
        root = os.path.join(REPO_ROOT, "brpc_tpu", sub)
        for dirpath, _dirs, files in os.walk(root):
            for f in files:
                path = os.path.join(dirpath, f)
                rel = os.path.relpath(path, REPO_ROOT)
                if not f.endswith(".py") or \
                        rel == "brpc_tpu/transport/base.py":
                    continue
                for i, line in enumerate(open(path), 1):
                    if _CONN_GETATTR.search(line):
                        hits.append(f"{rel}:{i}: {line.strip()}")
    assert not hits, "\n".join(hits)


# --------------------------------------------------------------- counters
def test_native_syscall_counters_moved_with_their_readers():
    """(e) fc_sys_* and syscall_counts() live in fastcore.cc now (they
    were defined beside the ring lane): four counts that never fall,
    and a sync tcp:// echo, whose joiner reads its reply in pluck_scan,
    moves them."""
    from brpc_tpu.native import fastcore
    from brpc_tpu.rpc import (Channel, ChannelOptions, Server,
                              ServerOptions, Service)
    from brpc_tpu.transport import syscall_stats
    fc = fastcore.get()
    if fc is None:
        pytest.skip("the native core cannot build here")
    before = fc.syscall_counts()
    assert len(before) == 4 and all(
        isinstance(n, int) and n >= 0 for n in before)
    server = Server(ServerOptions(enable_builtin_services=False))
    svc = Service("Bench")

    @svc.method()
    def Echo(cntl, request):
        return request

    server.add_service(svc)
    ep = server.start("tcp://127.0.0.1:0")
    try:
        ch = Channel(f"tcp://127.0.0.1:{ep.port}",
                     ChannelOptions(timeout_ms=5000))
        plucked = syscall_stats.snapshot()["join_plucked"]
        for i in range(3):
            cl = ch.call_sync("Bench", "Echo", b"m%d" % i)
            assert not cl.failed()
            assert cl.response_payload.to_bytes() == b"m%d" % i
        assert syscall_stats.snapshot()["join_plucked"] > plucked
        ch.close()
    finally:
        server.stop()
    after = fc.syscall_counts()
    assert all(a >= b for a, b in zip(after, before)), (before, after)
    assert sum(after) >= sum(before) + 1, (before, after)
    snap = syscall_stats.snapshot()
    assert snap["recv"] >= after[0] and snap["poll"] == after[3]
