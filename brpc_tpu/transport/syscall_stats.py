"""Syscall accounting floor: recv/writev/accept counted at the native
boundary, merged into the /vars ``syscalls_per_rpc`` derived key.

Two stamp sites, one per boundary kind ("not strace"):

* **Native loops** (fastcore's pluck_scan/serve_drain fd loops) bump
  process-wide C atomics in fastcore.cc at the actual recv/poll call
  sites; ``_brpc_fastcore.syscall_counts()`` reads them.
* **Python conns** (transport/tcp.py) bump the Adders below at the
  conn-method boundary, the Python→libc crossing.

Both stamp at the same altitude, so a call served by a native loop
and one served through the conn count alike.

The denominator (``rpc_messages``) is stamped by the two dispatch
authorities: ``input_messenger.record_dispatch_batch`` (classic +
turbo lanes, requests AND responses — a loopback process counts both
sides of each call) and ``Server.account_native_batch`` (frames the
all-C echo loops served without ever crossing the interpreter).
"""

from __future__ import annotations

from brpc_tpu.butil import interp_probe, thread_cpu
from brpc_tpu.bvar.reducer import Adder, PassiveStatus

# Python-side conn-boundary counters (tcp.py stamps these)
py_recv = Adder()
py_writev = Adder()
py_accept = Adder()

# messages dispatched / natively served — syscalls_per_rpc's denominator
rpc_msgs = Adder()

# sync joins (Controller.join on a plain thread) that settled on the
# pluck lane, the joiner reading its own reply, and those that fell to
# the event wait, woken by whichever thread processed the reply
join_plucked = Adder()
join_waited = Adder()

# the ici:// lane's idle ACK (IciConn._arm_idle_ack stamps these): quiet
# duties armed, duties that came due and found the ACK already carried
# by a reverse frame, and bare ACKs a duty sent (armed - carried - sent:
# still pending, or dropped by a close)
idle_ack_armed = Adder()
idle_ack_carried = Adder()
idle_ack_sent = Adder()

# arrays the ici:// lane copied from another device of this process onto
# the receiving one, by the call that copied: jax's own batched put,
# proved once against the public call (ici._d2d_put), or jax.device_put
d2d_copies_direct = Adder()
d2d_copies_public = Adder()

# the tpud:// lane's staged batches (TpudConn stamps these), each way:
# batches and their encoded bytes; us inside the encode (the wait for
# the device, D2H, the frame's fields), the decode and the device_put calls;
# frames the full out-buffer refused; device_puts that raised (the
# connection then fails: never numpy handed over in silence); batch
# bytes the conn copied in user space all the same: out, the snapshots
# of writeable (or non-contiguous) arrays; in, a frame's head that the
# read of its header had already taken into the conn's scratch
TPUD_COUNTERS = ("tpud_batches_out", "tpud_batches_in", "tpud_bytes_out",
                 "tpud_bytes_in", "tpud_encode_us", "tpud_decode_us",
                 "tpud_put_us", "tpud_out_full", "tpud_put_fallbacks",
                 "tpud_copied_bytes_out", "tpud_copied_bytes_in")
(tpud_batches_out, tpud_batches_in, tpud_bytes_out, tpud_bytes_in,
 tpud_encode_us, tpud_decode_us, tpud_put_us, tpud_out_full,
 tpud_put_fallbacks, tpud_copied_bytes_out,
 tpud_copied_bytes_in) = _tpud_adders = tuple(
    Adder() for _ in TPUD_COUNTERS)


def note_rpc_messages(n: int) -> None:
    rpc_msgs.add(n)


_native_fn = False      # unresolved; None = extension absent


def _native_counts():
    """(recv, send, accept, poll) from the native boundary, (0,0,0,0)
    when the extension is absent. Resolved once — a /vars scrape must
    never trigger a compile (the loader caches after first use, and
    any process doing socket I/O resolved it long before a scrape)."""
    global _native_fn
    fn = _native_fn
    if fn is False:
        from brpc_tpu.native import fastcore
        try:
            fc = fastcore.get()
        except RuntimeError:    # sanitize-mode mismatch guard raced
            return (0, 0, 0, 0)
        fn = _native_fn = (getattr(fc, "syscall_counts", None)
                           if fc is not None else None)
    if fn is None:
        return (0, 0, 0, 0)
    return fn()


def _io_totals() -> dict:
    """The always-on counts alone: what the ``/vars`` readers of this
    module need, without a walk of the threads."""
    nrecv, nsend, naccept, npoll = _native_counts()
    # claims of writership that sent in place / spawned a keep_write
    # fiber (socket.py imports this module, hence the late import)
    from brpc_tpu.transport.event_dispatcher import (dispatcher_quiet_wakes,
                                                     dispatcher_ticks)
    from brpc_tpu.transport.socket import write_mode_totals
    inplace, fibers = write_mode_totals()
    return {
        "recv": nrecv + (py_recv.get_value() or 0),
        "writev": nsend + (py_writev.get_value() or 0),
        "accept": naccept + (py_accept.get_value() or 0),
        "poll": npoll,
        "rpc_msgs": rpc_msgs.get_value() or 0,
        "write_inplace": inplace,
        "write_fiber_spawns": fibers,
        # wakeups of the event thread that fired a callback
        "dispatcher_ticks": dispatcher_ticks(),
        # select() timeouts it took for a quiet duty (the lane's idle
        # ACK), and that ACK's counters: armed, carried, sent
        "dispatcher_quiet_wakes": dispatcher_quiet_wakes(),
        "ici_idle_ack_armed": idle_ack_armed.get_value() or 0,
        "ici_idle_ack_carried": idle_ack_carried.get_value() or 0,
        "ici_idle_ack_sent": idle_ack_sent.get_value() or 0,
        "ici_d2d_copies_direct": d2d_copies_direct.get_value() or 0,
        "ici_d2d_copies_public": d2d_copies_public.get_value() or 0,
        **{name: var.get_value() or 0
           for name, var in zip(TPUD_COUNTERS, _tpud_adders)},
        "join_plucked": join_plucked.get_value() or 0,
        "join_waited": join_waited.get_value() or 0,
    }


def _worker_totals() -> dict:
    """The worker pool and what sync handlers took of it. Late imports:
    the scheduler and the dispatcher import bvar, which this module is
    loaded beside; a scrape builds no TaskControl."""
    from brpc_tpu.fiber import scheduler
    from brpc_tpu.rpc import usercode
    from brpc_tpu.transport.event_dispatcher import nstalls
    control = scheduler._global_control
    groups = control.groups if control is not None else ()
    return {
        **usercode.counters(),
        "fiber_workers": len(groups),
        "fiber_steals": sum(g.nsteals for g in groups),
        "dispatcher_stalls": nstalls.get_value() or 0,
    }


def snapshot() -> dict:
    """Merged totals since process start — the bench lanes window-delta
    this around their measurement to derive per-RPC costs."""
    return {
        **_io_totals(),
        # sync handlers' hold of their threads, and what stands ready
        # to take a request meanwhile: usercode_held_us|runs|over_1ms,
        # fiber_workers, fiber_steals; ticks the stall watchdog flagged
        **_worker_totals(),
        # CPU us by thread role (cpu_us_<role>, cpu_us_python: their
        # sum), read off the live threads' clocks now; no key where the
        # host lets no thread read another's clock
        **thread_cpu.snapshot(),
        # the probe of the wait for the interpreter: moves only while
        # spans record
        **interp_probe.snapshot(),
        # the event thread's wall time, asleep and awake, and what the
        # input pass took of awake (dispatcher_loop_us|awake_us|read_us|
        # cut_us|process_us): they too move only while spans record.
        # Beside them the always-on counts of input passes that
        # dispatched something and of the messages they dispatched
        **_input_pass_totals(),
    }


def _input_pass_totals() -> dict:
    # late imports: the messenger imports this module
    from brpc_tpu.transport import input_messenger
    from brpc_tpu.transport.event_dispatcher import loop_sums
    return {
        **loop_sums(),
        "dispatch_batches": input_messenger._batch_cycles.get_value() or 0,
        "dispatch_batch_msgs": input_messenger._batch_msgs.get_value() or 0,
    }


def syscalls_per_rpc() -> float:
    """Cumulative (recv + writev + accept) per dispatched RPC message.
    Poll/epoll wakeups are excluded:
    they amortize over whole ticks and would reward busy-waiting."""
    s = _io_totals()
    denom = s["rpc_msgs"]
    if not denom:
        return 0.0
    return round((s["recv"] + s["writev"] + s["accept"]) / denom, 3)


_recv_var = PassiveStatus(lambda: _io_totals()["recv"])
_writev_var = PassiveStatus(lambda: _io_totals()["writev"])
_accept_var = PassiveStatus(lambda: _io_totals()["accept"])
_ratio_var = PassiveStatus(syscalls_per_rpc)
_role_cpu_vars = {
    role: PassiveStatus(
        lambda role=role: thread_cpu.by_role_recent().get(role, 0))
    for role in thread_cpu.ROLES}


def expose_syscall_vars() -> None:
    """(Re-)expose the syscall-floor bvars — called at import and again
    from Server.start, surviving a test fixture's unexpose_all like the
    other transport counters."""
    _recv_var.expose("syscalls_recv")
    _writev_var.expose("syscalls_writev")
    _accept_var.expose("syscalls_accept")
    _ratio_var.expose("syscalls_per_rpc")
    for role, var in _role_cpu_vars.items():
        var.expose(f"thread_cpu_us_{role}")
    idle_ack_armed.expose("ici_idle_ack_armed")
    idle_ack_carried.expose("ici_idle_ack_carried")
    idle_ack_sent.expose("ici_idle_ack_sent")
    d2d_copies_direct.expose("ici_d2d_copies_direct")
    d2d_copies_public.expose("ici_d2d_copies_public")
    for name, var in zip(TPUD_COUNTERS, _tpud_adders):
        var.expose(name)
    interp_probe.probe_n.expose("interp_probe_n")
    interp_probe.probe_wait_us.expose("interp_probe_wait_us")
    interp_probe.probe_over_1ms.expose("interp_probe_over_1ms")
    interp_probe.probe_over_4ms.expose("interp_probe_over_4ms")


expose_syscall_vars()
