"""Builtin observability services, registered on every server
(brpc/builtin/*, server.cpp:468-540). Served over tpu_std for now; the
HTTP front-end arrives with the http protocol (SURVEY.md §7 stage 6)."""

from __future__ import annotations

import json

from brpc_tpu.bvar.prometheus import dump_prometheus
from brpc_tpu.bvar.variable import dump_exposed
from brpc_tpu.rpc import errno_codes as berr
from brpc_tpu.rpc.service import Service


def connections_page(server) -> dict:
    """Connection table + the robustness pane: per-endpoint breaker
    state and the chaos/deadline counters, so a chaos run (or a real
    incident) is debuggable from the browser — which peer is isolated,
    for how long, how much load was shed. ONE builder shared by the
    RPC builtin service and the HTTP /connections handler, so the two
    views cannot diverge. Each row carries its resource-census cost
    (bytes held, idle class, last-active) from the same accounting
    authority as /census (socket_census_rows), so THIS server's rows
    sum to the census sockets subsystem's server_bytes/server_count
    (the process-wide bytes/count additionally include client-channel
    sockets, which /connections does not list)."""
    import time as _time

    from brpc_tpu.butil.flags import flag as _flag
    from brpc_tpu.rpc.circuit_breaker import all_breaker_snapshots
    robustness = dict(dump_exposed("chaos_injected_"))
    for name in ("server_deadline_shed", "server_limit_shed",
                 "server_priority_shed", "client_priority_shed",
                 "retry_suppressed_budget", "retry_throttled",
                 "hedge_suppressed_budget", "naming_empty"):
        robustness.update(dump_exposed(name))
    idle_after = _flag("census_idle_s")
    now = _time.monotonic_ns()
    rows = []
    for s in server.connections():
        idle_s = (now - s.last_active_ns) / 1e9
        rows.append({
            "role": "server",
            "remote": str(s.remote_endpoint) if s.remote_endpoint else None,
            "failed": s.failed,
            "resident_bytes": s.input_portal.size + s.wq_bytes,
            "last_active_s": round(idle_s, 3),
            "idle_class": "idle" if idle_s >= idle_after else "active",
        })
    # client-channel sockets, labeled with their owner identity
    # (channel name + backend endpoint — Channel._label_socket): the
    # census always counted their bytes, but the rows were previously
    # invisible here, so a connection leak in a client channel was
    # indistinguishable from server fan-in. Listed SEPARATELY from the
    # server rows — /census's server_bytes equality is over
    # ``connections`` only.
    from brpc_tpu.transport.socket import socket_census_rows
    crows = []
    # a fresh walk, not the 0.2 s memo: a page a person reads must list
    # the connection made a moment ago (the walk re-arms the memo, so
    # a /census read right after still agrees with these rows)
    for s, resident, idle_s in socket_census_rows(max_age_s=0):
        ch = s.user_data.get("channel")
        if ch is None:
            continue
        crows.append({
            "role": "client",
            "channel": ch,
            "backend": s.user_data.get("backend"),
            "remote": str(s.remote_endpoint) if s.remote_endpoint else None,
            "resident_bytes": resident,
            "last_active_s": round(idle_s, 3),
            "idle_class": "idle" if idle_s >= idle_after else "active",
        })
    return {
        "connections": rows,
        "client_connections": crows,
        "breakers": all_breaker_snapshots(),
        "robustness": robustness,
    }


def census_page_payload(server=None) -> dict:
    """The /census payload: per-subsystem byte/object census (registered
    through butil.resource_census) plus the connection roll-up from the
    shared accounting authority. ONE builder shared by the RPC builtin
    service and the HTTP /census handler, so the two views cannot
    diverge."""
    from brpc_tpu.butil.resource_census import census_page
    out = census_page()
    # connection roll-up derived from the sockets subsystem's ONE walk
    # (a second socket pass here would double both the cost and the
    # race window, and could disagree with the subsystem numbers)
    sub = out["subsystems"].get("sockets", {})
    count = sub.get("count", 0) or 0
    total = sub.get("bytes", 0) or 0
    out["connections"] = {
        "count": count,
        "resident_bytes": total,
        "idle": sub.get("idle", 0) or 0,
        "avg_bytes": round(total / count, 1) if count else 0.0,
    }
    return out


def capture_page_payload(server=None) -> dict:
    """The /capture payload: the traffic recorder's live state —
    active/config, sampled/written/dropped counters, rotation + disk
    budget effects, and the corpus files ready for download. ONE
    builder shared by the RPC builtin service and the HTTP /capture
    handler, so the two views cannot diverge. A shard-group
    SUPERVISOR serves the merged per-shard view instead
    (ShardAggregator.merged_capture)."""
    from brpc_tpu.traffic.capture import global_recorder
    return global_recorder().snapshot()


def capture_control(action: str, params: dict) -> dict:
    """start/stop the recorder from a page action (shared by the HTTP
    handler and the builtin RPC method). Raises ValueError on a bad
    action or missing dir — the callers turn that into 400/EREQUEST."""
    from brpc_tpu.traffic.capture import start_capture, stop_capture
    if action == "stop":
        return stop_capture()
    if action != "start":
        raise ValueError(f"unknown capture action {action!r}")
    kw = {}
    if params.get("rate") not in (None, ""):
        kw["default_rate"] = float(params["rate"])
    if params.get("max_per_second") not in (None, ""):
        kw["max_per_second"] = int(params["max_per_second"])
    if params.get("rotate_mb") not in (None, ""):
        kw["rotate_bytes"] = int(params["rotate_mb"]) << 20
    if params.get("disk_budget_mb") not in (None, ""):
        kw["disk_budget_bytes"] = int(params["disk_budget_mb"]) << 20
    return start_capture(dir=params.get("dir") or None, **kw)


def capture_download_bytes(paths=None) -> bytes:
    """The merged, download-ready corpus: every corpus file (this
    process's capture dir, or the shard files the supervisor collected)
    merged in arrival order into one .brpccap byte string."""
    import os as _os
    import tempfile as _tempfile

    from brpc_tpu.traffic.capture import global_recorder
    from brpc_tpu.traffic.corpus import merge_corpora
    if paths is None:
        paths = global_recorder().corpus_paths()
    if not paths:
        return b""
    if len(paths) == 1:
        with open(paths[0], "rb") as f:
            return f.read()
    fd, tmp = _tempfile.mkstemp(suffix=".brpccap")
    _os.close(fd)
    try:
        merge_corpora(paths, tmp)
        with open(tmp, "rb") as f:
            return f.read()
    finally:
        for p in (tmp, tmp + ".idx"):
            try:
                _os.remove(p)
            except OSError:
                pass


def timeline_page_payload(server=None, names=None, prefix: str = "",
                          max_vars=None) -> dict:
    """The /timeline payload: every tracked variable's multi-resolution
    trend rings (60x1s -> 60x1m -> 24x1h, bvar/series.py), the anomaly
    watchdog's incident ring and its tracked keys. ONE builder shared
    by the RPC builtin service, the HTTP /timeline handler and the
    shard dump (write_shard_dump bounds max_vars), so the views cannot
    diverge. A shard-group SUPERVISOR serves the merged view instead
    (ShardAggregator.merged_timeline)."""
    import time as _time

    from brpc_tpu.bvar.anomaly import global_watchdog
    from brpc_tpu.bvar.series import (HOUR_BUCKETS, MIN_BUCKETS,
                                      SEC_BUCKETS, global_series,
                                      series_enabled)
    wd = global_watchdog()
    return {
        "enabled": series_enabled(),
        "now": _time.time(),
        "resolution": {"sec": SEC_BUCKETS, "min": MIN_BUCKETS,
                       "hr": HOUR_BUCKETS},
        "series": global_series().dump_series(names=names, prefix=prefix,
                                              max_vars=max_vars),
        "incidents": wd.incident_snapshot(),
        "watch_keys": wd.tracked_keys(),
    }


def incidents_page_payload(server=None) -> dict:
    """The /incidents payload: incident-capture state, the artifact
    ledger (id, trigger keys, size, snapshot inventory per artifact)
    and the disk-budget accounting. ONE builder shared by the RPC
    builtin service, the HTTP /incidents handler and the shard dump;
    a shard-group SUPERVISOR serves the merged view instead
    (ShardAggregator.merged_incidents)."""
    from brpc_tpu.incident.manager import incidents_snapshot_payload
    return incidents_snapshot_payload(server)


def status_page(server) -> dict:
    """The /status payload: server state, per-method latency windows
    (qps + p50/p90/p99/max — "which method is slow" without scraping
    /vars), and the saturation pane naming WHY it is slow (worker-busy
    fraction, run-queue depth, socket write-queue bytes — the three
    counters the rpcz stage timelines implicate). ONE builder shared by
    the RPC builtin service and the HTTP /status handler, so the two
    views cannot diverge."""
    from brpc_tpu.butil.iobuf import pool as iobuf_pool
    from brpc_tpu.transport.socket import ncoalesced, nwqueue_bytes
    from brpc_tpu.transport.input_messenger import (dispatch_batch_avg_10s,
                                                    dispatch_batch_peak_10s)
    saturation = server._control.saturation_snapshot()
    saturation["socket_wqueue_bytes"] = nwqueue_bytes.get_value()
    # hot-path batching health: is the input loop batching (avg > 1
    # under load), is the write path coalescing, are blocks recycling
    # (hit ratio ~1 once warm) — the three "is the overhaul working"
    # gauges next to the pressure counters they relieve
    saturation["dispatch_batch_size_avg_10s"] = dispatch_batch_avg_10s()
    saturation["dispatch_batch_size_peak_10s"] = dispatch_batch_peak_10s()
    saturation["socket_write_coalesced_frames"] = ncoalesced.get_value()
    saturation["iobuf_pool_hit_ratio"] = round(iobuf_pool.hit_ratio(), 4)
    saturation["iobuf_pool_bytes"] = iobuf_pool.cached_bytes()
    # overload-control pane: the limiter's live limit + in-flight, the
    # ELIMIT/deadline shed counters, and the process's most-drained
    # retry token bucket. Merged shard views: *limit takes the max,
    # inflight sums, *tokens takes the min (shard_group merge rules).
    from brpc_tpu.rpc.retry_policy import min_retry_tokens
    from brpc_tpu.rpc.server_dispatch import (nlimit_shed, npriority_shed,
                                              nshed)
    saturation["concurrency_limit"] = server.concurrency_limit()
    saturation["inflight"] = server.concurrency
    saturation["limit_shed"] = nlimit_shed.get_value()
    saturation["deadline_shed"] = nshed.get_value()
    saturation["priority_shed"] = npriority_shed.get_value()
    adm = server._admission
    if adm is not None:
        # the DAGOR admission threshold (0 = calm); merged shard views
        # take the max — the group's tightest gate is its headline
        saturation["admission_threshold"] = adm.wire_threshold()
    tokens = min_retry_tokens()
    if tokens is not None:
        saturation["retry_tokens"] = tokens
    # saturation -> /timeline links: a live spike on this pane is one
    # click from its history (only entries whose backing bvar has a
    # trend ring right now — a link to an empty series helps nobody)
    from brpc_tpu.bvar.series import global_series, series_enabled
    timeline_links = {}
    if series_enabled():
        col = global_series()
        for pane_key, var_name in (
                ("socket_wqueue_bytes", "socket_wqueue_bytes"),
                ("limit_shed", "server_limit_shed"),
                ("deadline_shed", "server_deadline_shed"),
                ("priority_shed", "server_priority_shed"),
                ("admission_threshold", "server_admission_threshold"),
                ("inflight", "server_concurrency_inflight"),
                ("concurrency_limit", "server_concurrency_limit"),
                ("iobuf_pool_hit_ratio", "iobuf_pool_hit_ratio"),
                ("retry_tokens", "retry_tokens_min")):
            if pane_key in saturation and col.has_series(var_name):
                timeline_links[pane_key] = f"/timeline?name={var_name}"
    # capture-on-anomaly headline: open window / bundled artifacts /
    # bytes on disk, linking to /incidents (incident/manager.py)
    from brpc_tpu.incident.manager import incident_status_line
    return {
        "running": server.is_running,
        "endpoint": str(server.endpoint) if server.endpoint else None,
        "incidents": incident_status_line(),
        "concurrency": server.concurrency,
        "processed": server.nprocessed,
        "errors": server.nerror,
        "services": {n: sorted(s.methods)
                     for n, s in server.services().items()},
        "method_status": {k: lr.get_value()
                          for k, lr in server.method_status.items()},
        "saturation": saturation,
        "saturation_timeline": timeline_links,
    }


def add_builtin_services(server) -> None:
    builtin = Service("builtin")

    @builtin.method()
    def health(cntl, request):
        return b"OK"

    @builtin.method()
    def status(cntl, request):
        # a shard-group SUPERVISOR serves the merged view: sums for
        # counters, pooled-reservoir percentiles, per-shard breakdown
        # (the supervisor itself serves no traffic worth reporting)
        agg = getattr(server, "shard_aggregator", None)
        if agg is not None:
            return json.dumps(agg.merged_status(), default=str).encode()
        return json.dumps(status_page(server), default=str).encode()

    @builtin.method()
    def vars(cntl, request):
        prefix = bytes(request).decode() if request else ""
        agg = getattr(server, "shard_aggregator", None)
        if agg is not None:
            return json.dumps(agg.merged_vars(prefix),
                              default=str).encode()
        return json.dumps(dict(dump_exposed(prefix)), default=str).encode()

    @builtin.method()
    def prometheus_metrics(cntl, request):
        agg = getattr(server, "shard_aggregator", None)
        if agg is not None:
            return agg.prometheus_text().encode()
        return dump_prometheus().encode()

    @builtin.method()
    def connections(cntl, request):
        return json.dumps(connections_page(server), default=str).encode()

    @builtin.method()
    def census(cntl, request):
        return json.dumps(census_page_payload(server),
                          default=str).encode()

    @builtin.method()
    def backends(cntl, request):
        # per-backend CLIENT telemetry (this process's channels) — the
        # builtin-RPC twin of HTTP /backends
        from brpc_tpu.rpc.backend_stats import backends_page_payload
        return json.dumps(backends_page_payload(), default=str).encode()

    @builtin.method()
    def device(cntl, request):
        # device-lane observatory (per-(peer, lane) transfer cells,
        # credit/queue panes, leak counters, last probe result) — the
        # builtin-RPC twin of HTTP /device, from the ONE shared builder
        from brpc_tpu.transport.device_stats import device_page_payload
        return json.dumps(device_page_payload(server),
                          default=str).encode()

    @builtin.method()
    def serving(cntl, request):
        # continuous-batching engine state (running/waiting/evicted,
        # batch-size histogram, KV occupancy) — the builtin-RPC twin
        # of HTTP /serving, from the ONE shared builder
        from brpc_tpu.serving.service import serving_page_payload
        return json.dumps(serving_page_payload(server),
                          default=str).encode()

    @builtin.method()
    def timeline(cntl, request):
        # multi-resolution trend rings + incident ring — the builtin-
        # RPC twin of HTTP /timeline, from the ONE shared builder.
        # Request bytes: optional name prefix filter. A shard-group
        # SUPERVISOR serves the merged per-shard view instead.
        prefix = bytes(request).decode().strip() if request else ""
        agg = getattr(server, "shard_aggregator", None)
        if agg is not None:
            return json.dumps(agg.merged_timeline(prefix=prefix),
                              default=str).encode()
        return json.dumps(timeline_page_payload(server, prefix=prefix),
                          default=str).encode()

    @builtin.method()
    def capture(cntl, request):
        # traffic-recorder state + runtime control — the builtin-RPC
        # twin of HTTP /capture. Request bytes: "" = snapshot, "stop",
        # or "start <dir>" (dir optional when the capture_dir flag is
        # set). Downloads stay on the HTTP side (binary body).
        arg = bytes(request).decode().strip() if request else ""
        if arg:
            verb, _, dirpart = arg.partition(" ")
            try:
                return json.dumps(
                    capture_control(verb, {"dir": dirpart.strip()}),
                    default=str).encode()
            except (ValueError, OSError) as e:
                cntl.set_failed(berr.EREQUEST, str(e))
                return b""
        return json.dumps(capture_page_payload(server),
                          default=str).encode()

    @builtin.method()
    def incidents(cntl, request):
        # capture-on-anomaly state + artifact ledger — the builtin-RPC
        # twin of HTTP /incidents, from the ONE shared builder. A
        # shard-group SUPERVISOR serves the merged per-shard view
        # instead (downloads stay on the HTTP side: binary body).
        agg = getattr(server, "shard_aggregator", None)
        if agg is not None:
            return json.dumps(agg.merged_incidents(),
                              default=str).encode()
        return json.dumps(incidents_page_payload(server),
                          default=str).encode()

    @builtin.method()
    def lb_trace(cntl, request):
        # request bytes = channel name (empty = channel directory)
        from brpc_tpu.rpc.backend_stats import lb_trace_payload
        name = bytes(request).decode() if request else ""
        payload = lb_trace_payload(name or None)
        if payload is None:
            cntl.set_failed(berr.EREQUEST, f"no such channel {name!r}")
            return b""
        return json.dumps(payload, default=str).encode()

    try:
        server.add_service(builtin)
    except ValueError:
        pass
