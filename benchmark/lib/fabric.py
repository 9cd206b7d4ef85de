"""Servers and channels of a configuration's ``layout``, through the
program's normal entry points (``Server``, ``Channel``,
``ParallelChannel``). One process holds the chip(s), so server and
client of a cell live in it."""

from __future__ import annotations


class Fabric:
    """``layout``: ``servers`` (a list of listen endpoints with
    ``#device=K``), ``dial`` (the channel address, ``{port}`` filled in
    per server), ``lane`` (the device-lane kind every connection must
    negotiate), ``channel_options``, and optionally ``combo``
    ("parallel": one ParallelChannel over the sub channels)."""

    def __init__(self, layout: dict, services: list,
                 call_mapper=None, response_merger=None):
        from brpc_tpu.rpc import (Channel, ChannelOptions, Server,
                                  ServerOptions)

        self.layout = layout
        self.servers, self.channels = [], []
        self.combo = None
        opts = ChannelOptions(**layout["channel_options"])
        try:
            for listen, svc in zip(layout["servers"], services):
                srv = Server(ServerOptions(enable_builtin_services=False))
                srv.add_service(svc)
                self.servers.append(srv)
                ep = srv.start(listen)
                self.channels.append(Channel(
                    layout["dial"].format(port=ep.port), opts))
            if layout.get("combo") == "parallel":
                from brpc_tpu.rpc.combo_channels import ParallelChannel
                # fail_limit 1: one failed shard fails the call
                self.combo = ParallelChannel(
                    fail_limit=1, call_mapper=call_mapper,
                    response_merger=response_merger)
                for ch in self.channels:
                    self.combo.add_sub_channel(ch)
        except Exception:
            self.close()
            raise

    def assert_lanes(self) -> list:
        kinds = [ch.device_lane_kind() for ch in self.channels]
        want = self.layout["lane"]
        if any(k != want for k in kinds):
            raise AssertionError(f"device lanes are {kinds}, the "
                                 f"configuration says {want!r}")
        return kinds

    def close(self) -> None:
        for ch in self.channels:
            ch.close()
        for srv in self.servers:
            srv.stop()
            srv.join(5)


def fresh(array):
    """A new array object over the same device buffer: no device work,
    no copy. A caller's payload is normally a fresh array each call (the
    output of some computation); the benchmark's come from a pool made in
    set-up, and on ``local-d2d`` the lane hands the very object over and
    ties its DeviceRecvPool reservation to that object's lifetime, so a
    pooled array sent N times would hold N reservations until the pool
    is exhausted (PERF.md, Open questions)."""
    import jax

    return jax.make_array_from_single_device_arrays(
        array.shape, array.sharding, [array])
