"""Streaming: median over frames and hops of the next peer's
``on_received`` start minus this peer's (the service's own stamps, one a
peer and frame, in ring order 1, 2, ... 0): what one hop of the ring
costs from outside the program."""

from benchmark.lib.stats import median


def read(run):
    hops = []
    for stamps in run.handlers.values():
        # peer 0 ends the circuit: it sorts last
        starts = [t0 for _peer, t0, _t1 in
                  sorted(stamps, key=lambda s: (s[0] == 0, s[0]))]
        hops += [(b - a) / 1e3 for a, b in zip(starts, starts[1:])]
    return median(hops) if hops else None
