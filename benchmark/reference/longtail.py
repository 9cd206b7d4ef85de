"""Plain references of the ``LongTail`` service, independent of brpc_tpu.

``Echo`` is the identity. ``SlowStep`` is ``Perf.Step``'s residual ReLU
MLP, y = relu(x @ w_in) @ w_out + x in float32 with every matmul at
"highest" precision; its 5 ms hold changes no value, so the reference
of the answer is ``Step``'s. ``schedule`` is the open loop's arrivals:
what the seed says is sent, when, on which connection, as which kind.
The driver issues this list and ``correct`` holds the server's handler
stamps to it."""

from __future__ import annotations

import random
from typing import List, NamedTuple

from benchmark.reference.perf import (echo_reference,  # noqa: F401
                                      step_reference)

slow_step_reference = step_reference


class Arrival(NamedTuple):
    at_s: float     # seconds after the window's start
    conn: int       # which of the connections carries it
    long: bool      # a SlowStep (True) or a short Echo
    size: int       # index into the short payload sizes (drawn for every
    #                 arrival, so that a long one shifts no later draw)


def schedule(seed: int, rate: float, seconds: float, connections: int,
             long_share: float, n_sizes: int) -> List[Arrival]:
    """The arrivals inside [0, seconds): exponential gaps at ``rate`` a
    second (a Poisson process), the connection uniform, long with
    probability ``long_share``, the short size uniform. A pure function
    of its arguments; a shorter window's list is a prefix of a longer
    one's."""
    rng = random.Random(seed)
    out, t = [], 0.0
    while True:
        t += rng.expovariate(rate)
        if t >= seconds:
            return out
        out.append(Arrival(t, rng.randrange(connections),
                           rng.random() < long_share,
                           rng.randrange(n_sizes)))
