"""Checks every response against its expectation ON THE DEVICE, a batch
at a time, so that no payload is copied to the host inside the window.
The verdicts (one small integer a batch) are read after it."""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Tuple


class DeviceVerifier:
    """``add(key, response, expected)`` queues a pair; every ``batch``
    pairs of one key go through one jitted program that returns how many
    of them differ by more than the key's tolerance. ``finish()`` flushes
    (padding the last batch with a repeated pair) and reads the
    verdicts."""

    def __init__(self, batch: int = 16):
        self.batch = batch
        self._pending: Dict[object, List[Tuple[object, object]]] = {}
        self._programs: Dict[object, Callable] = {}
        self._verdicts: List[object] = []
        self._lock = threading.Lock()       # callers may be many threads
        self.checked = 0

    def declare(self, key, tolerance: float) -> None:
        """A kind of response: arrays of one shape and dtype compared
        with expectations of one shape within ``tolerance`` (0 = bit
        exact)."""
        import jax
        import jax.numpy as jnp

        def count_bad(responses, expected):
            bad = jnp.int32(0)
            for r, e in zip(responses, expected):
                if tolerance == 0:
                    wrong = jnp.any(r != e.astype(r.dtype))
                else:
                    err = jnp.abs(r.astype(jnp.float32)
                                  - e.astype(jnp.float32))
                    # not (err <= tol) also catches a NaN
                    wrong = jnp.logical_not(jnp.all(err <= tolerance))
                bad = bad + wrong.astype(jnp.int32)
            return bad

        self._programs[key] = jax.jit(count_bad)
        self._pending[key] = []

    def warm(self, key, response, expected) -> None:
        """Compile the key's program in set-up; the pair must be good."""
        n = self.batch
        bad = int(self._programs[key]([response] * n, [expected] * n))
        if bad:
            raise AssertionError(
                f"warm-up response of kind {key!r} differs from its "
                f"reference ({bad} of {n})")

    def add(self, key, response, expected) -> None:
        with self._lock:
            pend = self._pending[key]
            pend.append((response, expected))
            self.checked += 1
            if len(pend) >= self.batch:
                self._flush(key)

    def _flush(self, key) -> None:
        pend = self._pending[key]
        if not pend:
            return
        while len(pend) < self.batch:
            pend.append(pend[-1])       # a repeated good pair adds 0
        self._verdicts.append(self._programs[key](
            [p[0] for p in pend], [p[1] for p in pend]))
        self._pending[key] = []

    def finish(self) -> int:
        """Bad responses seen (a bad pair that padded the last batch
        counts once per repeat: any number above 0 fails the run)."""
        import numpy as np

        for key in list(self._pending):
            self._flush(key)
        return int(sum(int(np.asarray(v)) for v in self._verdicts))
