"""ServingEngine: the model replica as a WorkerModule — decode slices
co-scheduled WITH the fiber workers instead of against them.

This is the first real consumer of the fork's eloq_module hook
(fiber/worker_module.py): every fiber worker's main loop polls
``has_task()`` and runs ``process(group_index)`` before considering
parking, so decode steps interleave with RPC fibers on the SAME
threads. No dedicated engine thread pool exists to fight the workers
for cores — when RPC load is high the workers spend their loop
iterations on fibers and decode steps squeeze between them; when the
server is quiet every worker offers the engine a slice. jax releases
the GIL for the step itself, so one worker decoding does not stall its
siblings' Python.

Only one worker decodes at a time (``_decode_lock`` try-acquire): the
batch arrays are shared state and a second concurrent step would race
the cache writes. A worker that loses the race reports ``False`` (no
progress) so its loop can still park — the hot-spin guard the
worker_module contract grew for exactly this shape.
"""

from __future__ import annotations

import threading
from collections import Counter

from brpc_tpu.fiber.worker_module import WorkerModule

from . import serving_stats as _sstats
from .batcher import ContinuousBatcher


class ServingEngine(WorkerModule):
    def __init__(self, batcher: ContinuousBatcher,
                 label: str = "GenerateService.Generate"):
        self.batcher = batcher
        # flight-recorder attribution: busy samples landing in a decode
        # slice report under the serving method, not "thread:worker-N"
        # (worker_module.active_label reads this while process runs)
        self.attribution_label = label
        self._decode_lock = threading.Lock()
        self.steps = 0
        self.contended = 0
        self.threads_seen: Counter = Counter()

    # ------------------------------------------------- WorkerModule hooks
    def has_task(self) -> bool:
        return self.batcher.has_work()

    def process(self, group_index: int):
        """Run ONE bounded decode slice (sweep + admit + one step).
        Returns False when no progress was made — the worker loop then
        treats this round as idle instead of spinning on a batch some
        other worker is already decoding."""
        if not self._decode_lock.acquire(False):
            self.contended += 1
            return False
        # flight-recorder thread label: while the module's
        # attribution_label claims busy samples first (rpc:<method>),
        # the serving:decode stamp keeps the decode slice attributable
        # when no module label is live (e.g. sampler races the
        # process-exit edge) — and documents WHICH serving work the
        # thread was doing
        stats_on = _sstats.enabled()
        if stats_on:
            _sstats.stamp_serving_thread("serving:decode")
        try:
            did = self.batcher.step(group_index)
        finally:
            self._decode_lock.release()
            if stats_on:
                _sstats.unstamp_serving_thread()
        if did:
            self.steps += 1
            self.threads_seen[threading.get_ident()] += 1
        return did

    # ------------------------------------------------------ observability
    def snapshot(self) -> dict:
        return {
            "steps": self.steps,
            "decode_lock_contended": self.contended,
            "worker_threads_used": len(self.threads_seen),
        }

    def warm_up(self) -> None:
        """Compile the model's programs once (every prefill bucket and
        the decode step) so the first real request's TTFT measures
        scheduling, not XLA."""
        stats_on = _sstats.enabled()
        if stats_on:
            # XLA compile runs on the start thread, outside any fiber
            # or module slice: without the stamp those busy samples
            # land on a bare thread-name leaf
            _sstats.stamp_serving_thread("serving:warmup")
        try:
            self.batcher.warm_up()
        finally:
            if stats_on:
                _sstats.unstamp_serving_thread()
