"""Serving lane: of the window's decode steps (the step records that
collected a decode), the share in percent whose ``step()`` dispatched
the next decode before its per-request work (the record's
``overlapped``), so that the host's hand-out of tokens ran while the
device decoded. Nothing where the records carry no such field (a
batcher that steps synchronously)."""

from benchmark.lib import generation


def read(run):
    steps = generation.decode_steps(generation.last().get("steps") or [])
    if not steps or any("overlapped" not in r for r, _made in steps):
        return None
    return 100.0 * sum(r["overlapped"] for r, _made in steps) / len(steps)
