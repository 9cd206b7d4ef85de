"""Serving lane: tokens the batcher emitted in the window's steps (its
step records: first tokens from prefill and decoded ones) per second of
the window."""

from benchmark.lib import generation


def read(run):
    steps = generation.last().get("steps")
    if not steps or run.window_s <= 0:
        return None
    return sum(r["tokens"] for r in steps) / run.window_s
