"""Serving lane: median gap between a generation's consecutive tokens
(the generation tracker's per-token stamps of the generations settled in
the window; the reservoir restarted at the window's start)."""

from benchmark.lib import generation


def read(run):
    return generation.median_or_none(generation.last().get("gap_us"))
