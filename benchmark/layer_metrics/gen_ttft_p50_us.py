"""Serving lane: median time to first token of the generations settled
in the window (submit to the first token, the generation tracker's
stamps; the per-method reservoir restarted at the window's start)."""

from benchmark.lib import generation


def read(run):
    return generation.median_or_none(generation.last().get("ttft_us"))
