"""Kernels, the whole step: the model's operations for the tokens the
window made (prompt and decoded tokens, each at its context:
``lib/generation.py``), per second of the window, over the chip's bf16
peak, in percent. A fair end-to-end utilization of the serving loop,
prefill, decode and the host's share of each step together; not a
kernel's roofline share."""

from benchmark.lib import generation


def read(run):
    last = generation.last()
    peaks = generation.peaks_or_none(run)
    if not last or peaks is None or run.window_s <= 0:
        return None
    flops = generation.model_flops(last["cfg"], last["model"],
                                   last["steps"])
    if not flops:
        return None
    return 100.0 * flops / run.window_s / peaks["bf16_flops_per_s"]
