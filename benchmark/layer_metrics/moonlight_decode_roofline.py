"""Kernels: the least time the chip could take for a decode step over
the program's median device time, in percent. The least time is the
larger of the step's operations over the bf16 peak and its bytes over
the HBM peak (``lib/generation.py``: every weight read once plus the
LIVE latent rows, ``kv_used`` of the window's step records, read once),
the median over the window's decode steps. The bytes bound: about 1.86
GB of weights and 10,368 B a live row against some 250 GFLOP, so HBM
binds (an info line gives both bounds). The decode kernel reads a
slot's rows a block at a time and skips the blocks past its live rows;
a slot's last block is read whole, and each row is stored 640 wide
(the chip's tiling of a 576-wide row), which the bound does not
count."""

import json

from benchmark.layer_metrics import moonlight_decode_step_us
from benchmark.lib import generation
from benchmark.lib.stats import median


def read(run):
    measured = moonlight_decode_step_us.read(run)
    last = generation.last()
    peaks = generation.peaks_or_none(run)
    if not measured or not last or peaks is None:
        return None
    steps = generation.decode_steps(last["steps"])
    if not steps:
        return None
    bounds = [generation.decode_least_us(last["cfg"], r, made, peaks)
              for r, made in steps]
    least = median([b[0] for b in bounds])
    flop_us, byte_us = median([b[1] for b in bounds]), median(
        [b[2] for b in bounds])
    print(json.dumps({"info": {"moonlight_decode_bound": {
        "flops_us": flop_us, "bytes_us": byte_us,
        "binds": "bytes" if byte_us >= flop_us else "operations",
        "measured_us": measured, "steps": len(steps)}}}), flush=True)
    return 100.0 * least / measured
