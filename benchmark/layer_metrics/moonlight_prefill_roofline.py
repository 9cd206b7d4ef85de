"""Kernels: the least time of the prefill programs
(``jit_moonlight_prefill_<bucket>``) over their device time in the
trace, in percent: each bucket's runs in the trace times the least time
of its mean run (the larger of operations over the bf16 peak and bytes
over the HBM peak; ``lib/generation.py``, from the model's own counts
of runs, prompt tokens and their squares a bucket), summed, over the
summed device durations. A 1,024-token prompt is some 0.9 TFLOP against
under 2 GB, so the operations bound binds."""

from benchmark.lib import generation

PREFIX = "moonlight_prefill_"


def read(run):
    last = generation.last()
    peaks = generation.peaks_or_none(run)
    if run.trace is None or not last or peaks is None:
        return None
    costs = generation.prefill_run_costs(last["cfg"], last["model"],
                                         last["steps"])
    least = measured = 0.0
    for bucket, (flops, nbytes) in costs.items():
        durs = run.trace.program_durations_us(f"{PREFIX}{bucket}",
                                              run.trace_devices)
        if not durs:
            continue
        one = 1e6 * max(flops / peaks["bf16_flops_per_s"],
                        nbytes / peaks["hbm_bytes_per_s"])
        least += len(durs) * one
        measured += sum(durs)
    if not measured:
        return None
    return 100.0 * least / measured
