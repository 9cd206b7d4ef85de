"""Kernels: median device duration of the lowered allreduce, the one
program ``jit_collective_Mesh_Shard``, over its ``XLA Modules`` events
on the cell's chips (one event a chip and call: scatter, the shard's
body and the all-reduce)."""

from benchmark.lib.stats import median

PROGRAM = "collective_Mesh_Shard"


def read(run):
    if run.trace is None:
        return None
    durs = run.trace.program_durations_us(PROGRAM, run.trace_devices)
    return median(durs) if durs else None
