"""Kernels: the least time the busiest chip could take for one lowered
allreduce over the program's median device time, in percent. The least
time is the larger of the bytes that chip must send over its
interconnect peak (``ici_bits_per_s`` / 8: every link of the chip at
once) and its memory traffic over the HBM peak, both from the shapes
(``benchmark/reference/collective_allreduce.py``). The all-reduce of
one 4 MB block over four chips sends 6,291,456 B; where the trace shows
the scatter inside the program (``collective-permute`` operations on
the device planes), the 3 blocks that leave the source chip are sent by
that same chip and count too: 18,874,368 B, 94.4 us at 200 GB/s.
Interconnect-bound either way (the memory bound is 25.6 us)."""

from benchmark.layer_metrics import collective_device_us
from benchmark.lib.trace_reduce import OPS_LINE
from benchmark.reference.collective_allreduce import (collective_bytes,
                                                      hbm_bytes,
                                                      scatter_bytes)

SCATTER_OP = "collective-permute"


def scatter_inside(run) -> bool:
    return any(SCATTER_OP in name
               for i in run.trace_devices
               for name, _s, _d in run.trace.devices.get(i, {}).get(
                   OPS_LINE, []))


def least_time_us(sizes: dict, n_chips: int, peaks: dict,
                  inside: bool) -> float:
    import jax.numpy as jnp

    rows, cols = sizes["shard_block"]
    block = rows * cols * jnp.dtype(sizes["dtype"]).itemsize
    sent = collective_bytes(n_chips, block) + (
        scatter_bytes(n_chips, block) if inside else 0)
    return 1e6 * max(sent / (peaks["ici_bits_per_s"] / 8),
                     hbm_bytes(n_chips, block, inside)
                     / peaks["hbm_bytes_per_s"])


def read(run):
    measured = collective_device_us.read(run)
    if not measured:
        return None
    return 100.0 * least_time_us(run.cell.sizes, run.cell.chips,
                                 run.peaks(), scatter_inside(run)) / measured
