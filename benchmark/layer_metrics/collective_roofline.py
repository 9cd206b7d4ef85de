"""Kernels: the least time the busiest chip could take for one lowered
allreduce over the program's median device time, in percent. The least
time is the larger of the bytes that chip must send over its
interconnect peak (``ici_bits_per_s`` / 8: every link of the chip at
once) and its memory traffic over the HBM peak, both from the shapes
(``benchmark/reference/collective_allreduce.py``). The deployment
commits the request to chip 0 and the lowered program scatters it
itself (the configuration's ``placement``), so that chip sends the 3
blocks that leave it as well as its all-reduce share, whatever op the
compiler names the scatter: 6,291,456 + 12,582,912 = 18,874,368 B, 94.4
us at 200 GB/s. Interconnect-bound (the memory bound is 25.6 us)."""

from benchmark.layer_metrics import collective_device_us
from benchmark.reference.collective_allreduce import (collective_bytes,
                                                      hbm_bytes,
                                                      scatter_bytes)


def least_time_us(sizes: dict, n_chips: int, peaks: dict) -> float:
    import jax.numpy as jnp

    rows, cols = sizes["shard_block"]
    block = rows * cols * jnp.dtype(sizes["dtype"]).itemsize
    sent = collective_bytes(n_chips, block) + scatter_bytes(n_chips, block)
    return 1e6 * max(sent / (peaks["ici_bits_per_s"] / 8),
                     hbm_bytes(n_chips, block, scatter_inside=True)
                     / peaks["hbm_bytes_per_s"])


def read(run):
    measured = collective_device_us.read(run)
    if not measured:
        return None
    return 100.0 * least_time_us(run.cell.sizes, run.cell.chips,
                                 run.peaks()) / measured
