"""Kernels: the least time the chip could take for one ``Step`` (the
larger of its operations over peak FLOP/s and its bytes over peak
bytes/s, both from the shapes) over the program's median device time,
in percent. At the configuration's widths the compute bound is the
larger one (174 us against 82 us on a v5e)."""

from benchmark.layer_metrics import step_device_us
from benchmark.reference.perf import step_bytes, step_flops


def least_time_us(sizes: dict, peaks: dict) -> float:
    st = sizes["step"]
    shape = (st["batch"], st["d_model"], st["d_ff"])
    return 1e6 * max(step_flops(*shape) / peaks["bf16_flops_per_s"],
                     step_bytes(*shape) / peaks["hbm_bytes_per_s"])


def read(run):
    measured = step_device_us.read(run)
    if not measured:
        return None
    return 100.0 * least_time_us(run.cell.sizes, run.peaks()) / measured
