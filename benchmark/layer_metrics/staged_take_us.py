"""Device lane: what a request costs the server on the staged lane, from
the server process's ``staged-dcn`` cells of ``/device`` over the
window: ``recv_us_sum`` / ``recv_transfers``: the take at the frame cut,
which decodes the batch and ``device_put``s it onto chip 0 (the put's
enqueue, not its completion). Nothing where the window took no batch
from such a lane."""

from benchmark.lib.loader import load_module


def read(run):
    cell = load_module("layer_metrics", "staged_send_us").staged(run)
    if not cell.get("recv_transfers") or not cell.get("recv_us_sum"):
        return None
    return cell["recv_us_sum"] / cell["recv_transfers"]
