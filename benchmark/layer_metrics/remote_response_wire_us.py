"""Socket and framing: a response's transit between the two processes,
from the SERVER's ``staged-dcn`` cells of ``/device`` over the window:
``wire_us_sum`` / transfers. The lane's tracker stamps a reply's batch
when it is encoded (the wait for the device, D2H and encode are behind
it: ``stage``) and again when TCP has taken the batch's last byte; the
leg between the two is the 2 MB on their way: the conn's writes, the
kernel's loopback, and the client's event thread reading, since TCP takes
no more than the peer makes room for. (The gap between the call's two
rpcz spans, server ``flushed_us`` -> client ``first_byte_us``, is NOT this:
the client has read nearly all of the reply by the time the server's
writer stamps ``flushed_us``, so that gap reads the client's last read
alone, some 0.1 ms.) Nothing where the window sent no batch on such a
lane, or where the program does not track a staged batch: a program
without the lane's ``tpud_*`` counters has no tracker on it either, and
its cells close ``stage`` and ``wire`` in one instant."""

from benchmark.lib.loader import load_module


def read(run):
    cell = load_module("layer_metrics", "staged_send_us").staged(run)
    if not cell.get("transfers") or not cell.get("wire_us_sum") \
            or "tpud_batches_out" not in run.counters.get("syscalls", ()):
        return None
    return cell["wire_us_sum"] / cell["transfers"]
