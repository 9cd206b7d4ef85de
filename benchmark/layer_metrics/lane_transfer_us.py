"""Device lane: stage + wire + ack time per transfer, from the /device
cells' sums over the window (a call makes one transfer each way)."""


def read(run):
    lane = run.counters["lane"]
    if not lane["transfers"]:
        return None
    return (lane["stage_us_sum"] + lane["wire_us_sum"]
            + lane["ack_us_sum"]) / lane["transfers"]
