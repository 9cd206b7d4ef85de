"""Device lane: time from a batch's flush to its ACK (the credit's
return), per transfer, from the /device cells' sums over the window."""


def read(run):
    lane = run.counters["lane"]
    if not lane["transfers"]:
        return None
    return lane["ack_us_sum"] / lane["transfers"]
