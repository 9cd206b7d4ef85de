"""Socket and framing: recv + writev + accept, counted by the program at
its Python and native socket boundaries, per verified call."""


def read(run):
    if not run.verified_calls:
        return None
    s = run.counters["syscalls"]
    return (s["recv"] + s["writev"] + s["accept"]) / run.verified_calls
