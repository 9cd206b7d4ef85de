"""Entry and dispatch: the CLIENT process's CPU time over the window
(the child's own ``getrusage``, from its first issue until its last
response was verified) less what its verifier thread burnt (that
thread's CPU clock, read by itself), per verified call: what issuing,
the staged lane's copies, the event thread and ``done=`` cost a caller
that has no accelerator. Nothing where the deployment has no client
process of its own, or where it did not report."""

import sys


def read(run):
    service = sys.modules.get("benchmark.services.remote_caller")
    win = getattr(service, "LAST", {}).get("window")
    if not win or not run.verified_calls:
        return None
    cpu_s = win["cpu_s"] - win["verify_cpu_s"]
    if cpu_s <= 0:
        return None
    return cpu_s * 1e6 / run.verified_calls
