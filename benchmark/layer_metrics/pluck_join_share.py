"""Socket and framing: of the sync joins that had to wait over the
window, the share that settled on the pluck lane, the joiner polling the
fd and processing its own reply (``join_plucked``), against those woken
through the event wait by whichever thread processed it
(``join_waited``); both counted in ``Controller.join``. A program that
does not count them reports nothing."""


def read(run):
    s = run.counters["syscalls"]
    if "join_plucked" not in s:
        return None
    joins = s["join_plucked"] + s["join_waited"]
    return 100.0 * s["join_plucked"] / joins if joins else None
