"""Socket and framing: of the claims of a socket's writership over the
window, the share that sent in place, in the claiming context, and not
through a keep_write fiber spawned for them (a program that does not
count them reports nothing)."""


def read(run):
    s = run.counters["syscalls"]
    if "write_inplace" not in s:
        return None
    claims = s["write_inplace"] + s["write_fiber_spawns"]
    return 100.0 * s["write_inplace"] / claims if claims else None
