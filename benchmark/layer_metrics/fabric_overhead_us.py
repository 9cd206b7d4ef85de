"""Entry and dispatch: median over the window's calls of the client's
latency minus the handler's own time (the longest handler where a call
fans out), both stamped by the benchmark and matched by sequence tag."""

from benchmark.lib.stats import median


def read(run):
    over = []
    for seq, issue, ready in run.calls:
        hs = run.handlers.get(seq)
        if hs:
            over.append((ready - issue - max(t1 - t0 for _s, t0, t1 in hs))
                        / 1e3)
    return median(over) if over else None
