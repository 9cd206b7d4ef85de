"""Entry and dispatch: what a long request costs a normal one. The
median client time of the short calls whose SCHEDULED arrival fell
while a ``SlowStep`` handler was running (between the benchmark's stamps
around that handler), less the median of the short calls that arrived
while none was. The ideal is 0: a held worker delays nobody. Both from
the benchmark's own call and handler stamps, over the whole window.
Nothing with fewer than ``MIN_EACH`` short calls on either side, and
nothing where no handler was stamped as long (another service)."""

from bisect import bisect_right

from benchmark.lib.stats import median

LONG = 1            # services/longtail.py: the stamp's shard of a SlowStep
MIN_EACH = 20


def split(calls, handlers):
    """(behind, clear): the short calls' times in us by whether the
    arrival lay inside some long handler's [start, end]."""
    holds = sorted((t0, t1) for hs in handlers.values()
                   for kind, t0, t1 in hs if kind == LONG)
    starts = [h[0] for h in holds]
    # the latest end among the holds that started at or before each one
    ends, latest = [], 0
    for _t0, t1 in holds:
        latest = max(latest, t1)
        ends.append(latest)
    behind, clear = [], []
    for _seq, arrived, ready in calls:
        i = bisect_right(starts, arrived)
        held = i > 0 and arrived <= ends[i - 1]
        (behind if held else clear).append((ready - arrived) / 1e3)
    return behind, clear


def read(run):
    behind, clear = split(run.calls, run.handlers)
    if len(behind) < MIN_EACH or len(clear) < MIN_EACH:
        return None
    return median(behind) - median(clear)
