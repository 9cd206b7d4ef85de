"""Experts: the mean tokens a held expert received in a decode step, in
an expert layer (the decode program's routing counter, one count a held
expert a layer, in the window's step records). At 192 slots it reads
back the sizing: 192 x 6 / 64 = 18, the deployment's share."""

from benchmark.lib import generation


def read(run):
    steps = generation.decode_steps(generation.last().get("steps") or [])
    if not steps:
        return None
    per = [sum(r["routed"]) / len(r["routed"]) for r, _made in steps]
    return sum(per) / len(per)
