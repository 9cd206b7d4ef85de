"""Kernels: of the cached rows the decode attention kernel fetched in
the window's decode steps (the step record's ``attn_fetched``, the
rows a layer's call fetched), the share in percent that were live
(``kv_used``). A kernel that fetches whole blocks reads the rows past a
slot's last live one up to its block's end; one that walks the live
rows in small chunks reads fewer. Nothing where the records carry no
such field (a decode that does not say what it fetched)."""

from benchmark.lib import generation


def read(run):
    steps = generation.decode_steps(generation.last().get("steps") or [])
    if not steps or any(r.get("attn_fetched") is None for r, _made in steps):
        return None
    fetched = sum(r["attn_fetched"] for r, _made in steps)
    if not fetched:
        return None
    return 100.0 * sum(r["kv_used"] for r, _made in steps) / fetched
