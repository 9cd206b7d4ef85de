"""``mla_live_fetch_share`` on synthetic step records: the live rows'
share of the rows the decode attention fetched where the records carry
``attn_fetched``, nothing where they do not (a decode that does not say
what it fetched, as before the field)."""

import sys
import types

import bench_testlib  # noqa: F401 - puts the repo on sys.path
from bench_testlib import bench
from benchmark.lib.loader import load_module

SERVICE = "benchmark.services.moonlight_16b_a3b"
CELL = "moonlight_16b_a3b.decode_d192_ctx4k"


def _reader():
    return load_module("layer_metrics", "mla_live_fetch_share")


def _with_steps(monkeypatch, steps):
    monkeypatch.setitem(sys.modules, SERVICE,
                        types.SimpleNamespace(LAST={"steps": steps}))


def _step(kv_used, fetched, *, tokens=4, admitted=0):
    return {"tokens": tokens, "admitted": admitted, "routed": (18, 18),
            "kv_used": kv_used, "attn_fetched": fetched}


def test_the_live_share_of_the_fetched_rows(monkeypatch):
    steps = [_step(1530, 2048), _step(1100, 1280), _step(700, 768),
             # a step that ran no decode (its one token from a prefill)
             # says nothing of the kernel
             _step(9, None, tokens=1, admitted=1)]
    _with_steps(monkeypatch, steps)
    assert _reader().read(None) == 100.0 * (1530 + 1100 + 700) / (
        2048 + 1280 + 768)


def test_nothing_without_the_field_or_the_records(monkeypatch):
    steps = [_step(1530, 2048), _step(1100, 1280)]
    _with_steps(monkeypatch, [{k: v for k, v in r.items()
                               if k != "attn_fetched"} for r in steps])
    assert _reader().read(None) is None
    _with_steps(monkeypatch, [_step(1530, None), _step(1100, 1280)])
    assert _reader().read(None) is None
    _with_steps(monkeypatch, [])
    assert _reader().read(None) is None
    monkeypatch.delitem(sys.modules, SERVICE, raising=False)
    assert _reader().read(None) is None


def test_the_entry_reads_the_kernels_in_the_moonlight_cell():
    entry = {m["name"]: m for m in bench()["per_layer"]}[
        "mla_live_fetch_share"]
    assert entry == {"name": "mla_live_fetch_share", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "kernels", "moves": "calls_per_s",
                     "workloads": [CELL]}
