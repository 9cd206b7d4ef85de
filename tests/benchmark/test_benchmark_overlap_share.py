"""``gen_decode_overlap_share`` on step records the program's batcher
writes on the CPU (``TinyDecoder``), and on the same records without
the ``overlapped`` field, as a batcher that steps synchronously leaves
them: a share where the field is there, nothing where it is not."""

import sys
import types

import bench_testlib  # noqa: F401 - puts the repo on sys.path
from bench_testlib import bench
from benchmark.lib.loader import load_module

SERVICE = "benchmark.services.moonlight_16b_a3b"
CELL = "moonlight_16b_a3b.decode_d192_ctx4k"


def _reader():
    return load_module("layer_metrics", "gen_decode_overlap_share")


def _with_steps(monkeypatch, steps):
    monkeypatch.setitem(sys.modules, SERVICE,
                        types.SimpleNamespace(LAST={"steps": steps}))


def _records():
    """The step records of a staggered run of four requests through two
    slots, with a counter on the decode (as Moonlight's routing
    counter) so that its records count as decode steps."""
    import jax.numpy as jnp

    from brpc_tpu.serving import ContinuousBatcher, GenRequest
    from brpc_tpu.serving import serving_stats as ss
    from brpc_tpu.serving.model import TinyDecoder, TinyDecoderConfig

    class Counted(TinyDecoder):
        def decode(self, cache, last_tokens, lengths):
            cache, nxt, _c, aux = super().decode(cache, last_tokens,
                                                 lengths)
            return cache, nxt, jnp.ones((2,), jnp.int32), aux

    b = ContinuousBatcher(Counted(TinyDecoderConfig(cache_len=64)),
                          max_batch=2, max_waiting=4)
    reg = ss.global_serving_stats()
    before = reg.steps_recorded()
    for i, n in enumerate((7, 3, 9, 5)):
        assert b.submit(GenRequest([1 + i, 2, 3], n))
    while b.has_work():
        b.step(0)
    return reg.step_records(reg.steps_recorded() - before)


def test_the_share_of_decode_steps_that_overlapped(monkeypatch):
    steps = _records()
    decodes = [r for r in steps
               if r["routed"] is not None and r["tokens"] > r["admitted"]]
    assert len(decodes) >= 9
    _with_steps(monkeypatch, steps)
    # only the step that drains the batch queues nothing behind it
    want = 100.0 * sum(r["overlapped"] for r in decodes) / len(decodes)
    assert _reader().read(None) == want
    assert 85.0 <= want < 100.0
    _with_steps(monkeypatch, [dict(r, overlapped=1) for r in steps])
    assert _reader().read(None) == 100.0


def test_nothing_without_the_field_or_the_records(monkeypatch):
    steps = _records()
    _with_steps(monkeypatch, [{k: v for k, v in r.items()
                               if k != "overlapped"} for r in steps])
    assert _reader().read(None) is None
    _with_steps(monkeypatch, [])
    assert _reader().read(None) is None
    monkeypatch.delitem(sys.modules, SERVICE, raising=False)
    assert _reader().read(None) is None


def test_the_entry_reads_the_serving_lane_in_the_moonlight_cell():
    entry = {m["name"]: m for m in bench()["per_layer"]}[
        "gen_decode_overlap_share"]
    assert entry == {"name": "gen_decode_overlap_share", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "serving lane", "moves": "calls_per_s",
                     "workloads": [CELL]}
