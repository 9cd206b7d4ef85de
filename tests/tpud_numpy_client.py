"""A client process without jax, for tests/test_tpud_remote.py: dials a
tpud:// server, makes a few calls with a bfloat16 numpy array and prints
one JSON line: whether jax ever got loaded, the replies' types and
bytes, its client spans, its /device cells and its tpud counters.

    python tests/tpud_numpy_client.py <port> <rows> <cols> <calls>"""

import base64
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402

from brpc_tpu.butil.flags import set_flag  # noqa: E402
from brpc_tpu.rpc import Channel, ChannelOptions  # noqa: E402
from brpc_tpu.rpc.span import global_collector  # noqa: E402
from brpc_tpu.transport import device_stats, syscall_stats  # noqa: E402


def main() -> int:
    port, rows, cols, calls = (int(a) for a in sys.argv[1:5])
    set_flag("rpcz_enabled", True)
    set_flag("device_stats_enabled", True)
    ch = Channel(f"tpud://127.0.0.1:{port}",
                 ChannelOptions(timeout_ms=20000, max_retry=0,
                                connection_type="single"))
    lane = ch.device_lane_kind()
    rng = np.random.default_rng(rows * cols)
    x = rng.standard_normal((rows, cols)).astype(ml_dtypes.bfloat16)
    replies = []
    for i in range(calls):
        cntl = ch.call_sync("Perf", "Step", b"tag%d" % i,
                            request_device_arrays=[x])
        if cntl.failed():
            print(json.dumps({"error": cntl.error_text}))
            return 1
        out = cntl.response_device_arrays[0]
        replies.append({
            "tag": cntl.response_payload.to_bytes().decode(),
            "type": type(out).__name__, "dtype": str(out.dtype),
            "shape": list(out.shape),
            "bytes": base64.b64encode(out.tobytes()).decode()})
    ch.close()
    snap = syscall_stats.snapshot()
    print(json.dumps({
        "jax_loaded": "jax" in sys.modules, "lane": lane,
        "x": base64.b64encode(x.tobytes()).decode(), "replies": replies,
        "spans": [s.to_dict() for s in global_collector.recent(1000)
                  if s.side == "client"],
        "cells": device_stats.device_page_payload(samples=0)["cells"],
        "counters": {k: v for k, v in snap.items()
                     if k.startswith(("tpud_", "write_"))}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
