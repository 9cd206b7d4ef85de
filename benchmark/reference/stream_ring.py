"""Plain reference of the streaming ring, independent of brpc_tpu: no
fabric, no stream, no chip placement. Frames go in as a list of (tag,
array); what peer 0 must receive comes out: the same tags in the same
order, each array plus one for every hop of the circuit. Float32; the
data are small integers, so the bf16 result must equal it exactly.

Beside it, the credit rule of a stream as a model that the tests hold
the program's counters to."""

from __future__ import annotations


def ring_reference(frames, hops: int):
    """[(tag, array + hops in float32)] in the order the frames came."""
    import jax
    import jax.numpy as jnp

    # kept for form: the body is an add, no matmul to lose precision in
    with jax.default_matmul_precision("highest"):
        return [(tag, a.astype(jnp.float32) + float(hops))
                for tag, a in frames]


def credit_model(frames: int, initial_credits: int, credit_batch: int) -> dict:
    """``frames`` data frames from a writer that writes whenever it holds
    a credit to a consumer slower than it, which delivers in order. The
    writer holds at most ``initial_credits`` un-granted frames; a grant
    follows every ``credit_batch`` deliveries, and the delivery of a
    frame that took the writer's last credit (it asked for feedback)."""
    credits, sent, pending = initial_credits, 0, 0
    grants = parks = worst = 0
    asked: list = []        # written, not delivered: took the last credit?
    while sent < frames or asked:
        dry = sent < frames and not credits
        while credits and sent < frames:
            credits, sent = credits - 1, sent + 1
            worst = max(worst, initial_credits - credits)
            asked.append(credits == 0)
        pending += 1
        if asked.pop(0) or pending >= credit_batch:
            credits, pending, grants = credits + pending, 0, grants + 1
            parks += dry
    return {"grant_frames": grants, "credit_parks": parks,
            "ungranted_frames_max": worst}
