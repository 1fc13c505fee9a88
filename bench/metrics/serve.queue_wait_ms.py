"""Serving engine: mean milliseconds a request waited in the engine's
queue (submit to the start of its drain), over every request of the traced
window, from the engine's ``serve_queue_wait_seconds`` histogram."""


def read(ctx):
    n = ctx.layer.get("queue_wait_count")
    if not n:
        return None
    return ctx.layer["queue_wait_sum_s"] / n * 1e3
