"""`staging.h2d_mb`: what one staged cohort puts on the device, from the
`bytes` the program counts on its `h2d` spans. A count, not a rate: an
asynchronous `device_put` returns before the copy ends, so the span's
seconds say nothing about the link."""


def read(ctx, params):
    sent = [s["bytes"] for s in ctx["tracer"].window_spans("h2d")
            if "bytes" in s]
    return sum(sent) / len(sent) / 1e6 if sent else None
