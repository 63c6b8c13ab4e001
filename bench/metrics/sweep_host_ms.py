"""Host milliseconds per sweep: the benchmark's span around each
``experiment.sweep`` call less the device-busy time inside it."""


def read(ctx):
    tr = ctx["trace"]
    spans = tr.span_s("sweep")
    if not spans:
        return None
    busy = tr.busy_within_s("sweep")
    return 1000.0 * sum(s - b for s, b in zip(spans, busy)) / len(spans)
