"""Mean dispatch phase per request, in ms: snapshot pin, coalesce and pad,
and the launch of the warmed executable (the tracer's dispatch phase over
the window)."""


def read(record):
    c = record.counters
    count = c.get("trace_phase_seconds.dispatch.count", 0.0)
    if not count:
        return None
    return 1e3 * c["trace_phase_seconds.dispatch.sum"] / count
