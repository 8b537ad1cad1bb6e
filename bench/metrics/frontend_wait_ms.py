"""Mean time a request spent being admitted and lingering in the front end's
deadline batcher, in ms (the tracer's admission and linger phases over the
window: histogram sums over counts)."""


def read(record):
    c = record.counters
    count = c.get("trace_phase_seconds.linger.count", 0.0)
    if not count:
        return None
    total = c["trace_phase_seconds.admission.sum"] + c["trace_phase_seconds.linger.sum"]
    return 1e3 * total / count
