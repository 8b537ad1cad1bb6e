"""Share of the traced window in which no operation ran on the device, in %
(1 - busy union / window, from the profiler trace), in the get cells."""


def read(record):
    if record.trace is None:
        return None
    return 100.0 * record.trace.idle_share
