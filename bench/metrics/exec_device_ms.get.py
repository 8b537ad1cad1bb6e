"""Mean device time of one read executable (``exec_query``) in the traced
window, in ms, from the profiler trace."""


def read(record):
    if record.trace is None:
        return None
    ex = record.trace.executable("exec_query")
    if ex is None or not ex["count"]:
        return None
    return 1e3 * ex["seconds"] / ex["count"]
