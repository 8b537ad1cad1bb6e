"""Seconds to warm the cell's executables in set-up (host clock around
``warm_server``, or around the first join calls, which compile)."""


def read(record):
    return record.spans.get("warm_s")
