"""Seconds to build the table in set-up (host clock around
``DistributedHashTable.init``, or the server that calls it, ending in
``block_until_ready``)."""


def read(record):
    return record.spans.get("build_s")
