"""Real keys over padded bucket keys shipped to the device, in % (the
batcher's keys-served and keys-padded counters over the window)."""


def read(record):
    c = record.counters
    served = c.get("batch_keys_served_total", 0.0)
    padded = c.get("batch_keys_padded_total", 0.0)
    if not served + padded:
        return None
    return 100.0 * served / (served + padded)
