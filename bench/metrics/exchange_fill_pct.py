"""Real bytes over shipped bytes in the join's two all-to-alls (probe keys
out to their owners, result rows back), over the window's calls, in %.

Each phase ships fixed-capacity slots (the exchange pads the paper's
ragged all-to-all to static shapes): its real rows are the program's
``ShardJoin.exchange_rows`` summed over the calls, its slots the program's
``exchange_slots_total`` counter, each weighted by the bytes a row of that
phase carries (a key out, a row of value columns back).  The count lane
that rides with the rows back is left out of both sides."""

PHASES = ("route", "return")


def read(record):
    c = record.counters
    width = record.work.get("exchange", {})
    shipped = real = 0.0
    for p in PHASES:
        b = width.get(f"bytes_per_row.{p}", 0)
        shipped += b * c.get(f"exchange_slots_total.{p}", 0.0)
        real += b * c.get(f"exchange_rows_total.{p}", 0.0)
    if not shipped:
        return None
    return 100.0 * real / shipped
