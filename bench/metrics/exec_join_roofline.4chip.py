"""The join executable's share of its HBM roofline on a multi-chip host, in
%: the least bytes any implementation must move for the calls traced
(``bench.roofline.probe_min_bytes`` of a whole call, from shapes) over the
HBM bandwidth of every chip the call runs on times their device time.

The trace has one ``exec_join`` per device per call, so the calls are its
count over the devices, and the summed seconds already hold one device
time per chip."""


def read(record):
    if record.trace is None or "hbm_bytes_per_s" not in record.peaks:
        return None
    ex = record.trace.executable("exec_join")
    if ex is None or not ex["seconds"]:
        return None
    calls = ex["count"] / record.trace.devices
    least = record.work["exec_join"]["min_bytes_per_call"] * calls
    return 100.0 * least / (record.peaks["hbm_bytes_per_s"] * ex["seconds"])
