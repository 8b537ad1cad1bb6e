"""The join executable's share of its HBM roofline, in %: the least bytes
any implementation must move for the calls traced (``bench.roofline.
probe_min_bytes``, from shapes) over the chip's HBM bandwidth times the
device time of ``exec_join`` in the trace.  Bound by bytes: the probe does
no arithmetic worth counting."""


def read(record):
    if record.trace is None or "hbm_bytes_per_s" not in record.peaks:
        return None
    ex = record.trace.executable("exec_join")
    if ex is None or not ex["seconds"]:
        return None
    least = record.work["exec_join"]["min_bytes_per_call"] * ex["count"]
    return 100.0 * least / (record.peaks["hbm_bytes_per_s"] * ex["seconds"])
