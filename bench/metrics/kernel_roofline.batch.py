"""The useful-work roofline time of the traced window's calls over the
device's busy time, in percent (``bench.work``; busy from the trace)."""


def read(record):
    t = record["trace"]
    roof = record["counters"].get("roofline_s")
    if not t or not t["busy_s"] or not roof:
        return None
    return 100.0 * roof / t["busy_s"]
