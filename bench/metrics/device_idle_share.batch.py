"""Share of the traced window in which no op ran on the device, in
percent."""


def read(record):
    t = record["trace"]
    if not t or not t["busy_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
