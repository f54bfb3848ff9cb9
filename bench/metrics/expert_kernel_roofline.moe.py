"""The routed experts' useful-work roofline time over the device time of
the ragged kernel's own ops (``bsr_spmm_ragged``) in the traced window,
in percent.  Roofline per call (``bench.moe.ragged_work``): every
expert's kept blocks read once, the token-expert pairs' rows in and out
once, ``2 * cells * pairs`` FLOPs."""

KERNEL = "bsr_spmm_ragged"


def read(record):
    t = record["trace"]
    roof = record["counters"].get("moe_expert_roofline_s")
    if not t or not roof:
        return None
    busy = sum(s for name, s in t["device_ops"] if KERNEL in name)
    if not busy:
        return None
    return 100.0 * roof / busy
