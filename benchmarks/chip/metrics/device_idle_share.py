"""1 - (union of device operation intervals / traced window)."""


def read(rec):
    if rec["peaks"] is None:  # no chip: the trace holds no device
        return None
    tr = rec["trace"]
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
