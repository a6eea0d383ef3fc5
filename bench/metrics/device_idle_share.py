"""Device, in percent: 1 - (union of device operation intervals / traced window)."""


def read(r: dict):
    tr = r["trace"]
    return 100.0 * tr["idle_share"] if tr and tr["op_count"] else None
