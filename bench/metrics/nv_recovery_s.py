"""NVCache engine, recovery: mean seconds to attach a new ``NVCache`` over
the crashed region (log replay included)."""


def read(r: dict):
    s = r["recoveries"]
    return sum(s) / len(s) if s else None
