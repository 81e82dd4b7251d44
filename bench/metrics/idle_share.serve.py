"""1 - (union of device-op intervals / traced window), in % (device
trace, averaged over the chips)."""


def read(rec):
    if "trace" not in rec:
        return None
    return 100.0 * rec["trace"]["idle_share"]
