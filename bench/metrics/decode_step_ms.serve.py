"""Median device time of the decode-step program: the model step and
its activation coding (device trace)."""
import statistics


def read(rec):
    runs = rec.get("trace", {}).get("modules", {}).get("decode_step")
    if not runs:
        return None
    return 1e3 * statistics.median(runs)
