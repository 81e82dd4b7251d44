"""95th percentile of every gap between consecutive tokens of every
sequence in the window (host clock; one static batch's sequences share
each gap)."""
import numpy as np


def read(rec):
    if not rec.get("gaps_s"):
        return None
    return float(np.percentile(rec["gaps_s"], 95)) * 1e3
