"""Device time per run of the prefill program (device trace)."""


def read(rec):
    runs = rec.get("trace", {}).get("modules", {}).get("prefill")
    if not runs:
        return None
    return 1e3 * sum(runs) / len(runs)
