"""Output tokens completed in the window over the window's time (host
clock).  The window is whole batches, their prefills included."""


def read(rec):
    if "output_tokens" not in rec:
        return None
    return rec["output_tokens"] / rec["window_s"]
