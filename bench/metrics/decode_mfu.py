"""Share of the chips' bf16 peak: the forward operations of every
output token served in the window (``flops.output_token_flops``:
matmuls, tied LM head, attention over the positions really attended)
over the window's time (host clock), chips and the published peak.
It reads the same window, tokens and time as ``output_tok_s``; a traced
run traces only after that window."""
from bench.peaks import peaks


def read(rec):
    if "flops_per_sequence" not in rec or not rec.get("sequences"):
        return None
    done = rec["flops_per_sequence"] * rec["sequences"] / rec["window_s"]
    return 100.0 * done / (rec["chips"] * peaks(rec["device_kind"])[
        "bf16_flop_s"])
