"""Model: the decode steps' model FLOPs over their host time times the
chip's bf16 peak.  The FLOPs are 2 x the weights a token is multiplied by,
as the configuration's reference counts them (for a mixture of experts, the
experts it is routed to), plus attention over each request's live length.
The whole step's share of the peak, not a kernel's.  Moves ``tpot_ms``."""


def read(run):
    spans = run.untraced("decode_step")
    seconds = sum(s.seconds for s in spans)
    if not spans or seconds <= 0:
        return None
    flops = sum(s.attrs["flops"] for s in spans)
    return 100.0 * flops / (seconds * run.chips * run.peaks["bf16_flops_per_s"])
