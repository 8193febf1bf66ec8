"""Engine admission: mean host time of an engine ``step()`` that admitted
requests (prefill, slot write, prefix gather and the decode that follows),
outside the traced slice.  Moves ``ttft_p50_ms``."""


def read(run):
    spans = run.untraced("admit_step")
    if not spans:
        return None
    return sum(s.seconds for s in spans) / len(spans) * 1e3
