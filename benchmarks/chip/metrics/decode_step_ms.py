"""Engine decode step: mean host time of an engine ``step()`` that admitted
nothing, outside the traced slice.  Moves ``tpot_ms``."""


def read(run):
    spans = run.untraced("decode_step")
    if not spans:
        return None
    return sum(s.seconds for s in spans) / len(spans) * 1e3
