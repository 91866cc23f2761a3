"""Median host time of one training step after the fit's first: the
duration of the program's ``repro.fit.step`` spans of the window's fit,
its first left out (batch gather, featurize launch and update dispatch
enqueued), from the traced run.  Once the runtime's queue of launches is
full the host waits inside a dispatch, so this reads the larger of the
host's work and the device's step."""
from bench import spans


def read(layer):
    sp = spans.program_spans(layer)
    fits = spans.named(sp, spans.FIT)
    if not fits:
        return None
    return spans.median_ms(spans.named(sp, spans.STEP, within=fits[0])[1:])
