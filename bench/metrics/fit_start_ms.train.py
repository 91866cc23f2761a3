"""Host time from the start of the window's fit to the end of its first
step: from the program's ``repro.fit`` span to the end of the first
``repro.fit.step`` inside it (set-up, the first step's trace, lowering
and executable lookups, and its dispatch), from the traced run."""
from bench import spans


def read(layer):
    sp = spans.program_spans(layer)
    fits = spans.named(sp, spans.FIT)
    if not fits:
        return None
    steps = spans.named(sp, spans.STEP, within=fits[0])
    if not steps:
        return None
    return (steps[0][2] - fits[0][1]) * 1e3
