"""Median host time to launch one chunk of FeaturePipeline.features (slice,
tail pad, enqueue): the duration of the program's
``repro.featurize.launch`` spans in the window, from the traced run.
Once the runtime's queue of launches is full the host waits inside a
dispatch, so this reads the larger of the host's work and a launch's
device time."""
from bench import spans


def read(layer):
    return spans.median_ms(spans.named(spans.program_spans(layer),
                                       spans.LAUNCH))
