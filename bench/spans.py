"""The program's own host spans in a traced run, for the per-layer
readers of host time.

The program marks its host work with ``jax.profiler`` annotations, on the
same clock as the device ops and the benchmark's ``bench.*`` spans:

    repro.fit               a whole fit_linear_streamed / resume call
    repro.fit.setup         inside it, everything before the first step
    repro.fit.step          one step on the host (a step annotation)
    repro.featurize.launch  one chunk launched by FeaturePipeline.features

``bench/xtrace.py`` keeps only the ``bench.*`` spans, so the trace a
reader is handed holds none of these.  ``program_spans`` reads them from
the run's own trace file instead: the newest ``.xplane.pb`` under a
run's scratch directory whose ``bench.window`` is the reader's window.
Where a trace holds no program span (a program that records none), the
readers find nothing and return None.
"""
from __future__ import annotations

import glob
import os
import statistics
import tempfile

from bench import xtrace

FIT = "repro.fit"
SETUP = "repro.fit.setup"
STEP = "repro.fit.step"
LAUNCH = "repro.featurize.launch"
PREFIXES = ("bench.", "repro.")


def host_spans(path: str) -> list:
    """(name, start_s, end_s) of every ``bench.*`` and ``repro.*`` host
    span in the trace at ``path``, in start order.  A name is cut at its
    first ``#``, where a profiler may write the span's arguments."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    spans = []
    for plane in pd.planes:
        if xtrace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name.split("#", 1)[0]
                if name.startswith(PREFIXES):
                    spans.append((name, e.start_ns * 1e-9, e.end_ns * 1e-9))
    return sorted(spans, key=lambda s: s[1])


def load(path: str) -> xtrace.Trace:
    """The trace as ``xtrace.load`` reads it, with the program's spans
    kept beside the benchmark's."""
    trace = xtrace.load(path)
    trace.spans = host_spans(path)
    return trace


def _run_file_spans(lo: float, hi: float) -> list:
    """The host spans of the run whose window is [lo, hi], from its trace
    file under the run's scratch directory (``bench-*`` in the temporary
    directory, as the harness makes it); [] where there is none."""
    pattern = os.path.join(tempfile.gettempdir(), "bench-*", "trace", "**",
                           "*.xplane.pb")
    for path in sorted(glob.glob(pattern, recursive=True),
                       key=os.path.getmtime, reverse=True):
        found = host_spans(path)
        if ("bench.window", lo, hi) in found:
            return found
    return []


def program_spans(layer) -> list:
    """The program's spans that lie inside the layer's window."""
    if layer.trace is None:
        return []
    spans = [s for s in layer.trace.spans if s[0].startswith("repro.")]
    if not spans:
        spans = [s for s in _run_file_spans(layer.lo, layer.hi)
                 if s[0].startswith("repro.")]
    return [s for s in spans if layer.lo <= s[1] and s[2] <= layer.hi]


def named(spans: list, name: str, within=None) -> list:
    """The spans called ``name``, those inside ``within``'s
    (name, start, end) where it is given."""
    out = [s for s in spans if s[0] == name]
    if within is not None:
        out = [s for s in out if within[1] <= s[1] and s[2] <= within[2]]
    return out


def median_ms(spans: list):
    """Median duration of ``spans`` in ms, or None where there are none."""
    if not spans:
        return None
    return statistics.median(e - s for _, s, e in spans) * 1e3
