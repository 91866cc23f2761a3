"""How many devices the window's fit split its batches over: the
``shards`` argument of the program's ``repro.fit.setup`` span (the mesh's
``data`` axis, 1 without a mesh), from a traced run.

``bench/spans.py`` keeps span names only, so this reads the argument
from the event's stats in the run's own trace file: the newest
``.xplane.pb`` under a ``bench-*`` scratch directory whose
``bench.window`` is the reader's window.  Where no such span is found
(a program that records no ``shards``), it reads None.
"""
from __future__ import annotations

import functools
import glob
import os
import tempfile

from bench import spans

SHARDS = "shards"


def setup_shards(path: str, lo: float, hi: float):
    """(found the window [lo, hi] in the trace at ``path``, ``shards`` of
    the first ``repro.fit.setup`` span inside it or None)."""
    from jax.profiler import ProfileData
    window, setups = False, []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                s, t = e.start_ns * 1e-9, e.end_ns * 1e-9
                if e.name == "bench.window" and (s, t) == (lo, hi):
                    window = True
                elif e.name == spans.SETUP and lo <= s and t <= hi:
                    shards = dict(e.stats).get(SHARDS)
                    setups.append((s, None if shards is None else int(shards)))
    return window, (min(setups)[1] if setups else None)


# the three exchange readers of one run ask for the same window
@functools.lru_cache(maxsize=8)
def _run_file_shards(lo: float, hi: float):
    pattern = os.path.join(tempfile.gettempdir(), "bench-*", "trace", "**",
                           "*.xplane.pb")
    for path in sorted(glob.glob(pattern, recursive=True),
                       key=os.path.getmtime, reverse=True):
        window, shards = setup_shards(path, lo, hi)
        if window:
            return shards
    return None


def read(layer):
    """``shards`` of the window's fit, or None."""
    if layer.trace is None:
        return None
    return _run_file_shards(layer.lo, layer.hi)
