"""The reduction from a profiler trace to busy time, kernel time, exposed
collectives and idle gaps: on hand-made intervals, and on a trace
recorded on a TPU v5e (a traced run of the featurization cell, with a
shortened window)."""
import pathlib

import pytest

from bench import kernels, xtrace

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_interval_arithmetic():
    assert xtrace.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert xtrace.total([(0, 2), (3, 4)]) == 3
    assert xtrace.clip([(0, 2), (3, 4), (5, 6)], 1, 3.5) == [(1, 2), (3, 3.5)]
    # [0, 10] less [1, 2] and [3, 5] and [4, 6]: 1 + 1 + 4
    assert xtrace.subtract([(0, 10)], [(4, 6), (1, 2), (3, 5)]) == 6
    assert xtrace.subtract([(0, 1), (2, 3)], []) == 2
    assert xtrace.subtract([(0, 1)], [(0, 1)]) == 0


def _device(ops, modules=()):
    return xtrace.Device("/device:TPU:0",
                         [(n, s, e, f"%{n} = f32[8] op()") for n, s, e in ops],
                         list(modules))


def test_busy_exposed_collective_and_kernel_time():
    dev = _device([("cws_encode_pallas.1", 0.0, 2.0),
                   ("fusion.3", 1.0, 3.0),
                   ("all-reduce.7", 2.5, 5.0),
                   ("fusion.4", 4.0, 4.5)])
    # union of ops in [0, 10]: [0, 5]
    assert xtrace.busy_s(dev, 0.0, 10.0) == 5.0
    # the all-reduce [2.5, 5] overlaps compute in [2.5, 3] and [4, 4.5]
    assert xtrace.exposed_collective_s(dev, 0.0, 10.0) == 1.5
    assert xtrace.op_time(dev, kernels.is_cws_encode, 0.0, 10.0) == 2.0
    assert xtrace.op_time(dev, kernels.is_cws_encode, 1.5, 10.0) == 0.5


def test_op_names_are_the_instruction_names():
    line = ("%cws_encode_rng_packed_pallas.1 = u32[8,8192,32]{2,1,0} "
            "custom-call(f32[8192,256]{1,0} %pad.2)")
    assert xtrace.op_name(line) == "cws_encode_rng_packed_pallas.1"
    assert kernels.is_cws_encode(xtrace.op_name(line))
    assert not kernels.is_cws_encode("fusion.1")


def test_idle_gaps_go_to_the_innermost_host_span():
    dev = _device([("fusion.1", 0.0, 1.0), ("fusion.2", 3.0, 4.0)])
    tr = xtrace.Trace([dev], [("bench.window", 0.0, 5.0),
                              ("bench.fit", 0.5, 5.0),
                              ("bench.pass", 1.5, 2.5)])
    gaps = dict(xtrace.idle_gaps(tr, 0.0, 5.0))
    # idle [1, 3] and [4, 5]: the pass holds [1.5, 2.5], the fit the rest
    assert gaps == {"bench.pass": 1.0, "bench.fit": 2.0}


@pytest.fixture(scope="module")
def recorded():
    return xtrace.load(str(DATA / "featurize.xplane.pb.gz"))


def test_recorded_trace(recorded):
    tr = recorded
    assert [d.name for d in tr.devices] == ["/device:TPU:0"]
    lo, hi = xtrace.window(tr)
    dev = tr.devices[0]
    busy = xtrace.busy_s(dev, lo, hi)
    assert 0 < busy <= hi - lo
    enc = xtrace.op_time(dev, kernels.is_cws_encode, lo, hi)
    # the regenerated, packed encode kernel does nearly all the work
    assert 0.9 * busy < enc <= busy
    top = xtrace.top_ops(tr, lo, hi)
    assert top[0][0] == "cws_encode_rng_packed_pallas"
    assert len(top) <= 10
    gaps = xtrace.idle_gaps(tr, lo, hi)
    assert sum(t for _, t in gaps) == pytest.approx(hi - lo - busy, abs=1e-9)
    assert any(n.startswith("jit_") for n, _, _ in dev.modules)
