"""The readers of the program's host spans (``bench/spans.py``,
``fit_start_ms.train``, ``step_host_ms.train``, ``launch_host_ms.featurize``):
on hand-made spans, on a trace recorded on a TPU v5e (``train.xplane.pb.gz``:
a traced run of the train cell at its widths with a 0.25 s window, 9
steps, its host planes cut to the ``bench.*`` and ``repro.*`` spans to
keep it small), and on the recorded featurization trace, which holds no
program span.  Idle time is put down to the innermost span, the
program's or the benchmark's."""
import pathlib

import pytest

from bench import harness, spans, xtrace

DATA = pathlib.Path(__file__).resolve().parent / "data"
NEW = ("fit_start_ms.train", "step_host_ms.train",
       "launch_host_ms.featurize")
OLD = ("update_ms.train", "cws_encode_roofline.train",
       "cws_encode_roofline.featurize", "train_mfu", "featurize_mfu",
       "device_idle.train", "device_idle.featurize")
TRAIN_STEPS = 9


def read(metric, layer):
    return harness.load_module(harness.BENCH / "metrics" /
                               f"{metric}.py").read(layer)


def layer_for(workload, trace, quantities):
    cell = harness.load_cell(workload)
    lo, hi = xtrace.window(trace)
    return harness.Layer(cfg=cell["config"], mix=cell["traffic"],
                         workload=cell["workload"],
                         peak=harness.peak_for("TPU v5 lite"), chips=1,
                         quantities=quantities, trace=trace, lo=lo, hi=hi)


def _device(busy):
    return xtrace.Device("/device:TPU:0",
                         [(f"fusion.{i}", s, e, f"%fusion.{i} = f32[8] op()")
                          for i, (s, e) in enumerate(busy)], [])


def hand_made():
    """A window [0, 10] around a fit whose device work starts at 1.2 s,
    and a warm-up fit before the window."""
    sp = [("repro.fit", -3.0, -1.0), ("repro.fit.step", -2.0, -1.5),
          ("bench.window", 0.0, 10.0), ("bench.fit", 0.1, 10.0),
          ("repro.fit", 0.2, 9.9), ("repro.fit.setup", 0.2, 0.5),
          ("repro.fit.step", 0.6, 1.0), ("repro.fit.step", 1.0, 1.1),
          ("repro.fit.step", 1.1, 1.13), ("repro.fit.step", 1.13, 1.15)]
    return xtrace.Trace([_device([(1.2, 9.0)])],
                        sorted(sp, key=lambda s: s[1]))


def test_nested_program_spans_take_the_idle_from_bench_fit():
    gaps = dict(xtrace.idle_gaps(hand_made(), 0.0, 10.0))
    # idle [0, 1.2) and [9, 10]: [0, 0.1] before the fit span; the setup
    # and the steps hold what they cover, repro.fit what lies between
    # them, bench.fit only what lies outside the program's fit
    assert gaps == pytest.approx({
        "host.other": 0.1, "bench.fit": 0.1 + 0.1,
        "repro.fit.setup": 0.3, "repro.fit.step": 0.55,
        "repro.fit": 0.1 + 0.05 + 0.9})


def test_readers_on_hand_made_spans():
    layer = harness.Layer(cfg={}, mix={}, workload={}, peak={}, chips=1,
                          quantities={}, trace=hand_made(), lo=0.0, hi=10.0)
    # the warm-up fit before the window is not read
    assert read("fit_start_ms.train", layer) == pytest.approx(800.0)
    # steps after the first: 100, 30, 20 ms
    assert read("step_host_ms.train", layer) == pytest.approx(30.0)
    assert read("launch_host_ms.featurize", layer) is None


def test_launch_reader_takes_the_median_inside_the_window():
    sp = [("repro.featurize.launch", -1.0, 0.5), ("bench.window", 0.0, 1.0),
          ("repro.featurize.launch", 0.1, 0.1002),
          ("repro.featurize.launch", 0.2, 0.2004),
          ("repro.featurize.launch", 0.3, 0.3009)]
    layer = harness.Layer(cfg={}, mix={}, workload={}, peak={}, chips=1,
                          quantities={}, trace=xtrace.Trace([], sp),
                          lo=0.0, hi=1.0)
    assert read("launch_host_ms.featurize", layer) == pytest.approx(0.4)
    assert read("fit_start_ms.train", layer) is None


@pytest.fixture(scope="module")
def featurize_trace():
    return DATA / "featurize.xplane.pb.gz"


@pytest.fixture(scope="module")
def train_trace():
    return DATA / "train.xplane.pb.gz"


FEATURIZE_Q = {"rows": 350000, "passes": 1, "launches": 43,
               "nnz_per_row": 84.0}
TRAIN_Q = {"rows": TRAIN_STEPS * 512, "steps": TRAIN_STEPS,
           "launches": TRAIN_STEPS, "nnz_per_row": 149.0}


@pytest.mark.parametrize("metric", NEW)
def test_no_program_span_no_value(featurize_trace, metric):
    layer = layer_for("webspam-regen-packed.featurize",
                      spans.load(str(featurize_trace)), FEATURIZE_Q)
    assert not spans.program_spans(layer)
    assert read(metric, layer) is None


@pytest.mark.parametrize("metric", OLD)
@pytest.mark.parametrize("which", ["featurize", "train"])
def test_old_readers_read_the_same_with_program_spans_kept(
        featurize_trace, train_trace, which, metric):
    path, workload, q = {
        "featurize": (featurize_trace, "webspam-regen-packed.featurize",
                      FEATURIZE_Q),
        "train": (train_trace, "mnist-stored.train", TRAIN_Q)}[which]
    plain = read(metric, layer_for(workload, xtrace.load(str(path)), q))
    kept = read(metric, layer_for(workload, spans.load(str(path)), q))
    assert kept == plain


def test_recorded_train_trace_spans(train_trace):
    tr = spans.load(str(train_trace))
    lo, hi = xtrace.window(tr)
    inside = [s for s in tr.spans if lo <= s[1] and s[2] <= hi]
    (fit,) = spans.named(inside, spans.FIT)
    (setup,) = spans.named(inside, spans.SETUP, within=fit)
    steps = spans.named(inside, spans.STEP, within=fit)
    assert len(steps) == TRAIN_STEPS
    assert setup[2] <= steps[0][1]
    assert all(a[2] <= b[1] for a, b in zip(steps, steps[1:]))
    # the kernel keeps the name the roofline readers match
    names = {n for n, _, _, _ in tr.devices[0].ops}
    assert any(n.startswith("cws_encode_pallas") for n in names)


def test_recorded_train_trace_readers(train_trace):
    layer = layer_for("mnist-stored.train", spans.load(str(train_trace)),
                      TRAIN_Q)
    # the first step traces, lowers and loads the update from the cache
    assert read("fit_start_ms.train", layer) == pytest.approx(286.274319)
    # eight steps after it: six before the device queue fills, two in it
    assert read("step_host_ms.train", layer) == pytest.approx(1.903585)


def test_readers_find_the_run_file_when_handed_bench_spans_only(
        tmp_path, monkeypatch):
    """As the harness hands them the trace: ``bench/xtrace.py`` keeps only
    ``bench.*`` spans, and the run's trace file lies under its scratch
    directory.  The profile is taken here, on the CPU, around a tiny fit."""
    import jax
    from repro.core.linear_model import TrainCfg, init_bag
    from repro.pipeline import FeaturePipeline, FeatureSpec
    from repro.training import fit_linear_streamed

    pipe = FeaturePipeline.create(jax.random.PRNGKey(0), 16,
                                  FeatureSpec(num_hashes=8, b_i=2))
    x = jax.random.uniform(jax.random.PRNGKey(1), (64, 16))
    y = (x[:, 0] > x[:, 1]).astype("int32")
    p0 = init_bag(jax.random.PRNGKey(2), pipe.num_features, 2)
    cfg = TrainCfg(n_classes=2, steps=6, batch_size=16)
    trace_dir = tmp_path / "bench-run" / "trace"
    jax.profiler.start_trace(str(trace_dir))
    with jax.profiler.TraceAnnotation("bench.window"):
        jax.block_until_ready(fit_linear_streamed(p0, pipe, x, y, cfg=cfg))
    jax.profiler.stop_trace()
    path = xtrace.find_xplane(str(trace_dir))

    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    handed = xtrace.load(path)
    assert not [s for s in handed.spans if s[0].startswith("repro.")]
    lo, hi = xtrace.window(handed)
    layer = harness.Layer(cfg={}, mix={}, workload={}, peak={}, chips=1,
                          quantities={}, trace=handed, lo=lo, hi=hi)
    full = harness.Layer(cfg={}, mix={}, workload={}, peak={}, chips=1,
                         quantities={}, trace=spans.load(path), lo=lo, hi=hi)
    assert len(spans.named(spans.program_spans(layer), spans.STEP)) == 6
    for metric in NEW[:2]:
        assert read(metric, layer) == read(metric, full) is not None
    # another run's window finds nothing there
    layer.hi += 1.0
    assert read("fit_start_ms.train", layer) is None


def test_recorded_train_fit_start_idle_goes_to_program_spans(train_trace):
    tr = spans.load(str(train_trace))
    lo, hi = xtrace.window(tr)
    gaps = dict(xtrace.idle_gaps(tr, lo, hi))
    # the fit's first step holds the idle at its start
    assert gaps["repro.fit.step"] > 0.25
    assert gaps["bench.fit"] < 0.05
