"""The data-parallel cell's readers of the exchange between chips
(``allreduce_ms.train-dp4``, ``allreduce_exposed_ms.train-dp4``,
``allreduce_gbps.train-dp4``), the bytes they count
(``bench/exchange.py``) and the ``shards`` they read from the program's
``repro.fit.setup`` span (``bench/fit_shards.py``); and the cell itself,
as BENCHMARK.json holds it, at a tiny size: a sound run is correct and
one whose chips leave out the exchange is not."""
import copy
import time

import jax
import pytest

from bench import exchange, fit_shards, harness, xtrace
from conftest import DP4, SEED

READERS = ("allreduce_ms.train-dp4", "allreduce_exposed_ms.train-dp4",
           "allreduce_gbps.train-dp4")
STEPS = 2


def read(metric, layer):
    return harness.load_module(harness.BENCH / "metrics" /
                               f"{metric}.py").read(layer)


def test_exchange_bytes_at_mnist_widths():
    cfg = harness.load_cell(DP4)["config"]
    # the (262,144 x 10) table's gradient and the 10 biases', in float32
    assert exchange.allreduce_bytes(cfg) == 10_485_800


def _chip(i, ops, modules):
    return xtrace.Device(f"/device:TPU:{i}",
                         [(n, s, e, f"%{n} = f32[8] op()") for n, s, e in ops],
                         modules)


def four_chips():
    """A window [0, 10] of two steps (``jit_update`` runs) on four chips.
    Chip i's first all-reduce runs [4, 5 + i / 4] beside a fusion in
    [4.5, 5]; the second step's, [9, 9.5], overlaps nothing."""
    return xtrace.Trace(
        [_chip(i, [("cws_encode_pallas.1", 0.0, 4.0),
                   ("fusion.2", 4.5, 5.0),
                   ("all-reduce.4", 4.0, 5.0 + i / 4),
                   ("cws_encode_pallas.1", 6.0, 9.0),
                   ("all-reduce.4", 9.0, 9.5)],
               [("jit_update(7)", 0.0, 5.0 + i / 4),
                ("jit_update(7)", 6.0, 9.5)]) for i in range(4)],
        [("bench.window", 0.0, 10.0)])


def layer_with(shards, monkeypatch, trace=None):
    monkeypatch.setattr(fit_shards, "read", lambda layer: shards)
    cell = harness.load_cell(DP4)
    return harness.Layer(cfg=cell["config"], mix=cell["traffic"],
                         workload=cell["workload"], peak={}, chips=4,
                         quantities={"steps": STEPS},
                         trace=trace or four_chips(), lo=0.0, hi=10.0)


def test_readers_on_four_chips(monkeypatch):
    layer = layer_with(4, monkeypatch)
    # collective time a chip: 1 + i / 4 + 0.5, so 1.875 s on average
    assert read("allreduce_ms.train-dp4", layer) == pytest.approx(
        1.875 / STEPS * 1e3)
    # less the fusion's [4.5, 5] on every chip: 1.375 s
    assert read("allreduce_exposed_ms.train-dp4", layer) == pytest.approx(
        1.375 / STEPS * 1e3)
    assert read("allreduce_gbps.train-dp4", layer) == pytest.approx(
        10_485_800 * STEPS / 1.875 / 1e9)


def test_a_chip_whose_trace_stops_early_counts_its_own_steps(monkeypatch):
    """The profiler can drop a chip's later events: chip 3's trace keeps
    its first step only, whose all-reduce took 1.75 s."""
    tr = four_chips()
    cut = tr.devices[3]
    cut.ops = [op for op in cut.ops if op[2] <= 5.75]
    cut.modules = cut.modules[:1]
    layer = layer_with(4, monkeypatch, tr)
    # chips 0-2: (1 + i / 4 + 0.5) / 2 s a step; chip 3: 1.75 s
    want = ((0.75 + 0.875 + 1.0) + 1.75) / 4
    assert read("allreduce_ms.train-dp4", layer) == pytest.approx(want * 1e3)


def test_readers_only_count_inside_the_window(monkeypatch):
    layer = layer_with(4, monkeypatch)
    layer.hi = 9.25
    # the second all-reduce is cut to [9, 9.25]
    assert read("allreduce_ms.train-dp4", layer) == pytest.approx(
        1.625 / STEPS * 1e3)


def test_a_trace_with_no_update_reads_nothing(monkeypatch):
    tr = four_chips()
    for d in tr.devices:
        d.modules = []
    layer = layer_with(4, monkeypatch, tr)
    assert all(read(m, layer) is None for m in READERS)


@pytest.mark.parametrize("shards", [1, None])
@pytest.mark.parametrize("metric", READERS)
def test_a_fit_on_fewer_devices_than_chips_reads_nothing(monkeypatch,
                                                         shards, metric):
    assert read(metric, layer_with(shards, monkeypatch)) is None


def _profiled_fit(tmp_path, mesh):
    """A tiny fit in a window, profiled on the CPU as the harness profiles
    a run, under a ``bench-*`` scratch directory; the window's layer as
    the harness hands it to the readers."""
    from repro.core.linear_model import TrainCfg, init_bag
    from repro.pipeline import FeaturePipeline, FeatureSpec
    from repro.training import fit_linear_streamed

    pipe = FeaturePipeline.create(jax.random.PRNGKey(0), 16,
                                  FeatureSpec(num_hashes=8, b_i=2))
    x = jax.random.uniform(jax.random.PRNGKey(1), (64, 16))
    y = (x[:, 0] > x[:, 1]).astype("int32")
    p0 = init_bag(jax.random.PRNGKey(2), pipe.num_features, 2)
    cfg = TrainCfg(n_classes=2, steps=3, batch_size=16)
    trace_dir = tmp_path / "bench-run" / "trace"
    jax.profiler.start_trace(str(trace_dir))
    with jax.profiler.TraceAnnotation("bench.window"):
        jax.block_until_ready(fit_linear_streamed(p0, pipe, x, y, cfg=cfg,
                                                  mesh=mesh))
    jax.profiler.stop_trace()
    handed = xtrace.load(xtrace.find_xplane(str(trace_dir)))
    lo, hi = xtrace.window(handed)
    return harness.Layer(cfg={}, mix={}, workload={}, peak={}, chips=4,
                         quantities={}, trace=handed, lo=lo, hi=hi)


@pytest.mark.parametrize("ndev", [1, 4])
def test_shards_read_from_the_run_file(tmp_path, monkeypatch, ndev):
    from repro.launch.mesh import make_data_mesh
    assert len(jax.devices()) >= 4
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    layer = _profiled_fit(tmp_path, make_data_mesh(4) if ndev == 4 else None)
    assert fit_shards.read(layer) == ndev
    # another run's window finds nothing there
    layer.hi += 1.0
    assert fit_shards.read(layer) is None


def real_dp4():
    """The data-parallel cell as BENCHMARK.json holds it, cut to a size a
    CPU test run holds (as ``conftest.tiny`` cuts the one-chip cell)."""
    cell = copy.deepcopy(harness.load_cell(DP4))
    assert cell["workload"]["chips"] == 4
    assert cell["traffic"]["batch_size"] == 512 * 4
    cell["config"].update(dim=32, num_hashes=64, b_i=4, n_train=16384,
                          n_test=256)
    cell["traffic"].update(batch_size=64 * 4, steps_per_s=200)
    cell["limits"] = {"grad_gap": 1e-5, "change3_gap": 1e-5,
                      "change_gap": 1e-4}
    return cell


def run_real_dp4():
    res, _ = harness.run(DP4, SEED, 0.5, False, t_start=time.perf_counter(),
                         need_chip=False, cell=real_dp4())
    return res


def test_the_cell_runs_correct():
    res = run_real_dp4()
    assert res["correct"], res["checks"]
    assert res["attempted"] == 100 and res["failed"] == 0


def test_the_cell_without_the_exchange_is_not_correct(monkeypatch):
    from repro.training import trainer
    monkeypatch.setattr(trainer, "_pmean_loss_grads",
                        lambda loss, grads, axis_name: (loss, grads))
    assert not run_real_dp4()["correct"]
