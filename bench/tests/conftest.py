"""CPU tests of the benchmark itself: ``python -m pytest bench/tests``.

They run on four virtual CPU devices, so that the train driver's
data-parallel path (a cell with ``"chips": 4``) can be driven here, and
at tiny sizes (``tiny``)."""
import copy
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

SEED = 2 ** 40 + 3   # above 32 bits, as the driver's seeds are
DP4 = "mnist-stored.train-dp4"
SERVE = "mnist-stored.serve"


def tiny(name: str) -> dict:
    """The cell's files, cut to a size a CPU test run holds.  ``DP4`` (the
    train cell on four chips) and ``SERVE`` (online scoring) are cells
    that BENCHMARK.json does not hold yet: they drive the train driver's
    data-parallel path and the serve driver."""
    from bench import harness
    if name == DP4:
        cell = tiny("mnist-stored.train")
        cell["workload"].update(name=DP4, chips=4)
        cell["traffic"].update(batch_size=256)
        return cell
    if name == SERVE:
        cell = tiny("mnist-stored.train")
        cell["workload"] = {"name": SERVE, "config": "mnist-stored",
                            "traffic": "serve", "chips": 1}
        cell["traffic"] = harness.load_json(harness.BENCH / "traffic" /
                                            "serve.json")
        cell["traffic"].update(rate_per_s=200, sample_rows=256, drain_s=10)
        cell["limits"] = harness.load_json(harness.BENCH / "limits" /
                                           f"{SERVE}.json")
        cell["spec"]["end_to_end"] += [
            {"name": "serve_p95_ms", "unit": "ms"},
            {"name": "serve_p50_ms", "unit": "ms"}]
        return cell
    cell = copy.deepcopy(harness.load_cell(name))
    cell["config"].update(dim=32, num_hashes=64, b_i=4, n_train=16384,
                          n_test=256)
    mix = cell["traffic"]
    if mix["driver"] == "train":
        mix.update(batch_size=64 * cell["workload"]["chips"], steps_per_s=200)
        # a hundred steps at this size: sound runs read under 1e-6 on the
        # CPU, the bfloat16 control far more
        cell["limits"] = {"grad_gap": 1e-5, "change3_gap": 1e-5,
                          "change_gap": 1e-4}
    elif mix["driver"] == "featurize":
        mix.update(sample_rows=128)
    return cell
