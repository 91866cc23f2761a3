"""The program's host spans in a profiler trace.

``fit_linear_streamed`` / ``resume_linear_streamed`` record ``repro.fit``
around the call, ``repro.fit.setup`` before the first step (carrying
``shards``, the devices each batch is split over) and one
``repro.fit.step`` per step (a step annotation carrying ``step_num``);
``FeaturePipeline.features`` records one ``repro.featurize.launch`` per
chunk it launches, with the chunk's ``rows``.  The set-up and launch
spans also carry ``cws_loop_share``, the real D over the padded D that
the CWS kernel would otherwise loop over.  Each test takes a profile
on the CPU with ``jax.profiler.trace`` and reads it back with
``jax.profiler.ProfileData``: the spans are there when a profile is being
taken, and the outputs are the same bits whether one is or not.
"""
import dataclasses
import glob
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.checkpoint import Checkpointer, latest_step
from repro.core.linear_model import TrainCfg, init_bag
from repro.data.synthetic import make_template_classification
from repro.pipeline import FeaturePipeline, FeatureSpec
from repro.pipeline import featurize
from repro.runtime import ChaosKill, ChaosPlan, kill_at
from repro.training import (fit_linear_streamed, linear_trainer,
                            resume_linear_streamed)

ROW_CHUNK = 8


@dataclasses.dataclass
class Span:
    name: str
    start: int     # ns
    end: int
    args: dict


def profiled(tmp_path, fn):
    """``fn()`` under a profile; (its result, the trace's ``repro.*``
    host spans in start order)."""
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
        jax.block_until_ready(out)
    path = sorted(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            spans.extend(Span(e.name, e.start_ns, e.end_ns, dict(e.stats))
                         for e in line.events if e.name.startswith("repro."))
    return out, sorted(spans, key=lambda s: s.start)


def named(spans, name):
    return [s for s in spans if s.name == name]


def inside(span, outer):
    return outer.start <= span.start and span.end <= outer.end


@pytest.fixture(scope="module")
def problem():
    ds = make_template_classification(3, n_train=160, n_test=80, dim=32,
                                      n_classes=3, mult_noise=1.1,
                                      spike_prob=0.02, density=0.3)
    spec = FeatureSpec(num_hashes=24, b_i=4)
    pipe = FeaturePipeline.create(jax.random.PRNGKey(7), 32, spec,
                                  row_chunk=ROW_CHUNK)
    cfg = TrainCfg(n_classes=3, steps=4, batch_size=32, lr=0.05)
    p0 = init_bag(jax.random.PRNGKey(1), pipe.num_features, 3)
    x, y = jnp.asarray(ds.x_train), jnp.asarray(ds.y_train)
    return pipe, cfg, p0, x, y


def test_span_names_are_the_documented_ones():
    assert (linear_trainer.FIT_SPAN, linear_trainer.SETUP_SPAN,
            linear_trainer.STEP_SPAN) == ("repro.fit", "repro.fit.setup",
                                          "repro.fit.step")
    assert featurize.LAUNCH_SPAN == "repro.featurize.launch"


def test_fit_is_one_span_holding_setup_then_each_step(problem, tmp_path):
    pipe, cfg, p0, x, y = problem
    _, spans = profiled(tmp_path, lambda: fit_linear_streamed(
        p0, pipe, x, y, cfg=cfg, shuffle_key=jax.random.PRNGKey(3)))
    (fit,) = named(spans, "repro.fit")
    (setup,) = named(spans, "repro.fit.setup")
    steps = named(spans, "repro.fit.step")
    assert [s.args.get("step_num") for s in steps] == [0, 1, 2, 3]
    assert inside(setup, fit)
    assert all(inside(s, fit) for s in steps)
    # setup ends before step 0 starts; steps follow one another
    assert setup.end <= steps[0].start
    assert all(a.end <= b.start for a, b in zip(steps, steps[1:]))
    # the fit's launches run inside its steps, not as featurize launches
    assert not named(spans, "repro.featurize.launch")


def test_resume_spans_only_the_steps_left(problem, tmp_path):
    pipe, cfg, p0, x, y = problem
    ckdir = tmp_path / "ckpt"
    ck = Checkpointer(ckdir)
    with pytest.raises(ChaosKill):
        fit_linear_streamed(p0, pipe, x, y, cfg=cfg, ckpt=ck, ckpt_every=2,
                            chaos=ChaosPlan(kill_at(2)))
    ck.wait()
    assert latest_step(ckdir) == 2
    _, spans = profiled(tmp_path / "trace", lambda: resume_linear_streamed(
        ckdir, pipe, x, y, cfg=cfg))
    (fit,) = named(spans, "repro.fit")
    (setup,) = named(spans, "repro.fit.setup")
    steps = named(spans, "repro.fit.step")
    assert [s.args.get("step_num") for s in steps] == [2, 3]
    assert inside(setup, fit) and setup.end <= steps[0].start
    assert all(inside(s, fit) for s in steps)


def test_features_gives_one_launch_span_per_chunk(problem, tmp_path):
    pipe, _, _, x, _ = problem
    n = 3 * ROW_CHUNK + 5
    _, spans = profiled(tmp_path, lambda: pipe.features(x[:n]))
    launches = named(spans, "repro.featurize.launch")
    assert [s.args.get("rows") for s in launches] == [ROW_CHUNK] * 3 + [5]
    assert all(a.end <= b.start for a, b in zip(launches, launches[1:]))


def test_features_of_one_chunk_is_one_launch_span(problem, tmp_path):
    pipe, _, _, x, _ = problem
    _, spans = profiled(tmp_path, lambda: pipe.features(x[:ROW_CHUNK - 3]))
    launches = named(spans, "repro.featurize.launch")
    assert [s.args.get("rows") for s in launches] == [ROW_CHUNK - 3]


def test_outputs_are_the_same_bits_under_a_profile(problem, tmp_path):
    pipe, cfg, p0, x, y = problem

    def work():
        params = fit_linear_streamed(p0, pipe, x, y, cfg=cfg,
                                     shuffle_key=jax.random.PRNGKey(3))
        return params, pipe.features(x[:3 * ROW_CHUNK + 5])

    plain = work()
    traced, spans = profiled(tmp_path, work)
    assert named(spans, "repro.fit") and named(spans,
                                               "repro.featurize.launch")
    for a, b in zip(jax.tree_util.tree_leaves(plain),
                    jax.tree_util.tree_leaves(traced)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("call", ["fit", "resume"])
def test_setup_carries_one_shard_without_a_mesh(problem, tmp_path, call):
    pipe, cfg, p0, x, y = problem
    if call == "fit":
        def work():
            return fit_linear_streamed(p0, pipe, x, y, cfg=cfg)
    else:
        ck = Checkpointer(tmp_path / "ckpt")
        with pytest.raises(ChaosKill):
            fit_linear_streamed(p0, pipe, x, y, cfg=cfg, ckpt=ck,
                                ckpt_every=2, chaos=ChaosPlan(kill_at(2)))
        ck.wait()

        def work():
            return resume_linear_streamed(tmp_path / "ckpt", pipe, x, y,
                                          cfg=cfg)
    _, spans = profiled(tmp_path / "trace", work)
    (setup,) = named(spans, "repro.fit.setup")
    assert setup.args.get("shards") == 1


@pytest.mark.parametrize("dim,share", [
    (32, 1.0),          # choose_blocks' bd = 32 divides D
    (50, 50 / 64),      # bd = 32: the last D step loops over 18 of 32
])
def test_setup_and_launches_carry_the_loop_share(tmp_path, dim, share):
    pipe = FeaturePipeline.create(jax.random.PRNGKey(7), dim,
                                  FeatureSpec(num_hashes=24, b_i=4),
                                  row_chunk=ROW_CHUNK)
    x = jax.random.uniform(jax.random.PRNGKey(0), (64, dim))
    y = (x[:, 0] > x[:, 1]).astype(jnp.int32)
    cfg = TrainCfg(n_classes=2, steps=2, batch_size=16)
    p0 = init_bag(jax.random.PRNGKey(1), pipe.num_features, 2)
    assert pipe.loop_share(cfg.batch_size) == share

    def work():
        return (fit_linear_streamed(p0, pipe, x, y, cfg=cfg),
                pipe.features(x[:3 * ROW_CHUNK + 5]))

    _, spans = profiled(tmp_path, work)
    (setup,) = named(spans, "repro.fit.setup")
    assert setup.args.get("cws_loop_share") == share
    launches = named(spans, "repro.featurize.launch")
    assert [s.args.get("cws_loop_share") for s in launches] == [share] * 4


FOUR_DEVICE_SETUP = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import glob, json, sys
import jax
from jax.profiler import ProfileData
from repro.checkpoint import Checkpointer
from repro.core.linear_model import TrainCfg, init_bag
from repro.launch.mesh import make_data_mesh
from repro.pipeline import FeaturePipeline, FeatureSpec
from repro.runtime import ChaosKill, ChaosPlan, kill_at
from repro.training import fit_linear_streamed, resume_linear_streamed

tmp = sys.argv[1]
pipe = FeaturePipeline.create(jax.random.PRNGKey(0), 16,
                              FeatureSpec(num_hashes=8, b_i=2))
x = jax.random.uniform(jax.random.PRNGKey(1), (64, 16))
y = (x[:, 0] > x[:, 1]).astype("int32")
p0 = init_bag(jax.random.PRNGKey(2), pipe.num_features, 2)
cfg = TrainCfg(n_classes=2, steps=4, batch_size=16)
mesh = make_data_mesh(4)
ck = Checkpointer(os.path.join(tmp, "ckpt"))
try:
    fit_linear_streamed(p0, pipe, x, y, cfg=cfg, mesh=mesh, ckpt=ck,
                        ckpt_every=2, chaos=ChaosPlan(kill_at(2)))
except ChaosKill:
    ck.wait()
out = {}
for call in ("fit", "resume"):
    with jax.profiler.trace(os.path.join(tmp, call)):
        if call == "fit":
            params = fit_linear_streamed(p0, pipe, x, y, cfg=cfg, mesh=mesh)
        else:
            params = resume_linear_streamed(os.path.join(tmp, "ckpt"), pipe,
                                            x, y, cfg=cfg, mesh=mesh)
        jax.block_until_ready(params)
    path = glob.glob(os.path.join(tmp, call, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out[call] = [dict(e.stats).get("shards")
                 for plane in ProfileData.from_file(path).planes
                 for line in plane.lines for e in line.events
                 if e.name == "repro.fit.setup"]
print(json.dumps(out))
"""


def test_setup_carries_four_shards_on_a_four_device_mesh(tmp_path):
    """In a subprocess that forces four host devices."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", FOUR_DEVICE_SETUP,
                          str(tmp_path)], cwd=root, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got == {"fit": [4], "resume": [4]}
