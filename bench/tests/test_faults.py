"""A run whose timed path is broken underneath comes out not correct,
once for each fault its cell can have; a sound run comes out correct."""
import jax
import numpy as np
import pytest

from bench import harness
from conftest import DP4, SEED, SERVE, tiny


def run(name, seconds=0.5):
    import time
    res, _ = harness.run(name, SEED, seconds, False,
                         t_start=time.perf_counter(), need_chip=False,
                         cell=tiny(name))
    return res


@pytest.mark.parametrize("name", ["mnist-stored.train",
                                  DP4,
                                  "webspam-regen-packed.featurize",
                                  SERVE])
def test_sound_run_is_correct(name):
    res = run(name)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"


def test_state_left_unchanged(monkeypatch):
    from repro import optim
    monkeypatch.setattr(optim, "apply_updates", lambda p, u: p)
    res = run("mnist-stored.train")
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out(monkeypatch):
    from repro.training import linear_trainer
    real = linear_trainer.microbatch_grads

    def half(loss_fn, params, batch, **kw):
        h = batch["labels"].shape[0] // 2
        return real(loss_fn, params,
                    {k: v[:h] for k, v in batch.items()}, **kw)

    monkeypatch.setattr(linear_trainer, "microbatch_grads", half)
    assert not run("mnist-stored.train")["correct"]


def test_exchange_between_chips_left_out(monkeypatch):
    assert len(jax.devices()) >= 4
    from repro.training import trainer
    monkeypatch.setattr(trainer, "_pmean_loss_grads",
                        lambda loss, grads, axis_name: (loss, grads))
    assert not run(DP4)["correct"]


def test_featurize_answer_altered(monkeypatch):
    from repro.pipeline import FeaturePipeline
    # whole-array launches and streamed chunks take different entries
    one, chunk = FeaturePipeline._launch, FeaturePipeline._launch_with
    monkeypatch.setattr(FeaturePipeline, "_launch",
                        lambda self, x: one(self, x) ^ np.uint32(1))
    monkeypatch.setattr(FeaturePipeline, "_launch_with",
                        lambda self, x, s: chunk(self, x, s) ^ np.uint32(1))
    assert not run("webspam-regen-packed.featurize")["correct"]


def test_served_answer_altered(monkeypatch):
    from repro.serving.runner import BucketRunner
    real = BucketRunner.run
    monkeypatch.setattr(BucketRunner, "run",
                        lambda self, xb: real(self, xb) + 1e-3)
    assert not run(SERVE)["correct"]
