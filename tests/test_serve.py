"""Online serving stack tests: bit-identity, compile discipline, chaos.

Three suites over ``repro.serving`` (runner / gateway / monitor / bundle):

  * BIT-IDENTITY — served scores equal the offline
    ``features(x) -> bag_logits`` composition down to the bit, for
    stored-param, ``create_regen``, and ``packed=True`` pipelines,
    including single-row requests, empty batches, requests larger than
    the largest bucket (split + reassembled), and bundle round trips.
    Why it must hold: pad rows are all-zero (sentinel -> bucket 0) and
    sliced off, and the kernels are row-parallel, so coalescing cannot
    perturb any real row's logits.
  * COMPILE DISCIPLINE — mixed-size traffic over B buckets drives
    exactly B fused featurize+score compiles and ZERO retraces after:
    the serving twin of the streaming single-compile invariant, asserted
    through ``analysis.compile_guard``.
  * CHAOS — ``runtime/chaos.py`` faults injected into the runner step
    under a live gateway: a hang is caught MID-flight by the watchdog
    (clients get a clean ``ServeTimeout`` in bounded time, never a
    hang), a kill fails in-flight requests with ``RunnerCrashed``, and
    in every case the service recovers and serves subsequent requests
    bit-identically with zero fresh compiles.
"""
import json
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import compile_guard
from repro.core.linear_model import (LinearParams, bag_logits,
                                     bag_logits_packed)
from repro.kernels import registry
from repro.pipeline import FeaturePipeline, FeatureSpec
from repro.runtime import (ChaosPlan, serve_hang_at, serve_kill_at,
                           serve_raise_at)
from repro.serving import (BucketRunner, DeadlineExceeded, QueueFull,
                           RunnerCrashed, ServeError, ServeMonitor,
                           ServeTimeout, ServingService, load_bundle,
                           save_bundle, start_stats_server)
from repro.training import export_served_model

DIM, C, K = 24, 3, 16
MODES = ("stored", "regen", "packed")


def make_problem(mode: str, seed: int = 0):
    """(params, pipe) for one serving mode, with nonzero random weights
    so bit-identity is a real claim (zero tables score zero always)."""
    spec = FeatureSpec(num_hashes=K, b_i=4, packed=(mode == "packed"))
    if mode == "stored":
        pipe = FeaturePipeline.create(jax.random.PRNGKey(seed), DIM, spec)
    else:
        pipe = FeaturePipeline.create_regen(jax.random.PRNGKey(seed), DIM,
                                            spec)
    rng = np.random.default_rng(seed + 100)
    params = LinearParams(
        jnp.asarray(rng.standard_normal((pipe.num_features, C)),
                    jnp.float32),
        jnp.asarray(rng.standard_normal((C,)), jnp.float32))
    return params, pipe


def make_rows(n: int, seed: int = 1) -> np.ndarray:
    rng = np.random.default_rng(seed)
    x = np.abs(rng.standard_normal((n, DIM))).astype(np.float32)
    return x * (rng.random((n, DIM)) < 0.4)


def offline_scores(params, pipe, x) -> np.ndarray:
    """The offline oracle the serving path must match bit-for-bit."""
    fb = pipe.features(jnp.asarray(x))
    if pipe.spec.packed:
        out = bag_logits_packed(params, fb, num_hashes=pipe.spec.num_hashes,
                                b=pipe.spec.bits)
    else:
        out = bag_logits(params, fb)
    return np.asarray(out)


# ---------------------------------------------------------------------------
# bit-identity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", MODES)
def test_served_matches_offline(mode):
    params, pipe = make_problem(mode)
    x = make_rows(29)
    ref = offline_scores(params, pipe, x)
    with ServingService(params, pipe, buckets=(4, 16, 32)) as svc:
        np.testing.assert_array_equal(svc.score(x), ref)


@pytest.mark.parametrize("mode", MODES)
def test_single_row_request(mode):
    params, pipe = make_problem(mode)
    x = make_rows(1)
    ref = offline_scores(params, pipe, x)
    with ServingService(params, pipe, buckets=(8,)) as svc:
        got = svc.score(x)
        assert got.shape == (1, C)
        np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("mode", MODES)
def test_empty_batch(mode):
    params, pipe = make_problem(mode)
    with ServingService(params, pipe, buckets=(8,)) as svc:
        got = svc.score(make_rows(0))
        assert got.shape == (0, C) and got.dtype == np.float32
        # nothing launched: an empty request completes inline
        assert svc.stats().get("batches", 0) == 0
        assert svc.stats()["completed"] == 1


@pytest.mark.parametrize("mode", MODES)
def test_request_larger_than_largest_bucket(mode):
    params, pipe = make_problem(mode)
    x = make_rows(41)                      # 41 > 16: 16 + 16 + pad(9->16)
    ref = offline_scores(params, pipe, x)
    with ServingService(params, pipe, buckets=(4, 16)) as svc:
        np.testing.assert_array_equal(svc.score(x), ref)
        s = svc.stats()
        assert s["batches"] == 3           # split into max-bucket segments
        assert s["completed"] == 1         # ...but ONE request to the caller


def test_interleaved_async_submissions_all_bit_identical():
    params, pipe = make_problem("regen")
    xs = [make_rows(n, seed=n) for n in (1, 7, 3, 16, 2, 11, 5)]
    refs = [offline_scores(params, pipe, x) for x in xs]
    with ServingService(params, pipe, buckets=(4, 16)) as svc:
        futs = [svc.submit(x) for x in xs]
        for f, ref in zip(futs, refs):
            np.testing.assert_array_equal(f.result(timeout=30), ref)
        s = svc.stats()
        assert s["completed"] == len(xs)
        # coalescing happened or not depending on timing — either way the
        # total real rows dispatched must equal the rows submitted
        assert sum(b["rows"] for b in s["buckets"].values()) == \
            sum(x.shape[0] for x in xs)


def test_runner_score_path_matches_offline():
    params, pipe = make_problem("stored")
    runner = BucketRunner(params, pipe, buckets=(4, 16))
    x = make_rows(23)
    np.testing.assert_array_equal(runner.score(x),
                                  offline_scores(params, pipe, x))


def test_submit_rejects_bad_shape():
    params, pipe = make_problem("regen")
    with ServingService(params, pipe, buckets=(8,)) as svc:
        with pytest.raises(ValueError, match="rows"):
            svc.submit(np.zeros((4, DIM + 1), np.float32))


def test_runner_rejects_mismatched_table():
    params, pipe = make_problem("regen")
    bad = LinearParams(params.w[:-1], params.b)
    with pytest.raises(ValueError, match="mismatch"):
        BucketRunner(bad, pipe, buckets=(8,))


# ---------------------------------------------------------------------------
# served-model bundles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", MODES)
def test_bundle_roundtrip_bit_identical(mode, tmp_path):
    params, pipe = make_problem(mode)
    x = make_rows(9)
    ref = offline_scores(params, pipe, x)
    export_served_model(params, pipe, tmp_path / "model")
    p2, pipe2 = load_bundle(tmp_path / "model")
    assert pipe2.fingerprint() == pipe.fingerprint()
    with ServingService(p2, pipe2, buckets=(16,)) as svc:
        np.testing.assert_array_equal(svc.score(x), ref)


def test_bundle_tamper_fails_loudly(tmp_path):
    params, pipe = make_problem("regen")
    save_bundle(tmp_path / "model", params, pipe)
    # swap the key words: arrays no longer match the manifest fingerprint
    with np.load(tmp_path / "model" / "arrays.npz") as z:
        arrays = {k: z[k] for k in z.files}
    arrays["key_words"] = arrays["key_words"] + np.uint32(1)
    np.savez(tmp_path / "model" / "arrays.npz", **arrays)
    with pytest.raises(ValueError, match="fingerprint mismatch"):
        load_bundle(tmp_path / "model")


def test_bundle_format_guard(tmp_path):
    params, pipe = make_problem("regen")
    save_bundle(tmp_path / "model", params, pipe)
    mpath = tmp_path / "model" / "bundle.json"
    manifest = json.loads(mpath.read_text())
    manifest["format"] = "something-else/v9"
    mpath.write_text(json.dumps(manifest))
    with pytest.raises(ValueError, match="not a served-model bundle"):
        load_bundle(tmp_path / "model")


def test_export_validates_table(tmp_path):
    params, pipe = make_problem("regen")
    bad = LinearParams(params.w[:-1], params.b)
    with pytest.raises(ValueError, match="mismatch"):
        export_served_model(bad, pipe, tmp_path / "model")
    assert not (tmp_path / "model").exists()


def test_service_from_bundle(tmp_path):
    params, pipe = make_problem("packed")
    x = make_rows(6)
    ref = offline_scores(params, pipe, x)
    export_served_model(params, pipe, tmp_path / "model")
    with ServingService.from_bundle(tmp_path / "model",
                                    buckets=(8,)) as svc:
        np.testing.assert_array_equal(svc.score(x), ref)


# ---------------------------------------------------------------------------
# compile discipline
# ---------------------------------------------------------------------------


def test_mixed_traffic_compiles_exactly_one_executable_per_bucket():
    params, pipe = make_problem("regen")
    buckets = (2, 8, 32)
    with compile_guard() as g:
        g.watch(pipe.scoring_chunk_fn(), expect=len(buckets),
                label="scoring_chunk_fn")
        with ServingService(params, pipe, buckets=buckets,
                            warmup=False) as svc:
            # ragged sizes landing in every bucket, several times each
            for n in (1, 2, 3, 8, 5, 17, 32, 1, 25, 7, 2, 30):
                svc.score(make_rows(n, seed=n))
    # and the runner agrees with the jit cache
    assert svc.runner.compile_count() == len(buckets)


def test_warmup_compiles_every_bucket_and_traffic_adds_zero():
    params, pipe = make_problem("packed")
    buckets = (4, 16)
    svc = ServingService(params, pipe, buckets=buckets)   # warmed
    assert svc.runner.compile_count() == len(buckets)
    try:
        with compile_guard() as g:
            g.watch(pipe.scoring_chunk_fn(), expect=0,
                    label="scoring_chunk_fn post-warmup")
            for n in (3, 16, 1, 9, 4, 13):
                svc.score(make_rows(n, seed=n))
        assert svc.stats()["compile_count"] == len(buckets)
    finally:
        svc.stop()


def test_ragged_sizes_within_one_bucket_share_one_executable():
    params, pipe = make_problem("stored")
    with compile_guard() as g:
        g.watch(pipe.scoring_chunk_fn(), expect=1)
        with ServingService(params, pipe, buckets=(8,),
                            warmup=False) as svc:
            for n in (3, 5, 7, 8, 1):
                svc.score(make_rows(n, seed=n))


def test_oversized_requests_reuse_bucket_executables():
    params, pipe = make_problem("regen")
    with compile_guard() as g:
        g.watch(pipe.scoring_chunk_fn(), expect=2)
        with ServingService(params, pipe, buckets=(4, 16),
                            warmup=False) as svc:
            svc.score(make_rows(50))       # 16+16+16+pad(2->4)
            svc.score(make_rows(33))       # 16+16+pad(1->4)


def test_bucket_for():
    params, pipe = make_problem("regen")
    runner = BucketRunner(params, pipe, buckets=(4, 16, 64))
    assert runner.bucket_for(1) == 4
    assert runner.bucket_for(4) == 4
    assert runner.bucket_for(5) == 16
    assert runner.bucket_for(64) == 64
    with pytest.raises(ValueError):
        runner.bucket_for(65)
    with pytest.raises(ValueError):
        runner.bucket_for(0)


def test_runner_rejects_non_bucket_dispatch():
    params, pipe = make_problem("regen")
    runner = BucketRunner(params, pipe, buckets=(4,))
    with pytest.raises(ValueError, match="not a bucket"):
        runner.run(jnp.zeros((3, DIM), jnp.float32))


def test_serve_bucket_table_roundtrip(tmp_path):
    try:
        registry.update_serve_buckets({"cws_rng": (2, 16, 256)})
        # keyed by block family, like the block table: a regen pipeline's
        # launches are (regen, index) -> cws_rng
        assert registry.serve_buckets(
            registry.cws_family("regen", "index")) == (2, 16, 256)
        assert registry.serve_buckets("cws") == registry.DEFAULT_SERVE_BUCKETS
        registry.save_serve_buckets(tmp_path / "buckets.json")
        registry.SERVE_BUCKET_TABLE.clear()
        entries = registry.load_serve_buckets(tmp_path / "buckets.json")
        assert entries == {"cws_rng": (2, 16, 256)}
        assert registry.serve_buckets("cws_rng") == (2, 16, 256)
        # a runner built without buckets= picks the persisted ladder
        params, pipe = make_problem("regen")
        assert BucketRunner(params, pipe).buckets == (2, 16, 256)
    finally:
        registry.SERVE_BUCKET_TABLE.clear()


def test_serve_bucket_validation():
    with pytest.raises(ValueError):
        registry.update_serve_buckets({"cws": (8, 4)})        # not sorted
    with pytest.raises(ValueError):
        registry.update_serve_buckets({"cws": (0, 4)})        # nonpositive
    with pytest.raises(ValueError):
        registry.update_serve_buckets({"cws": ()})            # empty


# ---------------------------------------------------------------------------
# chaos: hang / kill / raise on the runner step under a live gateway
# ---------------------------------------------------------------------------


def test_hang_watchdog_fires_and_request_fails_cleanly():
    params, pipe = make_problem("regen")
    x = make_rows(5)
    ref = offline_scores(params, pipe, x)
    plan = ChaosPlan(serve_hang_at(0, 2.0))
    svc = ServingService(params, pipe, buckets=(8,), chaos=plan,
                         hard_timeout_s=0.2)
    try:
        t0 = time.monotonic()
        with pytest.raises(ServeTimeout):
            svc.score(x, timeout=10.0)
        waited = time.monotonic() - t0
        # the CLEAN-timeout contract: the client was failed mid-hang by
        # the watchdog, long before the 2s hang drained
        assert waited < 1.5, f"client waited {waited:.2f}s through the hang"
        assert [e["action"] for e in plan.log("serve_step")] == ["hang"]
        # let the hung dispatch limp home, then the service must recover
        time.sleep(2.2)
        with compile_guard() as g:
            g.watch(pipe.scoring_chunk_fn(), expect=0,
                    label="post-hang traffic")
            np.testing.assert_array_equal(svc.score(x, timeout=10.0), ref)
        s = svc.stats()
        assert s["watchdog_fired"] >= 1
        assert s["timed_out"] >= 1
        assert s["hang_recovered"] == 1
        assert s["completed"] == 1
    finally:
        svc.stop()


def test_kill_fails_inflight_and_service_recovers_bit_identically():
    params, pipe = make_problem("regen")
    x = make_rows(6)
    ref = offline_scores(params, pipe, x)
    plan = ChaosPlan(serve_kill_at(0))
    svc = ServingService(params, pipe, buckets=(8,), chaos=plan,
                         hard_timeout_s=5.0)
    try:
        with pytest.raises(RunnerCrashed):
            svc.score(x, timeout=10.0)
        # recovery: zero fresh compiles (regen restart = 2 key words +
        # the table, all still resident), scores bit-identical
        with compile_guard() as g:
            g.watch(pipe.scoring_chunk_fn(), expect=0, label="post-kill")
            np.testing.assert_array_equal(svc.score(x, timeout=10.0), ref)
        s = svc.stats()
        assert s["restarts"] == 1 and s["failed"] == 1
        assert s["completed"] == 1
    finally:
        svc.stop()


def test_software_fault_fails_only_inflight_requests():
    params, pipe = make_problem("stored")
    x = make_rows(4)
    ref = offline_scores(params, pipe, x)
    plan = ChaosPlan(serve_raise_at(0))
    svc = ServingService(params, pipe, buckets=(8,), chaos=plan)
    try:
        with pytest.raises(ServeError, match="FaultInjected"):
            svc.score(x, timeout=10.0)
        np.testing.assert_array_equal(svc.score(x, timeout=10.0), ref)
        assert svc.stats()["failed_batches"] == 1
    finally:
        svc.stop()


def test_repeated_faults_then_sustained_recovery():
    params, pipe = make_problem("packed")
    plan = ChaosPlan(serve_raise_at(1), serve_kill_at(3))
    svc = ServingService(params, pipe, buckets=(8,), chaos=plan,
                         hard_timeout_s=5.0)
    try:
        xs = [make_rows(n, seed=50 + n) for n in (2, 5, 3, 7, 4, 6)]
        refs = [offline_scores(params, pipe, x) for x in xs]
        outcomes = []
        for x, ref in zip(xs, refs):
            try:
                np.testing.assert_array_equal(svc.score(x, timeout=10.0),
                                              ref)
                outcomes.append("ok")
            except (ServeError, RunnerCrashed):
                outcomes.append("failed")
        # dispatches 1 and 3 die; every other request is bit-identical
        assert outcomes == ["ok", "failed", "ok", "failed", "ok", "ok"]
        assert [e["action"] for e in plan.log("serve_step")] == \
            ["raise", "kill"]
    finally:
        svc.stop()


def test_mixed_bucket_speeds_never_trip_a_straggler_abort():
    """The serving watchdog must run with the statistical straggler tier
    OFF: dispatch wall time varies by bucket, so after fast small-bucket
    traffic builds a tiny trailing median, slow (here: hang-delayed)
    dispatches would read as 3 consecutive stragglers and abort — which
    used to drop the in-flight futures on the floor, hanging synchronous
    callers forever.  Every request must complete, bit-identically."""
    params, pipe = make_problem("regen")
    # 6 fast dispatches build the median; 3 slow ones exceed 5x it
    plan = ChaosPlan(serve_hang_at(6, 0.25), serve_hang_at(7, 0.25),
                     serve_hang_at(8, 0.25))
    svc = ServingService(params, pipe, buckets=(8,), chaos=plan,
                         hard_timeout_s=30.0)
    try:
        for i in range(9):
            x = make_rows(3, seed=80 + i)
            got = svc.score(x, timeout=10.0)
            np.testing.assert_array_equal(got,
                                          offline_scores(params, pipe, x))
        assert svc.stats()["completed"] == 9
    finally:
        svc.stop()


def test_spurious_watchdog_abort_fails_inflight_cleanly():
    """Defense in depth for the same bug: even if end_step aborts a
    dispatch the hard-timeout monitor never flagged (so _on_hard_timeout
    never failed the futures), callers must get a clean error in bounded
    time — never a silent hang — and the service must keep serving."""
    from repro.runtime import TrainingAborted

    params, pipe = make_problem("regen")
    svc = ServingService(params, pipe, buckets=(8,), hard_timeout_s=30.0)

    class AbortOnce:
        hard_timeout_s = 30.0
        calls = 0

        def start_step(self, index=None):
            pass

        def end_step(self):
            AbortOnce.calls += 1
            if AbortOnce.calls == 1:
                raise TrainingAborted("spurious abort, monitor never fired")

        def clear_step(self):
            pass

        def stop(self):
            pass

    try:
        svc.gateway._watchdog = AbortOnce()
        x = make_rows(5)
        with pytest.raises(ServeTimeout, match="aborted"):
            svc.score(x, timeout=10.0)
        np.testing.assert_array_equal(svc.score(x, timeout=10.0),
                                      offline_scores(params, pipe, x))
    finally:
        svc.stop()


def test_queue_backpressure_rejects_when_full():
    params, pipe = make_problem("regen")
    plan = ChaosPlan(serve_hang_at(0, 1.0))
    svc = ServingService(params, pipe, buckets=(8,), max_queue_rows=8,
                         chaos=plan)
    try:
        f1 = svc.submit(make_rows(8))          # dispatches, then hangs
        deadline = time.monotonic() + 5.0
        while svc.stats()["queue_rows"] > 0:   # wait until it is IN FLIGHT
            assert time.monotonic() < deadline
            time.sleep(0.01)
        f2 = svc.submit(make_rows(8))          # fills the queue
        with pytest.raises(QueueFull):
            svc.submit(make_rows(1))
        assert svc.stats()["rejected"] == 1
        f1.result(timeout=10.0)
        f2.result(timeout=10.0)
    finally:
        svc.stop()


def test_request_larger_than_max_queue_rows_streams_through_idle_queue():
    """max_queue_rows bounds BACKLOG, not request size: an idle service
    admits a request bigger than the whole queue bound and streams it
    through segment by segment (the docstring's 'any request size is
    servable' claim, which a whole-request admission check broke)."""
    params, pipe = make_problem("regen")
    x = make_rows(20)
    ref = offline_scores(params, pipe, x)
    with ServingService(params, pipe, buckets=(8,),
                        max_queue_rows=10) as svc:
        np.testing.assert_array_equal(svc.score(x, timeout=30.0), ref)
        assert svc.stats().get("rejected", 0) == 0


def test_stop_fails_queued_requests_without_draining():
    params, pipe = make_problem("regen")
    plan = ChaosPlan(serve_hang_at(0, 0.5))
    svc = ServingService(params, pipe, buckets=(8,), chaos=plan)
    f1 = svc.submit(make_rows(4))               # dispatches, then hangs
    deadline = time.monotonic() + 5.0
    while svc.stats()["queue_rows"] > 0:        # wait until IN FLIGHT
        assert time.monotonic() < deadline
        time.sleep(0.01)
    f2 = svc.submit(make_rows(4))               # queued behind the hang
    svc.stop()                                  # in-flight finishes, but
    f1.result(timeout=10.0)                     # the QUEUED one is never
    with pytest.raises(ServeError, match="gateway stopped"):
        f2.result(timeout=10.0)                 # dispatched: clean fail


def test_queued_request_deadline_expires_cleanly():
    params, pipe = make_problem("regen")
    plan = ChaosPlan(serve_hang_at(0, 1.0))
    svc = ServingService(params, pipe, buckets=(8,), chaos=plan)
    try:
        f1 = svc.submit(make_rows(4))               # hangs in flight
        deadline = time.monotonic() + 5.0
        while svc.stats()["queue_rows"] > 0:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        f2 = svc.submit(make_rows(4), deadline_s=0.05)   # expires queued
        with pytest.raises(DeadlineExceeded):
            f2.result(timeout=10.0)
        f1.result(timeout=10.0)                     # the hung one finishes
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# monitoring surface
# ---------------------------------------------------------------------------


def test_stats_schema_and_percentiles():
    params, pipe = make_problem("regen")
    with ServingService(params, pipe, buckets=(4, 16)) as svc:
        for n in (1, 7, 16, 3):
            svc.score(make_rows(n, seed=n))
        s = svc.stats()
        assert s["requests"] == 4 and s["completed"] == 4
        assert s["rows"] == 27
        assert s["compile_count"] == 2
        # live backlog gauges: present (the documented schema) and empty
        assert s["queue_rows"] == 0 and s["queue_requests"] == 0
        lat = s["latency_ms"]
        assert lat["count"] == 4
        assert 0 < lat["p50"] <= lat["p99"] <= lat["max"]
        total_rows = sum(b["rows"] for b in s["buckets"].values())
        assert total_rows == 27
        # pad accounting: every dispatch padded to its bucket
        for rows, b in s["buckets"].items():
            assert b["rows"] + b["pad_rows"] == int(rows) * b["batches"]


def test_stats_http_endpoint():
    params, pipe = make_problem("regen")
    with ServingService(params, pipe, buckets=(8,)) as svc:
        svc.score(make_rows(2))
        srv = svc.start_stats_server()
        with urllib.request.urlopen(srv.url, timeout=10) as resp:
            assert resp.headers["Content-Type"] == "application/json"
            got = json.loads(resp.read())
        assert got["requests"] == 1
        assert got["compile_count"] == 1
        assert "latency_ms" in got and "buckets" in got
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(srv.url.replace("/stats", "/nope"),
                                   timeout=10)


def test_monitor_standalone_empty_snapshot():
    m = ServeMonitor()
    s = m.snapshot()
    assert s["latency_ms"]["count"] == 0
    assert s["buckets"] == {}
    srv = start_stats_server(m)
    try:
        got = json.loads(urllib.request.urlopen(srv.url, timeout=10).read())
        assert got["latency_ms"]["p50"] == 0.0
    finally:
        srv.close()
