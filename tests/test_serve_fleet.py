"""The self-healing serve fleet (ISSUE 12), end to end on CPU:

* **in-process self-healing** — an injected dispatch-loop death
  (``serve_dispatch_death``) relaunches the core with every in-flight
  future resolved (never hung), 503+``Retry-After``/``ready: false``
  during the gap, and the front serving again after;
* **health-gated rollout** — a mid-traffic checkpoint hot-swap promotes
  with zero 5xx and masks bit-identical to offline predict.py of the
  new checkpoint; an injected ``swap_crash`` and a pinned-sample Dice
  regression both auto-roll back with the old weights still serving;
* **supervised serve workers** — ``elastic --workload serve`` argv
  plumbing, the stub-driven relaunch state machine, and THE drill: a
  real serve worker SIGKILLed mid-traffic is detected, relaunched, and
  serving 200s again;
* satellites: the prediction cache (exact-match, versioned, bounded
  LRU), the autoscale hint's hysteresis, the serve chaos sites, and
  bench_serve's chaos/rollout legs.
"""

import http.client
import json
import os
import socket
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from distributedpytorch_tpu.config import TrainConfig
from distributedpytorch_tpu.predict import run_prediction
from distributedpytorch_tpu.train import Trainer
from distributedpytorch_tpu.utils import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZE_WH = (48, 32)  # (W, H) CLI order → input_hw (32, 48)
WIDTHS = (8, 16)


# ---------------------------------------------------------------------------
# rigs: two tiny trained checkpoints (A serves, B rolls out) + disk images
# ---------------------------------------------------------------------------


def _train(tmp, sub: str, seed: int) -> str:
    cfg = TrainConfig(
        train_method="singleGPU",
        epochs=1,
        batch_size=8,
        val_percent=25.0,
        seed=seed,
        compute_dtype="float32",
        image_size=SIZE_WH,
        model_widths=WIDTHS,
        synthetic_samples=16,
        checkpoint_dir=str(tmp / sub / "checkpoints"),
        log_dir=str(tmp / sub / "logs"),
        loss_dir=str(tmp / sub / "loss"),
        num_workers=0,
    )
    Trainer(cfg).train()
    return str(tmp / sub / "checkpoints")


@pytest.fixture(scope="module")
def rigs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("fleet")
    dir_a = _train(tmp, "a", seed=42)
    dir_b = _train(tmp, "b", seed=7)
    from distributedpytorch_tpu.data import write_synthetic_carvana_tree

    images_dir, _ = write_synthetic_carvana_tree(
        str(tmp / "data"), n=4, size_wh=SIZE_WH
    )
    return tmp, dir_a, dir_b, images_dir


@pytest.fixture(scope="module")
def engine(rigs):
    """One AOT-compiled engine from checkpoint A, shared module-wide
    (servers are cheap and built per test; tests that swap weights
    restore them via ``restore_weights`` — a pointer flip)."""
    _tmp, dir_a, _dir_b, _images = rigs
    from distributedpytorch_tpu.serve.engine import engine_from_checkpoint

    return engine_from_checkpoint(
        "singleGPU",
        checkpoint_dir=dir_a,
        image_size=SIZE_WH,
        model_widths=WIDTHS,
        bucket_sizes=(1, 2, 4),
        replicas=1,
        host_cache_mb=16,
    )


@pytest.fixture
def pristine_weights(engine):
    """Tests that hot-swap weights on the shared engine leave it exactly
    as found (variables AND versions)."""
    saved = engine.snapshot_weights()
    yield
    engine.restore_weights(saved)


@pytest.fixture
def clean_faults():
    faults.reset()
    yield
    faults.reset()


def _image_files(images_dir):
    return sorted(
        os.path.join(images_dir, f) for f in os.listdir(images_dir)
        if not f.startswith(".")
    )


def _offline_masks(rigs, ckpt_dir: str, tag: str):
    from PIL import Image

    tmp, _a, _b, images_dir = rigs
    out = tmp / f"predict_{tag}"
    written = run_prediction(
        "singleGPU", images_dir, str(out),
        image_size=SIZE_WH, batch_size=4,
        checkpoint_dir=ckpt_dir, model_widths=WIDTHS,
    )
    return [np.asarray(Image.open(p)) for p in written]


def _serve(engine, **kwargs):
    from distributedpytorch_tpu.serve.server import Server

    kwargs.setdefault("restart_backoff_s", 0.05)
    return Server(engine, **kwargs).start()


def _img(seed=0):
    return np.random.default_rng(seed).random((32, 48, 3), np.float32)


# ---------------------------------------------------------------------------
# chaos sites (utils/faults.py)
# ---------------------------------------------------------------------------


class TestServeFaultSites:
    def test_serve_sites_parse(self):
        for spec in ("serve_dispatch_death", "serve_replica_wedge:*:3",
                     "serve_decode:*:*:2", "swap_crash"):
            assert faults.parse_fault_spec(spec).site == spec.split(":")[0]

    def test_serve_decode_fault_is_an_error_response(
            self, engine, clean_faults):
        server = _serve(engine)
        try:
            faults.install(("serve_decode",))
            first = server.submit(_img()).result(30)
            assert first.status == "error"
            assert "serve_decode" in first.reason
            # one request's decode failing never takes the server down
            assert server.submit(_img()).result(30).ok
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# in-process self-healing: dispatch death → relaunch
# ---------------------------------------------------------------------------


class TestSelfHealingCore:
    def test_dispatch_death_mid_traffic_relaunches_with_no_hung_future(
            self, engine, clean_faults):
        """THE in-process chaos drill: kill the dispatch loop mid-
        traffic; every in-flight future resolves (ok/error/rejected —
        never a hang), the core relaunches, and the front serves 200s
        again."""
        server = _serve(engine)
        try:
            futures = [server.submit(_img(i), key=str(i)) for i in range(6)]
            faults.install(("serve_dispatch_death",))
            futures += [server.submit(_img(i), key=f"b{i}")
                        for i in range(6, 24)]
            statuses = {f.result(30).status for f in futures}  # no hangs
            assert statuses <= {"ok", "error", "rejected", "shutdown"}
            deadline = time.monotonic() + 20
            recovered = False
            while time.monotonic() < deadline and not recovered:
                recovered = server.submit(_img(99)).result(30).ok
                time.sleep(0.02)
            assert recovered, "core never relaunched"
            assert server.core_restarts == 1
            assert server.state == "serving"
            assert server.stats()["core_restarts"] == 1
        finally:
            server.stop()

    def test_relaunch_gap_answers_relaunching_not_shutdown(
            self, engine, clean_faults):
        server = _serve(engine, restart_backoff_s=2.0)
        try:
            faults.install(("serve_dispatch_death",))
            server.submit(_img()).result(30)  # triggers the death
            deadline = time.monotonic() + 5
            while (server.state != "relaunching"
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert server.state == "relaunching"
            assert not server.ready
            gap = server.submit(_img(1)).result(5)
            assert gap.status == "rejected"
            assert gap.reason == "relaunching"
            # Retry-After mirrors the CURRENT gap's backoff (first
            # restart sleeps backoff * 2**0 = 2.0 s), not double it
            assert server.retry_after_s("relaunching") == 2
        finally:
            server.stop()

    def test_restart_budget_exhausted_goes_terminal(
            self, engine, clean_faults):
        """Past the in-process budget the server answers shutdown
        ("retry elsewhere") — the layer above (elastic --workload
        serve) owns the relaunch from here."""
        server = _serve(engine, restart_limit=1, restart_backoff_s=0.02)
        try:
            faults.install(("serve_dispatch_death:*:*:*",))  # every time
            deadline = time.monotonic() + 30
            while server.state != "stopped" and time.monotonic() < deadline:
                server.submit(_img()).result(30)
                time.sleep(0.01)
            assert server.state == "stopped"
            assert server.core_restarts == 2  # budget 1 + the fatal one
            final = server.submit(_img()).result(5)
            assert final.status == "shutdown"
        finally:
            server.stop(drain=False)


# ---------------------------------------------------------------------------
# health-gated zero-downtime rollout
# ---------------------------------------------------------------------------


class TestRollout:
    def _manager(self, server, **kwargs):
        from distributedpytorch_tpu.serve.rollout import RolloutManager

        kwargs.setdefault("window_s", 0.4)
        manager = RolloutManager(server, **kwargs)
        server.rollout = manager
        return manager

    def test_mid_traffic_rollout_promotes_with_zero_5xx_and_offline_parity(
            self, rigs, engine, pristine_weights):
        """Mid-traffic hot-swap to checkpoint B: zero non-ok answers
        while the canary runs, and the promoted masks are BIT-IDENTICAL
        to offline predict.py with checkpoint B — the served flip is the
        real checkpoint, not an approximation of it."""
        from distributedpytorch_tpu.checkpoint import resolve_checkpoint

        tmp, _dir_a, dir_b, images_dir = rigs
        offline_b = _offline_masks(rigs, dir_b, "b")
        server = _serve(engine)
        manager = self._manager(server)
        stop_traffic = threading.Event()
        responses = []

        def traffic():
            i = 0
            while not stop_traffic.is_set():
                responses.append(
                    server.submit(_img(i % 8), key=str(i)).result(30)
                )
                i += 1
                time.sleep(0.005)

        t = threading.Thread(target=traffic, daemon=True)
        expected_version = engine.next_weights_version()
        try:
            t.start()
            manager.start(resolve_checkpoint("singleGPU", dir_b))
            assert manager.wait(60) == "promoted"
            stop_traffic.set()
            t.join(30)
            assert responses, "no traffic flowed during the rollout"
            assert all(r.ok for r in responses)  # zero 5xx-shaped answers
            assert engine.weights_version == expected_version
            assert server.stats()["weights_version"] == expected_version
            served = server.submit(_image_files(images_dir)).result(60)
            assert served.ok
            for mask, ref in zip(served.masks, offline_b):
                np.testing.assert_array_equal(mask, ref)
        finally:
            stop_traffic.set()
            server.stop()

    def test_swap_crash_rolls_back_with_old_weights_still_serving(
            self, rigs, engine, pristine_weights, clean_faults):
        _tmp, dir_a, _dir_b, images_dir = rigs
        offline_a = _offline_masks(rigs, dir_a, "a")
        server = _serve(engine)
        manager = self._manager(server)
        try:
            version_before = engine.weights_version
            faults.install(("swap_crash",))
            manager.start(self._negated_candidate(engine))
            assert manager.wait(30) == "swap_failed"
            assert "swap_crash" in manager.last_reason
            assert engine.weights_version == version_before
            served = server.submit(_image_files(images_dir)).result(60)
            for mask, ref in zip(served.masks, offline_a):
                np.testing.assert_array_equal(mask, ref)
        finally:
            server.stop()

    def _negated_candidate(self, engine):
        """A deterministically-regressed candidate: checkpoint A's
        params sign-flipped (masks ≈ complemented — maximally far from
        the baseline's)."""
        import jax

        saved = engine.snapshot_weights()[0][0]  # replica 0's variables
        params = jax.tree_util.tree_map(lambda a: -a, saved["params"])
        model_state = saved.get("batch_stats")
        return (params, model_state)

    def test_dice_regression_canary_rolls_back(
            self, rigs, engine, pristine_weights):
        """The pinned-sample Dice probe: a candidate whose masks
        disagree with the old weights' on the probe images beyond the
        margin must roll back — the regression gate, no faults
        involved."""
        _tmp, dir_a, _dir_b, images_dir = rigs
        offline_a = _offline_masks(rigs, dir_a, "a")
        probe_rows = [engine.preprocess(p)
                      for p in _image_files(images_dir)[:2]]
        server = _serve(engine)
        manager = self._manager(server, probe_rows=probe_rows,
                                dice_margin=0.02, window_s=0.2)
        try:
            manager.start(self._negated_candidate(engine))
            assert manager.wait(30) == "rolled_back"
            assert "Dice" in manager.last_reason
            assert engine.weights_version == 0
            served = server.submit(_image_files(images_dir)).result(60)
            for mask, ref in zip(served.masks, offline_a):
                np.testing.assert_array_equal(mask, ref)
        finally:
            server.stop()

    def test_version_numbers_never_reused_after_rollback(
            self, rigs, engine, pristine_weights):
        """A rejected candidate's version number is cache-key material:
        the next candidate must get a FRESH number, or cache hits under
        the old number would serve the rejected candidate's masks."""
        _tmp, _dir_a, _dir_b, images_dir = rigs
        probe_rows = [engine.preprocess(p)
                      for p in _image_files(images_dir)[:2]]
        server = _serve(engine)
        manager = self._manager(server, probe_rows=probe_rows,
                                dice_margin=0.02, window_s=0.1)
        try:
            first = engine.next_weights_version()
            manager.start(self._negated_candidate(engine))
            assert manager.wait(30) == "rolled_back"
            saved = engine.snapshot_weights()[0][0]
            manager.start((saved["params"], saved.get("batch_stats")))
            assert manager.wait(30) == "promoted"
            # the rolled-back attempt consumed `first`; the promoted one
            # is strictly newer, never a reuse
            assert engine.weights_version == first + 1
        finally:
            server.stop()

    def test_readiness_flips_false_during_canary(
            self, engine, pristine_weights):
        server = _serve(engine)
        manager = self._manager(server, window_s=1.0)
        try:
            assert server.ready
            saved = engine.snapshot_weights()[0][0]
            manager.start((saved["params"], saved.get("batch_stats")))
            deadline = time.monotonic() + 5
            while not manager.canarying and time.monotonic() < deadline:
                time.sleep(0.01)
            assert manager.canarying
            assert not server.ready  # the LB signal during the canary
            assert manager.wait(30) == "promoted"
            assert server.ready
        finally:
            server.stop()

    def test_canary_swaps_one_replica_group_first(self, rigs):
        """With two replica groups the canary really is partial: only
        group 0 serves the candidate until promotion, and
        ``versions_mixed`` (the prediction-cache bypass) holds exactly
        while they diverge."""
        _tmp, dir_a, _dir_b, _images = rigs
        from distributedpytorch_tpu.serve.engine import (
            engine_from_checkpoint,
        )

        eng2 = engine_from_checkpoint(
            "singleGPU", checkpoint_dir=dir_a, image_size=SIZE_WH,
            model_widths=WIDTHS, bucket_sizes=(1, 2), replicas=2,
        )
        import jax

        saved = eng2.snapshot_weights()
        bad = jax.tree_util.tree_map(
            lambda a: -a, saved[0][0]["params"]
        )
        eng2.swap_weights(bad, saved[0][0].get("batch_stats"),
                          version=1, replica_indices=[0])
        assert eng2.versions_mixed
        assert eng2.weights_version == 0  # promoted floor stays old
        row = _img(3)
        m0 = eng2.postprocess(eng2.infer(row[None], replica_index=0))[0]
        m1 = eng2.postprocess(eng2.infer(row[None], replica_index=1))[0]
        assert not np.array_equal(m0, m1)  # the canary really diverged
        eng2.restore_weights(saved)
        assert not eng2.versions_mixed
        np.testing.assert_array_equal(
            eng2.postprocess(eng2.infer(row[None], replica_index=0))[0], m1
        )

    def test_checkpoint_watcher_triggers_on_replace(
            self, rigs, engine, pristine_weights, tmp_path):
        """--watch-checkpoint: replacing the watched file starts a
        canaried rollout of the new bytes."""
        import shutil

        from distributedpytorch_tpu.checkpoint import resolve_checkpoint
        from distributedpytorch_tpu.serve.rollout import CheckpointWatcher

        _tmp, dir_a, dir_b, _images = rigs
        watched = str(tmp_path / "watched.ckpt")
        shutil.copy(resolve_checkpoint("singleGPU", dir_a), watched)
        server = _serve(engine)
        manager = self._manager(server, window_s=0.1)
        watcher = CheckpointWatcher(manager, watched, poll_s=0.05)
        server.watcher = watcher
        watcher.start()
        expected_version = engine.next_weights_version()
        try:
            time.sleep(0.2)  # a quiet file must never trigger
            assert watcher.triggered == 0
            shutil.copy(resolve_checkpoint("singleGPU", dir_b), watched)
            deadline = time.monotonic() + 20
            while (engine.weights_version != expected_version
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert watcher.triggered == 1
            assert manager.wait(30) == "promoted"
            assert engine.weights_version == expected_version
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# prediction cache (Clipper-style, satellite)
# ---------------------------------------------------------------------------


class TestPredictionCache:
    def test_lru_bounded_by_bytes(self):
        from distributedpytorch_tpu.serve.cache import PredictionCache

        mask = np.zeros((10, 10), np.uint8)  # 100 B/entry
        cache = PredictionCache(250)
        for i in range(3):
            assert cache.put(f"k{i}", [mask])
        assert len(cache) == 2  # k0 evicted (LRU)
        assert cache.get("k0") is None
        assert cache.get("k2") is not None
        assert cache.used_bytes <= 250
        # an oversized single entry is refused, not cache-flushing
        assert not cache.put("big", [np.zeros((64, 64), np.uint8)])

    def test_request_key_depends_on_rows_and_version(self):
        from distributedpytorch_tpu.serve.cache import request_key

        row = _img(0)
        assert request_key([row], 0) == request_key([row.copy()], 0)
        assert request_key([row], 0) != request_key([row], 1)
        assert request_key([row], 0) != request_key([_img(1)], 0)

    def test_server_serves_exact_repeat_from_cache(self, engine):
        server = _serve(engine, predict_cache_mb=4)
        try:
            img = _img(5)
            first = server.submit(img).result(30)
            second = server.submit(img.copy()).result(30)
            assert first.ok and second.ok
            assert not first.cached and second.cached
            for a, b in zip(first.masks, second.masks):
                np.testing.assert_array_equal(a, b)
            snap = server.stats()["predict_cache"]
            assert snap["hits"] == 1 and snap["entries"] >= 1
            assert server.stats()["requests_cached"] == 1
        finally:
            server.stop()

    def test_rollout_invalidates_cached_masks(
            self, engine, pristine_weights):
        """A promoted weight version changes the key: the same input
        must MISS and recompute under the new weights."""
        server = _serve(engine, predict_cache_mb=4)
        try:
            img = _img(6)
            assert server.submit(img).result(30).ok
            assert server.submit(img).result(30).cached
            saved = engine.snapshot_weights()[0][0]
            engine.swap_weights(saved["params"],
                                saved.get("batch_stats"), version=1)
            after = server.submit(img).result(30)
            assert after.ok and not after.cached
        finally:
            server.stop()

    def test_cache_families_in_exposition(self, engine):
        from distributedpytorch_tpu.obs import validate_exposition
        from distributedpytorch_tpu.obs.registry import REGISTRY

        types = validate_exposition(REGISTRY.expose())
        assert "dpt_serve_predict_cache_total" in types
        assert "dpt_serve_weights_version" in types
        assert "dpt_serve_core_restarts_total" in types
        assert "dpt_serve_rollouts_total" in types
        assert "dpt_serve_replica_hint" in types


# ---------------------------------------------------------------------------
# autoscale hint (recommendation only, satellite)
# ---------------------------------------------------------------------------


class TestAutoscaleHint:
    def _hint(self, replicas=2, **kwargs):
        import types

        from distributedpytorch_tpu.serve.autoscale import AutoscaleHint

        fake = types.SimpleNamespace(
            engine=types.SimpleNamespace(
                planner=types.SimpleNamespace(max_size=4),
                num_replicas=replicas,
            ),
        )
        kwargs.setdefault("interval_s", 999.0)  # policy only, no thread
        return AutoscaleHint(fake, **kwargs)

    def test_up_needs_sustained_pressure(self):
        hint = self._hint(replicas=2, up_windows=2)
        assert hint.observe_window(shed_delta=5, max_depth=0) == 2
        assert hint.observe_window(shed_delta=5, max_depth=0) == 3
        # pressure relieved: back to the current size, streaks reset
        assert hint.observe_window(shed_delta=0, max_depth=1) == 2

    def test_depth_at_high_water_counts_as_pressure(self):
        hint = self._hint(replicas=2, up_windows=2)  # depth_high = 4*2
        assert hint.observe_window(0, max_depth=8) == 2
        assert hint.observe_window(0, max_depth=8) == 3

    def test_down_needs_long_quiet_and_floors_at_one(self):
        hint = self._hint(replicas=2, down_windows=3)
        for _ in range(2):
            assert hint.observe_window(0, 0) == 2
        assert hint.observe_window(0, 0) == 1  # third quiet window
        single = self._hint(replicas=1, down_windows=1)
        assert single.observe_window(0, 0) == 1  # never below 1

    def test_one_burst_does_not_flap(self):
        hint = self._hint(replicas=2, up_windows=2, down_windows=6)
        assert hint.observe_window(3, 0) == 2  # one burst: no change
        assert hint.observe_window(0, 1) == 2
        assert hint.observe_window(0, 0) == 2

    def test_gauge_tracks_recommendation(self):
        from distributedpytorch_tpu.obs import defs as obsm

        hint = self._hint(replicas=2, up_windows=1)
        hint.observe_window(9, 0)
        assert obsm.SERVE_REPLICA_HINT.value == 3

    def test_stale_fleet_metrics_count_as_pressure(self):
        """A worker that stops answering the metrics scrape is load you
        cannot SEE, not load that vanished: stale windows arm the
        up-streak like sheds do, and break any quiet streak — the fleet
        never scales down on blindness."""
        hint = self._hint(replicas=2, up_windows=2)
        assert hint.observe_window(0, 0, stale=True) == 2
        assert hint.observe_window(0, 0, stale=True) == 3
        quiet = self._hint(replicas=2, down_windows=2)
        assert quiet.observe_window(0, 0) == 2
        # one blind window resets the quiet streak...
        assert quiet.observe_window(0, 0, stale=True) == 2
        assert quiet.observe_window(0, 0) == 2
        # ...so the down takes a FULL fresh quiet run after sight returns
        assert quiet.observe_window(0, 0) == 1


# ---------------------------------------------------------------------------
# HTTP front: Retry-After, readiness vs liveness, /admin/rollout
# ---------------------------------------------------------------------------


class TestHTTPFront:
    def _http(self, server):
        from distributedpytorch_tpu.serve.cli import make_http_server

        httpd = make_http_server(server, port=0)
        threading.Thread(target=lambda: httpd.serve_forever(poll_interval=0.02),
        daemon=True).start()
        return httpd, httpd.server_address[1]

    def test_relaunch_gap_is_503_with_retry_after_and_unready_healthz(
            self, rigs, engine, clean_faults):
        """The degradation story over real HTTP: during the relaunch
        gap /predict answers 503 + Retry-After (not a dropped
        connection), /healthz is 503 ready:false, /livez stays 200 —
        then everything recovers."""
        _tmp, _a, _b, images_dir = rigs
        with open(_image_files(images_dir)[0], "rb") as f:
            body = f.read()
        server = _serve(engine, restart_backoff_s=3.0)
        httpd, port = self._http(server)
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            assert resp.status == 200
            assert json.loads(resp.read())["ready"] is True

            faults.install(("serve_dispatch_death",))
            server.submit(_img()).result(30)  # trigger the death
            deadline = time.monotonic() + 5
            while (server.state != "relaunching"
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert server.state == "relaunching"

            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            payload = json.loads(resp.read())
            assert resp.status == 503
            assert payload["ready"] is False
            assert payload["state"] == "relaunching"

            conn.request("GET", "/livez")
            resp = conn.getresponse()
            resp.read()
            assert resp.status == 200  # live the whole time

            conn.request("POST", "/predict", body=body)
            resp = conn.getresponse()
            assert resp.status == 503
            assert int(resp.getheader("Retry-After")) >= 1
            assert json.loads(resp.read())["reason"] == "relaunching"

            deadline = time.monotonic() + 30
            recovered = False
            while time.monotonic() < deadline and not recovered:
                conn.request("POST", "/predict", body=body)
                resp = conn.getresponse()
                data = resp.read()
                recovered = resp.status == 200
                time.sleep(0.05)
            assert recovered, "front never served 200s again"
            conn.close()
        finally:
            httpd.shutdown()
            server.stop()

    def test_admin_rollout_endpoint(self, rigs, engine, pristine_weights):
        from distributedpytorch_tpu.checkpoint import resolve_checkpoint
        from distributedpytorch_tpu.serve.rollout import RolloutManager

        _tmp, _dir_a, dir_b, images_dir = rigs
        offline_b = _offline_masks(rigs, dir_b, "b_admin")
        server = _serve(engine)
        server.rollout = RolloutManager(server, window_s=0.2)
        httpd, port = self._http(server)
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            conn.request("GET", "/admin/rollout")
            status = json.loads(conn.getresponse().read())
            assert status["state"] == "idle"
            assert status["weights_version"] == 0

            conn.request("POST", "/admin/rollout", body=b"not json")
            assert conn.getresponse().status == 400

            spec = json.dumps({
                "checkpoint": resolve_checkpoint("singleGPU", dir_b)
            }).encode()
            conn.request("POST", "/admin/rollout", body=spec)
            resp = conn.getresponse()
            assert resp.status == 202
            assert json.loads(resp.read())["accepted"] is True
            assert server.rollout.wait(60) == "promoted"

            with open(_image_files(images_dir)[0], "rb") as f:
                conn.request("POST", "/predict", body=f.read())
            resp = conn.getresponse()
            assert resp.status == 200
            import io

            from PIL import Image

            mask = np.asarray(Image.open(io.BytesIO(resp.read())))
            np.testing.assert_array_equal(mask, offline_b[0])
            conn.close()
        finally:
            httpd.shutdown()
            server.stop()


# ---------------------------------------------------------------------------
# elastic --workload serve: argv plumbing + stub state machine
# ---------------------------------------------------------------------------

# A stub serve worker: beats by hand (serve-shaped: epoch stays 0, step
# counts completions, timed=True), serves "forever" until torn down —
# or dies on cue. Argv-compatible with the flags the supervisor appends.
SERVE_STUB = textwrap.dedent(
    """
    import json, os, sys, time

    def flag(name, default=None):
        argv = sys.argv
        return argv[argv.index(name) + 1] if name in argv else default

    hb_dir = flag("--heartbeat-dir")
    rank = int(os.environ.get("RANK", "0"))
    marker = flag("--marker")

    def beat(step=0):
        os.makedirs(hb_dir, exist_ok=True)
        path = os.path.join(hb_dir, f"rank_{rank}.beat")
        with open(path + ".tmp", "w") as f:
            json.dump({"rank": rank, "pid": os.getpid(), "epoch": 0,
                       "step": step, "time": time.time(),
                       "progress_time": time.time(), "timed": True,
                       "status": "ok"}, f)
        os.replace(path + ".tmp", path)

    beat()
    behavior = flag(f"--rank{rank}", "serve")
    if behavior == "die-once" and not os.path.exists(marker):
        open(marker, "w").close()
        sys.exit(1)
    i = 0
    while True:  # a serve worker runs until the supervisor says stop
        i += 1
        beat(i)
        time.sleep(0.05)
    """
)


def _stub_serve_supervisor(tmp_path, nprocs, rank_behaviors, **kw):
    from distributedpytorch_tpu.dist.elastic import ElasticSupervisor

    stub = tmp_path / "serve_stub.py"
    stub.write_text(SERVE_STUB)
    args = ["--marker", str(tmp_path / "attempt.marker"),
            "--port", "9400"]
    for rank, behavior in rank_behaviors.items():
        args += [f"--rank{rank}", behavior]
    defaults = dict(
        worker_cmd=[sys.executable, str(stub)],
        nprocs=nprocs,
        workload="serve",
        max_restarts=3,
        heartbeat_timeout_s=2.0,
        heartbeat_interval_s=0.1,
        poll_interval_s=0.05,
        restart_backoff_s=0.05,
        teardown_grace_s=2.0,
        spawn_timeout_s=30.0,
        run_dir=str(tmp_path / "run"),
    )
    defaults.update(kw)
    return ElasticSupervisor(args, **defaults)


class TestElasticServeWorkload:
    def test_serve_argv_ports_heartbeats_chaos_no_resume(self, tmp_path):
        from distributedpytorch_tpu.dist.elastic import ElasticSupervisor

        sup = ElasticSupervisor(
            ["-c", "singleGPU", "--port", "9000", "--replicas", "1"],
            nprocs=3, workload="serve", run_dir=str(tmp_path / "run"),
            chaos=("serve_dispatch_death",),
        )
        assert sup.worker_cmd[-1] == "serve"
        argv = sup._worker_argv(0, rank=2)
        assert argv[-2:] == ["--port", "9002"]  # last occurrence wins
        assert "--heartbeat-dir" in argv
        assert "--inject-fault" in argv  # chaos on attempt 0
        # request tracing (ISSUE 13): serve workers DO get the timeline
        # now — per-request span ledgers merged into the fleet pane
        i = argv.index("--trace-timeline")
        assert argv[i + 1] == sup._timeline_base(0)
        off = ElasticSupervisor(
            ["-c", "singleGPU", "--port", "9000"], nprocs=1,
            workload="serve", run_dir=str(tmp_path / "run2"), trace=False,
        )
        assert "--trace-timeline" not in off._worker_argv(0, rank=0)
        relaunch = sup._worker_argv(1, rank=0)
        assert "--inject-fault" not in relaunch
        # no resume -c appended: the user's own -c rides in worker_args
        # untouched and stays the only occurrence
        assert relaunch.count("-c") == 1
        assert relaunch[-2:] == ["--port", "9000"]
        # serving is collective-free: the static preflight has nothing
        # to check and must not pay an analyzer subprocess
        assert sup.static_preflight() == []

    def test_workload_validated(self, tmp_path):
        from distributedpytorch_tpu.dist.elastic import ElasticSupervisor

        with pytest.raises(ValueError, match="workload"):
            ElasticSupervisor([], nprocs=1, workload="coffee",
                              run_dir=str(tmp_path))

    def test_dead_serve_worker_is_relaunched_then_stop_requested(
            self, tmp_path):
        """The supervision state machine on stub serve workers: rank 0
        dies once → detected, world torn down, relaunched; the fleet
        then serves until request_stop ends the run cleanly."""
        sup = _stub_serve_supervisor(tmp_path, 2, {0: "die-once"})
        rc = []
        t = threading.Thread(target=lambda: rc.append(sup.run()),
                             daemon=True)
        t.start()
        deadline = time.monotonic() + 60
        while sup.restarts < 1 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert sup.restarts == 1, "dead serve worker was not relaunched"
        time.sleep(0.5)  # let the relaunched attempt settle into serving
        sup.request_stop()
        t.join(60)
        assert rc == [0]
        report = json.load(open(sup.report_path))
        assert report["final"] == "stopped"
        assert any(
            line.startswith("rank 0: dead")
            for line in report["attempts"][0]["failures"]
        )
        assert report["attempts"][-1]["ok"] is True

    def test_request_stop_ends_a_healthy_fleet(self, tmp_path):
        sup = _stub_serve_supervisor(tmp_path, 2, {})
        rc = []
        t = threading.Thread(target=lambda: rc.append(sup.run()),
                             daemon=True)
        t.start()
        deadline = time.monotonic() + 30
        while not sup._procs and time.monotonic() < deadline:
            time.sleep(0.05)
        time.sleep(0.3)  # workers beating
        sup.request_stop()
        t.join(30)
        assert rc == [0]
        report = json.load(open(sup.report_path))
        assert report["final"] == "stopped"
        # exit codes snapshot BEFORE teardown: healthy workers the stop
        # SIGTERMed must not be recorded as if they died on their own
        assert all(
            code is None
            for code in report["attempts"][-1]["exit_codes"].values()
        )


# ---------------------------------------------------------------------------
# THE drill: a real serve worker, SIGKILLed mid-traffic, back serving
# ---------------------------------------------------------------------------


def _free_port(span: int = 1) -> int:
    """A port P with P..P+span-1 all free right now: supervised serve
    worker R binds base+R, so a drill that starts N workers needs N
    consecutive free ports — asking the OS for one and hoping for its
    neighbour cost a 600 s spawn wait whenever the neighbour was taken."""
    while True:
        socks = []
        try:
            first = socket.socket()
            socks.append(first)
            first.bind(("127.0.0.1", 0))
            base = first.getsockname()[1]
            for offset in range(1, span):
                s = socket.socket()
                socks.append(s)
                s.bind(("127.0.0.1", base + offset))
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()


def _http_predict(port: int, body: bytes, timeout=5.0):
    """One POST /predict; returns the status code or None when the
    worker's port is down (the relaunch gap)."""
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=timeout)
        conn.request("POST", "/predict", body=body)
        resp = conn.getresponse()
        resp.read()
        status = resp.status
        conn.close()
        return status
    except OSError:
        return None


class TestElasticServeDrill:
    def test_sigkilled_serve_worker_relaunched_and_serving_again(
            self, rigs, tmp_path):
        """THE acceptance drill (ISSUE 12): a real serve worker under
        the elastic supervisor is SIGKILLed mid-traffic; the supervisor
        classifies it dead within the heartbeat window, relaunches it,
        and the HTTP front serves 200s again — clients in the gap get
        connection errors or 503s, never a hang."""
        import getpass
        import signal

        from distributedpytorch_tpu.dist.elastic import ElasticSupervisor

        _tmp, dir_a, _dir_b, images_dir = rigs
        with open(_image_files(images_dir)[0], "rb") as f:
            body = f.read()
        port = _free_port()
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        # share the suite-wide AOT store (see test_serve_router's
        # _supervisor_env): relaunch + cold start become loads
        env["DPT_AOT_CACHE"] = (
            f"/tmp/dpt_test_aot_store_{getpass.getuser()}"
        )
        sup = ElasticSupervisor(
            [
                "-c", "singleGPU",
                "--checkpoint-dir", dir_a,
                "--image-size", "48", "32",
                "--model-widths", "8", "16",
                "--buckets", "1", "2",
                "--replicas", "1",
                "--slo-ms", "25",
                "--host-cache-mb", "0",
                "--autoscale-interval", "0",
                "--port", str(port),
            ],
            nprocs=1,
            workload="serve",
            cpu_devices=1,
            max_restarts=2,
            heartbeat_timeout_s=60.0,
            heartbeat_interval_s=0.2,
            poll_interval_s=0.1,
            restart_backoff_s=0.1,
            teardown_grace_s=10.0,
            spawn_timeout_s=600.0,
            run_dir=str(tmp_path / "run"),
            env=env,
        )
        rc = []
        t = threading.Thread(target=lambda: rc.append(sup.run()),
                             daemon=True)
        t.start()
        try:
            # worker up: AOT compiles, then serves
            deadline = time.monotonic() + 300
            while time.monotonic() < deadline:
                if _http_predict(port, body) == 200:
                    break
                time.sleep(0.2)
            else:
                pytest.fail("serve worker never served its first 200")

            pid = sup._procs[0].pid
            os.kill(pid, signal.SIGKILL)  # mid-traffic: keep requesting
            saw_gap = False
            relaunched = False
            deadline = time.monotonic() + 300
            while time.monotonic() < deadline:
                status = _http_predict(port, body)
                if status != 200:
                    saw_gap = True
                elif saw_gap and status == 200:
                    relaunched = True
                    break
                time.sleep(0.2)
            assert relaunched, "worker never served 200s again after SIGKILL"
            assert sup.restarts == 1
            assert sup._procs[0].pid != pid  # a NEW process serves
        finally:
            sup.request_stop()
            t.join(60)
        assert rc == [0]
        report = json.load(open(sup.report_path))
        assert report["final"] == "stopped"
        assert any(
            "dead" in line and "signal 9" in line
            for line in report["attempts"][0]["failures"]
        )


# ---------------------------------------------------------------------------
# bench_serve: chaos + rollout legs
# ---------------------------------------------------------------------------


class TestBenchServeFleetLegs:
    def test_chaos_and_rollout_legs_in_report(self, clean_faults):
        import tools.bench_serve as bench_serve

        args = bench_serve.get_args([
            "--image-size", "48", "32",
            "--buckets", "1", "2", "4",
            "--replicas", "1",
            "--levels", "1", "2", "4",
            "--duration", "0.6",
        ])
        report = bench_serve.run_bench(budget_s=60.0, args=args)
        chaos = report["chaos"]
        assert chaos["recovered"]
        assert chaos["unresolved_futures"] == 0
        assert chaos["core_restarts"] >= 1
        assert os.path.exists(chaos["flight_recorder"])
        rollout = report["rollout"]
        assert rollout["outcome"] == "promoted"
        assert rollout["zero_5xx"]
        assert rollout["weights_version"] == 1
        assert os.path.exists(rollout["flight_recorder"])
        router = report["router"]
        assert router["requests"] > 0
        assert router["zero_client_failures"]
        assert os.path.exists(router["flight_recorder"])
        hedge = report["hedge"]
        assert hedge["hedges_fired"] >= 1
        assert hedge["hedged_p99_improved"]  # hedged p99 < unhedged p99
        # exactly-once: hedge losers never double-count in the ledger
        assert hedge["ledger_exact"]
        assert hedge["unhedged"]["ledger_exact"]
        assert os.path.exists(hedge["flight_recorder"])
        json.dumps(report)  # still a writable JSON artifact


# ---------------------------------------------------------------------------
# live replica-group scaling + sustained weight A/B
# ---------------------------------------------------------------------------


class TestReplicaResize:
    def test_grow_serves_then_shrink_drains(self, engine):
        """``resize_replicas`` is the autoscaler's actuator: grow makes
        the next flush able to land on the new replica, shrink drains
        the victim's slots before dropping it — both mid-serve, no
        restart, and the server keeps answering through each."""
        server = _serve(engine)
        try:
            assert engine.num_replicas == 1
            assert server.resize_replicas(2) == 2
            assert server.stats()["replicas"] == 2
            resp = server.submit([_img(i) for i in range(4)]).result(30)
            assert resp.ok and len(resp.masks) == 4
            assert server.resize_replicas(1) == 1
            assert engine.num_replicas == 1
            resp = server.submit(_img(9)).result(30)
            assert resp.ok
        finally:
            server.stop()
            while engine.num_replicas > 1:  # the fixture is shared
                engine.retire_replica()

    def test_resize_floors_at_one(self, engine):
        server = _serve(engine)
        try:
            assert server.resize_replicas(0) == 1
            assert engine.num_replicas == 1
        finally:
            server.stop()


class TestSustainedAB:
    def _ab(self, server, **kwargs):
        from distributedpytorch_tpu.serve.rollout import ABTest

        ab = ABTest(server, **kwargs)
        server.abtest = ab
        return ab

    def test_needs_two_replica_groups(self, rigs, engine):
        from distributedpytorch_tpu.checkpoint import resolve_checkpoint

        _tmp, _dir_a, dir_b, _images = rigs
        server = _serve(engine)
        try:
            ab = self._ab(server)
            with pytest.raises(ValueError, match="replica groups"):
                ab.start(resolve_checkpoint("singleGPU", dir_b))
            assert not ab.active
            assert server.ab_arms is None
        finally:
            server.stop()

    def test_arms_pin_groups_split_traffic_and_promote_winner(
            self, rigs, engine, pristine_weights):
        """The sustained-A/B lifecycle on a live 2-replica server:
        disjoint replica groups pinned per arm, traffic split by the
        deterministic request-id hash with per-arm ledgers, explicit
        ``X-AB-Arm``-shaped placement landing on the arm's OWN weights,
        resize refused while arms pin the groups, and ``stop(winner)``
        promoting the winner fleet-wide as a pointer flip."""
        from distributedpytorch_tpu.checkpoint import resolve_checkpoint
        from distributedpytorch_tpu.obs import defs as obsm
        from distributedpytorch_tpu.serve.rollout import ab_arm_for

        _tmp, _dir_a, dir_b, _images = rigs
        server = _serve(engine)
        ab = None
        try:
            assert server.resize_replicas(2) == 2
            probe_rows = [_img(100 + i) for i in range(3)]
            ab = self._ab(server, probe_rows=probe_rows, split=0.5)
            status = ab.start(resolve_checkpoint("singleGPU", dir_b),
                              label="candidate-b")
            assert ab.active and status["active"]
            assert server.ab_arms == {"a": frozenset([0]),
                                      "b": frozenset([1])}
            assert engine.versions_mixed  # two promoted versions, pinned
            assert obsm.SERVE_AB_ACTIVE.value == 1
            # resizing would tear a group boundary: refused, not queued
            assert server.resize_replicas(3) == 2

            rids = [f"ab-req-{i}" for i in range(12)]
            for i, rid in enumerate(rids):
                resp = server.submit(_img(i % 4), request_id=rid).result(30)
                assert resp.ok
            expected = {"a": 0, "b": 0}
            for rid in rids:
                expected[ab_arm_for(rid, 0.5)] += 1
            snap = server.metrics.ab_snapshot()
            for arm, n in expected.items():
                if n:
                    assert snap[arm]["requests_ok"] == n
                    assert snap[arm]["p50_ms"] is not None

            # explicit arm placement lands on that arm's own weights
            row = _img(99)
            for arm, idx in (("a", 0), ("b", 1)):
                served = server.submit(row, arm=arm).result(30)
                assert served.ok
                ref = engine.postprocess(
                    engine.infer(np.stack([row]), replica_index=idx)[0]
                )
                np.testing.assert_array_equal(served.masks[0], ref)

            verdict = ab.verdict()
            assert verdict["active"]
            assert 0.0 <= verdict["inter_arm_dice"] <= 1.0
            assert set(verdict["arms"]) == {"a", "b"}

            version_b = ab.versions["b"]
            out = ab.stop(winner="b")
            assert out["stopped"] and out["winner"] == "b"
            assert not ab.active
            assert server.ab_arms is None
            assert not engine.versions_mixed
            assert all(r.weights_version == version_b
                       for r in engine.replicas)
            assert obsm.SERVE_AB_ACTIVE.value == 0
            # the promoted fleet serves the candidate everywhere now
            served = server.submit(row).result(30)
            ref_b = engine.postprocess(
                engine.infer(np.stack([row]), replica_index=0)[0]
            )
            np.testing.assert_array_equal(served.masks[0], ref_b)
        finally:
            if ab is not None and ab.active:
                ab.stop()
            server.resize_replicas(1)
            server.stop()
            while engine.num_replicas > 1:  # the fixture is shared
                engine.retire_replica()
