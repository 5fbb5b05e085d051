"""The fleet's front door (ISSUE 17), end to end on CPU:

* **failure matrix** against scripted stub workers — a worker dying
  mid-request is retried on a sibling (and ejected); an all-shedding
  fleet degrades to ONE 503 merging the worst per-worker reason and the
  soonest Retry-After; non-shed 5xx answers are retried (inference is
  idempotent); a hedge's loser is torn down and never double-counted in
  the router's ledger; ejected workers are re-admitted off /healthz;
* **placement feed** — ``ingest_fleet_metrics`` parses scraped queue
  depths and marks silent workers stale (stale scores as pressure);
* **sustained A/B plumbing** — ``POST /admin/ab`` fans out to every
  worker, arms are stamped deterministically, and the per-arm ledger
  splits traffic by the configured ratio;
* **THE drill** — two REAL serve workers under the elastic supervisor
  behind one router address; one worker is SIGKILLed mid-traffic and
  relaunched ALONE (per-rank, the sibling keeps serving) while every
  client request through the router answers 200 — zero client-visible
  failures;
* **diurnal autoscaling** — the pinned synthetic diurnal trace
  (tests/data/serve/arrivals_diurnal.jsonl) drives the hint + scaler
  through a load swell and ebb: exactly one scale-up and one
  scale-down, each decision citing the plan-serve grid point it
  executes.

And the front door's OWN failure story (ISSUE 18 — the router must not
be the last single point of failure):

* **active/standby HA matrix** — takeover mid-traffic with the
  two-address client seeing only 200s; takeover during a sustained A/B
  with the split + per-arm ledger preserved; double failure (dead
  active + all-shedding workers) degrading to ONE honest merged 503;
  a relaunched ex-active demoting to standby behind the epoch fence
  and resyncing;
* **THE HA chaos drill** — the active router as a real OS process,
  SIGKILLed mid-traffic; the standby takes over off a missed probe,
  zero client-visible failures, both /admin/state snapshots written
  for CI;
* **fleet A/B verdict fan-in** — ``{"action": "verdict"}`` merges every
  worker's ledger deterministically, excluding probe-less workers from
  the Dice mean BY NAME (never zero-averaging them);
* **fleet elasticity drill** — the diurnal swell/ebb re-pinned at
  fleet level: whole worker processes spawn (warm off the shared AOT
  store, zero recompiles) and retire (router-drained), every decision
  citing its plan-serve grid point.
"""

import http.client
import json
import os
import socket
import threading
import time
import types

import pytest

from distributedpytorch_tpu.serve.router import Router, make_router_http

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA_DIR = os.path.join(REPO, "tests", "data", "serve")
DIURNAL_TRACE = os.path.join(DATA_DIR, "arrivals_diurnal.jsonl")
SMOKE_PROFILE = os.path.join(DATA_DIR, "profile_smoke.json")


# ---------------------------------------------------------------------------
# scripted stub workers: each /predict answer comes from a script queue
# ---------------------------------------------------------------------------


def _stub_worker(script=None, default=("ok",), healthz_ready=True,
                 ab_response=None):
    """One scripted fleet worker. ``script`` entries (consumed FIFO,
    then ``default`` forever): ``("ok", [delay_s])``, ``("shed",
    reason, retry_after)``, ``("error", code)``, ``("abort",)`` (close
    the socket mid-exchange — the SIGKILL shape). ``ab_response``
    scripts what ``/admin/ab`` answers (the verdict fan-in tests feed
    per-worker verdict payloads through it). Returns
    ``(httpd, port, seen)``; ``seen`` counts per-path hits and records
    each /predict's X-AB-Arm header."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    script = list(script or [])
    seen = {"predict": 0, "healthz": 0, "ab": 0, "arms": []}
    lock = threading.Lock()

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *args):  # noqa: D102 — quiet test server
            pass

        def _json(self, code, obj, extra=None):
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for key, value in (extra or {}).items():
                self.send_header(key, value)
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):  # noqa: N802
            if self.path == "/healthz":
                with lock:
                    seen["healthz"] += 1
                ready = healthz_ready
                self._json(200 if ready else 503, {"ready": ready})
            elif self.path == "/stats":
                self._json(200, {"queue_depth_images": 0})
            else:
                self._json(404, {})

        def do_POST(self):  # noqa: N802
            length = int(self.headers.get("Content-Length", 0))
            self.rfile.read(length)
            if self.path == "/admin/ab":
                with lock:
                    seen["ab"] += 1
                self._json(200, ab_response if ab_response is not None
                           else {"ok": True, "active": True})
                return
            with lock:
                seen["predict"] += 1
                seen["arms"].append(self.headers.get("X-AB-Arm", ""))
                step = script.pop(0) if script else default
            kind = step[0]
            if kind == "ok":
                if len(step) > 1:
                    time.sleep(float(step[1]))
                self._json(200, {"status": "ok"}, extra={
                    "X-Request-Id": self.headers.get("X-Request-Id", ""),
                })
            elif kind == "shed":
                self._json(503, {"status": "rejected", "reason": step[1]},
                           extra={"Retry-After": str(step[2])})
            elif kind == "error":
                self._json(int(step[1]), {"status": "error"})
            elif kind == "abort":
                try:
                    self.connection.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                self.connection.close()
            else:  # pragma: no cover — script typo guard
                raise AssertionError(f"unknown step {step!r}")

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=lambda: httpd.serve_forever(poll_interval=0.02),
        daemon=True).start()
    return httpd, httpd.server_address[1], seen


@pytest.fixture
def stub_fleet(request):
    httpds = []

    def make(*args, **kwargs):
        httpd, port, seen = _stub_worker(*args, **kwargs)
        httpds.append(httpd)
        return port, seen

    yield make
    for httpd in httpds:
        httpd.shutdown()


def _router(ports, **kwargs):
    kwargs.setdefault("backoff_base_s", 0.01)
    kwargs.setdefault("backoff_cap_s", 0.05)
    return Router([("127.0.0.1", p) for p in ports], **kwargs)


# ---------------------------------------------------------------------------
# the failure matrix
# ---------------------------------------------------------------------------


class TestRouterFailureMatrix:
    def test_worker_death_mid_request_is_retried_on_sibling(
            self, stub_fleet):
        """An aborted exchange (the SIGKILL shape) never reaches the
        client: the corpse is ejected and the request re-lands on the
        sibling, immediately (no backoff for a dead socket)."""
        port_a, seen_a = stub_fleet(script=[("abort",)])
        port_b, seen_b = stub_fleet()
        router = _router([port_a, port_b])
        code, headers, body = router.proxy_predict(b"x", request_id="r1")
        assert code == 200
        assert headers["X-Router-Attempts"] == "2"
        assert headers["X-Router-Worker"] == f"127.0.0.1:{port_b}"
        assert seen_a["predict"] == 1 and seen_b["predict"] == 1
        stats = router.stats()
        assert stats["retries"] == 1
        assert stats["healthy_workers"] == 1  # the corpse was ejected
        assert not router.workers[0].healthy

    def test_all_shedding_degrades_to_one_merged_503(self, stub_fleet):
        """When EVERY worker sheds past the retry budget the client gets
        exactly one 503: reason = the worst across the fleet,
        Retry-After = the soonest any worker advertised, body naming
        each worker's own reason."""
        port_a, _ = stub_fleet(default=("shed", "overloaded", 2))
        port_b, _ = stub_fleet(default=("shed", "relaunching", 5))
        router = _router([port_a, port_b], retry_budget=2)
        code, headers, body = router.proxy_predict(b"x", request_id="r2")
        assert code == 503
        payload = json.loads(body)
        assert payload["reason"] == "relaunching"  # the worse story
        assert headers["Retry-After"] == "2"       # the soonest retry
        assert payload["workers"] == {
            f"127.0.0.1:{port_a}": "overloaded",
            f"127.0.0.1:{port_b}": "relaunching",
        }
        assert router.stats()["requests_failed"] == 1

    def test_shedding_worker_retried_after_backoff_on_sibling(
            self, stub_fleet):
        port_a, seen_a = stub_fleet(script=[("shed", "overloaded", 1)])
        port_b, seen_b = stub_fleet()
        router = _router([port_a, port_b])
        code, headers, _ = router.proxy_predict(b"x", request_id="r3")
        assert code == 200
        assert headers["X-Router-Attempts"] == "2"
        assert router.stats()["retries"] == 1
        # the shedding worker stays healthy — shed is load, not death
        assert router.stats()["healthy_workers"] == 2

    def test_non_shed_5xx_is_retried_because_inference_is_idempotent(
            self, stub_fleet):
        """A worker 500 (an in-flight future dying with a relaunching
        core) is resubmitted to a sibling instead of surfacing."""
        port_a, _ = stub_fleet(script=[("error", 500)])
        port_b, _ = stub_fleet()
        router = _router([port_a, port_b])
        code, _, _ = router.proxy_predict(b"x", request_id="r4")
        assert code == 200
        assert router.stats()["retries"] == 1

    def test_persistent_5xx_surfaces_as_itself_not_a_fake_503(
            self, stub_fleet):
        port_a, _ = stub_fleet(default=("error", 500))
        port_b, _ = stub_fleet(default=("error", 500))
        router = _router([port_a, port_b], retry_budget=2)
        code, _, body = router.proxy_predict(b"x", request_id="r5")
        assert code == 500  # the honest answer, not an invented shed

    def test_ejected_worker_readmitted_off_healthz(self, stub_fleet):
        port_a, seen_a = stub_fleet()
        port_b, _ = stub_fleet()
        router = _router([port_a, port_b])
        router._eject(router.workers[0])
        assert router.stats()["healthy_workers"] == 1
        router.probe_once()
        assert router.workers[0].healthy
        assert seen_a["healthz"] == 1
        assert router.stats()["healthy_workers"] == 2

    def test_hedge_loser_is_cancelled_and_never_double_counted(
            self, stub_fleet):
        """With hedging on, a slow primary gets a duplicate fired at a
        sibling past the deadline; the fast sibling's answer wins and
        the router's ledger counts the request EXACTLY once, even
        though two workers each saw a copy."""
        port_a, seen_a = stub_fleet(default=("ok", 0.8))  # always slow
        port_b, seen_b = stub_fleet()                     # always fast
        # tie-break placement picks worker 0 first → the slow one is
        # always primary, deterministically
        router = _router([port_a, port_b], hedge=True, hedge_floor_ms=60)
        code, _, _ = router.proxy_predict(b"x", request_id="r6")
        assert code == 200
        stats = router.stats()
        assert stats["hedges_fired"] == 1
        assert stats["hedge_wins"] == 1
        # both workers saw a copy, the client and the ledger saw ONE
        assert seen_a["predict"] == 1 and seen_b["predict"] == 1
        assert stats["requests_ok"] == 1
        assert stats["requests_failed"] == 0

    def test_nobody_healthy_is_an_unreachable_503(self, stub_fleet):
        port_a, _ = stub_fleet(default=("abort",))
        router = _router([port_a])
        code, _, body = router.proxy_predict(b"x", request_id="r7")
        assert code == 503
        assert json.loads(body)["reason"] == "unreachable"


class TestPlacementFeed:
    def test_ingest_parses_depth_and_marks_missing_workers_stale(
            self, stub_fleet):
        port_a, _ = stub_fleet()
        port_b, _ = stub_fleet()
        router = _router([port_a, port_b])
        router.ingest_fleet_metrics({
            "0": 'dpt_serve_queue_depth_images{worker="0"} 7\n',
            # worker 1 missing from the sweep entirely
        })
        assert router.workers[0].depth == 7
        assert not router.workers[0].stale
        assert router.workers[1].stale
        # a stale worker scores as PRESSURE: placement avoids it
        assert (router.workers[1].score(router.stale_penalty)
                > router.workers[0].score(router.stale_penalty))
        code, headers, _ = router.proxy_predict(b"x", request_id="r8")
        assert code == 200
        assert headers["X-Router-Worker"] == f"127.0.0.1:{port_a}"
        # the worker answers the next sweep: stale clears
        router.ingest_fleet_metrics({
            "0": "dpt_serve_queue_depth_images 0\n",
            "1": "dpt_serve_queue_depth_images 2\n",
        })
        assert not router.workers[1].stale
        assert router.workers[1].depth == 2

    def test_least_loaded_placement_prefers_the_idle_worker(
            self, stub_fleet):
        port_a, seen_a = stub_fleet()
        port_b, seen_b = stub_fleet()
        router = _router([port_a, port_b], policy="least")
        router.ingest_fleet_metrics({
            "0": "dpt_serve_queue_depth_images 9\n",
            "1": "dpt_serve_queue_depth_images 0\n",
        })
        for i in range(3):
            code, headers, _ = router.proxy_predict(b"x", f"r9-{i}")
            assert code == 200
            assert headers["X-Router-Worker"] == f"127.0.0.1:{port_b}"
        assert seen_a["predict"] == 0 and seen_b["predict"] == 3


class TestRouterABPlumbing:
    def test_admin_ab_fans_out_and_splits_traffic_by_request_id(
            self, stub_fleet):
        from distributedpytorch_tpu.serve.rollout import ab_arm_for

        port_a, seen_a = stub_fleet()
        port_b, seen_b = stub_fleet()
        router = _router([port_a, port_b])
        code, payload = router.admin_ab({
            "action": "start", "checkpoint": "x.ckpt", "split": 0.5,
        })
        assert code == 200 and payload["ok"]
        assert seen_a["ab"] == 1 and seen_b["ab"] == 1
        assert router.ab_active
        for i in range(20):
            assert router.proxy_predict(b"x", f"req-{i}")[0] == 200
        status = router.ab_status()
        arms = status["arms"]
        expected = {"a": 0, "b": 0}
        for i in range(20):
            expected[ab_arm_for(f"req-{i}", 0.5)] += 1
        for arm, n in expected.items():
            if n:
                assert arms[arm]["requests_ok"] == n
        assert sum(led["requests_ok"] for led in arms.values()) == 20
        # every forwarded request carried its arm stamp to the worker
        stamped = seen_a["arms"] + seen_b["arms"]
        assert all(arm in ("a", "b") for arm in stamped)
        code, payload = router.admin_ab({"action": "stop"})
        assert code == 200
        assert not router.ab_active

    def test_bad_action_is_a_400(self, stub_fleet):
        port_a, _ = stub_fleet()
        router = _router([port_a])
        code, payload = router.admin_ab({"action": "meddle"})
        assert code == 400

    def test_router_http_front_proxies_and_reports(self, stub_fleet):
        port_a, _ = stub_fleet()
        router = _router([port_a])
        httpd = make_router_http(router, port=0)
        threading.Thread(target=lambda: httpd.serve_forever(poll_interval=0.02),
        daemon=True).start()
        try:
            conn = http.client.HTTPConnection(
                "127.0.0.1", httpd.server_address[1], timeout=10)
            conn.request("POST", "/predict", body=b"x")
            resp = conn.getresponse()
            assert resp.status == 200
            assert resp.getheader("X-Request-Id")
            resp.read()
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            assert resp.status == 200
            assert json.loads(resp.read())["ready"] is True
            conn.request("GET", "/stats")
            resp = conn.getresponse()
            stats = json.loads(resp.read())
            assert stats["requests_ok"] == 1
            conn.close()
        finally:
            httpd.shutdown()


# ---------------------------------------------------------------------------
# active/standby HA: the failover matrix (ISSUE 18) — in-process pairs,
# ha_once() driven by hand so every exchange is deterministic
# ---------------------------------------------------------------------------


def _fronted(router):
    """Wrap a router in its HTTP front (ephemeral port) and serve it.
    Returns ``(httpd, front_port)``."""
    httpd = make_router_http(router, port=0)
    threading.Thread(target=lambda: httpd.serve_forever(poll_interval=0.02),
        daemon=True).start()
    return httpd, httpd.server_address[1]


def _kill_front(httpd):
    """Make an in-process router front die like a SIGKILLed process:
    ``shutdown()`` alone leaves the LISTENING socket open, so a peer
    probe would hang against its 2 s timeout instead of refusing —
    ``server_close()`` is what makes the death immediately visible."""
    httpd.shutdown()
    httpd.server_close()


def _failover_post(fronts, body, timeout=30.0):
    """The two-address client contract (docs/SERVING.md): try each
    router front in order, failing over on TRANSPORT errors only — an
    HTTP answer (any code) from either front is THE answer."""
    last_err = None
    for port in fronts:
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=timeout)
            conn.request("POST", "/predict", body=body)
            resp = conn.getresponse()
            data = resp.read()
            status = resp.status
            conn.close()
            return status, data
        except OSError as exc:
            last_err = exc
    raise last_err


def _ha_pair(worker_ports, **kwargs):
    """An active/standby router pair, each behind its own front, peered
    at each other's front address. Probe loops are NOT started — tests
    drive ``ha_once()`` by hand. Returns
    ``(active, standby, httpd_a, httpd_s, front_a, front_s)``."""
    kwargs.setdefault("probe_interval_s", 999.0)
    active = _router(worker_ports, role="active", **kwargs)
    httpd_a, front_a = _fronted(active)
    standby = _router(worker_ports, role="standby",
                      peer=("127.0.0.1", front_a), **kwargs)
    httpd_s, front_s = _fronted(standby)
    active.peer = ("127.0.0.1", front_s)
    return active, standby, httpd_a, httpd_s, front_a, front_s


class TestRouterHA:
    def test_active_front_death_mid_traffic_zero_client_failures(
            self, stub_fleet):
        """THE in-process takeover shape: traffic flows through the
        two-address client while the active front dies; the standby
        takes over on its next (single) HA exchange and no request ever
        surfaces a failure."""
        port_a, _ = stub_fleet(default=("ok", 0.02))
        port_b, _ = stub_fleet()
        active, standby, httpd_a, httpd_s, front_a, front_s = _ha_pair(
            [port_a, port_b])
        statuses = []
        stop = threading.Event()

        def traffic():
            i = 0
            while not stop.is_set():
                try:
                    statuses.append(
                        _failover_post([front_a, front_s], b"x")[0])
                except OSError:
                    statuses.append(-1)
                i += 1
                time.sleep(0.005)

        t = threading.Thread(target=traffic, daemon=True)
        t.start()
        try:
            deadline = time.monotonic() + 30
            while len(statuses) < 5 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert len(statuses) >= 5, "no traffic flowed pre-kill"
            standby.ha_once()           # peer alive: a sync, no takeover
            assert standby.role == "standby" and standby.ha_syncs == 1
            _kill_front(httpd_a)        # mid-traffic
            standby.ha_once()           # ONE missed probe → takeover
            assert standby.role == "active"
            assert standby.takeovers == 1
            assert standby.ha_epoch == 1
            deadline = time.monotonic() + 30
            n = len(statuses)
            while len(statuses) < n + 5 and time.monotonic() < deadline:
                time.sleep(0.01)
        finally:
            stop.set()
            t.join(30)
            httpd_s.shutdown()
        assert set(statuses) == {200}, (
            f"client saw failures: {sorted(set(statuses))} "
            f"over {len(statuses)} requests")
        assert standby.stats()["ha"]["takeovers"] == 1

    def test_takeover_preserves_active_ab_split_and_ledger(
            self, stub_fleet):
        """A takeover during a sustained A/B keeps the experiment: the
        synced standby carries the split, the label, and the per-arm
        ledger the active accumulated — the verdict survives the
        router that was keeping it."""
        port_a, _ = stub_fleet()
        port_b, _ = stub_fleet()
        active, standby, httpd_a, httpd_s, _, _ = _ha_pair(
            [port_a, port_b])
        try:
            code, payload = active.admin_ab({
                "action": "start", "checkpoint": "x.ckpt",
                "split": 0.25, "label": "ha-drill",
            })
            assert code == 200 and payload["ok"]
            for i in range(12):
                assert active.proxy_predict(b"x", f"ha-ab-{i}")[0] == 200
            standby.ha_once()       # snapshot pull while active lives
            assert standby.ha_syncs == 1
            before = active.ab_status()["arms"]
            assert sum(led["requests_ok"]
                       for led in before.values()) == 12
            _kill_front(httpd_a)
            standby.ha_once()       # takeover, with the state already in
            assert standby.role == "active"
            status = standby.ab_status()
            assert status["active"] is True
            assert status["split"] == 0.25
            assert status["label"] == "ha-drill"
            after = status["arms"]
            assert ({a: led["requests_ok"] for a, led in after.items()}
                    == {a: led["requests_ok"]
                        for a, led in before.items()})
            # the experiment CONTINUES through the survivor: new
            # traffic keeps landing in the same per-arm ledger
            assert standby.proxy_predict(b"x", "ha-ab-12")[0] == 200
            grown = standby.ab_status()["arms"]
            assert sum(led["requests_ok"]
                       for led in grown.values()) == 13
        finally:
            httpd_s.shutdown()

    def test_double_failure_is_one_honest_merged_503(self, stub_fleet):
        """Active router dead AND every worker shedding: the client's
        failover lands on the standby and gets exactly ONE honest
        merged 503 (worst reason, per-worker stories) — not a transport
        error, not an invented success."""
        port_a, _ = stub_fleet(default=("shed", "overloaded", 2))
        port_b, _ = stub_fleet(default=("shed", "relaunching", 5))
        active, standby, httpd_a, httpd_s, front_a, front_s = _ha_pair(
            [port_a, port_b], retry_budget=2)
        try:
            _kill_front(httpd_a)
            standby.ha_once()
            assert standby.role == "active"
            code, body = _failover_post([front_a, front_s], b"x")
            assert code == 503
            payload = json.loads(body)
            assert payload["reason"] == "relaunching"
            assert payload["workers"] == {
                f"127.0.0.1:{port_a}": "overloaded",
                f"127.0.0.1:{port_b}": "relaunching",
            }
            assert standby.stats()["requests_failed"] == 1
        finally:
            httpd_s.shutdown()

    def test_relaunched_ex_active_demotes_to_standby_and_resyncs(
            self, stub_fleet):
        """The readmission leg: after a takeover, the relaunched
        ex-active comes back on its old address claiming active at
        epoch 0 — the epoch fence demotes it to standby under the
        survivor (who keeps the role), and its next exchange pulls the
        snapshot back. The pair is whole again, roles swapped."""
        port_a, _ = stub_fleet()
        port_b, _ = stub_fleet()
        active, standby, httpd_a, httpd_s, front_a, front_s = _ha_pair(
            [port_a, port_b])
        httpd_r = None
        try:
            code, payload = active.admin_ab({
                "action": "start", "checkpoint": "x.ckpt",
                "split": 0.5, "label": "resync",
            })
            assert code == 200 and payload["ok"]
            standby.ha_once()                       # sync
            _kill_front(httpd_a)
            standby.ha_once()                       # takeover @ epoch 1
            assert standby.role == "active" and standby.ha_epoch == 1
            # the supervisor relaunches the dead router on the SAME
            # address, born active at epoch 0 (it has no memory)
            relaunched = _router([port_a, port_b], role="active",
                                 peer=("127.0.0.1", front_s),
                                 probe_interval_s=999.0)
            httpd_r = make_router_http(relaunched, port=front_a)
            threading.Thread(target=lambda: httpd_r.serve_forever(poll_interval=0.02),
                             daemon=True).start()
            relaunched.ha_once()    # both active: higher epoch wins
            assert relaunched.role == "standby"
            assert relaunched.ha_epoch == 1
            relaunched.ha_once()    # now standby: pulls the snapshot
            assert relaunched.ha_syncs == 1
            assert relaunched.ab_active is True
            assert relaunched.ab_label == "resync"
            # the survivor keeps the role against its new standby
            standby.ha_once()
            assert standby.role == "active"
            assert standby.ha_epoch == 1
            assert standby.takeovers == 1
        finally:
            if httpd_r is not None:
                httpd_r.shutdown()
            httpd_s.shutdown()


# ---------------------------------------------------------------------------
# fleet A/B verdict fan-in: POST /admin/ab {"action": "verdict"} merges
# every worker's ledger into ONE verdict with per-worker provenance
# ---------------------------------------------------------------------------


def _worker_verdict(dice, n_ok=5, p99=12.0):
    """A scripted per-worker ``/admin/ab`` verdict payload, the shape
    serve/rollout.py's ABTest.verdict() emits."""
    return {
        "ok": True, "active": True,
        "arms": {
            "a": {"requests_ok": n_ok, "requests_failed": 0,
                  "images_ok": n_ok, "rejected": 0,
                  "weights_version": 1, "p99_ms": p99},
            "b": {"requests_ok": n_ok + 1, "requests_failed": 1,
                  "images_ok": n_ok + 1, "rejected": 0,
                  "weights_version": 2, "p99_ms": p99 * 2},
        },
        "inter_arm_dice": dice,
    }


class TestFleetVerdictFanIn:
    def test_probeless_worker_is_excluded_from_dice_never_zeroed(
            self, stub_fleet):
        """The Dice fan-in correctness pin (ISSUE 18): a worker with no
        pinned probe rows reports ``inter_arm_dice: null`` and the
        fleet mean averages ONLY workers with evidence — the excluded
        address is NAMED, never silently zero-averaged (a 0.0 would
        claim 'the arms fully disagree' on a worker that never
        compared them)."""
        port_a, _ = stub_fleet(
            ab_response=_worker_verdict(0.9, n_ok=5, p99=10.0))
        port_b, _ = stub_fleet(
            ab_response=_worker_verdict(None, n_ok=3, p99=30.0))
        router = _router([port_a, port_b])
        code, body = router.admin_ab({"action": "verdict"})
        assert code == 200
        fleet = body["fleet"]
        addr_a = f"127.0.0.1:{port_a}"
        addr_b = f"127.0.0.1:{port_b}"
        assert fleet["workers"] == sorted([addr_a, addr_b])
        # counters sum exactly across the fleet
        assert fleet["arms"]["a"]["requests_ok"] == 8
        assert fleet["arms"]["b"]["requests_ok"] == 10
        assert fleet["arms"]["b"]["requests_failed"] == 2
        # p99 is worst-of-fleet, with per-worker provenance kept
        assert fleet["arms"]["a"]["p99_ms"] == 30.0
        assert fleet["arms"]["a"]["p99_ms_by_worker"] == {
            addr_a: 10.0, addr_b: 30.0}
        # the Dice term: mean over evidence only, exclusion by name
        assert fleet["dice"]["fleet_mean"] == 0.9
        assert fleet["dice"]["excluded"] == [addr_b]
        assert fleet["dice"]["per_worker"][addr_b] is None
        assert fleet["dice"]["per_worker"][addr_a] == 0.9

    def test_all_probeless_fleet_dice_is_null(self, stub_fleet):
        port_a, _ = stub_fleet(ab_response=_worker_verdict(None))
        port_b, _ = stub_fleet(ab_response=_worker_verdict(None))
        router = _router([port_a, port_b])
        code, body = router.admin_ab({"action": "verdict"})
        assert code == 200
        dice = body["fleet"]["dice"]
        assert dice["fleet_mean"] is None
        assert len(dice["excluded"]) == 2

    def test_merged_verdict_is_deterministic(self, stub_fleet):
        """Same per-worker payloads → byte-identical fleet verdict,
        every time (sorted-address merge, no dict-order leakage)."""
        port_a, _ = stub_fleet(ab_response=_worker_verdict(0.8))
        port_b, _ = stub_fleet(ab_response=_worker_verdict(0.6))
        router = _router([port_a, port_b])
        first = router.admin_ab({"action": "verdict"})[1]["fleet"]
        second = router.admin_ab({"action": "verdict"})[1]["fleet"]
        assert json.dumps(first, sort_keys=True) == json.dumps(
            second, sort_keys=True)
        assert first["dice"]["fleet_mean"] == 0.7

    def test_armless_worker_answer_is_unmergeable_not_a_crash(
            self, stub_fleet):
        port_a, _ = stub_fleet(ab_response=_worker_verdict(0.5))
        port_b, _ = stub_fleet(ab_response={"ok": True, "active": False})
        router = _router([port_a, port_b])
        code, body = router.admin_ab({"action": "verdict"})
        assert code == 200
        fleet = body["fleet"]
        assert fleet["workers"] == [f"127.0.0.1:{port_a}"]
        assert fleet["unmergeable"] == [f"127.0.0.1:{port_b}"]
        assert fleet["dice"]["fleet_mean"] == 0.5

    def test_abtest_verdict_reports_null_dice_with_zero_probes(self):
        """The worker half of the contract, pinned at the unit level:
        an ABTest with NO probe rows says ``inter_arm_dice: None`` —
        the null merge_fleet_verdict keys its exclusion off."""
        from distributedpytorch_tpu.serve.rollout import ABTest

        server = types.SimpleNamespace(
            engine=types.SimpleNamespace(num_replicas=2),
            metrics=types.SimpleNamespace(ab_snapshot=lambda: {}),
        )
        ab = ABTest(server, probe_rows=None)
        ab.active = True
        ab.started_t = 0.0
        ab.arms = {"a": [0], "b": [1]}
        ab.versions = {"a": 1, "b": 2}
        verdict = ab.verdict()
        assert verdict["active"] is True
        assert verdict["inter_arm_dice"] is None


# ---------------------------------------------------------------------------
# diurnal autoscaling: the pinned trace through hint + scaler + plan
# ---------------------------------------------------------------------------


class _FakeServeStack:
    """A jax-free server stand-in for the scaler's control law: a live
    replica count the resizer mutates, and the gates the scaler checks.
    The REAL resize path is pinned by tests/test_serve_fleet.py."""

    def __init__(self):
        self.engine = types.SimpleNamespace(
            num_replicas=1,
            versions_mixed=False,
            planner=types.SimpleNamespace(max_size=4),
        )
        self.ab_arms = None
        self.abtest = None

    def resize_replicas(self, target, timeout=30.0):
        self.engine.num_replicas = int(target)
        return int(target)


def _diurnal_plan():
    from distributedpytorch_tpu.analysis.serve_planner import (
        build_serve_plan,
    )
    from distributedpytorch_tpu.serve import sim

    with open(SMOKE_PROFILE) as f:
        profile = json.load(f)

    def scenario(rate):
        return {
            "label": f"poisson:{rate:g}rps", "kind": "poisson",
            "rate_rps": float(rate),
            "arrivals": sim.poisson_arrivals(rate, 10.0, seed=3),
        }

    return profile, build_serve_plan(
        profile, [scenario(40.0), scenario(320.0)],
        bucket_ladders=[(1, 2, 4, 8)], slos_ms=(50.0,),
        replicas=(1, 2), latency_slo_ms=50.0,
    )


class TestDiurnalScaling:
    def test_trace_fixture_is_pinned_and_deterministic(self, tmp_path):
        """The checked-in diurnal trace is exactly what its generator
        produces — regeneration is byte-identical (the artifact can
        always be rebuilt, never hand-edited)."""
        from distributedpytorch_tpu.serve import sim

        arrivals = sim.scheduled_poisson_arrivals(
            [(5.0, 40.0), (5.0, 320.0), (5.0, 40.0)], seed=7)
        regen = tmp_path / "regen.jsonl"
        sim.write_arrival_trace(str(regen), arrivals, created_unix=0.0)
        with open(DIURNAL_TRACE, "rb") as f:
            pinned = f.read()
        assert regen.read_bytes() == pinned

    def test_diurnal_trace_scales_up_and_down_citing_plan_points(self):
        """Replay the diurnal trace in 1 s windows through the hint's
        hysteresis and the scaler's control law: the 320 rps swell
        forces exactly one scale-up (citing the plan's r2 point for the
        320 rps scenario) and the ebb exactly one scale-down (citing
        the r1 point for 40 rps) — no flapping anywhere else."""
        from distributedpytorch_tpu.serve import sim
        from distributedpytorch_tpu.serve.autoscale import AutoscaleHint
        from distributedpytorch_tpu.serve.scaler import ReplicaScaler

        profile, plan = _diurnal_plan()
        # the plan itself must split the rates across replica counts —
        # otherwise the citations below would be vacuous
        recs = {r["scenario"]: r["replicas"]
                for r in plan["recommendations"]}
        assert recs["poisson:40rps"] == 1
        assert recs["poisson:320rps"] == 2

        arrivals = sim.load_arrival_trace(DIURNAL_TRACE)
        assert arrivals, "pinned diurnal trace failed to load"
        n_windows = int(max(t for t, _ in arrivals)) + 1
        counts = [0] * n_windows
        for t, rows in arrivals:
            counts[min(int(t), n_windows - 1)] += rows

        per_replica = sim.ServiceModel(profile).capacity_rows_per_s(
            (1, 2, 4, 8), 1)
        stack = _FakeServeStack()
        hint = AutoscaleHint(stack, interval_s=999.0,
                             up_windows=2, down_windows=4)
        scaler = ReplicaScaler(stack, hint, plan=plan, max_replicas=2)

        sizes = []
        for count in counts:
            capacity = per_replica * stack.engine.num_replicas
            shed = max(0, count - int(capacity))
            hint.observe_window(shed_delta=shed, max_depth=0)
            scaler.step(observed_rate_rps=float(count))
            sizes.append(stack.engine.num_replicas)

        assert scaler.scale_ups == 1
        assert scaler.scale_downs == 1
        assert sizes[-1] == 1 and max(sizes) == 2
        acted = [d for d in scaler.decisions
                 if d["direction"] != "hold"]
        assert [d["direction"] for d in acted] == ["up", "down"]
        up, down = acted
        assert up["target"] == 2
        assert up["plan_point"] == \
            "poisson:320rps/b1x2x4x8/slo50/r2/eager/capauto"
        assert up["plan_replicas"] == 2  # the plan agrees with the hint
        assert down["target"] == 1
        assert down["plan_point"] == \
            "poisson:40rps/b1x2x4x8/slo50/r1/eager/capauto"
        assert down["plan_replicas"] == 1
        # the swell acted DURING the swell, the ebb right after it
        assert 5 <= sizes.index(2) < 10
        assert sizes.index(1, sizes.index(2)) >= 10

    def test_scaler_holds_while_ab_pins_replica_groups(self):
        from distributedpytorch_tpu.serve.autoscale import AutoscaleHint
        from distributedpytorch_tpu.serve.scaler import ReplicaScaler

        stack = _FakeServeStack()
        stack.ab_arms = {"a": frozenset([0]), "b": frozenset([1])}
        hint = AutoscaleHint(stack, interval_s=999.0)
        scaler = ReplicaScaler(stack, hint, max_replicas=2)
        decision = scaler.decide(2)
        assert decision.direction == "hold"
        assert "A/B" in decision.reason

    def test_scaler_cooldown_refuses_to_flap(self):
        from distributedpytorch_tpu.serve.autoscale import AutoscaleHint
        from distributedpytorch_tpu.serve.scaler import ReplicaScaler

        stack = _FakeServeStack()
        hint = AutoscaleHint(stack, interval_s=999.0)
        scaler = ReplicaScaler(stack, hint, max_replicas=4,
                               cooldown_windows=3)
        applied = scaler.apply(scaler.decide(2))
        assert applied.target == 2
        # immediately after acting, a new divergence must hold
        decision = scaler.decide(3)
        assert decision.direction == "hold"
        assert "cooldown" in decision.reason


# ---------------------------------------------------------------------------
# THE drill: SIGKILL one of two supervised workers; zero client-visible
# failures through the router
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


def _http_json(port: int, path: str, timeout=5.0):
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=timeout)
        conn.request("GET", path)
        resp = conn.getresponse()
        payload = json.loads(resp.read())
        conn.close()
        return resp.status, payload
    except (OSError, ValueError):
        return None, None


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    """One trained singleGPU checkpoint + one synthetic carvana image,
    shared by every supervisor-level drill in this module."""
    from distributedpytorch_tpu.config import TrainConfig
    from distributedpytorch_tpu.train import Trainer

    tmp = tmp_path_factory.mktemp("router_drill")
    cfg = TrainConfig(
        train_method="singleGPU", epochs=1, batch_size=8,
        val_percent=25.0, seed=42, compute_dtype="float32",
        image_size=(48, 32), model_widths=(8, 16),
        synthetic_samples=16,
        checkpoint_dir=str(tmp / "checkpoints"),
        log_dir=str(tmp / "logs"), loss_dir=str(tmp / "loss"),
        num_workers=0,
    )
    Trainer(cfg).train()
    from distributedpytorch_tpu.data import (
        write_synthetic_carvana_tree,
    )

    images_dir, _ = write_synthetic_carvana_tree(
        str(tmp / "data"), n=2, size_wh=(48, 32))
    image = sorted(
        os.path.join(images_dir, f) for f in os.listdir(images_dir)
        if not f.startswith(".")
    )[0]
    return str(tmp / "checkpoints"), image


def _supervisor_env():
    import getpass

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # ONE AOT store across every drill in the suite AND across pytest
    # runs (operator env wins over the supervisor's per-run default):
    # after the first run, every serve worker cold-starts as loads, not
    # compiles — this is the product feature doing its job for the
    # test suite's own wall clock. Safe to share: entries are
    # content-keyed + integrity-footed, skew refuses loudly.
    env["DPT_AOT_CACHE"] = (
        f"/tmp/dpt_test_aot_store_{getpass.getuser()}"
    )
    return env


class TestRouterSupervisorDrill:
    def test_sigkilled_worker_behind_router_zero_client_failures(
            self, checkpoint, tmp_path):
        """THE acceptance drill (ISSUE 17): two real serve workers under
        the elastic supervisor behind ONE router address. One worker is
        SIGKILLed mid-traffic; the supervisor relaunches it ALONE (the
        sibling keeps serving) and the router retries the gap away —
        every client request answers 200, and the fleet returns to two
        healthy workers."""
        import signal

        from distributedpytorch_tpu.dist.elastic import ElasticSupervisor

        ckpt_dir, image_path = checkpoint
        with open(image_path, "rb") as f:
            body = f.read()
        base_port = _free_port(span=2)  # two workers: base, base+1
        router_port = _free_port()
        env = _supervisor_env()
        sup = ElasticSupervisor(
            [
                "-c", "singleGPU",
                "--checkpoint-dir", ckpt_dir,
                "--image-size", "48", "32",
                "--model-widths", "8", "16",
                "--buckets", "1", "2",
                "--replicas", "1",
                "--slo-ms", "25",
                "--host-cache-mb", "0",
                "--autoscale-interval", "0",
                "--port", str(base_port),
            ],
            nprocs=2,
            workload="serve",
            router_port=router_port,
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
        statuses = []
        stop_traffic = threading.Event()

        def traffic():
            while not stop_traffic.is_set():
                try:
                    conn = http.client.HTTPConnection(
                        "127.0.0.1", router_port, timeout=120.0)
                    conn.request("POST", "/predict", body=body)
                    resp = conn.getresponse()
                    resp.read()
                    statuses.append(resp.status)
                    conn.close()
                except OSError:
                    statuses.append(-1)  # router itself unreachable
                time.sleep(0.05)

        try:
            # both workers READY on their own ports first (the router
            # assumes workers healthy until proven otherwise, so its
            # /stats lies until the fleet has actually come up)
            deadline = time.monotonic() + 600
            for worker_port in (base_port, base_port + 1):
                while time.monotonic() < deadline:
                    status, _ = _http_json(worker_port, "/healthz")
                    if status == 200:
                        break
                    time.sleep(0.5)
                else:
                    pytest.fail(
                        f"worker on :{worker_port} never became ready")

            traffic_thread = threading.Thread(target=traffic, daemon=True)
            traffic_thread.start()
            deadline = time.monotonic() + 60
            while not statuses and time.monotonic() < deadline:
                time.sleep(0.1)
            assert statuses, "no traffic flowed before the kill"

            pid = sup._procs[0].pid
            os.kill(pid, signal.SIGKILL)  # mid-traffic

            # the fleet heals: the dead worker relaunched IN PLACE and
            # readmitted while its sibling kept serving through the gap
            deadline = time.monotonic() + 600
            healed = False
            while time.monotonic() < deadline and not healed:
                status, payload = _http_json(router_port, "/stats")
                healed = (
                    sup.restarts >= 1
                    and status == 200
                    and payload["healthy_workers"] == 2
                )
                time.sleep(0.5)
            assert healed, "fleet never healed back to 2 workers"
            assert sup._procs[0].pid != pid  # a NEW process serves
            time.sleep(1.0)  # a little post-heal traffic
            stop_traffic.set()
            traffic_thread.join(120)

            # the acceptance number: ZERO client-visible failures
            assert statuses
            assert set(statuses) == {200}, (
                f"client saw non-200s: {sorted(set(statuses))} "
                f"over {len(statuses)} requests"
            )
            status, payload = _http_json(router_port, "/stats")
            assert status == 200
            assert payload["retries"] >= 1  # the gap WAS retried away
        finally:
            stop_traffic.set()
            sup.request_stop()
            t.join(120)
        assert rc == [0]
        report = json.load(open(sup.report_path))
        assert report["final"] == "stopped"
        # the wave ledger: one failed entry naming the SIGKILLed rank,
        # and the run still ends clean
        assert any(
            not attempt["ok"] and any(
                "rank 0" in line and "dead" in line
                for line in attempt["failures"]
            )
            for attempt in report["attempts"]
        )
        assert report["attempts"][-1]["ok"] is True


# ---------------------------------------------------------------------------
# THE HA chaos drill: SIGKILL the ACTIVE ROUTER (a real OS process)
# mid-traffic; the standby takes over, zero client-visible failures
# ---------------------------------------------------------------------------


class TestRouterHAChaosDrill:
    def test_sigkill_active_router_zero_client_failures(
            self, stub_fleet, tmp_path):
        """The front door's own acceptance drill (ISSUE 18): the active
        router runs as a REAL process (``python -m ...serve.router``)
        whose SIGKILL is a real death; the in-process standby probes it
        every 0.2 s, pulls its state while it lives, and takes over the
        moment it misses a probe. The two-address client never sees a
        failure. Both routers' /admin/state snapshots land in tmp_path
        (CI uploads them on failure)."""
        import signal
        import subprocess
        import sys

        port_a, _ = stub_fleet(default=("ok", 0.02))
        port_b, _ = stub_fleet()
        front_a = _free_port()
        standby = _router(
            [port_a, port_b], role="standby",
            peer=("127.0.0.1", front_a), probe_interval_s=0.2)
        httpd_s, front_s = _fronted(standby)
        log = open(tmp_path / "router_active.log", "wb")
        proc = subprocess.Popen(
            [sys.executable, "-m", "distributedpytorch_tpu.serve.router",
             "--port", str(front_a),
             "--workers", f"127.0.0.1:{port_a},127.0.0.1:{port_b}",
             "--role", "active", "--peer", f"127.0.0.1:{front_s}",
             "--probe-interval", "0.2",
             "--backoff-base", "0.01"],
            env=_supervisor_env(), stdout=log, stderr=subprocess.STDOUT)
        statuses = []
        stop = threading.Event()

        def traffic():
            while not stop.is_set():
                try:
                    statuses.append(
                        _failover_post([front_a, front_s], b"x")[0])
                except OSError:
                    statuses.append(-1)
                time.sleep(0.01)

        t = threading.Thread(target=traffic, daemon=True)
        try:
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                status, _ = _http_json(front_a, "/healthz", timeout=2.0)
                if status == 200:
                    break
                time.sleep(0.2)
            else:
                pytest.fail("active router process never became ready")
            standby.start()     # live probe loop: sync now, takeover later
            t.start()
            deadline = time.monotonic() + 30
            while len(statuses) < 10 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert len(statuses) >= 10, "no traffic flowed pre-kill"
            # the state-reconstruction evidence, captured BEFORE the
            # kill: what the standby had to rebuild the front door from
            status, active_state = _http_json(
                front_a, "/admin/state", timeout=5.0)
            assert status == 200
            with open(tmp_path / "router_state_active.json", "w") as f:
                json.dump(active_state, f, indent=2)

            proc.send_signal(signal.SIGKILL)    # mid-traffic
            proc.wait()
            deadline = time.monotonic() + 30
            while (standby.role != "active"
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            # a little post-takeover traffic through the survivor
            n = len(statuses)
            deadline = time.monotonic() + 30
            while len(statuses) < n + 10 and time.monotonic() < deadline:
                time.sleep(0.02)
            stop.set()
            t.join(60)
            with open(tmp_path / "router_state_standby.json", "w") as f:
                json.dump(standby.export_state(), f, indent=2)

            assert standby.role == "active"
            assert standby.takeovers == 1
            assert standby.ha_epoch >= 1
            assert standby.ha_syncs >= 1    # it synced while active lived
            assert statuses
            assert set(statuses) == {200}, (
                f"client saw failures: {sorted(set(statuses))} "
                f"over {len(statuses)} requests")
        finally:
            stop.set()
            if proc.poll() is None:
                proc.kill()
                proc.wait()
            log.close()
            standby.stop()
            httpd_s.shutdown()


# ---------------------------------------------------------------------------
# fleet elasticity: the diurnal trace re-pinned at FLEET level — whole
# serve workers spawn and retire under the supervisor
# ---------------------------------------------------------------------------


class TestFleetElasticDrill:
    def test_diurnal_swell_spawns_and_ebb_retires_a_whole_worker(
            self, checkpoint, tmp_path):
        """The fleet-level diurnal drill (ISSUE 18): ONE real serve
        worker under the supervisor behind an HA router pair. The
        320 rps swell makes the FleetScaler spawn a second WORKER
        PROCESS (riding the relaunch machinery + the fleet-shared AOT
        store: zero recompiles), the 40 rps ebb drains and retires it
        via the routers. Exactly one up, one down, each decision citing
        its plan-serve grid point."""
        from distributedpytorch_tpu.dist.elastic import ElasticSupervisor

        _, plan = _diurnal_plan()
        ckpt_dir, image_path = checkpoint
        with open(image_path, "rb") as f:
            body = f.read()
        base_port = _free_port(span=2)  # the swell spawns worker 1
        router_port = _free_port()
        standby_port = _free_port()
        sup = ElasticSupervisor(
            [
                "-c", "singleGPU",
                "--checkpoint-dir", ckpt_dir,
                "--image-size", "48", "32",
                "--model-widths", "8", "16",
                "--buckets", "1", "2",
                "--replicas", "1",
                "--slo-ms", "25",
                "--host-cache-mb", "0",
                "--autoscale-interval", "0",
                "--port", str(base_port),
            ],
            nprocs=1,
            workload="serve",
            router_port=router_port,
            router_standby_port=standby_port,
            fleet_plan=plan,
            fleet_min_workers=1,
            fleet_max_workers=2,
            fleet_interval_s=0.0,   # windows are stepped BY HAND below
            cpu_devices=1,
            max_restarts=2,
            heartbeat_timeout_s=60.0,
            heartbeat_interval_s=0.2,
            poll_interval_s=0.1,
            restart_backoff_s=0.1,
            teardown_grace_s=10.0,
            spawn_timeout_s=600.0,
            run_dir=str(tmp_path / "run"),
            env=_supervisor_env(),
        )
        rc = []
        t = threading.Thread(target=lambda: rc.append(sup.run()),
                             daemon=True)
        t.start()
        try:
            deadline = time.monotonic() + 600
            while time.monotonic() < deadline:
                status, _ = _http_json(base_port, "/healthz")
                if status == 200 and sup.fleet_scaler is not None:
                    break
                time.sleep(0.5)
            else:
                pytest.fail("worker 0 / fleet scaler never became ready")
            scaler = sup.fleet_scaler
            assert sup.active_serve_ranks() == [0]

            # the swell: 320 rps windows — hysteresis holds for
            # up_windows - 1, then ONE spawn
            for _ in range(scaler.up_windows):
                scaler.step(observed_rate_rps=320.0)
            assert scaler.spawns == 1
            assert sup.active_serve_ranks() == [0, 1]
            # the spawned worker cold-started WARM off the fleet-shared
            # AOT store: zero compiles, every executable a cache hit
            status, stats = _http_json(base_port + 1, "/stats",
                                       timeout=10.0)
            assert status == 200
            aot = stats["aot_cache"]
            assert aot["enabled"] is True
            assert aot["compiles"] == 0
            assert aot["hit"] >= 1
            # BOTH routers admitted the newcomer
            status, rstats = _http_json(router_port, "/stats")
            assert status == 200 and len(rstats["workers"]) == 2
            status, sstats = _http_json(standby_port, "/stats")
            assert status == 200 and len(sstats["workers"]) == 2
            # traffic lands through the front door at peak
            conn = http.client.HTTPConnection(
                "127.0.0.1", router_port, timeout=120.0)
            conn.request("POST", "/predict", body=body)
            resp = conn.getresponse()
            resp.read()
            assert resp.status == 200
            conn.close()

            # the ebb: 40 rps windows — the down streak AND the
            # cooldown must both run out before the ONE retire
            for _ in range(max(scaler.down_windows,
                               scaler.cooldown_windows)):
                scaler.step(observed_rate_rps=40.0)
            assert scaler.retires == 1
            assert sup.active_serve_ranks() == [0]
            # a further quiet window holds — no flapping
            scaler.step(observed_rate_rps=40.0)
            assert scaler.spawns == 1 and scaler.retires == 1

            # every actuation cites the plan-serve grid point it ran
            acted = [d for d in scaler.decisions
                     if d["direction"] != "hold"]
            assert [d["direction"] for d in acted] == ["up", "down"]
            up, down = acted
            assert up["plan_point"] == \
                "poisson:320rps/b1x2x4x8/slo50/r2/eager/capauto"
            assert up["plan_replicas"] == 2
            assert up["achieved"] == 2
            assert down["plan_point"] == \
                "poisson:40rps/b1x2x4x8/slo50/r1/eager/capauto"
            assert down["plan_replicas"] == 1
            assert down["achieved"] == 1

            # the survivor still serves after the retire
            conn = http.client.HTTPConnection(
                "127.0.0.1", router_port, timeout=120.0)
            conn.request("POST", "/predict", body=body)
            resp = conn.getresponse()
            resp.read()
            assert resp.status == 200
            conn.close()
        finally:
            sup.request_stop()
            t.join(120)
        assert rc == [0]
        report = json.load(open(sup.report_path))
        assert report["final"] == "stopped"
