"""tools/bench_multi.py: resume/poison-marking semantics and the
single-process config-sequencing loop, with bench.run and the probe
mocked (no TPU, no subprocesses).

The contract under test is what protects chip windows: a config whose
previous attempt wedged a window is never retried, a config that failed
only because the runtime was already dead IS retried, and a mid-config
process death is durably attributed to the config that caused it.
"""

import json
import os
import sys
import types

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

import bench_multi


def _lines(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _write(path, objs):
    with open(path, "w") as f:
        for o in objs:
            f.write(json.dumps(o) + "\n")


class TestLoadState:
    def test_empty_or_missing_artifact(self, tmp_path):
        assert bench_multi.load_state(str(tmp_path / "none.jsonl")) == {}

    def test_statuses(self, tmp_path):
        p = tmp_path / "a.jsonl"
        _write(p, [
            {"config": "pixel", "value": 19.6},
            {"config": "b8",
             "error": "watchdog: no result after 1200s (compile wedged)"},
            {"config": "milesial_s2d",
             "error": "runtime_error: RuntimeError: UNAVAILABLE"},
            {"config": "milesial_pixel",
             "error": "config_error: ValueError: bad arch"},
        ])
        state = bench_multi.load_state(str(p))
        assert state == {
            "pixel": "ok",
            "b8": "poison",
            "milesial_s2d": "innocent",
            "milesial_pixel": "permanent",
        }

    def test_attempting_without_result_is_poisoned_durably(self, tmp_path):
        """A process killed mid-compile leaves only the marker; load_state
        must both report poison AND write the attribution line so the
        next read needs no marker inference."""
        p = tmp_path / "a.jsonl"
        _write(p, [
            {"config": "pixel", "value": 19.6},
            {"event": "attempting", "config": "pallas_loss"},
        ])
        state = bench_multi.load_state(str(p))
        assert state["pallas_loss"] == "poison"
        last = _lines(p)[-1]
        assert last["config"] == "pallas_loss"
        assert last["error"].startswith("wedged_previous_attempt")
        # durable: a second parse sees the written line, not the marker
        assert bench_multi.load_state(str(p))["pallas_loss"] == "poison"

    def test_attempting_then_result_is_not_poisoned(self, tmp_path):
        p = tmp_path / "a.jsonl"
        _write(p, [
            {"event": "attempting", "config": "pixel"},
            {"config": "pixel", "value": 19.6},
        ])
        assert bench_multi.load_state(str(p))["pixel"] == "ok"


class TestMainLoop:
    def _fake_bench(self, results):
        """A stand-in for the bench module: run() pops from `results`
        (dict → return, Exception → raise)."""
        mod = types.SimpleNamespace(BATCH=4, H=640, W=960, ARCH="unet")

        def run():
            r = results.pop(0)
            if isinstance(r, Exception):
                raise r
            return r

        mod.run = run
        return mod

    def _patch(self, monkeypatch, tmp_path, fake_mod, configs):
        monkeypatch.setattr(bench_multi, "CONFIGS", configs)
        monkeypatch.setattr(
            bench_multi, "_CONFIG_ENV_KEYS",
            sorted({k for _, env, _ in configs for k in env}))
        # main() imports bench lazily; plant the fake in sys.modules
        monkeypatch.setitem(sys.modules, "bench", fake_mod)

    def test_all_configs_measured(self, tmp_path, monkeypatch):
        out = str(tmp_path / "m.jsonl")
        configs = [("a", {"BENCH_S2D_LEVELS": "0"}, 60.0),
                   ("b", {"BENCH_BATCH": "8"}, 60.0)]
        mod = self._fake_bench([{"value": 1.0}, {"value": 2.0}])
        self._patch(monkeypatch, tmp_path, mod, configs)
        rc = bench_multi.main(["--out", out])
        assert rc == 0
        state = bench_multi.load_state(out)
        assert state == {"a": "ok", "b": "ok"}
        # config b's env must not have leaked config a's lever
        assert os.environ.get("BENCH_S2D_LEVELS") is None

    def test_resume_skips_ok_and_poison_retries_innocent(
            self, tmp_path, monkeypatch):
        out = str(tmp_path / "m.jsonl")
        _write(out, [
            {"config": "a", "value": 1.0},
            {"config": "b", "error": "watchdog: no result after 60s"},
            {"config": "c", "error": "runtime_error: RuntimeError: dead"},
        ])
        configs = [("a", {}, 60.0), ("b", {}, 60.0), ("c", {}, 60.0)]
        mod = self._fake_bench([{"value": 3.0}])  # only c should run
        self._patch(monkeypatch, tmp_path, mod, configs)
        rc = bench_multi.main(["--out", out])
        assert rc == 0
        assert bench_multi.load_state(out) == {
            "a": "ok", "b": "poison", "c": "ok"}

    def test_runtime_error_is_that_configs_error(
            self, tmp_path, monkeypatch):
        """An exception in a config — XlaRuntimeError included — is that
        config's error: no probe child is asked for a second opinion
        (this process holds the chip), the config is marked permanent
        and the sequence CONTINUES."""
        out = str(tmp_path / "m.jsonl")
        configs = [("a", {}, 60.0), ("b", {}, 60.0)]
        mod = self._fake_bench(
            [RuntimeError("INVALID_ARGUMENT: bad lowering"),
             {"value": 2.0}])
        self._patch(monkeypatch, tmp_path, mod, configs)
        rc = bench_multi.main(["--out", out])
        assert rc == 0
        assert bench_multi.load_state(out) == {
            "a": "permanent", "b": "ok"}

    def test_deterministic_failure_continues(self, tmp_path, monkeypatch):
        out = str(tmp_path / "m.jsonl")
        configs = [("a", {}, 60.0), ("b", {}, 60.0)]
        mod = self._fake_bench([ValueError("bad"), {"value": 2.0}])
        self._patch(monkeypatch, tmp_path, mod, configs)
        rc = bench_multi.main(["--out", out])
        assert rc == 0  # both terminally resolved (permanent + ok)
        assert bench_multi.load_state(out) == {
            "a": "permanent", "b": "ok"}

    def test_nothing_todo(self, tmp_path, monkeypatch):
        out = str(tmp_path / "m.jsonl")
        _write(out, [{"config": "a", "value": 1.0}])
        configs = [("a", {}, 60.0)]
        mod = self._fake_bench([])
        self._patch(monkeypatch, tmp_path, mod, configs)
        assert bench_multi.main(["--out", out]) == 0

    def test_compile_only_probe_config(self):
        """The 30 s wgrad_pallas compile-only probe (VERDICT r05 next-8)
        sits AHEAD of the full taps legs and carries the compile-only
        lever, so a Mosaic rejection is learned before a 2700 s budget
        is committed."""
        names = [n for n, _, _ in bench_multi.CONFIGS]
        probe_i = names.index("wgrad_pallas_probe")
        assert probe_i < names.index("wgrad_taps")
        assert probe_i < names.index("wgrad_taps_pallas")
        _, env, budget = bench_multi.CONFIGS[probe_i]
        assert budget == 30.0
        assert env["BENCH_COMPILE_ONLY"] == "1"
        assert env["DPT_WGRAD_BACKEND"] == "pallas"
        assert "BENCH_COMPILE_ONLY" in bench_multi._CONFIG_ENV_KEYS

    def test_run_one_sets_module_config(self, monkeypatch):
        """_run_one must re-derive bench's module globals per config —
        they are frozen from env at bench import and would otherwise
        mislabel every non-default config's metric series."""
        captured = {}
        mod = types.SimpleNamespace(BATCH=4, H=640, W=960, ARCH="unet")

        def run():
            captured.update(BATCH=mod.BATCH, ARCH=mod.ARCH,
                            taps=os.environ.get("BENCH_WGRAD_TAPS"))
            return {"value": 1.0}

        mod.run = run
        monkeypatch.delenv("BENCH_WGRAD_TAPS", raising=False)
        bench_multi._run_one(
            mod, "x", {"BENCH_BATCH": "8", "BENCH_ARCH": "milesial",
                       "BENCH_WGRAD_TAPS": "1"}, 60.0)
        assert captured == {"BATCH": 8, "ARCH": "milesial", "taps": "1"}
        for k in ("BENCH_WGRAD_TAPS", "BENCH_ARCH", "BENCH_BATCH"):
            os.environ.pop(k, None)

    def test_pipeline_sweep_config_dispatches_in_process(self, monkeypatch):
        """The 300 s 1f1b-vs-gpipe sweep config routes _run_one to
        tools/bench_pipeline.schedule_sweep (with the config's own budget)
        instead of bench.run() — the next chip window measures the
        schedule A/B without a separate launcher."""
        names = [n for n, _, _ in bench_multi.CONFIGS]
        _, env, budget = bench_multi.CONFIGS[names.index("pipeline_sched_sweep")]
        assert budget == 300.0
        assert env == {"BENCH_PIPELINE_SWEEP": "1"}
        assert "BENCH_PIPELINE_SWEEP" in bench_multi._CONFIG_ENV_KEYS

        import tools.bench_pipeline as bp

        called = {}

        def fake_sweep(budget_s=0.0):
            called["budget_s"] = budget_s
            return {"kind": "pipeline_schedule_sweep"}

        monkeypatch.setattr(bp, "schedule_sweep", fake_sweep)
        mod = types.SimpleNamespace()  # bench module must never be touched
        out = bench_multi._run_one(mod, "pipeline_sched_sweep", env, 300.0)
        assert out == {"kind": "pipeline_schedule_sweep"}
        assert called["budget_s"] == 300.0
        assert "BENCH_PIPELINE_SWEEP" not in os.environ  # snapshot restored


class TestStaticPreflight:
    """The chip-window preflight (ISSUE 5): a config whose step fails
    static checks is poison-marked with a ``static_check_failed``
    provenance line BEFORE any budget is spent — no attempting marker,
    no watchdog, no bench run; analyzer infra failures never block."""

    def test_static_check_failed_is_poison_in_load_state(self, tmp_path):
        p = tmp_path / "a.jsonl"
        _write(p, [
            {"config": "pipeline_sched_sweep",
             "error": "static_check_failed: [ppermute-deadlock] "
                      "MP/1f1b train step: tick-program deadlock"},
        ])
        assert bench_multi.load_state(str(p)) == {
            "pipeline_sched_sweep": "poison"}

    def test_failing_preflight_poisons_without_spending_budget(
            self, tmp_path, monkeypatch):
        out = str(tmp_path / "m.jsonl")
        configs = [("sweep", {"BENCH_PIPELINE_SWEEP": "1"}, 300.0),
                   ("a", {}, 60.0)]
        mod = TestMainLoop._fake_bench(None, [{"value": 1.0}])
        TestMainLoop._patch(None, monkeypatch, tmp_path, mod, configs)
        finding = ("[ppermute-deadlock] MP/1f1b train step: "
                   "tick-program deadlock: flipped edge")
        calls = []

        def fake_analyze(strategies, schedules, timeout):
            calls.append((tuple(strategies), tuple(schedules)))
            return 1, [finding]

        monkeypatch.setattr(bench_multi, "_run_analyze", fake_analyze)
        # the sweep must never be dispatched
        import tools.bench_pipeline as bp

        def no_sweep(budget_s=0.0):
            raise AssertionError("poisoned config spent chip budget")

        monkeypatch.setattr(bp, "schedule_sweep", no_sweep)
        rc = bench_multi.main(["--out", out])
        assert rc == 0  # sweep poisoned (terminal) + a measured
        assert calls == [(("MP",), ("gpipe", "1f1b"))]
        state = bench_multi.load_state(out)
        assert state == {"sweep": "poison", "a": "ok"}
        lines = _lines(out)
        poison = [d for d in lines
                  if d.get("config") == "sweep" and "error" in d]
        assert poison[0]["error"].startswith("static_check_failed")
        assert poison[0]["findings"] == [finding]
        # no budget spent: the config never even reached "attempting"
        assert not any(
            d.get("event") == "attempting" and d.get("config") == "sweep"
            for d in lines
        )

    def test_clean_preflight_lets_the_sweep_run(self, tmp_path, monkeypatch):
        out = str(tmp_path / "m.jsonl")
        configs = [("sweep", {"BENCH_PIPELINE_SWEEP": "1"}, 300.0)]
        mod = TestMainLoop._fake_bench(None, [])
        TestMainLoop._patch(None, monkeypatch, tmp_path, mod, configs)
        monkeypatch.setattr(
            bench_multi, "_run_analyze", lambda *a: (0, []))
        import tools.bench_pipeline as bp

        monkeypatch.setattr(
            bp, "schedule_sweep",
            lambda budget_s=0.0: {"kind": "pipeline_schedule_sweep"})
        assert bench_multi.main(["--out", out]) == 0
        assert bench_multi.load_state(out) == {"sweep": "ok"}

    def test_analyzer_infra_failure_never_blocks(self, tmp_path, monkeypatch):
        out = str(tmp_path / "m.jsonl")
        configs = [("sweep", {"BENCH_PIPELINE_SWEEP": "1"}, 300.0)]
        mod = TestMainLoop._fake_bench(None, [])
        TestMainLoop._patch(None, monkeypatch, tmp_path, mod, configs)
        monkeypatch.setattr(
            bench_multi, "_run_analyze",
            lambda *a: (2, ["analyzer did not run: TimeoutExpired"]))
        import tools.bench_pipeline as bp

        monkeypatch.setattr(
            bp, "schedule_sweep",
            lambda budget_s=0.0: {"kind": "pipeline_schedule_sweep"})
        assert bench_multi.main(["--out", out]) == 0
        assert bench_multi.load_state(out) == {"sweep": "ok"}

    def test_non_distributed_configs_skip_the_preflight(
            self, tmp_path, monkeypatch):
        out = str(tmp_path / "m.jsonl")
        configs = [("a", {"BENCH_BATCH": "8"}, 60.0)]
        mod = TestMainLoop._fake_bench(None, [{"value": 1.0}])
        TestMainLoop._patch(None, monkeypatch, tmp_path, mod, configs)

        def never(*a):
            raise AssertionError("preflight ran for a collective-free "
                                 "single-device config")

        monkeypatch.setattr(bench_multi, "_run_analyze", never)
        assert bench_multi.main(["--out", out]) == 0
        assert bench_multi.load_state(out) == {"a": "ok"}


class TestServeBenchConfig:
    """The serving-tier load generator as a bench_multi config (ISSUE 6):
    registered, dispatched to tools/bench_serve.py in-process, and —
    being collective-free single-replica data parallelism — SKIPPED by
    the static preflight rather than blocked on a vacuous check."""

    def test_registered_with_budget(self):
        rows = [(n, e, b) for n, e, b in bench_multi.CONFIGS
                if e.get("BENCH_SERVE") == "1"]
        assert len(rows) == 1
        name, _env, budget = rows[0]
        assert name == "serve_bench"
        assert budget >= 300.0  # per-bucket×replica AOT compiles + legs

    def test_preflight_treats_serve_as_non_collective(self):
        assert bench_multi._preflight_combos({"BENCH_SERVE": "1"}) == ()

    def test_preflight_skips_without_invoking_analyzer(
            self, tmp_path, monkeypatch):
        out = str(tmp_path / "m.jsonl")
        configs = [("serve_bench", {"BENCH_SERVE": "1"}, 600.0)]
        mod = TestMainLoop._fake_bench(None, [])
        TestMainLoop._patch(None, monkeypatch, tmp_path, mod, configs)

        def never(*a):
            raise AssertionError("preflight ran for the collective-free "
                                 "serve bench")

        monkeypatch.setattr(bench_multi, "_run_analyze", never)
        import tools.bench_serve as bench_serve

        calls = []

        def fake_run_bench(budget_s=0.0, **kwargs):
            calls.append(budget_s)
            return {"metric": "serve_bench", "value": 42.0, "levels": []}

        monkeypatch.setattr(bench_serve, "run_bench", fake_run_bench)
        assert bench_multi.main(["--out", out]) == 0
        assert calls == [600.0]  # dispatched in-process with its budget
        assert bench_multi.load_state(out) == {"serve_bench": "ok"}


class TestFlightArtifacts:
    """ISSUE 7: every leg's result row names its flight-recorder
    artifact path, and a poisoned or failed leg dumps the ring buffer
    at mark time — a dead leg ships its own post-mortem."""

    _fake_bench = TestMainLoop._fake_bench
    _patch = TestMainLoop._patch

    def test_result_rows_record_artifact_path(self, tmp_path, monkeypatch):
        out = str(tmp_path / "m.jsonl")
        configs = [("a", {}, 60.0)]
        mod = self._fake_bench([{"value": 1.0}])
        self._patch(monkeypatch, tmp_path, mod, configs)
        assert bench_multi.main(["--out", out]) == 0
        rows = [d for d in _lines(out) if d.get("config") == "a"
                and "error" not in d and d.get("event") is None]
        assert rows and rows[0]["flight_recorder"] == (
            bench_multi.flight_artifact_path(out, "a")
        )

    def test_config_error_dumps_and_references_artifact(
            self, tmp_path, monkeypatch):
        from distributedpytorch_tpu.obs import flight

        flight.get().clear()
        out = str(tmp_path / "m.jsonl")
        configs = [("a", {}, 60.0)]
        mod = self._fake_bench([ValueError("deterministically broken")])
        self._patch(monkeypatch, tmp_path, mod, configs)
        assert bench_multi.main(["--out", out]) == 0
        row = [d for d in _lines(out)
               if d.get("config") == "a" and "error" in d][0]
        assert row["error"].startswith("config_error")
        d = json.load(open(row["flight_recorder"]))
        assert d["reason"].startswith("config_error")

    def test_wedged_previous_attempt_line_references_artifact(
            self, tmp_path):
        out = str(tmp_path / "m.jsonl")
        _write(out, [{"event": "attempting", "config": "a"}])
        state = bench_multi.load_state(out)
        assert state == {"a": "poison"}
        line = [d for d in _lines(out) if d.get("error")][-1]
        assert line["flight_recorder"] == (
            bench_multi.flight_artifact_path(out, "a")
        )


class TestSupervisorRestarts:
    """Window reports carry the elastic supervisor's restart count, so a
    flapping chip window (job survived via relaunches) reads differently
    from a clean one."""

    def test_reads_elastic_report(self, tmp_path, monkeypatch):
        report = tmp_path / "report.json"
        report.write_text(json.dumps({"restarts": 3, "final": "ok"}))
        monkeypatch.setenv("DPT_ELASTIC_REPORT", str(report))
        assert bench_multi.supervisor_restarts() == 3

    def test_none_without_report(self, tmp_path, monkeypatch):
        monkeypatch.setenv(
            "DPT_ELASTIC_REPORT", str(tmp_path / "missing.json"))
        assert bench_multi.supervisor_restarts() is None

    def test_session_lines_record_restarts(self, tmp_path, monkeypatch):
        report = tmp_path / "report.json"
        report.write_text(json.dumps({"restarts": 2}))
        monkeypatch.setenv("DPT_ELASTIC_REPORT", str(report))
        out = str(tmp_path / "m.jsonl")
        configs = [("a", {"BENCH_S2D_LEVELS": "0"}, 60.0)]
        mod = TestMainLoop._fake_bench(None, [{"value": 1.0}])
        TestMainLoop._patch(None, monkeypatch, tmp_path, mod, configs)
        assert bench_multi.main(["--out", out]) == 0
        lines = [json.loads(x) for x in open(out) if x.strip()]
        start = [d for d in lines if d.get("event") == "session_start"]
        end = [d for d in lines if d.get("event") == "session_end"]
        assert start[0]["supervisor_restarts"] == 2
        assert end[0]["supervisor_restarts"] == 2 and end[0]["rc"] == 0

    def test_none_when_env_unset(self, monkeypatch):
        """No $DPT_ELASTIC_REPORT → None, never a guessed default path:
        a stale report from some past drill must not stamp bogus restart
        counts onto unrelated sessions."""
        monkeypatch.delenv("DPT_ELASTIC_REPORT", raising=False)
        assert bench_multi.supervisor_restarts() is None


class TestDtypeSweepConfig:
    """The precision-policy A/B as a bench_multi config (ISSUE 8):
    registered with a budget, dispatched to tools/bench_dtype.py
    in-process, and — single-device, collective-free — skipped by the
    static preflight like serve_bench, never blocked on a vacuous
    check."""

    def test_registered_with_budget(self):
        rows = [(n, e, b) for n, e, b in bench_multi.CONFIGS
                if e.get("BENCH_DTYPE_SWEEP") == "1"]
        assert len(rows) == 1
        name, _env, budget = rows[0]
        assert name == "dtype_sweep"
        assert budget >= 300.0  # 3 train-step + 2 forward compiles + steps

    def test_preflight_treats_dtype_sweep_as_non_collective(self):
        assert bench_multi._preflight_combos({"BENCH_DTYPE_SWEEP": "1"}) == ()

    def test_dispatched_in_process_with_budget(self, tmp_path, monkeypatch):
        out = str(tmp_path / "m.jsonl")
        configs = [("dtype_sweep", {"BENCH_DTYPE_SWEEP": "1"}, 900.0)]
        mod = TestMainLoop._fake_bench(None, [])
        TestMainLoop._patch(None, monkeypatch, tmp_path, mod, configs)

        def never(*a):
            raise AssertionError("preflight ran for the collective-free "
                                 "dtype sweep")

        monkeypatch.setattr(bench_multi, "_run_analyze", never)
        import tools.bench_dtype as bench_dtype

        calls = []

        def fake_sweep(budget_s=0.0, **kwargs):
            calls.append(budget_s)
            return {"kind": "dtype_sweep", "rows": []}

        monkeypatch.setattr(bench_dtype, "dtype_sweep", fake_sweep)
        assert bench_multi.main(["--out", out]) == 0
        assert calls == [900.0]
        assert bench_multi.load_state(out) == {"dtype_sweep": "ok"}


class TestPlanOrdering:
    """ISSUE 10: ``--plan`` orders legs by the auto-planner's predicted
    rank (planned winners first; unmodeled legs keep their hand-ordered
    safety position), stamps ``plan_rank``/``plan_cost_s`` into the
    provenance rows, and a missing or stale plan file degrades to the
    default ordering."""

    _fake_bench = TestMainLoop._fake_bench
    _patch = TestMainLoop._patch

    CONFIGS = [
        ("pixel", {"BENCH_S2D_LEVELS": "0"}, 60.0),
        ("b8", {"BENCH_BATCH": "8"}, 60.0),
    ]

    def _plan_file(self, tmp_path):
        from distributedpytorch_tpu.analysis.planner import PLAN_VERSION

        plan = {
            "kind": "dpt_plan", "version": PLAN_VERSION,
            "points": [
                # b8's point predicted fastest, pixel's slowest
                {"strategy": "singleGPU", "batch": 8, "s2d_levels": 2,
                 "remat": False, "dtype": "bf16", "feasible": True,
                 "rank": 0,
                 "key": "singleGPU/s2d2/remat-off/b8/bf16",
                 "predicted": {"cost_s": 0.01}},
                {"strategy": "singleGPU", "batch": 4, "s2d_levels": 0,
                 "remat": False, "dtype": "bf16", "feasible": True,
                 "rank": 4,
                 "key": "singleGPU/s2d0/remat-off/b4/bf16",
                 "predicted": {"cost_s": 0.05}},
            ],
        }
        path = tmp_path / "plan.json"
        path.write_text(json.dumps(plan))
        return str(path)

    def _ordered_bench(self, order):
        """A fake bench whose run() records which config's levers were
        active — the execution order probe."""
        mod = types.SimpleNamespace(BATCH=4, H=640, W=960, ARCH="unet")

        def run():
            order.append((mod.BATCH, os.environ.get("BENCH_S2D_LEVELS")))
            return {"value": float(len(order))}

        mod.run = run
        return mod

    def test_legs_reordered_and_rows_stamped(self, tmp_path, monkeypatch):
        out = str(tmp_path / "m.jsonl")
        order = []
        mod = self._ordered_bench(order)
        self._patch(monkeypatch, tmp_path, mod, self.CONFIGS)
        rc = bench_multi.main(
            ["--out", out, "--plan", self._plan_file(tmp_path)])
        assert rc == 0
        # b8 (rank 0) ran before pixel (rank 4) despite CONFIGS order
        assert order == [(8, None), (4, "0")]
        rows = {d["config"]: d for d in _lines(out)
                if d.get("config") and "error" not in d
                and d.get("event") is None}
        assert rows["b8"]["plan_rank"] == 0
        assert rows["b8"]["plan_cost_s"] == 0.01
        assert rows["b8"]["plan_point"] == "singleGPU/s2d2/remat-off/b8/bf16"
        assert rows["pixel"]["plan_rank"] == 4
        start = [d for d in _lines(out)
                 if d.get("event") == "session_start"][0]
        assert start["plan"]["legs"] == {"b8": 0, "pixel": 4}

    def test_unmodeled_legs_keep_tail_safety_order(
            self, tmp_path, monkeypatch):
        """A wedge-suspect leg the plan cannot model must NOT move
        earlier — prediction never overrides the compile-safety order."""
        configs = self.CONFIGS + [
            ("wgrad_taps", {"BENCH_WGRAD_TAPS": "1"}, 60.0)]
        out = str(tmp_path / "m.jsonl")
        order = []
        mod = self._ordered_bench(order)
        self._patch(monkeypatch, tmp_path, mod, configs)
        rc = bench_multi.main(
            ["--out", out, "--plan", self._plan_file(tmp_path)])
        assert rc == 0
        attempts = [d["config"] for d in _lines(out)
                    if d.get("event") == "attempting"]
        assert attempts == ["b8", "pixel", "wgrad_taps"]
        taps_row = [d for d in _lines(out)
                    if d.get("config") == "wgrad_taps"
                    and d.get("event") is None and "error" not in d][0]
        assert "plan_rank" not in taps_row

    def test_missing_plan_degrades_to_default_order(
            self, tmp_path, monkeypatch):
        out = str(tmp_path / "m.jsonl")
        order = []
        mod = self._ordered_bench(order)
        self._patch(monkeypatch, tmp_path, mod, self.CONFIGS)
        rc = bench_multi.main(
            ["--out", out, "--plan", str(tmp_path / "missing.json")])
        assert rc == 0
        assert order == [(4, "0"), (8, None)]  # CONFIGS order kept
        rows = [d for d in _lines(out) if d.get("config")]
        assert not any("plan_rank" in d for d in rows)

    def test_stale_plan_degrades_to_default_order(
            self, tmp_path, monkeypatch):
        from distributedpytorch_tpu.analysis.planner import PLAN_VERSION

        stale = tmp_path / "stale.json"
        stale.write_text(json.dumps({
            "kind": "dpt_plan", "version": PLAN_VERSION + 99,
            "points": [{"strategy": "singleGPU", "batch": 8,
                        "s2d_levels": 2, "remat": False,
                        "feasible": True, "rank": 0}],
        }))
        out = str(tmp_path / "m.jsonl")
        order = []
        mod = self._ordered_bench(order)
        self._patch(monkeypatch, tmp_path, mod, self.CONFIGS)
        rc = bench_multi.main(["--out", out, "--plan", str(stale)])
        assert rc == 0
        assert order == [(4, "0"), (8, None)]
        rows = [d for d in _lines(out) if d.get("config")]
        assert not any("plan_rank" in d for d in rows)

    def test_semantically_corrupt_plan_degrades_not_crashes(
            self, tmp_path, monkeypatch):
        """A plan that passes the schema check but carries garbage point
        fields (hand edit, torn write) must degrade to the default
        order — never kill the window driver before session_start."""
        from distributedpytorch_tpu.analysis.planner import PLAN_VERSION

        bad = tmp_path / "corrupt.json"
        bad.write_text(json.dumps({
            "kind": "dpt_plan", "version": PLAN_VERSION,
            "points": [
                {"strategy": "singleGPU", "batch": 8, "s2d_levels": 2,
                 "remat": False, "feasible": True,
                 "rank": {"oops": "not a number"}},
                {"strategy": "singleGPU", "batch": 4, "s2d_levels": 0,
                 "remat": False, "feasible": True, "rank": True},
            ],
        }))
        out = str(tmp_path / "m.jsonl")
        order = []
        mod = self._ordered_bench(order)
        self._patch(monkeypatch, tmp_path, mod, self.CONFIGS)
        rc = bench_multi.main(["--out", out, "--plan", str(bad)])
        assert rc == 0
        assert order == [(4, "0"), (8, None)]  # default order kept
        rows = [d for d in _lines(out) if d.get("config")]
        assert not any("plan_rank" in d for d in rows)

    def test_no_plan_flag_is_unchanged_behavior(self, tmp_path, monkeypatch):
        out = str(tmp_path / "m.jsonl")
        order = []
        mod = self._ordered_bench(order)
        self._patch(monkeypatch, tmp_path, mod, self.CONFIGS)
        assert bench_multi.main(["--out", out]) == 0
        assert order == [(4, "0"), (8, None)]
        start = [d for d in _lines(out)
                 if d.get("event") == "session_start"][0]
        assert start["plan"] is None


class TestDtypeSweepTool:
    """tools/bench_dtype.py itself on the CPU tier at tiny size: every
    policy cell runs, the memory claims hold (param bytes halved under
    bf16_params, int8 serve weights < 0.3x f32), budget exhaustion skips
    cleanly instead of overrunning."""

    def test_tiny_sweep_end_to_end(self):
        from tools.bench_dtype import dtype_sweep

        s = dtype_sweep(batch=4, hw=(16, 24), widths=(8,), steps=1)
        by = {r["policy"]: r for r in s["rows"]}
        assert set(by) == {"f32", "bf16", "bf16_params",
                           "serve_f32", "serve_int8"}
        for name in ("f32", "bf16", "bf16_params"):
            assert by[name].get("step_ms") is not None, by[name]
        assert s["bf16_params_param_bytes_ratio"] == 0.5
        assert s["int8_weight_bytes_ratio"] < 0.3

    def test_budget_exhausted_skips_cells(self):
        from tools.bench_dtype import dtype_sweep

        s = dtype_sweep(batch=4, hw=(16, 24), widths=(8,), steps=1,
                        budget_s=1e-9)
        skipped = [r for r in s["rows"] if r.get("skipped") == "budget"]
        # every cell — 3 policies + the 2 serve-forward labels — leaves
        # an explicit marker; none overran, none vanished silently
        assert len(skipped) == 5
