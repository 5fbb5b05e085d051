"""Elastic supervisor (dist/elastic.py).

Two tiers:

  * **fast** — the supervision logic (spawn/classify/teardown/relaunch/
    slot-drop) driven by STUB workers: tiny argv-compatible python
    scripts that write beat files by hand and fail on cue. No jax import
    in any child, so the whole restart state machine proves out in
    seconds inside tier-1.
  * **slow** (``-m slow``) — the real thing on a live CPU/gloo mesh:
    ``rank_kill`` SIGKILLs one rank mid-epoch, the supervisor detects it
    within the heartbeat window, relaunches from the newest intact
    checkpoint, and the resumed run's final loss matches an
    uninterrupted run (the acceptance criterion); a persistently dying
    slot shrinks the world N→M; ``rank_hang`` wedges a rank and the
    progress timeout catches it.
"""

import ast
import json
import os
import re
import sys
import textwrap

import pytest

from distributedpytorch_tpu.dist.elastic import (
    STATIC_CHECK_EXIT,
    ElasticSupervisor,
    _checkpoint_exists,
    _worker_arg,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Fast: argv plumbing
# ---------------------------------------------------------------------------


class TestWorkerArgPlumbing:
    def test_worker_arg_last_occurrence_and_eq_form(self):
        args = ["-t", "DDP", "--checkpoint-dir=/a", "--checkpoint-dir", "/b"]
        assert _worker_arg(args, ("-t", "--train-method"), "x") == "DDP"
        assert _worker_arg(args, ("--checkpoint-dir",), "x") == "/b"
        assert _worker_arg([], ("--missing",), "dflt") == "dflt"

    def test_checkpoint_exists_sees_retained_chain(self, tmp_path):
        assert not _checkpoint_exists(str(tmp_path), "DDP")
        open(tmp_path / "DDP.ckpt.2", "wb").close()  # only a chain slot
        assert _checkpoint_exists(str(tmp_path), "DDP")

    def test_chaos_armed_on_first_attempt_only(self, tmp_path):
        sup = ElasticSupervisor(
            ["-t", "DDP", "--checkpoint-dir", str(tmp_path)],
            nprocs=2,
            run_dir=str(tmp_path / "run"),
            chaos=("rank_kill@1:1:6",),
        )
        first = sup._worker_argv(0)
        assert ["--inject-fault", "rank_kill@1:1:6"] == first[
            first.index("--inject-fault"): first.index("--inject-fault") + 2
        ]
        assert "--inject-fault" not in sup._worker_argv(1)

    def test_resume_flag_appended_once_checkpoint_exists(self, tmp_path):
        sup = ElasticSupervisor(
            ["-t", "DDP", "--checkpoint-dir", str(tmp_path)],
            nprocs=2,
            run_dir=str(tmp_path / "run"),
        )
        assert "-c" not in sup._worker_argv(1)  # nothing on disk yet
        open(tmp_path / "DDP.ckpt", "wb").close()
        argv = sup._worker_argv(1)
        assert argv[-2:] == ["-c", "DDP"]
        assert "-c" not in sup._worker_argv(0)  # attempt 0 never resumes

    def test_worker_env_contract(self, tmp_path):
        sup = ElasticSupervisor(
            [], nprocs=2, run_dir=str(tmp_path), cpu_devices=2
        )
        env = sup._worker_env(rank=1, world=2, port=12345)
        assert env["RANK"] == "1" and env["WORLD_SIZE"] == "2"
        assert env["MASTER_PORT"] == "12345"
        assert env["JAX_PLATFORMS"] == "cpu"
        assert "--xla_force_host_platform_device_count=2" in env["XLA_FLAGS"]
        assert env["DPT_DIST_INIT_TIMEOUT_S"]

    def test_trace_timeline_armed_by_default_and_disableable(
        self, tmp_path
    ):
        # ISSUE 7: every elastic attempt arms per-rank step timelines so
        # a dead attempt leaves a mergeable Perfetto post-mortem
        sup = ElasticSupervisor(
            ["-t", "DDP"], nprocs=2, run_dir=str(tmp_path / "run"),
        )
        argv = sup._worker_argv(0)
        i = argv.index("--trace-timeline")
        assert argv[i + 1] == sup._timeline_base(0)
        assert "attempt0" in argv[i + 1]
        off = ElasticSupervisor(
            ["-t", "DDP"], nprocs=2, run_dir=str(tmp_path / "run"),
            trace=False,
        )
        assert "--trace-timeline" not in off._worker_argv(0)

    def test_worker_env_routes_flight_dumps_to_attempt_dir(self, tmp_path):
        sup = ElasticSupervisor(
            [], nprocs=2, run_dir=str(tmp_path / "run"), cpu_devices=2
        )
        env = sup._worker_env(rank=1, world=2, port=1, attempt=3)
        assert env["DPT_FLIGHT_DIR"] == os.path.join(
            sup.run_dir, "attempt3"
        )

    def test_merge_timelines_builds_rank_disambiguated_trace(
        self, tmp_path
    ):
        from distributedpytorch_tpu.utils.trace import StepTimeline

        sup = ElasticSupervisor(
            [], nprocs=2, run_dir=str(tmp_path / "run"),
        )
        sup.world_history = [2]  # one attempt happened
        base = sup._timeline_base(0)
        os.makedirs(os.path.dirname(base), exist_ok=True)
        for rank in (0, 1):
            path = base if rank == 0 else f"{base}.rank{rank}"
            tl = StepTimeline(path, rank=rank)
            tl.record("dispatch", 1.0, 1.5, step=rank)
            tl.flush()
        out = sup._merge_timelines()
        assert out == os.path.join(sup.run_dir, "timeline_merged.json")
        trace = json.load(open(out))
        events = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        assert {e["pid"] for e in events} == {0, 1}
        # and the report JSON references the merged artifact
        sup._write_report(final="ok")
        report = json.load(open(sup.report_path))
        assert report["merged_timeline"] == out

    def test_supervisor_module_is_jax_free(self):
        """The supervisor process must never initialize a backend (it
        would hold the chip its workers need): no jax import anywhere in
        elastic.py."""
        src = os.path.join(
            REPO, "distributedpytorch_tpu", "dist", "elastic.py"
        )
        tree = ast.parse(open(src).read())
        imported = {
            n.name if isinstance(node, ast.Import) else node.module
            for node in ast.walk(tree)
            for n in getattr(node, "names", [])
            if isinstance(node, (ast.Import, ast.ImportFrom))
        }
        assert not any("jax" in (m or "") for m in imported)


# ---------------------------------------------------------------------------
# Fast: the static launch preflight (ISSUE 5) — the supervisor refuses to
# spawn ranks whose step program fails static distributed-correctness
# checks, and analyzer infrastructure failures never block a launch
# ---------------------------------------------------------------------------


class TestWorkerArgParsing:
    def test_exact_checkpoint_flag_not_misread_as_checkpoint_dir(
        self, tmp_path
    ):
        # --checkpoint (load a .pth) is a DISTINCT exact trainer flag;
        # prefix-matching it into --checkpoint-dir would point the
        # relaunch's resume probe at <cwd>/model.pth and silently
        # restart training from scratch (review regression)
        sup = ElasticSupervisor(
            ["-t", "FSDP", "--checkpoint", "model.pth"],
            nprocs=2, run_dir=str(tmp_path / "run"),
        )
        assert sup.checkpoint_dir.endswith("checkpoints")

    def test_abbreviated_strategy_flag_resolves_method_tag(self, tmp_path):
        # the trainer's argparse accepts prefix spellings; the
        # supervisor's method_tag gates the static preflight, so a
        # fallback to singleGPU would silently skip the gate
        sup = ElasticSupervisor(
            ["--train-meth", "DDP_MP"],
            nprocs=2, run_dir=str(tmp_path / "run"),
        )
        assert sup.method_tag == "DDP_MP"

    def test_glued_short_strategy_flag_resolves_method_tag(self, tmp_path):
        # argparse's glued short form (-tMP) is equally valid worker
        # argv — missing it falls back to singleGPU, which silently
        # skips the preflight gate AND breaks relaunch resume (the
        # checkpoint probe would look for singleGPU.ckpt) (review
        # regression)
        sup = ElasticSupervisor(
            ["-tMP"], nprocs=2, run_dir=str(tmp_path / "run"),
        )
        assert sup.method_tag == "MP"


class TestStaticPreflight:
    def _sup(self, tmp_path, worker_args=("-t", "DDP_MP"), **kw):
        defaults = dict(nprocs=2, run_dir=str(tmp_path / "run"))
        defaults.update(kw)
        return ElasticSupervisor(list(worker_args), **defaults)

    def test_findings_refuse_launch_before_any_spawn(
        self, tmp_path, monkeypatch
    ):
        sup = self._sup(tmp_path)
        monkeypatch.setattr(
            ElasticSupervisor, "static_preflight",
            lambda self: ["[ppermute-deadlock] MP/1f1b train step: boom"],
        )

        def no_spawn(*a, **k):
            raise AssertionError("spawned a rank past a failed preflight")

        monkeypatch.setattr(ElasticSupervisor, "_spawn", no_spawn)
        assert sup.run() == STATIC_CHECK_EXIT
        report = json.load(open(sup.report_path))
        assert report["final"] == "static_check_failed"
        assert report["preflight_findings"] == [
            "[ppermute-deadlock] MP/1f1b train step: boom"
        ]
        assert report["attempts"] == []  # no budget, no world history

    def test_no_preflight_flag_skips_the_check(self, tmp_path, monkeypatch):
        sup = self._sup(tmp_path, preflight=False)

        def never(self):
            raise AssertionError("preflight ran despite preflight=False")

        monkeypatch.setattr(ElasticSupervisor, "static_preflight", never)
        # reaching _spawn proves the preflight gate was bypassed
        sentinel = RuntimeError("reached spawn")

        def spawn(*a, **k):
            raise sentinel

        monkeypatch.setattr(ElasticSupervisor, "_spawn", spawn)
        with pytest.raises(RuntimeError, match="reached spawn"):
            sup.run()

    def test_preflight_command_carries_strategy_and_schedule(
        self, tmp_path, monkeypatch
    ):
        import distributedpytorch_tpu.analysis.preflight as preflight_mod

        sup = self._sup(
            tmp_path,
            worker_args=["-t", "DDP_MP", "--pipeline-schedule", "1f1b"],
        )
        seen = {}

        class Done:
            returncode = 0
            stdout = ""
            stderr = ""

        def fake_run(cmd, env=None, **kw):
            seen["cmd"] = cmd
            seen["env"] = env
            return Done()

        monkeypatch.setattr(preflight_mod.subprocess, "run", fake_run)
        assert sup.static_preflight() == []
        cmd = seen["cmd"]
        assert cmd[-4:] == ["--strategies", "DDP_MP", "--schedules", "1f1b"]
        assert "analyze" in cmd
        # collective layer only: a package-wide lint nit must never
        # refuse an otherwise-sound launch (that's CI's gate)
        assert cmd[cmd.index("--layer") + 1] == "collectives"
        # provisioned: the analyzer child is held to the CPU
        assert seen["env"]["JAX_PLATFORMS"] == "cpu"
        assert seen["env"]["DPT_ANALYZE_PROVISIONED"] == "1"

    def test_preflight_carries_fingerprint_world(
        self, tmp_path, monkeypatch
    ):
        # the gloo-desync gate (ISSUE 10): the analyzer compares each
        # combo's ordered-collective fingerprint under every simulated
        # rank of THIS job's world size — a collective gated on a rank
        # the dual-rank re-trace never simulates refuses the launch here
        import distributedpytorch_tpu.analysis.preflight as preflight_mod

        sup = self._sup(
            tmp_path, nprocs=3,
            worker_args=["-t", "DDP_MP", "--pipeline-schedule", "1f1b"],
        )
        seen = {}

        class Done:
            returncode = 0
            stdout = ""
            stderr = ""

        def fake_run(cmd, env=None, **kw):
            seen["cmd"] = cmd
            return Done()

        monkeypatch.setattr(preflight_mod.subprocess, "run", fake_run)
        assert sup.static_preflight() == []
        cmd = seen["cmd"]
        assert cmd[cmd.index("--fingerprint-world") + 1] == "3"
        # the world-N fingerprint comparison subsumes the dual-rank
        # (0 vs 1) re-trace — the preflight must not pay both
        assert "--no-rank-check" in cmd
        # the strategy/schedule tail stays intact behind the new flags
        assert cmd[-4:] == ["--strategies", "DDP_MP", "--schedules", "1f1b"]

    def test_preflight_follows_abbreviated_schedule_flag(
        self, tmp_path, monkeypatch
    ):
        # the trainer's argparse accepts prefix spellings
        # (--train-meth DDP_MP --pipeline-sched 1f1b); the preflight
        # must validate the strategy × schedule the workers actually
        # run — falling back to singleGPU would skip the gate entirely,
        # falling back to gpipe would validate the wrong program
        # (review regressions)
        import distributedpytorch_tpu.analysis.preflight as preflight_mod

        sup = self._sup(
            tmp_path,
            worker_args=["--train-meth", "DDP_MP",
                         "--pipeline-sched", "1f1b"],
        )
        seen = {}

        class Done:
            returncode = 0
            stdout = ""
            stderr = ""

        def fake_run(cmd, env=None, **kw):
            seen["cmd"] = cmd
            return Done()

        monkeypatch.setattr(preflight_mod.subprocess, "run", fake_run)
        assert sup.static_preflight() == []
        cmd = seen["cmd"]
        assert cmd[-4:] == ["--strategies", "DDP_MP", "--schedules", "1f1b"]

    def test_findings_parsed_from_json_report(self, tmp_path, monkeypatch):
        import distributedpytorch_tpu.analysis.preflight as preflight_mod

        sup = self._sup(tmp_path)

        class Found:
            returncode = 1
            stdout = json.dumps({"findings": [
                {"rule": "comms-contract", "where": "DDP_MP/1f1b train step",
                 "message": "no psum over ['data', 'stage']"},
            ]})
            stderr = ""

        monkeypatch.setattr(
            preflight_mod.subprocess, "run", lambda *a, **k: Found())
        assert sup.static_preflight() == [
            "[comms-contract] DDP_MP/1f1b train step: "
            "no psum over ['data', 'stage']"
        ]

    def test_analyzer_infra_failure_never_blocks(self, tmp_path, monkeypatch):
        import distributedpytorch_tpu.analysis.preflight as preflight_mod

        sup = self._sup(tmp_path)

        class Infra:
            returncode = 2
            stdout = ""
            stderr = "analyze: infrastructure failure: boom"

        monkeypatch.setattr(
            preflight_mod.subprocess, "run", lambda *a, **k: Infra())
        assert sup.static_preflight() == []

        def timeout_run(*a, **k):
            raise preflight_mod.subprocess.TimeoutExpired(cmd="x", timeout=1)

        monkeypatch.setattr(preflight_mod.subprocess, "run", timeout_run)
        assert sup.static_preflight() == []

    def test_crashed_interpreter_rc1_is_infra_not_findings(
        self, tmp_path, monkeypatch
    ):
        # a Python-level crash (import error, traceback) also exits 1,
        # with no JSON report — that's an INFRA failure and must
        # proceed, not refuse the launch (review regression)
        import distributedpytorch_tpu.analysis.preflight as preflight_mod

        sup = self._sup(tmp_path)

        class Crashed:
            returncode = 1
            stdout = ""
            stderr = ("Traceback (most recent call last):\n"
                      "ModuleNotFoundError: No module named "
                      "'distributedpytorch_tpu'")

        monkeypatch.setattr(
            preflight_mod.subprocess, "run", lambda *a, **k: Crashed())
        assert sup.static_preflight() == []

    def test_malformed_report_shape_still_refuses_without_crashing(
        self, tmp_path, monkeypatch
    ):
        # rc 1 with a report that parses as JSON but not the expected
        # shape (version-skewed analyzer): the launch must still be
        # refused with the fallback line, never crash the supervisor
        import distributedpytorch_tpu.analysis.preflight as preflight_mod

        sup = self._sup(tmp_path)
        for bad_stdout in ("null", '{"findings": ["a bare string"]}'):
            class Skewed:
                returncode = 1
                stdout = bad_stdout
                stderr = ""

            monkeypatch.setattr(
                preflight_mod.subprocess, "run", lambda *a, **k: Skewed())
            assert sup.static_preflight() == [
                "analyzer reported findings but the JSON report was "
                "unreadable"
            ]

    def test_non_collective_strategy_skips_the_analyzer(
        self, tmp_path, monkeypatch
    ):
        # singleGPU runs no collectives — the analyzer has nothing to
        # verify, so the launch must not pay a provisioned subprocess.
        import distributedpytorch_tpu.analysis.preflight as preflight_mod

        def no_subprocess(*a, **k):
            raise AssertionError("analyzer subprocess ran for singleGPU")

        monkeypatch.setattr(preflight_mod.subprocess, "run", no_subprocess)
        sup = self._sup(tmp_path, worker_args=("-t", "singleGPU"))
        assert sup.static_preflight() == []


# ---------------------------------------------------------------------------
# Fast: the restart state machine, driven by stub workers
# ---------------------------------------------------------------------------

# A stub worker: beats by hand (no package import — keeps each child at
# python-startup cost), then follows a per-rank script written by the
# test. Argv-compatible with the flags the supervisor appends.
STUB = textwrap.dedent(
    """
    import json, os, sys, time

    def flag(name, default=None):
        argv = sys.argv
        return argv[argv.index(name) + 1] if name in argv else default

    hb_dir = flag("--heartbeat-dir")
    rank = int(os.environ["RANK"])
    attempt_marker = flag("--marker")

    def beat(epoch=0, step=0, status="ok"):
        os.makedirs(hb_dir, exist_ok=True)
        path = os.path.join(hb_dir, f"rank_{rank}.beat")
        with open(path + ".tmp", "w") as f:
            json.dump({"rank": rank, "pid": os.getpid(), "epoch": epoch,
                       "step": step, "time": time.time(),
                       "progress_time": time.time(), "status": status}, f)
        os.replace(path + ".tmp", path)

    beat()
    behavior = flag(f"--rank{rank}", "ok")
    if behavior == "fail-once":
        # fail on the first attempt, succeed after (marker file keyed)
        if not os.path.exists(attempt_marker):
            open(attempt_marker, "w").close()
            sys.exit(7)
    elif behavior == "fail-always":
        sys.exit(7)
    elif behavior == "wedge-once":
        # beat once, then stop beating (a frozen process) — first attempt
        if not os.path.exists(attempt_marker):
            open(attempt_marker, "w").close()
            time.sleep(600)
    elif behavior == "desync-once":
        # the agreed-teardown shape: mark the beat desynced, exit 0
        if not os.path.exists(attempt_marker):
            open(attempt_marker, "w").close()
            beat(status="desynced")
            sys.exit(0)
    # epoch stays 0: a healthy stub racing ahead in epochs would trip
    # the epoch-skew desync rule against a deliberately-wedged peer
    # before the beat-age hung rule this suite pins
    for i in range(3):
        beat(epoch=0, step=i * 2)
        time.sleep(0.05)
    sys.exit(0)
    """
)


def _stub_supervisor(tmp_path, nprocs, rank_behaviors, **kw):
    stub = tmp_path / "stub_worker.py"
    stub.write_text(STUB)
    # checkpoint dir pinned under tmp so a stray repo ./checkpoints can
    # never make the supervisor append -c (stubs ignore it either way)
    args = ["--checkpoint-dir", str(tmp_path / "ckpt"),
            "--marker", str(tmp_path / "attempt.marker")]
    for rank, behavior in rank_behaviors.items():
        args += [f"--rank{rank}", behavior]
    defaults = dict(
        worker_cmd=[sys.executable, str(stub)],
        nprocs=nprocs,
        max_restarts=3,
        heartbeat_timeout_s=2.0,
        heartbeat_interval_s=0.1,
        poll_interval_s=0.05,
        restart_backoff_s=0.05,
        teardown_grace_s=2.0,
        spawn_timeout_s=30.0,
        run_dir=str(tmp_path / "run"),
        # stub workers aren't training jobs — the static preflight is
        # exercised by TestStaticPreflight, not paid by every state
        # machine test (~8 s of analyzer subprocess each)
        preflight=False,
    )
    defaults.update(kw)
    return ElasticSupervisor(args, **defaults)


class TestSupervisorStateMachine:
    def test_clean_world_completes_without_restart(self, tmp_path):
        sup = _stub_supervisor(tmp_path, 2, {})
        assert sup.run() == 0
        assert sup.restarts == 0
        assert sup.world_history == [2]
        report = json.load(open(sup.report_path))
        assert report["final"] == "ok"
        assert report["attempts"][0]["ok"] is True
        # per-rank logs landed
        assert os.path.exists(sup._log_path(0, 0))
        assert os.path.exists(sup._log_path(0, 1))

    def test_dead_rank_detected_classified_and_relaunched(self, tmp_path):
        sup = _stub_supervisor(tmp_path, 2, {1: "fail-once"})
        assert sup.run() == 0
        assert sup.restarts == 1
        assert sup.world_history == [2, 2]
        report = json.load(open(sup.report_path))
        # the single-line per-rank summary, with the exit code attributed
        assert any(
            re.match(r"rank 1: dead at \d+:\d+ \(exit 7\)", line)
            for line in report["attempts"][0]["failures"]
        ), report["attempts"][0]["failures"]
        assert report["attempts"][1]["ok"] is True

    def test_hung_rank_detected_by_beat_age(self, tmp_path):
        sup = _stub_supervisor(tmp_path, 2, {0: "wedge-once"})
        assert sup.run() == 0
        assert sup.restarts == 1
        report = json.load(open(sup.report_path))
        assert any(
            line.startswith("rank 0: hung")
            for line in report["attempts"][0]["failures"]
        ), report["attempts"][0]["failures"]

    def test_clean_desync_exit_is_a_failure_not_a_success(self, tmp_path):
        """A desynced world tears itself down CLEANLY (every rank marks
        its beat via the step agreement, snapshots, exits 0): all-zero
        exit codes must NOT read as success — the job was truncated and
        must relaunch from the checkpoint."""
        sup = _stub_supervisor(tmp_path, 2, {1: "desync-once"})
        assert sup.run() == 0
        assert sup.restarts == 1
        report = json.load(open(sup.report_path))
        assert any(
            line.startswith("rank 1: desynced")
            for line in report["attempts"][0]["failures"]
        ), report["attempts"][0]["failures"]
        assert report["attempts"][1]["ok"] is True

    def test_restart_budget_exhausts_to_failure(self, tmp_path):
        sup = _stub_supervisor(
            tmp_path, 2, {1: "fail-always"}, max_restarts=1, min_ranks=2
        )
        assert sup.run() == 1
        assert sup.restarts == 1
        report = json.load(open(sup.report_path))
        assert report["final"] == "failed"
        assert len(report["attempts"]) == 2

    def test_persistently_dead_slot_shrinks_world(self, tmp_path):
        """Elastic world size: rank 1 dies every attempt; after
        rank_fail_limit consecutive failures the slot is dropped and the
        job relaunches on world=1, where (no rank 1 to die) it
        completes."""
        sup = _stub_supervisor(
            tmp_path, 2, {1: "fail-always"},
            rank_fail_limit=2, min_ranks=1, max_restarts=4,
        )
        assert sup.run() == 0
        assert sup.world_history == [2, 2, 1]
        assert sup.restarts == 2
        report = json.load(open(sup.report_path))
        assert report["attempts"][-1]["world"] == 1
        assert report["attempts"][-1]["ok"] is True

    def test_min_ranks_floor_is_respected(self, tmp_path):
        sup = _stub_supervisor(
            tmp_path, 2, {0: "fail-always", 1: "fail-always"},
            rank_fail_limit=1, min_ranks=2, max_restarts=2,
        )
        assert sup.run() == 1
        assert all(w == 2 for w in sup.world_history)


# ---------------------------------------------------------------------------
# Slow: the real elastic runtime on a live CPU/gloo mesh
# ---------------------------------------------------------------------------


def _train_args(tmp_path, method="DDP", epochs=2, extra=()):
    return [
        "-t", method,
        "-e", str(epochs),
        "-b", "4",
        "-v", "25",
        "--synthetic", "32",
        "--image-size", "48", "32",
        "--model-widths", "8", "16",
        "--num-workers", "0",
        "--checkpoint-dir", str(tmp_path / "checkpoints"),
        *extra,
    ]


def _real_supervisor(tmp_path, args, extra_env=None, **kw):
    cwd = tmp_path / "cwd"  # relative ./loss, ./logs land here
    cwd.mkdir(exist_ok=True)
    env = dict(os.environ)
    # workers run under a tmp cwd — the package must resolve from the
    # repo checkout even when not pip-installed
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra_env or {})
    # warm per-rank XLA caches: the supervisor keeps the suite's
    # $JAX_COMPILATION_CACHE_DIR and gives worker R its rank<R>/ beneath
    defaults = dict(
        nprocs=2,
        cpu_devices=1,
        max_restarts=2,
        heartbeat_timeout_s=60.0,
        heartbeat_interval_s=0.2,
        poll_interval_s=0.2,
        restart_backoff_s=0.2,
        teardown_grace_s=15.0,
        spawn_timeout_s=600.0,
        run_dir=str(tmp_path / "run"),
        cwd=str(cwd),
        env=env,
        # chaos drills measure detection/relaunch, not static analysis;
        # preflight behavior has its own tests (TestStaticPreflight)
        preflight=False,
    )
    defaults.update(kw)
    return ElasticSupervisor(args, **defaults)


def _final_result(sup):
    """Parse the trainer's closing "Done: {...}" dict from rank 0's log
    of the final attempt."""
    last_attempt = len(sup.attempts) - 1
    text = open(sup._log_path(last_attempt, 0)).read()
    m = re.findall(r"Done: (\{.*\})", text)
    assert m, f"no final result in rank 0 log:\n{text[-2000:]}"
    return ast.literal_eval(m[-1])


@pytest.mark.slow
def test_rank_kill_is_detected_and_job_resumes_equivalently(tmp_path):
    """THE elastic acceptance drill: SIGKILL rank 1 mid-epoch (epoch 1,
    after the epoch-0 checkpoint landed) via the rank_kill fault site.
    The supervisor must classify `rank 1: dead`, tear down the survivor,
    relaunch from the newest intact checkpoint, and the resumed run's
    final loss must match an uninterrupted run within the
    restart-equivalence tolerance (seeded data order: the redone epoch
    is the same epoch)."""
    base = _real_supervisor(
        tmp_path, _train_args(tmp_path / "base", method="DDP"),
        run_dir=str(tmp_path / "run_base"),
    )
    (tmp_path / "base").mkdir()
    assert base.run() == 0
    assert base.restarts == 0
    baseline = _final_result(base)

    chaos = _real_supervisor(
        tmp_path, _train_args(tmp_path / "chaos", method="DDP"),
        run_dir=str(tmp_path / "run_chaos"),
        chaos=("rank_kill@1:1:6",),
    )
    (tmp_path / "chaos").mkdir()
    assert chaos.run() == 0
    assert chaos.restarts == 1
    report = json.load(open(chaos.report_path))
    assert any(
        line.startswith("rank 1: dead") and "signal 9" in line
        for line in report["attempts"][0]["failures"]
    ), report["attempts"][0]["failures"]
    # relaunch resumed (the -c flag) rather than restarting from scratch
    resumed_log = open(chaos._log_path(1, 0)).read()
    assert "Resumed from" in resumed_log
    result = _final_result(chaos)
    assert result["val_loss"] == pytest.approx(
        baseline["val_loss"], rel=1e-6
    )
    assert result["steps"] == baseline["steps"]


@pytest.mark.slow
def test_rank_hang_is_detected_by_progress_timeout(tmp_path):
    """rank_hang wedges rank 1's step loop mid-epoch-1 (steady state —
    the first executed epoch is untimed, mirroring the watchdog): its
    beat file stays fresh (the beat thread survives) but step progress
    stops — the progress timeout must classify it hung, tear the world
    down, and the relaunched attempt resumes and completes."""
    sup = _real_supervisor(
        tmp_path, _train_args(tmp_path / "art", method="DDP", epochs=2),
        chaos=("rank_hang@1:1:4",),
        progress_timeout_s=45.0,
        extra_env={"DPT_FAULT_HANG_S": "600"},
    )
    (tmp_path / "art").mkdir()
    assert sup.run() == 0
    assert sup.restarts == 1
    report = json.load(open(sup.report_path))
    assert any(
        "hung" in line and "no step progress" in line
        for line in report["attempts"][0]["failures"]
    ), report["attempts"][0]["failures"]


@pytest.mark.slow
def test_lost_slot_shrinks_world_and_reshards(tmp_path):
    """Elastic world size end-to-end: rank 1 SIGKILLs itself at the
    first step of epoch 1 on EVERY attempt (a persistently dead slot —
    the fault is armed in the worker argv proper, not --chaos, so it
    re-arms in every relaunched process). After rank_fail_limit
    consecutive deaths the supervisor relaunches on world=1, where the
    FSDP job RESUMES the checkpoint its 2-process epoch 0 wrote — the
    mesh-resharding restore, driven by the supervisor itself — and
    completes on the 1-process mesh."""
    sup = _real_supervisor(
        tmp_path,
        _train_args(
            tmp_path / "art", method="FSDP", epochs=2,
            extra=("--inject-fault", "rank_kill@1:1:*:*"),
        ),
        run_dir=str(tmp_path / "run"),
        rank_fail_limit=2,
        max_restarts=3,
    )
    (tmp_path / "art").mkdir()
    assert sup.run() == 0
    assert sup.world_history == [2, 2, 1]
    report = json.load(open(sup.report_path))
    assert report["attempts"][-1]["world"] == 1
    assert report["attempts"][-1]["ok"] is True
    # the world-1 attempt resumed the 2-process checkpoint (reshard)
    final_log = open(sup._log_path(2, 0)).read()
    assert "Resumed from" in final_log
    assert "mesh-resharding restore" in final_log
    assert _final_result(sup)["steps"] > 0
