"""The content-addressed AOT executable store (utils/aotstore.py).

The acceptance lever: a second ``ServeEngine`` startup against a warm
store performs ZERO AOT compiles (a spy on the engine's only compile
site proves it) while serving masks bit-identical to the cold-compiled
engine's across every bucket shape. Around it, the full hit/miss/skew
matrix: key material (fingerprint / bucket shape / dtype / kernels /
device), faked-jaxlib runtime skew refusing loudly, corrupt entries as
miss-with-note + self-healing re-persist, torn writes never leaving an
entry, gc LRU order, the rollout path's zero-recompile stamp, and the
elastic supervisor handing one shared store to every serve rank and
relaunch attempt.

Everything runs on the 8-virtual-CPU test mesh with tmpdir stores —
``jax.experimental.serialize_executable`` round-trips on the CPU
backend, so the skew/integrity logic gets real serialized executables,
not stand-ins.
"""

import logging
import os
import shutil

import numpy as np
import pytest

from distributedpytorch_tpu.utils import aotstore
from distributedpytorch_tpu.utils.aotstore import (
    ENTRY_SUFFIX,
    AOTStore,
    entry_key,
)

SIZE_HW = (32, 48)
WIDTHS = (8, 16)
BUCKETS = (1, 2)
FP = "deadbeefcafe"  # a stable stand-in engine fingerprint


@pytest.fixture(scope="module")
def pieces():
    import jax

    from distributedpytorch_tpu.config import TrainConfig
    from distributedpytorch_tpu.models import create_model

    cfg = TrainConfig(model_widths=WIDTHS, compute_dtype="float32",
                      s2d_levels=0)
    model, init_fn = create_model(cfg)
    params, model_state = init_fn(jax.random.key(0), SIZE_HW)
    return model, params, model_state


def make_engine(pieces, store_dir, fingerprint=FP, **kw):
    from distributedpytorch_tpu.serve.engine import ServeEngine

    model, params, model_state = pieces
    return ServeEngine(
        model, params, model_state, input_hw=SIZE_HW,
        bucket_sizes=BUCKETS, replicas=1, host_cache_mb=0,
        aot_cache=str(store_dir), engine_fingerprint=fingerprint, **kw,
    )


@pytest.fixture(scope="module")
def warm(pieces, tmp_path_factory):
    """A store warmed by one cold engine build — the shared read-only
    baseline. Tests that would poison entries copy it first."""
    root = tmp_path_factory.mktemp("aot") / "store"
    engine = make_engine(pieces, root)
    return root, engine


def _copy_store(root, tmp_path):
    dst = tmp_path / "store_copy"
    shutil.copytree(root, dst)
    return dst


def _entries(root):
    return sorted(
        p for p in os.listdir(root) if p.endswith(ENTRY_SUFFIX)
    )


class TestEntryKey:
    def test_stable_and_distinct_across_key_material(self):
        base = dict(kernels="xla", mask_threshold=None, quantized=False,
                    stateful=False, device="TFRT_CPU_0")
        key0, meta0 = entry_key(FP, 2, (2, 32, 48, 3), "float32", **base)
        again, _ = entry_key(FP, 2, (2, 32, 48, 3), "float32", **base)
        assert key0 == again  # pure function of the identity
        variants = [
            entry_key("feedfacef00d", 2, (2, 32, 48, 3), "float32",
                      **base),
            entry_key(FP, 4, (4, 32, 48, 3), "float32", **base),
            entry_key(FP, 2, (2, 64, 48, 3), "float32", **base),
            entry_key(FP, 2, (2, 32, 48, 3), "bfloat16", **base),
            entry_key(FP, 2, (2, 32, 48, 3), "float32",
                      **{**base, "kernels": "pallas"}),
            entry_key(FP, 2, (2, 32, 48, 3), "float32",
                      **{**base, "mask_threshold": 0.5}),
            entry_key(FP, 2, (2, 32, 48, 3), "float32",
                      **{**base, "quantized": True}),
            entry_key(FP, 2, (2, 32, 48, 3), "float32",
                      **{**base, "device": "TFRT_CPU_1"}),
        ]
        keys = [key0] + [k for k, _ in variants]
        assert len(set(keys)) == len(keys)
        assert meta0["input_shape"] == [2, 32, 48, 3]


class TestLoadPinnedToItsDevice:
    """A store entry is a ONE-device executable. Loaded without
    ``execution_devices`` the installed jax spreads it over every device
    of the backend and it then refuses its one-shard arguments ("Expected
    args to execute_sharded_on_local_devices to have 8 shards") — on any
    host with more than one device, and for every replica that is not
    device 0. ``AOTStore.load`` pins the load to the replica's device."""

    def _engine(self, pieces, store_dir, replicas):
        from distributedpytorch_tpu.serve.engine import ServeEngine

        model, params, model_state = pieces
        return ServeEngine(
            model, params, model_state, input_hw=SIZE_HW,
            bucket_sizes=BUCKETS, replicas=replicas, host_cache_mb=0,
            aot_cache=str(store_dir), engine_fingerprint=FP,
        )

    def test_warm_start_of_a_replica_that_is_not_device_zero(
        self, pieces, tmp_path, devices
    ):
        """Three replicas cold, then warm: every replica — devices 1 and
        2 included — loads its buckets with zero compiles, runs them on
        ITS device, and answers bit-identically."""
        cold = self._engine(pieces, tmp_path / "store", replicas=3)
        assert cold.aot_compiles == 3 * len(BUCKETS)
        hot = self._engine(pieces, tmp_path / "store", replicas=3)
        assert hot.aot_compiles == 0
        assert hot.aot_cache_stats["hit"] == 3 * len(BUCKETS)
        rng = np.random.default_rng(11)
        for index in (1, 2):
            replica = hot.replicas[index]
            assert replica.device == devices[index]
            for n in BUCKETS:
                batch = rng.random((n, *SIZE_HW, 3)).astype(np.float32)
                out = hot.run(replica, hot.place(replica, batch))
                assert out.devices() == {devices[index]}
                np.testing.assert_array_equal(
                    np.asarray(out), cold.infer(batch, replica_index=index)
                )

    def test_load_runs_on_the_device_it_is_given(
        self, pieces, tmp_path, devices
    ):
        """The store API itself: an entry compiled for device 5 and
        loaded with ``device=devices[5]`` executes there; the same bytes
        are never usable through a device-0 default."""
        import jax

        engine = self._engine(pieces, tmp_path / "one", replicas=1)
        store = engine.aot_store
        dev = devices[5]
        replica = engine._build_replica(5, dev, engine.replicas[0].variables)
        key, meta = engine._entry_key(BUCKETS[0], dev)
        exe = store.load(key, meta, dev)
        assert exe is not None
        x = jax.device_put(
            np.zeros((BUCKETS[0], *SIZE_HW, 3), np.float32), replica.sharding
        )
        assert exe(replica.variables, x).devices() == {dev}


class TestColdThenWarm:
    def test_cold_build_persists_every_bucket(self, warm):
        root, engine = warm
        assert engine.aot_compiles == len(BUCKETS)
        stats = engine.aot_cache_stats
        assert stats["enabled"] and stats["dir"] == str(root)
        assert stats["miss"] == len(BUCKETS) and stats["hit"] == 0
        device = engine.replicas[0].device
        for b in BUCKETS:
            key, _ = engine._entry_key(b, device)
            assert os.path.exists(os.path.join(root, key + ENTRY_SUFFIX))

    def test_second_startup_zero_compiles_bit_identical(
        self, pieces, warm, monkeypatch
    ):
        """The acceptance lever: warm store → the engine's only compile
        site is never reached, and the served masks are bit-identical
        to the cold-compiled engine's across all buckets."""
        from distributedpytorch_tpu.obs import flight
        from distributedpytorch_tpu.serve.engine import ServeEngine

        root, cold = warm
        calls = []
        orig = ServeEngine._compile_bucket

        def spy(self, *args, **kwargs):
            calls.append(1)
            return orig(self, *args, **kwargs)

        monkeypatch.setattr(ServeEngine, "_compile_bucket", spy)
        hot = make_engine(pieces, root)
        assert calls == []
        assert hot.aot_compiles == 0
        assert hot.aot_cache_stats["hit"] == len(BUCKETS)
        assert hot.aot_cache_stats["miss"] == 0

        rng = np.random.default_rng(7)
        for n in BUCKETS:
            batch = rng.random((n, *SIZE_HW, 3)).astype(np.float32)
            probs_cold = cold.infer(batch)
            probs_hot = hot.infer(batch)
            np.testing.assert_array_equal(probs_cold, probs_hot)
            np.testing.assert_array_equal(
                cold.postprocess(probs_cold), hot.postprocess(probs_hot)
            )

        events = [e for e in flight.get().snapshot()
                  if e.get("kind") == "aot_cache"]
        assert any(e.get("result") == "hit" for e in events)
        assert any(e.get("result") == "miss" for e in events)

    def test_persisted_compiles_bypass_xla_compilation_cache(
        self, pieces, tmp_path, monkeypatch
    ):
        """An executable rehydrated from the persistent XLA compilation
        cache serializes WITHOUT its backend kernel symbols — a sibling
        process loading the store entry gets "Symbols not found" and
        recompiles, which silently defeats the whole store. Pin the
        fix: a compile whose result will be persisted runs with the
        compilation cache disabled, and the flag is restored after."""
        import jax

        before = jax.config.jax_enable_compilation_cache
        calls = []
        real_update = jax.config.update

        def spy(name, value):
            if name == "jax_enable_compilation_cache":
                calls.append(value)
            real_update(name, value)

        monkeypatch.setattr(jax.config, "update", spy)
        engine = make_engine(pieces, tmp_path / "store")
        assert engine.aot_compiles == len(BUCKETS)
        assert calls and calls[0] is False
        assert jax.config.jax_enable_compilation_cache == before

    def test_counter_family_sees_hits_and_misses(self, pieces, warm):
        from distributedpytorch_tpu.obs import defs as obsm

        before = obsm.AOT_CACHE.as_dict()
        make_engine(pieces, warm[0])  # all-hit load
        counts = obsm.AOT_CACHE.as_dict()
        assert counts["hit"] - before.get("hit", 0) == len(BUCKETS)
        assert counts.get("miss", 0) >= len(BUCKETS)  # the cold build


class TestSkewMatrix:
    def test_fingerprint_skew_is_a_plain_miss(
        self, pieces, warm, tmp_path
    ):
        # a different model identity hashes to different KEYS — the
        # warm entries are invisible, never wrongly loaded (copied
        # store: this build persists its own entries alongside)
        root = _copy_store(warm[0], tmp_path)
        other = make_engine(pieces, root, fingerprint="feedfacef00d")
        assert other.aot_compiles == len(BUCKETS)
        assert other.aot_cache_stats["miss"] == len(BUCKETS)
        assert other.aot_cache_stats["skew"] == 0

    def test_runtime_skew_refuses_loudly_and_recompiles(
        self, pieces, warm, tmp_path, monkeypatch, caplog
    ):
        root = _copy_store(warm[0], tmp_path)
        fake = dict(aotstore.runtime_versions())
        fake["jaxlib"] = "0.0.0-faked"
        monkeypatch.setattr(aotstore, "runtime_versions", lambda: fake)
        with caplog.at_level(
            logging.WARNING, logger="distributedpytorch_tpu.utils.aotstore"
        ):
            engine = make_engine(pieces, root)
        assert engine.aot_cache_stats["skew"] == len(BUCKETS)
        assert engine.aot_cache_stats["hit"] == 0
        assert engine.aot_compiles == len(BUCKETS)
        assert any("REFUSING" in r.message for r in caplog.records)

    def test_corrupt_entry_miss_with_note_then_self_heals(
        self, pieces, warm, tmp_path, caplog
    ):
        root = _copy_store(warm[0], tmp_path)
        victim = os.path.join(root, _entries(root)[0])
        blob = open(victim, "rb").read()
        with open(victim, "wb") as f:
            f.write(blob[: len(blob) // 2])  # torn: footer gone
        with caplog.at_level(
            logging.WARNING, logger="distributedpytorch_tpu.utils.aotstore"
        ):
            engine = make_engine(pieces, root)
        assert engine.aot_cache_stats["skew"] == 1
        assert engine.aot_cache_stats["hit"] == len(BUCKETS) - 1
        assert engine.aot_compiles == 1
        assert any("REFUSING" in r.message for r in caplog.records)
        # compile-and-persist overwrote the torn entry: fully warm again
        healed = make_engine(pieces, root)
        assert healed.aot_cache_stats["hit"] == len(BUCKETS)
        assert healed.aot_compiles == 0


class TestTornWrite:
    def test_killed_mid_persist_never_leaves_an_entry(
        self, pieces, tmp_path
    ):
        """A SIGKILL mid-persist = the tmp file stops short of its
        atomic rename: the store dir must hold NO entry, and the next
        cold start must see clean misses (not skews)."""
        root = tmp_path / "store"

        def dying_commit(self, tmp, path, body):
            with open(tmp, "wb") as f:
                f.write(body[: len(body) // 2])
            raise RuntimeError("injected SIGKILL mid-persist")

        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(AOTStore, "_commit", dying_commit)
            engine = make_engine(pieces, root)
        # the engine itself is unharmed (persist is best-effort) ...
        assert engine.aot_compiles == len(BUCKETS)
        # ... and no torn entry exists to poison the next start
        assert _entries(root) == []
        leftovers = [n for n in os.listdir(root) if ".tmp." in n]
        assert leftovers  # the dead writer's droppings, not entries

        second = make_engine(pieces, root)
        assert second.aot_cache_stats["miss"] == len(BUCKETS)
        assert second.aot_cache_stats["skew"] == 0
        assert _entries(root) != []
        # gc sweeps the dead writer's tmp files
        AOTStore(str(root)).gc(max_bytes=10**12)
        assert [n for n in os.listdir(root) if ".tmp." in n] == []


class TestGcAndLs:
    def test_lru_eviction_order(self, warm, tmp_path):
        from distributedpytorch_tpu.obs import defs as obsm

        root = _copy_store(warm[0], tmp_path)
        names = _entries(root)
        assert len(names) >= 2
        paths = [os.path.join(root, n) for n in names]
        # stagger recency: paths[0] oldest ... paths[-1] newest
        for i, p in enumerate(paths):
            os.utime(p, (1_000_000 + i, 1_000_000 + i))
        store = AOTStore(str(root))
        rows = store.ls()
        assert [r["key"] + ENTRY_SUFFIX for r in rows] == names
        keep = os.path.getsize(paths[-1])
        before = obsm.AOT_CACHE.as_dict().get("evicted", 0)
        evicted = store.gc(max_bytes=keep)
        # oldest-first, newest survives
        assert evicted == [n[: -len(ENTRY_SUFFIX)] for n in names[:-1]]
        assert _entries(root) == [names[-1]]
        assert obsm.AOT_CACHE.as_dict()["evicted"] == before + len(evicted)
        assert store.gc(max_bytes=0) == [names[-1][: -len(ENTRY_SUFFIX)]]
        assert _entries(root) == []

    def test_ls_reports_corrupt_entries_without_crashing(
        self, warm, tmp_path
    ):
        root = _copy_store(warm[0], tmp_path)
        victim = os.path.join(root, _entries(root)[0])
        with open(victim, "wb") as f:
            f.write(b"not an entry")
        rows = AOTStore(str(root)).ls()
        assert len(rows) == len(_entries(root))
        assert sum(1 for r in rows if r.get("corrupt")) == 1
        good = [r for r in rows if not r.get("corrupt")]
        assert all(r["engine_fingerprint"] == FP for r in good)


class TestRolloutPath:
    def test_rollout_performs_zero_recompiles(self, pieces, warm):
        """Weight hot-swaps are pointer flips into the SAME (store-
        loaded) executables: a full load → canary → promote cycle must
        stamp recompiles=0 into its finish transition."""
        from distributedpytorch_tpu.serve.rollout import (
            OUTCOME_PROMOTED,
            RolloutManager,
        )
        from distributedpytorch_tpu.serve.server import Server

        _, params, model_state = pieces
        engine = make_engine(pieces, warm[0])
        compiles_before = engine.aot_compiles
        server = Server(engine).start()
        try:
            mgr = RolloutManager(server, window_s=0.2)
            mgr.start((params, model_state), label="candidate")
            assert mgr.wait(60.0) == OUTCOME_PROMOTED
        finally:
            server.stop()
        assert engine.aot_compiles == compiles_before
        finish = mgr.history[-1]
        assert finish["outcome"] == OUTCOME_PROMOTED
        assert finish["recompiles"] == 0


class TestElasticInheritsStore:
    def _supervisor(self, tmp_path, workload):
        from distributedpytorch_tpu.dist.elastic import ElasticSupervisor

        return ElasticSupervisor(
            worker_args=[], nprocs=2, run_dir=str(tmp_path / "run"),
            workload=workload, preflight=False,
        )

    def test_serve_ranks_and_relaunches_share_one_store(
        self, tmp_path, monkeypatch
    ):
        monkeypatch.delenv(aotstore.ENV_VAR, raising=False)
        sup = self._supervisor(tmp_path, "serve")
        expected = os.path.join(sup.run_dir, "aot_cache")
        envs = [
            sup._worker_env(rank, 2, 29500, attempt=attempt)
            for rank in (0, 1) for attempt in (0, 1, 2)
        ]
        # ONE dir for every rank and every relaunch attempt — attempt
        # N+1 loads what attempt 0 compiled
        assert {e["DPT_AOT_CACHE"] for e in envs} == {expected}

    def test_operator_env_wins(self, tmp_path, monkeypatch):
        monkeypatch.setenv(aotstore.ENV_VAR, "/operators/choice")
        sup = self._supervisor(tmp_path, "serve")
        env = sup._worker_env(0, 2, 29500, attempt=1)
        assert env["DPT_AOT_CACHE"] == "/operators/choice"

    def test_train_workload_gets_no_store(self, tmp_path, monkeypatch):
        monkeypatch.delenv(aotstore.ENV_VAR, raising=False)
        sup = self._supervisor(tmp_path, "train")
        assert "DPT_AOT_CACHE" not in sup._worker_env(0, 2, 29500)


class TestCli:
    def test_ls_and_gc(self, warm, tmp_path, capsys):
        import json

        root = str(_copy_store(warm[0], tmp_path))
        assert aotstore.main(["ls", "--aot-cache", root, "--json"]) == 0
        rows = json.loads(capsys.readouterr().out)
        assert len(rows) == len(BUCKETS)
        assert all(r["engine_fingerprint"] == FP for r in rows)
        assert aotstore.main(["gc", "--max-gb", "0",
                              "--aot-cache", root]) == 0
        assert json.loads(capsys.readouterr().out.splitlines()[-1])[
            "evicted"
        ]
        assert _entries(root) == []

    def test_no_store_dir_is_a_loud_exit(self, monkeypatch, capsys):
        monkeypatch.delenv(aotstore.ENV_VAR, raising=False)
        assert aotstore.main(["ls"]) == 2
        assert "DPT_AOT_CACHE" in capsys.readouterr().out


class _FakeDevice:
    """A device stand-in whose ``str()`` decoration is independent of
    its (platform, kind, ordinal) identity — the pod-slice shape where
    identical chips in different processes stringify differently."""

    def __init__(self, platform, kind, ordinal, decoration):
        self.platform = platform
        self.device_kind = kind
        self.id = ordinal
        self._decoration = decoration

    def __str__(self):
        return self._decoration


class TestDeviceKeyScheme:
    """``DPT_AOT_KEY_SCHEME=kind``: same-kind chips at the same local
    ordinal share entries across processes/incarnations; the default
    ``exact`` scheme pins the full device decoration."""

    TWIN_A = _FakeDevice("tpu", "TPU v4", 0, "TPU_0(process=0,(0,0,0,0))")
    TWIN_B = _FakeDevice("tpu", "TPU v4", 0, "TPU_0(process=1,(1,0,0,0))")

    def test_exact_scheme_splits_identical_chips_across_processes(
            self, monkeypatch):
        monkeypatch.delenv(aotstore.KEY_SCHEME_ENV, raising=False)
        assert aotstore.device_key(self.TWIN_A) == str(self.TWIN_A)
        assert (aotstore.device_key(self.TWIN_A)
                != aotstore.device_key(self.TWIN_B))

    def test_kind_scheme_merges_them_but_keeps_the_ordinal(
            self, monkeypatch):
        monkeypatch.setenv(aotstore.KEY_SCHEME_ENV, "kind")
        key = aotstore.device_key(self.TWIN_A)
        assert key == "tpu:TPU v4:0"
        assert key == aotstore.device_key(self.TWIN_B)
        # a deserialized executable only runs on its compile-time
        # device: the LOCAL ordinal never leaves the key
        other_ordinal = _FakeDevice("tpu", "TPU v4", 1,
                                    "TPU_1(process=0,(0,0,0,0))")
        assert aotstore.device_key(other_ordinal) != key

    def test_kind_scheme_flows_into_distinct_entry_keys(
            self, monkeypatch):
        monkeypatch.setenv(aotstore.KEY_SCHEME_ENV, "kind")
        base = dict(kernels="xla", mask_threshold=None, quantized=False,
                    stateful=False)
        shared_a, meta_a = entry_key(
            FP, 2, (2, 32, 48, 3), "float32",
            device=aotstore.device_key(self.TWIN_A), **base)
        shared_b, _ = entry_key(
            FP, 2, (2, 32, 48, 3), "float32",
            device=aotstore.device_key(self.TWIN_B), **base)
        assert shared_a == shared_b  # the fleet-sharing property
        assert meta_a["device"] == "tpu:TPU v4:0"
        split, _ = entry_key(
            FP, 2, (2, 32, 48, 3), "float32",
            device=aotstore.device_key(
                _FakeDevice("tpu", "TPU v4", 1, "TPU_1")), **base)
        assert split != shared_a

    def test_unknown_scheme_warns_and_falls_back_to_exact(
            self, monkeypatch, caplog):
        monkeypatch.setenv(aotstore.KEY_SCHEME_ENV, "banana")
        with caplog.at_level(
                logging.WARNING,
                logger="distributedpytorch_tpu.utils.aotstore"):
            key = aotstore.device_key(self.TWIN_A)
        assert key == str(self.TWIN_A)
        assert any("banana" in rec.message for rec in caplog.records)

    def test_kind_scheme_second_startup_zero_compiles(
            self, pieces, tmp_path, monkeypatch):
        """The warm-store acceptance lever holds under the kind scheme
        too — and the persisted entries carry kind-format device
        components, so skew verification sees the scheme it was
        written under."""
        monkeypatch.setenv(aotstore.KEY_SCHEME_ENV, "kind")
        root = tmp_path / "store"
        cold = make_engine(pieces, root)
        assert cold.aot_compiles == len(BUCKETS)
        device = cold.replicas[0].device
        _, meta = cold._entry_key(BUCKETS[0], device)
        assert meta["device"] == aotstore.device_key(device)
        assert ":" in meta["device"]  # kind-format, not a decoration
        warm_engine = make_engine(pieces, root)
        assert warm_engine.aot_compiles == 0
        assert warm_engine.aot_cache_stats["hit"] == len(BUCKETS)
        assert warm_engine.aot_cache_stats["skew"] == 0

    def test_kind_scheme_keeps_runtime_skew_refusal(
            self, pieces, tmp_path, monkeypatch):
        """Relaxing the DEVICE component must not relax the RUNTIME
        cross-check: a faked jaxlib bump still refuses every entry
        loudly instead of serving a stale executable."""
        monkeypatch.setenv(aotstore.KEY_SCHEME_ENV, "kind")
        root = tmp_path / "store"
        make_engine(pieces, root)
        real = aotstore.runtime_versions()
        monkeypatch.setattr(
            aotstore, "runtime_versions",
            lambda: {**real, "jaxlib": "99.99.99"})
        bumped = make_engine(pieces, root)
        assert bumped.aot_compiles == len(BUCKETS)
        assert bumped.aot_cache_stats["skew"] == len(BUCKETS)


class TestScaledReplicaWarmStore:
    def test_re_added_replica_loads_instead_of_compiling(
            self, pieces, tmp_path):
        """The autoscaler's grow path rides the store: the FIRST grow
        onto a device compiles (ordinal 1 had no entries), but after a
        shrink the next grow re-loads what that ordinal persisted —
        zero compiles, which is what makes scale-up cheap enough to
        actuate from a control loop."""
        import jax

        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices (conftest forces 8 on CPU)")
        root = tmp_path / "store"
        engine = make_engine(pieces, root)
        base = engine.aot_compiles
        assert base == len(BUCKETS)
        engine.add_replica()  # ordinal 1, cold: compile + persist
        after_first_grow = engine.aot_compiles
        assert after_first_grow == base + len(BUCKETS)
        engine.retire_replica()
        engine.add_replica()  # ordinal 1 again, warm: pure loads
        assert engine.aot_compiles == after_first_grow
        assert engine.aot_cache_stats["hit"] >= len(BUCKETS)
        assert engine.num_replicas == 2
