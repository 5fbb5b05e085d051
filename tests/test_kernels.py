"""The kernel-policy layer (ops/kernels.py, ``--kernels``): resolver +
legacy-alias semantics, the two NEW kernels pinned against their XLA
twins in interpret mode on CPU (the fused DoubleConv epilogue
forward+VJP vs ``jax.grad`` of the XLA BN+ReLU; the serve mask kernel
bit-identical at the operating threshold across bucket shapes), the
policy-off path bit-identical to today's defaults, the Mosaic probe
registry + priors-file schema (stale/corrupt → ignored-with-note), and
the planner's ``kernels`` axis accepting/rejecting kernel-on points from
priors with zero device execution — the ISSUE-11 acceptance pins."""

import dataclasses
import json
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedpytorch_tpu.config import TrainConfig
from distributedpytorch_tpu.ops import kernels as km
from distributedpytorch_tpu.ops.kernels import (
    KERNEL_POLICIES,
    apply_priors,
    fused_bn_act,
    get_kernel_policy,
    load_priors,
    run_probes,
    save_priors,
    sigmoid_threshold_mask,
)


def _priors(**kernels):
    """A well-formed priors payload; kwargs: name=(accepted, reason)."""
    return {
        "kind": km.PRIORS_KIND,
        "version": km.PRIORS_VERSION,
        "platform": "tpu",
        "device_kind": "test",
        "kernels": {
            name: (
                {"accepted": True, "compile_s": 0.1}
                if ok
                else {"accepted": False, "reason": reason, "compile_s": 0.1}
            )
            for name, (ok, reason) in kernels.items()
        },
    }


class TestKernelPolicy:
    """The resolver: one object owns every engagement decision."""

    def test_default_config_is_xla_nothing_engaged(self):
        policy = get_kernel_policy(TrainConfig())
        assert policy.name == "xla"
        assert not policy.any_engaged()

    def test_pallas_engages_every_site(self):
        policy = get_kernel_policy(TrainConfig(kernels="pallas"))
        assert policy.name == "pallas"
        assert policy.train_loss_fused and policy.eval_stats_fused
        assert policy.conv_epilogue and policy.serve_mask

    def test_unknown_policy_rejected(self):
        with pytest.raises(ValueError, match="unknown kernel policy"):
            get_kernel_policy("mosaic")

    def test_legacy_use_pallas_is_a_loud_alias_with_historical_scope(
        self, caplog
    ):
        """use_pallas=True keeps meaning exactly what it meant before the
        policy layer: fused training loss + eval stats — never the new
        kernels — and logs the migration pointer."""
        with caplog.at_level(logging.WARNING,
                             logger="distributedpytorch_tpu.ops.kernels"):
            policy = get_kernel_policy(TrainConfig(use_pallas=True))
        assert policy.train_loss_fused and policy.eval_stats_fused
        assert not policy.conv_epilogue and not policy.serve_mask
        assert any("legacy alias" in r.message for r in caplog.records)

    def test_explicit_kernels_supersedes_the_alias(self):
        policy = get_kernel_policy(
            TrainConfig(kernels="pallas", use_pallas=True)
        )
        assert policy.name == "pallas" and policy.conv_epilogue

    def test_priors_rejection_disengages_exactly_that_kernel(self):
        priors = _priors(conv_epilogue=(False, "Mosaic: unsupported"))
        policy = apply_priors(KERNEL_POLICIES["pallas"], priors)
        assert not policy.conv_epilogue
        assert policy.train_loss_fused and policy.serve_mask  # untouched

    def test_priors_flow_through_config_resolution(self, tmp_path):
        path = tmp_path / "priors.json"
        save_priors(_priors(fused_loss=(False, "nope")), str(path))
        policy = get_kernel_policy(
            TrainConfig(kernels="pallas", kernel_priors=str(path))
        )
        assert not policy.train_loss_fused
        assert policy.eval_stats_fused  # unprobed kernels stay engaged

    def test_config_property_is_the_same_resolution_path(self):
        """TrainConfig.kernel_policy wraps get_kernel_policy(self) —
        the precision property's pattern, pinned so it cannot rot."""
        assert TrainConfig().kernel_policy.name == "xla"
        policy = TrainConfig(kernels="pallas", use_pallas=True).kernel_policy
        assert policy.name == "pallas" and policy.conv_epilogue

    def test_name_resolution_honors_env_priors(self, tmp_path, monkeypatch):
        """The serve engine resolves by NAME ('pallas'): the session's
        $DPT_KERNEL_PRIORS verdicts must still revoke rejected kernels
        there."""
        path = tmp_path / "priors.json"
        save_priors(_priors(serve_mask=(False, "refused")), str(path))
        monkeypatch.setenv("DPT_KERNEL_PRIORS", str(path))
        policy = get_kernel_policy("pallas")
        assert not policy.serve_mask
        assert policy.train_loss_fused

    def test_strategy_resolves_the_policy_once(self):
        from distributedpytorch_tpu.parallel import build_strategy

        s = build_strategy(TrainConfig(kernels="pallas"))
        assert s.kernels.train_loss_fused
        assert s._train_loss_impl() is not None
        s0 = build_strategy(TrainConfig())
        assert s0._train_loss_impl() is None and not s0._pallas_eval()

    def test_conv_epilogue_gated_off_on_gspmd_strategies(self):
        assert km.conv_epilogue_engaged(
            TrainConfig(kernels="pallas", train_method="singleGPU"))
        assert km.conv_epilogue_engaged(
            TrainConfig(kernels="pallas", train_method="MP"))
        assert not km.conv_epilogue_engaged(
            TrainConfig(kernels="pallas", train_method="FSDP"))
        assert not km.conv_epilogue_engaged(TrainConfig())

    def test_train_step_kernels_by_config(self):
        assert km.train_step_kernels(TrainConfig()) == ("fused_loss",)
        assert km.train_step_kernels(
            TrainConfig(model_arch="milesial")
        ) == ("fused_loss", "conv_epilogue")


def _bn_case(shape=(2, 6, 9, 16), seed=0):
    rng = np.random.default_rng(seed)
    c = shape[-1]
    return (
        jnp.asarray(rng.standard_normal(shape), jnp.float32),
        jnp.asarray(rng.standard_normal(c), jnp.float32),
        jnp.asarray(rng.random(c) + 0.1, jnp.float32),
        jnp.asarray(rng.standard_normal(c), jnp.float32),
        jnp.asarray(rng.standard_normal(c), jnp.float32),
    )


def _bn_relu_ref(x, mean, var, scale, bias, eps=1e-5):
    """The XLA twin: BN-normalize + ReLU exactly as DoubleConv's
    nn.BatchNorm path computes the elementwise tail."""
    return jax.nn.relu(
        (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias
    )


class TestFusedEpilogue:
    """The NEW conv-epilogue kernel: forward AND hand-written VJP pinned
    against ``jax.grad`` of the XLA BN+nonlinearity (interpret mode)."""

    @pytest.mark.parametrize("shape", [
        (2, 6, 9, 16),     # ragged rows: one partial block, zero-padded
        (1, 16, 32, 128),  # a full lane tile of channels
        (3, 40, 52, 24),   # multi-block rows: cross-block accumulation
    ])
    def test_forward_matches_xla_twin(self, shape):
        args = _bn_case(shape)
        got = fused_bn_act(*args)
        ref = _bn_relu_ref(*args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_vjp_matches_jax_grad_of_xla_twin_for_every_operand(self):
        args = _bn_case((3, 40, 52, 24), seed=2)
        # a non-trivial downstream cotangent so relu's mask matters
        w = jnp.asarray(
            np.random.default_rng(3).standard_normal((3, 40, 52, 24)),
            jnp.float32,
        )
        g_kernel = jax.grad(
            lambda *a: jnp.sum(fused_bn_act(*a) * w), argnums=(0, 1, 2, 3, 4)
        )(*args)
        g_ref = jax.grad(
            lambda *a: jnp.sum(_bn_relu_ref(*a) * w), argnums=(0, 1, 2, 3, 4)
        )(*args)
        for got, ref, name in zip(
            g_kernel, g_ref, ("x", "mean", "var", "scale", "bias")
        ):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-5,
                err_msg=f"cotangent w.r.t. {name}",
            )

    def test_milesial_epilogue_model_parity(self):
        """DoubleConv with the fused epilogue: identical param/stats
        trees, loss+grads+BN-stat updates matching the XLA path on the
        training path (train=True, mutable batch_stats)."""
        from distributedpytorch_tpu.models.milesial import (
            MilesialUNet,
            init_milesial,
        )

        widths = (8, 16, 32)
        m_xla = MilesialUNet(widths=widths, dtype=jnp.float32, s2d_levels=0)
        m_pls = MilesialUNet(widths=widths, dtype=jnp.float32, s2d_levels=0,
                             conv_epilogue=True)
        params, stats = init_milesial(m_xla, jax.random.key(0),
                                      input_hw=(32, 48))
        p2, s2 = init_milesial(m_pls, jax.random.key(0), input_hw=(32, 48))
        assert jax.tree.structure(params) == jax.tree.structure(p2)
        assert jax.tree.structure(stats) == jax.tree.structure(s2)

        x = jnp.asarray(
            np.random.default_rng(0).random((2, 32, 48, 3)), jnp.float32
        )

        def loss(model, p):
            y, upd = model.apply(
                {"params": p, "batch_stats": stats}, x, train=True,
                mutable=["batch_stats"],
            )
            return jnp.sum(y * y), upd["batch_stats"]

        (l0, bs0), g0 = jax.value_and_grad(
            lambda p: loss(m_xla, p), has_aux=True)(params)
        (l1, bs1), g1 = jax.value_and_grad(
            lambda p: loss(m_pls, p), has_aux=True)(params)
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-4)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)
        for a, b in zip(jax.tree.leaves(bs0), jax.tree.leaves(bs1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    def test_eval_mode_uses_running_stats(self):
        from distributedpytorch_tpu.models.milesial import (
            MilesialUNet,
            init_milesial,
        )

        widths = (8, 16)
        m_xla = MilesialUNet(widths=widths, dtype=jnp.float32, s2d_levels=0)
        m_pls = MilesialUNet(widths=widths, dtype=jnp.float32, s2d_levels=0,
                             conv_epilogue=True)
        params, stats = init_milesial(m_xla, jax.random.key(1),
                                      input_hw=(16, 32))
        x = jnp.asarray(
            np.random.default_rng(1).random((2, 16, 32, 3)), jnp.float32
        )
        y0 = m_xla.apply({"params": params, "batch_stats": stats}, x,
                         train=False)
        y1 = m_pls.apply({"params": params, "batch_stats": stats}, x,
                         train=False)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   rtol=1e-5, atol=1e-6)


class TestServeMaskKernel:
    """The NEW sigmoid/threshold mask kernel: bit-identical to the host
    postprocess at the operating threshold, across bucket shapes."""

    @pytest.mark.parametrize("shape", [
        (1, 32, 48),    # one bucket row
        (4, 32, 48),    # a full bucket
        (2, 33, 47),    # ragged plane: exercises the flat-pad tail
        (8, 80, 120),   # multi-block grid
    ])
    def test_bit_identical_to_postprocess_mask(self, shape):
        from distributedpytorch_tpu.serve.infer import postprocess_mask

        rng = np.random.default_rng(7)
        probs = rng.random(shape).astype(np.float32)
        # seed exact-threshold pixels: the >= boundary must agree too
        probs.flat[:: max(1, probs.size // 17)] = 0.5
        got = np.asarray(sigmoid_threshold_mask(jnp.asarray(probs), 0.5))
        ref = postprocess_mask(probs, 0.5)
        assert got.dtype == np.uint8
        assert (got == ref).all()

    def test_from_logits_fuses_the_sigmoid(self):
        z = jnp.asarray(
            np.random.default_rng(8).standard_normal((2, 16, 24)) * 4,
            jnp.float32,
        )
        got = np.asarray(sigmoid_threshold_mask(z, 0.5, from_logits=True))
        ref = (np.asarray(jax.nn.sigmoid(z)) >= 0.5).astype(np.uint8) * 255
        assert (got == ref).all()

    def test_engaged_engine_masks_bit_identical_across_buckets(self):
        """ServeEngine(kernels='pallas'): the AOT bucket executables
        return uint8 masks equal to the xla engine's postprocess —
        padding rows can't perturb real rows in either mode."""
        from distributedpytorch_tpu.models.unet import (
            UNet,
            init_unet_params,
        )
        from distributedpytorch_tpu.serve.engine import ServeEngine

        model = UNet(dtype=jnp.float32, widths=(8, 16))
        params = init_unet_params(model, jax.random.key(0), input_hw=(32, 48))
        e_xla = ServeEngine(model, params, None, input_hw=(32, 48),
                            bucket_sizes=(1, 2, 4))
        e_pls = ServeEngine(model, params, None, input_hw=(32, 48),
                            bucket_sizes=(1, 2, 4), kernels="pallas")
        assert e_pls.mask_on_device and not e_xla.mask_on_device
        rng = np.random.default_rng(1)
        for n in (1, 2, 3, 4):
            batch = rng.random((n, 32, 48, 3)).astype(np.float32)
            ref = e_xla.postprocess(e_xla.infer(batch))
            got = e_pls.postprocess(e_pls.infer(batch))
            assert got.dtype == np.uint8 and (got == ref).all(), n

    def test_postprocess_mask_passes_uint8_through(self):
        from distributedpytorch_tpu.serve.infer import postprocess_mask

        mask = (np.random.default_rng(2).random((4, 8)) > 0.5).astype(
            np.uint8) * 255
        assert postprocess_mask(mask, 0.5) is mask


class TestPolicyOffBitIdentical:
    """--kernels unset: every output bit-identical to today's paths."""

    def test_default_train_step_is_the_plain_xla_step(self):
        """A strategy-built step under the default config produces
        BIT-identical state/loss to the directly-built XLA step on the
        same data — the policy-off path adds nothing to the trace."""
        from distributedpytorch_tpu.models.unet import (
            UNet,
            init_unet_params,
        )
        from distributedpytorch_tpu.parallel import build_strategy
        from distributedpytorch_tpu.train.steps import (
            create_train_state,
            make_train_step,
        )

        cfg = TrainConfig(model_widths=(8, 16), compute_dtype="float32",
                          batch_size=2)
        strategy = build_strategy(cfg)
        model = UNet(dtype=jnp.float32, widths=(8, 16))
        params = init_unet_params(model, jax.random.key(0), input_hw=(16, 32))
        rng = np.random.default_rng(0)
        batch = {
            "image": rng.random((2, 16, 32, 3)).astype(np.float32),
            "mask": (rng.random((2, 16, 32)) > 0.5).astype(np.int32),
        }
        state_a, tx_a = create_train_state(params, 1e-4)
        state_b, tx_b = create_train_state(params, 1e-4)
        step_strategy = strategy.build_train_step(model, tx_a)
        step_plain = jax.jit(make_train_step(model, tx_b, batch_size=2))
        placed = {k: jnp.asarray(v) for k, v in batch.items()}
        out_a = step_strategy(state_a, placed)
        out_b = step_plain(state_b, placed)
        assert float(out_a[1]) == float(out_b[1])
        for a, b in zip(jax.tree.leaves(out_a[0].params),
                        jax.tree.leaves(out_b[0].params)):
            assert (np.asarray(a) == np.asarray(b)).all()

    def test_default_forward_returns_probs_not_masks(self):
        from distributedpytorch_tpu.models.unet import (
            UNet,
            init_unet_params,
        )
        from distributedpytorch_tpu.serve.infer import make_forward

        model = UNet(dtype=jnp.float32, widths=(8, 16))
        params = init_unet_params(model, jax.random.key(0), input_hw=(16, 32))
        fwd = make_forward(model)
        out = fwd({"params": params}, jnp.zeros((1, 16, 32, 3)))
        assert out.dtype == jnp.float32

    def test_default_milesial_has_no_epilogue(self):
        from distributedpytorch_tpu.models import create_model

        model, _ = create_model(TrainConfig(model_arch="milesial",
                                            model_widths=(8, 16)))
        assert model.conv_epilogue is False

    def test_mosaic_rejected_pallas_collapses_to_xla_engagements(self):
        """--kernels pallas with EVERY kernel Mosaic-rejected = the xla
        engagement set (bit-identical fallback by construction)."""
        priors = _priors(**{
            name: (False, "refused") for name in km.KERNEL_GATES
        })
        policy = apply_priors(KERNEL_POLICIES["pallas"], priors)
        assert not policy.any_engaged()


class TestProbesAndPriors:
    """The probe registry + the per-chip priors file schema."""

    def test_registry_covers_every_gated_kernel(self):
        assert set(km.PROBES) == set(km.KERNEL_GATES)

    def test_run_probes_compile_only_all_accepted_here(self):
        rows = []
        payload = run_probes(emit=rows.append)
        assert payload["kind"] == km.PRIORS_KIND
        assert payload["version"] == km.PRIORS_VERSION
        assert payload["platform"] == "cpu"
        assert set(payload["kernels"]) == set(km.PROBES)
        for name, row in payload["kernels"].items():
            assert row["accepted"] is True, (name, row)
            assert row["compile_s"] >= 0
        assert len(rows) == len(km.PROBES)

    def test_probe_failure_recorded_as_rejection_not_raised(
        self, monkeypatch
    ):
        def boom():
            raise RuntimeError("INTERNAL: Mosaic failed to lower")

        monkeypatch.setitem(km.PROBES, "fused_loss", boom)
        payload = run_probes(names=["fused_loss"])
        row = payload["kernels"]["fused_loss"]
        assert row["accepted"] is False
        assert "Mosaic failed to lower" in row["reason"]

    def test_unknown_probe_name_rejected(self):
        with pytest.raises(ValueError, match="unknown probe"):
            run_probes(names=["warp_drive"])

    def test_priors_roundtrip(self, tmp_path):
        path = str(tmp_path / "p.json")
        save_priors(_priors(fused_loss=(True, "")), path)
        loaded = load_priors(path)
        assert loaded["kernels"]["fused_loss"]["accepted"] is True

    def test_missing_priors_is_none(self, tmp_path):
        assert load_priors(str(tmp_path / "absent.json")) is None

    def test_corrupt_priors_ignored_with_note(self, tmp_path, caplog):
        path = tmp_path / "garbage.json"
        path.write_text("{not json")
        with caplog.at_level(logging.WARNING,
                             logger="distributedpytorch_tpu.ops.kernels"):
            assert load_priors(str(path)) is None
        assert any("unreadable" in r.message for r in caplog.records)

    def test_stale_version_ignored_with_note(self, tmp_path, caplog):
        path = tmp_path / "stale.json"
        stale = _priors(fused_loss=(True, ""))
        stale["version"] = km.PRIORS_VERSION + 1
        path.write_text(json.dumps(stale))
        with caplog.at_level(logging.WARNING,
                             logger="distributedpytorch_tpu.ops.kernels"):
            assert load_priors(str(path)) is None
        assert any("stale or malformed" in r.message for r in caplog.records)

    def test_probe_tool_writes_loadable_priors(self, tmp_path):
        import sys

        sys.path.insert(0, ".")
        from tools.probe_kernels import run_and_save

        path = str(tmp_path / "kernel_priors.json")
        summary = run_and_save(path, names=["serve_mask"])
        assert summary["rejected"] == []
        assert load_priors(path)["kernels"]["serve_mask"]["accepted"]


    def test_probe_tool_exit_code_is_the_refusal(self, tmp_path, monkeypatch):
        """A kernel Mosaic refuses is recorded in the priors file AND is
        the tool's exit code — never just a line in a log."""
        import sys

        sys.path.insert(0, ".")
        from tools import probe_kernels

        path = str(tmp_path / "kernel_priors.json")
        assert probe_kernels.main(["--out", path, "--kernels", "serve_mask"]) == 0

        def refused():
            raise RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel")

        monkeypatch.setitem(km.PROBES, "serve_mask", refused)
        assert probe_kernels.main(["--out", path, "--kernels", "serve_mask"]) == 1
        row = load_priors(path)["kernels"]["serve_mask"]
        assert not row["accepted"] and "Mosaic failed" in row["reason"]


class TestPlannerKernelsAxis:
    """ISSUE-11 acceptance: ``plan --kernel-priors`` ranks kernel-on
    points (rejected ones carrying the Mosaic reject reason) with zero
    device execution."""

    BASE = dict(
        strategies=("singleGPU",), schedules=(), microbatches=(),
        s2d_levels=(0,), remats=(False,), batches=(4,), dtypes=("bf16",),
        image_size=(48, 32), widths=(8, 16), hbm_gb=16.0,
    )

    def test_kernel_on_points_rank_against_their_twins(self):
        from distributedpytorch_tpu.analysis import planner

        payload = planner.plan(
            kernels=("xla", "pallas"),
            kernel_priors=_priors(fused_loss=(True, "")),
            **self.BASE,
        )
        by_key = {r["key"]: r for r in payload["points"]}
        twin = by_key["singleGPU/s2d0/remat-off/b4/bf16"]
        k_on = by_key["singleGPU/s2d0/remat-off/b4/bf16/k-pallas"]
        assert twin["feasible"] and k_on["feasible"]
        assert k_on["predicted"]["kernel_saving_s"] > 0
        assert (k_on["predicted"]["cost_s"]
                < twin["predicted"]["cost_s"])
        assert k_on["key"] in payload["ranking"]
        assert k_on["predicted"]["kernel_priors"] == "accepted"

    def test_mosaic_rejected_point_carries_the_probe_reason_no_compile(
        self, monkeypatch
    ):
        """A rejected kernel point never opens a compile: the twin is
        compiled once, the pallas row derives (and here rejects) with
        the probe's verdict."""
        from distributedpytorch_tpu.analysis import planner

        payload = planner.plan(
            kernels=("xla", "pallas"),
            kernel_priors=_priors(
                fused_loss=(False, "INTERNAL: Mosaic refused")
            ),
            **self.BASE,
        )
        k_on = [r for r in payload["points"] if r["kernels"] == "pallas"][0]
        assert k_on["feasible"] is False
        assert "Mosaic rejected fused_loss" in k_on["reject"]
        assert "INTERNAL: Mosaic refused" in k_on["reject"]
        assert k_on["rank"] is None
        assert payload["kernel_priors"]["rejected"] == ["fused_loss"]

    def test_unprobed_kernels_rank_with_marker(self):
        from distributedpytorch_tpu.analysis import planner

        payload = planner.plan(kernels=("xla", "pallas"), **self.BASE)
        k_on = [r for r in payload["points"] if r["kernels"] == "pallas"][0]
        assert k_on["feasible"]
        assert k_on["predicted"]["kernel_priors"] == "unprobed"

    def test_missing_priors_file_never_widens_the_kernels_axis(
        self, tmp_path
    ):
        """`plan --kernel-priors <missing/stale>` must degrade to the
        xla-only axis (no unprobed pallas points can rank) — pinned at
        the CLI layer, where the widening decision lives."""
        from distributedpytorch_tpu.analysis import planner

        out = str(tmp_path / "plan.json")
        argv = [
            "--out", out, "--strategies", "singleGPU", "--schedules",
            "gpipe", "--microbatches", "2", "--s2d-levels", "0",
            "--remat", "off", "--batches", "4", "--dtypes", "bf16",
            "--image-size", "48", "32", "--widths", "8", "16",
            "--kernel-priors", str(tmp_path / "absent.json"),
        ]
        rc = planner.run(argv)
        assert rc == planner.EXIT_CLEAN
        payload = planner.load_plan(out)
        assert payload["grid"]["kernels"] == ["xla"]
        assert payload["kernel_priors"] is None
        assert all(p["kernels"] == "xla" for p in payload["points"])
