"""Shared inference pieces: preprocess → forward → postprocess.

ONE implementation of the inference data path, used by BOTH surfaces:

* the batch-offline CLI (``predict.py`` / ``dpt-predict``) — streams a
  directory of images through it batch-by-batch;
* the serving tier (``serve/engine.py`` / ``python -m
  distributedpytorch_tpu serve``) — AOT-compiles the same forward per
  padded bucket shape and runs it under the continuous-batching queue.

Because both paths run these exact functions, the offline-vs-serve
parity test (tests/test_serve.py) can pin masks *bit-identical* across
the two surfaces — any drift in preprocessing, the forward, or the
thresholding is a test failure, not a silent production skew.

Kept import-light at module scope (numpy/PIL only); jax loads inside
the functions that trace, mirroring predict.py's historical layout so
``--help`` and queue-only tests never pay a backend init.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

logger = logging.getLogger(__name__)


def preprocess_image(pil_img, size_wh: Sequence[int]) -> np.ndarray:
    """One decoded PIL image → the model's input row: forced RGB, BICUBIC
    resize to ``(W, H)``, /255, NHWC float32 — exactly the training-side
    ``BasicDataset.preprocess`` (any divergence here would silently skew
    every served prediction against the trained distribution)."""
    from distributedpytorch_tpu.data.dataset import BasicDataset

    # palette GIFs, RGBA PNGs, grayscale: the model wants exactly 3 channels
    pil_img = pil_img.convert("RGB")
    return BasicDataset.preprocess(pil_img, size_wh, is_mask=False)


def load_image(path: str, size_wh: Sequence[int]) -> np.ndarray:
    """Decode + preprocess one image file (PIL / .npy / .pt dispatch via
    ``BasicDataset.load``)."""
    from distributedpytorch_tpu.data.dataset import BasicDataset

    return preprocess_image(BasicDataset.load(path), size_wh)


def make_forward(
    model, quantized: bool = False, mask_threshold: Optional[float] = None
) -> Callable:
    """The eval forward as a plain jittable ``fwd(variables, x) -> probs``:
    ``variables`` is ``{"params": ...}`` (plus ``"batch_stats"`` for
    stateful families — milesial BatchNorm — applied in eval mode),
    ``x`` is ``(B, H, W, 3) float32``, the result ``(B, H, W) float32``
    sigmoid probabilities (the trailing channel squeezed inside the
    traced program). Taking the variables as an ARGUMENT (not a closure)
    is what lets the serving engine place them per replica device and
    AOT-compile against device-pinned ShapeDtypeStructs.

    ``quantized=True`` consumes int8 weights-only variables (``params``
    holds ``{"q": int8, "scale": f32}`` kernel subtrees — ops/quant.py):
    dequantization happens INSIDE the traced forward, so the executable's
    resident weight arguments stay one byte per element and the float
    kernels exist only as temps.

    ``mask_threshold`` (the ``--kernels pallas`` serve-mask engagement,
    ops/kernels.py) traces the fused sigmoid/threshold mask kernel onto
    the tail: the forward then returns the served ``{0, 255} uint8``
    mask itself — 1 byte/pixel over the D2H drain instead of 4, and no
    host threshold pass — bit-identical to ``postprocess_mask`` of the
    probabilities at the same threshold (the model's sigmoid already ran
    under the LOSS_DTYPE contract, so the kernel runs its exact-compare
    threshold leg)."""
    stateful = bool(getattr(model, "is_stateful", False))

    def fwd(variables, x):
        if quantized:
            from distributedpytorch_tpu.ops.quant import dequantize_tree

            variables = dict(variables)
            variables["params"] = dequantize_tree(variables["params"])
        if stateful:
            probs = model.apply(variables, x, train=False)
        else:
            probs = model.apply(variables, x)
        probs = probs[..., 0]
        if mask_threshold is not None:
            from distributedpytorch_tpu.ops.kernels import (
                sigmoid_threshold_mask,
            )

            return sigmoid_threshold_mask(probs, mask_threshold)
        return probs

    return fwd


def bundle_variables(model, params, model_state=None) -> dict:
    """The flax variables dict ``make_forward`` consumes — batch_stats
    included exactly when the model family is stateful."""
    if getattr(model, "is_stateful", False):
        return {"params": params, "batch_stats": model_state}
    return {"params": params}


def postprocess_mask(probs: np.ndarray, threshold: float = 0.5) -> np.ndarray:
    """Probabilities → the served artifact: ``{0, 255} uint8`` masks
    (same shape in, channelless out). Works on a single ``(H, W)`` row or
    a ``(B, H, W)`` batch. A ``uint8`` input passes through untouched —
    it IS the mask already, thresholded on-device by the serve-mask
    kernel (``make_forward(mask_threshold=...)``), so the completion
    drain stays one code path under either kernel policy."""
    arr = np.asarray(probs)
    if arr.dtype == np.uint8:
        return arr
    return (arr >= threshold).astype(np.uint8) * 255


@dataclasses.dataclass
class InferenceBundle:
    """Everything one checkpoint needs to serve: the model object, its
    weights (+ BatchNorm stats for stateful families), and the resolved
    TrainConfig whose geometry/arch fields sized the model.
    ``quantized=True`` means ``params`` is an int8 weights-only tree
    (ops/quant.py) and the forward dequantizes in-trace."""

    model: object
    params: object
    model_state: object
    config: object
    input_hw: Tuple[int, int]  # (H, W) — note: CLI flags order (W, H)
    quantized: bool = False

    def forward(self) -> Callable:
        return make_forward(self.model, quantized=self.quantized)

    @property
    def variables(self) -> dict:
        return bundle_variables(self.model, self.params, self.model_state)


def load_inference_bundle(
    checkpoint: str,
    checkpoint_dir: str = "./checkpoints",
    image_size: Sequence[int] = (960, 640),
    model_arch: str = "unet",
    model_widths: Optional[Sequence[int]] = None,
    s2d_levels: int = -1,
    quantize: Optional[str] = None,
) -> InferenceBundle:
    """Resolve a checkpoint name/path and build the model + weights for
    inference. ``model_arch``/``model_widths`` must match the trained
    checkpoint's architecture. Image sizes the space-to-depth mode cannot
    express (H or W not divisible by ``2**levels``) fall back to the
    (equivalent) pixel path — checkpoints are identical across execution
    modes, so this changes speed, never results.

    ``quantize="int8"`` serves weights-only int8 (ops/quant.py): a file
    written by tools/quantize.py loads directly (its manifest records the
    source checkpoint hash), a regular checkpoint is quantized on load
    (convenient for A/Bs; persist with the tool for production). A
    quantized file is also auto-detected when ``quantize`` is unset —
    loudly, since the serving numerics change."""
    from distributedpytorch_tpu.checkpoint import resolve_checkpoint
    from distributedpytorch_tpu.config import TrainConfig
    from distributedpytorch_tpu.models import create_model, model_entry
    from distributedpytorch_tpu.ops import quant

    if not model_entry(model_arch).servable:
        raise ValueError(
            f"serve/ and predict run an image forward (image in, mask "
            f"out); model {model_arch!r} is a token model that this "
            f"package only trains: it has no prefill, decode or cache "
            f"here, and the published model's generation by diffusion "
            f"over blocks is not supported (ROADMAP M10)")
    if quantize not in (None, "int8"):
        raise ValueError(
            f"quantize must be None or 'int8', got {quantize!r}"
        )
    path = resolve_checkpoint(checkpoint, checkpoint_dir)
    w, h = int(image_size[0]), int(image_size[1])
    cfg = TrainConfig(
        model_arch=model_arch,
        model_widths=tuple(model_widths) if model_widths else None,
        s2d_levels=s2d_levels,
    )
    div = 2 ** cfg.model_levels
    if s2d_levels != 0 and (h % div or w % div):
        logger.info(
            "image size %dx%d not divisible by %d: space-to-depth execution "
            "unavailable, using the (equivalent) pixel path", w, h, div,
        )
        cfg = dataclasses.replace(cfg, s2d_levels=0)
    model, _ = create_model(cfg)

    # ONE file read decides the kind AND feeds whichever loader applies —
    # a multi-GB checkpoint must not be deserialized twice per startup
    # (the same read_payload seam the trainer's restore uses)
    payload = None
    if not path.endswith(".pth"):
        from distributedpytorch_tpu.checkpoint import read_payload

        payload = read_payload(path)
    if isinstance(payload, dict) and payload.get("kind") == quant.QUANT_KIND:
        if quantize is None:
            logger.warning(
                "%s is an int8 weights file — serving quantized "
                "(pass --quantize int8 to make this explicit)", path,
            )
        qtree, raw_state, manifest = quant.load_quantized(
            path, payload=payload
        )
        _check_quantized_identity(manifest, model_arch, model_widths, path)
        model_state = _restore_model_state(model, raw_state, (h, w), path)
        return InferenceBundle(
            model=model, params=qtree, model_state=model_state, config=cfg,
            input_hw=(h, w), quantized=True,
        )
    params, model_state = load_params_for_inference(
        path, model, input_hw=(h, w), payload=payload
    )
    if quantize == "int8":
        logger.info(
            "quantizing %s to int8 weights on load (per-out-channel "
            "symmetric); persist with tools/quantize.py to skip this at "
            "every startup", path,
        )
        params = quant.quantize_tree(params)
        return InferenceBundle(
            model=model, params=params, model_state=model_state, config=cfg,
            input_hw=(h, w), quantized=True,
        )
    return InferenceBundle(
        model=model, params=params, model_state=model_state, config=cfg,
        input_hw=(h, w),
    )


def _check_quantized_identity(manifest, model_arch, model_widths, path):
    """A quantized file's manifest records the model identity its ints
    were produced for (tools/quantize.py); a mismatched --model /
    --model-widths would otherwise surface as an opaque flax/XLA shape
    error deep in the engine's AOT compile — the qtree is handed to the
    model raw, never bound against a template like the float path."""
    saved_arch = manifest.get("model_arch")
    if saved_arch is not None and saved_arch != model_arch:
        raise ValueError(
            f"{path} was quantized from a {saved_arch!r} checkpoint but "
            f"--model is {model_arch!r} — pass the architecture the "
            f"manifest records"
        )
    saved_widths = manifest.get("model_widths")
    got_widths = list(model_widths) if model_widths else None
    if saved_widths is not None and list(saved_widths or []) != (
        got_widths or []
    ):
        raise ValueError(
            f"{path} was quantized for model_widths={saved_widths} but "
            f"--model-widths is {got_widths} — pass the widths the "
            f"manifest records"
        )


def _restore_model_state(model, raw_state, input_hw, path):
    """BatchNorm running stats from a quantized file's raw state dict,
    restored against the model's own template (stateless models: None)."""
    if raw_state is None:
        return None
    import flax.serialization
    import jax
    import jax.numpy as jnp

    variables = model.init(
        jax.random.key(0), jnp.zeros((1, input_hw[0], input_hw[1], 3))
    )
    template = variables.get("batch_stats")
    if template is None:
        logger.warning(
            "%s carries model_state but the model family is stateless — "
            "ignored", path,
        )
        return None
    return flax.serialization.from_state_dict(template, raw_state)


def load_params_for_inference(
    checkpoint_path: str, model, input_hw: Tuple[int, int], payload=None
):
    """(params, model_state) from a native .ckpt or a reference-format .pth
    (the format dispatch lives in checkpoint.load_weights, shared with the
    trainer). ``model_state`` is the BatchNorm running stats for stateful
    models, None otherwise. ``payload`` is an already-read checkpoint
    payload (checkpoint.read_payload) — the bundle loader probes the file
    kind first and hands the bytes down instead of re-reading.

    Params are routed through the precision policy's restore seam
    (ops/precision.ensure_restored_dtypes — the ckpt-dtype-drift
    contract): a checkpoint trained under ``--dtype bf16_params`` stores
    bf16 weights, and serving promotes them to the model template's f32
    loudly, so inference numerics are identical whatever policy trained
    the checkpoint."""
    import jax
    import jax.numpy as jnp

    from distributedpytorch_tpu.ops.precision import (
        POLICIES,
        ensure_restored_dtypes,
    )

    variables = model.init(
        jax.random.key(0), jnp.zeros((1, input_hw[0], input_hw[1], 3))
    )
    template = variables["params"]
    state_template = variables.get("batch_stats")
    inference_policy = POLICIES["f32"]  # f32 param storage for serving
    if checkpoint_path.endswith(".pth"):
        if state_template is not None:
            # stateful family: milesial/Pytorch-UNet-layout .pth (the
            # public upstream checkpoints load directly)
            from distributedpytorch_tpu.checkpoint import import_milesial_pth

            params, stats = import_milesial_pth(
                checkpoint_path, template, state_template
            )
            return (
                ensure_restored_dtypes(
                    params, inference_policy, f"inference {checkpoint_path}"
                ),
                stats,
            )
        from distributedpytorch_tpu.checkpoint import load_weights

        params = load_weights(checkpoint_path, template)
        return (
            ensure_restored_dtypes(
                params, inference_policy, f"inference {checkpoint_path}"
            ),
            state_template,
        )
    from distributedpytorch_tpu.checkpoint import load_checkpoint

    restored = load_checkpoint(
        checkpoint_path, template, model_state_target=state_template,
        payload=payload,
    )
    model_state = restored["model_state"]
    if state_template is not None and model_state is None:
        logger.warning(
            "checkpoint %s has no batch_stats; using init statistics",
            checkpoint_path,
        )
        model_state = state_template
    params = ensure_restored_dtypes(
        restored["params"], inference_policy, f"inference {checkpoint_path}"
    )
    return params, model_state
