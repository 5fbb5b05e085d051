"""Model table: one entry per ``TrainConfig.model_arch``.

An entry says how the model is built, what its training loss is, what a
batch of it holds, which counters ride back with its loss, and whether
``serve/`` can run it. The trainer, the strategies and the loader ask the
entry; none of them names a model.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

from distributedpytorch_tpu.models.unet import UNet, ConvBlock, Encoder, Decoder  # noqa: F401
from distributedpytorch_tpu.models.milesial import MilesialUNet  # noqa: F401


@dataclasses.dataclass(frozen=True)
class BatchSpec:
    """What one batch holds: its fields (every one with the samples on its
    leading axis) and what a sample is called in logs and rates."""

    fields: Tuple[str, ...]
    unit: str

    def rows(self, batch) -> int:
        return int(batch[self.fields[0]].shape[0])


IMAGE_BATCH = BatchSpec(("image", "mask"), "img")
#: ``tokens`` (B, S) int32: packed documents, no padding; position t
#: predicts token t + 1 (data/tokens.py).
TOKEN_BATCH = BatchSpec(("tokens",), "seq")


@dataclasses.dataclass(frozen=True)
class ModelEntry:
    """``build(config, compute_dtype) -> (model, init_fn)`` with
    ``init_fn(rng, input_hw) -> (params, model_state or None)``;
    ``loss(model, params, model_state, batch, loss_impl) -> (loss,
    model_state, train/steps.Counted or None)``; ``evaluate(model, variables, batch)
    -> {'loss', 'dice'}`` (None: the image metrics of train/steps.py);
    ``dataset(config)`` builds the data set where the caller gave none
    (None: the image data sets of data/dataset.py); ``counters(model)``
    names what ``loss`` counts; ``attention_kernel_blocks(model, config)``
    is how many attention blocks take the fused kernel on this backend;
    ``kept_activation_bytes(model, config)`` is how many bytes of named
    activations a step keeps across its blocks' backward passes;
    ``moe_wgrad_kernel_layers(model, config)`` is how many sparse expert
    layers take the grouped weight-gradient kernel on this backend."""

    build: Callable
    loss: Callable
    batch: BatchSpec = IMAGE_BATCH
    evaluate: Optional[Callable] = None
    dataset: Optional[Callable] = None
    counters: Callable = lambda model: ()
    attention_kernel_blocks: Callable = lambda model, config: 0
    kept_activation_bytes: Callable = lambda model, config: 0
    moe_wgrad_kernel_layers: Callable = lambda model, config: 0
    adam_b2: float = 0.999
    # the reference's ``(batch_size * loss).backward()`` quirk
    # (TrainConfig.faithful_loss_scaling) belongs to its image models
    batch_scaled_backward: bool = True
    servable: bool = True
    single_device_only: bool = False


def _image_loss(model, params, model_state, batch, loss_impl=None):
    from distributedpytorch_tpu.train.steps import image_loss

    return image_loss(model, params, model_state, batch, loss_impl)


def _build_unet(config, compute_dtype):
    from distributedpytorch_tpu.models.unet import create_unet, init_unet_params

    model = create_unet(config, dtype=compute_dtype)

    def init_fn(rng, input_hw):
        return init_unet_params(model, rng, input_hw=input_hw), None

    return model, init_fn


def _build_milesial(config, compute_dtype):
    from distributedpytorch_tpu.models.milesial import (
        MILESIAL_WIDTHS,
        init_milesial,
    )
    from distributedpytorch_tpu.ops.kernels import conv_epilogue_engaged

    widths = tuple(config.model_widths) if config.model_widths else MILESIAL_WIDTHS
    model = MilesialUNet(
        widths=widths,
        dtype=compute_dtype,
        s2d_levels=getattr(config, "s2d_levels", -1),
        conv_epilogue=conv_epilogue_engaged(config),
    )

    def init_fn(rng, input_hw):
        return init_milesial(model, rng, input_hw=input_hw)

    return model, init_fn


def _token_builder(module: str, model: str, sizes: str):
    """``build`` of a token model: class ``model`` of ``models/<module>.py``
    at its published share with ``config.model_overrides`` laid over it
    (``sizes``), told what the device reports as its memory."""
    def build(config, compute_dtype):
        import importlib

        from distributedpytorch_tpu.utils.backend import device_memory_bytes

        mod = importlib.import_module(f"{__name__}.{module}")
        net = getattr(mod, model)(
            getattr(mod, sizes)(getattr(config, "model_overrides", None)),
            dtype=compute_dtype, memory_bytes=device_memory_bytes())
        return net, lambda rng, input_hw: (net.init(rng), None)

    return build


def _token_loss(model, params, model_state, batch, loss_impl=None):
    from distributedpytorch_tpu.train.steps import Counted

    loss, counters, biases = model.loss(params, batch["tokens"])
    return loss, model_state, Counted(counters.reshape(-1), biases)


def _token_eval(model, variables, batch):
    import jax.numpy as jnp

    loss = model.loss(variables, batch["tokens"])[0]
    # no Dice for a token model: the trainer's second validation number
    # reads NaN, and --save-best (highest Dice) never fires
    return {"loss": loss, "dice": jnp.full((), jnp.nan, loss.dtype)}


def _token_dataset(config):
    from distributedpytorch_tpu.data.tokens import build_token_dataset

    # the vocabulary is the model's: built for its sizes alone (no array)
    return build_token_dataset(config, create_model(config)[0].cfg.vocab_size)


def _token_counters(model):
    return model.counter_names


def _token_kernel_blocks(model, config):
    import jax

    return model.attention_kernel_blocks(jax.default_backend(), config.seq_len)


def _token_kept_bytes(model, config):
    import jax

    return sum(model.kept_activation_bytes(
        config.batch_size, config.seq_len, jax.default_backend()))


def _token_wgrad_layers(model, config):
    import jax

    return model.moe_wgrad_kernel_layers(
        jax.default_backend(), config.batch_size * config.seq_len)


#: What the token models share: a batch of packed tokens, mean next-token
#: cross-entropy with counters and the routers' own biases beside it,
#: trained on one device, not served.
_TOKEN_MODEL = ModelEntry(
    build=None, loss=_token_loss, batch=TOKEN_BATCH, evaluate=_token_eval,
    dataset=_token_dataset, counters=_token_counters,
    attention_kernel_blocks=_token_kernel_blocks,
    kept_activation_bytes=_token_kept_bytes,
    moe_wgrad_kernel_layers=_token_wgrad_layers, adam_b2=0.95,
    batch_scaled_backward=False, servable=False, single_device_only=True)

MODELS = {
    "unet": ModelEntry(build=_build_unet, loss=_image_loss),
    "milesial": ModelEntry(build=_build_milesial, loss=_image_loss),
    # the 52-block tower of Nemotron-Labs-TwoTower-30B-A3B-Base-BF16's
    # config.json, one chip's share (models/twotower.py); its denoising
    # tower and block-diffusion decoding are not supported
    "twotower": dataclasses.replace(
        _TOKEN_MODEL, build=_token_builder("twotower", "TwoTower",
                                           "twotower_config")),
    # LFM2-24B-A2B's config.json (lfm2_moe), one chip's share
    # (models/lfm2.py): gated short convolutions and QK-normed attention,
    # a dense or a sparse SwiGLU feed-forward in every layer, tied head
    "lfm2": dataclasses.replace(
        _TOKEN_MODEL, build=_token_builder("lfm2", "Lfm2", "lfm2_config")),
    # SmallThinker-21BA3B-Instruct's config.json, one chip's share
    # (models/smallthinker.py): sliding-window attention with rotary and
    # full attention without positions in one stack, a router that reads
    # the layer's input before attention, softmax-gated ReGLU experts
    "smallthinker": dataclasses.replace(
        _TOKEN_MODEL, build=_token_builder("smallthinker", "SmallThinker",
                                           "smallthinker_config")),
}


def model_entry(config_or_arch: Any) -> ModelEntry:
    arch = (config_or_arch if isinstance(config_or_arch, str)
            else getattr(config_or_arch, "model_arch", "unet"))
    try:
        return MODELS[arch]
    except KeyError:
        raise ValueError(
            f"unknown model_arch {arch!r} (known: {sorted(MODELS)})") from None


def create_model(config):
    """Model factory: TrainConfig.model_arch → (model, init_fn).

    ``init_fn(rng, input_hw) -> (params, model_state_or_None)`` — stateful
    models (milesial's BatchNorm) return their non-trainable collections as
    the second element. The model's compute dtype comes from the resolved
    precision policy (config.precision — ops/precision.py), so ``--dtype``
    and the legacy ``compute_dtype`` override resolve in exactly one place;
    the kernel policy's conv-epilogue engagement resolves through
    ``ops.kernels.conv_epilogue_engaged`` the same way (``--kernels``,
    Mosaic probe priors, and the device-local-forward gate in one place).
    """
    from distributedpytorch_tpu.ops.precision import get_policy

    return model_entry(config).build(config, get_policy(config).compute_dtype)
