"""Logical work of a configuration's convolutions, from its shapes alone.

The counts are of the model's work, not of what an implementation
executes: the zeros a space-to-depth kernel multiplies and anything
recomputed are not in them. Each configuration's plain reference
(``benchmark/references/<name>.py``) exports ``conv_layers(config)``,
the walk of its convolutions; everything here is a sum over that walk,
so a new configuration brings its walk and needs no edit here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import List

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Conv:
    """One convolution of the model, at one image.

    ``kind`` is ``conv`` (stride 1, same padding) or ``upconv`` (the
    k x k, stride k transposed convolution). ``h`` and ``w`` are the
    OUTPUT's height and width. ``norm`` counts the per-channel
    normalisation parameters that follow it (2 for BatchNorm's scale and
    bias). ``first`` marks the convolution whose input is the image: no
    input gradient is due for it.
    """

    name: str
    kind: str
    k: int
    cin: int
    cout: int
    h: int
    w: int
    bias: bool = True
    norm: int = 0
    first: bool = False

    @property
    def in_hw(self):
        return (self.h // self.k, self.w // self.k) if self.kind == "upconv" else (self.h, self.w)

    @property
    def weights(self) -> int:
        return self.k * self.k * self.cin * self.cout

    @property
    def params(self) -> int:
        return self.weights + (self.cout if self.bias else 0) + self.norm * self.cout

    @property
    def fwd_flops(self) -> float:
        """2 x multiply-accumulates of the forward pass, one image."""
        if self.kind == "upconv":
            # every output pixel takes one input pixel through one tap
            return 2.0 * self.cin * self.cout * self.h * self.w
        return 2.0 * self.k * self.k * self.cin * self.cout * self.h * self.w

    @property
    def train_flops(self) -> float:
        """Forward, weight gradient and (but for the first) input gradient."""
        return self.fwd_flops * (2.0 if self.first else 3.0)

    def train_bytes(self, batch: int, act_bytes: int, param_bytes: int) -> float:
        """Least bytes moved for a batch: each operand read once and each
        result written once, in forward, weight gradient and input
        gradient; activations in the compute type, weights and their
        gradient in the parameter type."""
        ih, iw = self.in_hw
        x = batch * ih * iw * self.cin * act_bytes
        y = batch * self.h * self.w * self.cout * act_bytes
        wgt = self.weights * param_bytes
        fwd = x + wgt + y
        wgrad = x + y + wgt
        dgrad = 0 if self.first else y + wgt + x
        return float(fwd + wgrad + dgrad)

    def roofline_s(self, batch: int, act_bytes: int, param_bytes: int,
                   peak_flops: float, peak_bytes_per_s: float):
        """Least seconds the chip could take for this convolution's
        forward and gradients over a batch, and which bound sets it."""
        compute = batch * self.train_flops / peak_flops
        memory = self.train_bytes(batch, act_bytes, param_bytes) / peak_bytes_per_s
        return (compute, "compute") if compute >= memory else (memory, "bytes")


def load_reference(config: dict):
    """Import the configuration's plain reference module by its file."""
    path = os.path.join(HERE, "references", config["reference"] + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_reference_" + config["reference"], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def conv_layers(config: dict) -> List[Conv]:
    return load_reference(config).conv_layers(config)


def param_count(config: dict) -> int:
    return sum(c.params for c in conv_layers(config))


def forward_flops_per_image(config: dict) -> float:
    return sum(c.fwd_flops for c in conv_layers(config))


def train_flops_per_image(config: dict) -> float:
    """Forward and backward of the convolutions, one image: what
    ``step_mfu_pct`` counts. Elementwise work, pooling, the loss and the
    optimiser are left out (they are under 1% of the multiply-adds)."""
    return sum(c.train_flops for c in conv_layers(config))


_DTYPE_BYTES = {"bf16": 2, "bfloat16": 2, "f32": 4, "float32": 4}


def conv_roofline_seconds(config: dict, batch: int, peak: dict) -> dict:
    """Least seconds for all convolutions of one step on one chip, with
    the share of it that each bound sets, per convolution."""
    act = _DTYPE_BYTES[config["compute_dtype"]]
    par = _DTYPE_BYTES[config["param_dtype"]]
    total, by_bound, rows = 0.0, {"compute": 0.0, "bytes": 0.0}, []
    for c in conv_layers(config):
        s, bound = c.roofline_s(batch, act, par, peak["bf16_flops"],
                                peak["hbm_bytes_per_s"])
        total += s
        by_bound[bound] += s
        rows.append((c.name, s, bound))
    return {"seconds": total, "by_bound": by_bound, "rows": rows}
