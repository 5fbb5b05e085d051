"""Plain reference of the course UNet (notnitsuj/DistributedPyTorch
``model/unet_parts.py``, ``model/unet_model.py``): four encoder blocks of
two 3x3 convolutions with ReLU, 2x2 max-pooling between them, a middle
block, four decoder levels (2x2 stride-2 transposed convolution, skip
concatenated before the upsampled tensor, block of two convolutions), a
1x1 head and a sigmoid. No normalisation. NHWC, weights HWIO.

Imports nothing of the program. Parameters arrive as a flat dict keyed
by ``<module path>/<kernel|bias>``; the harness makes them from the seed.
"""

from __future__ import annotations

from flops import Conv
from reference import sub

stateful = False


def conv_layers(config):
    widths = list(config["widths"])
    mid = config["mid_width"]
    w, h = config["image_size"]
    out = []
    cin = config["in_channels"]
    for i, c in enumerate(widths):
        hh, ww = h >> i, w >> i
        out.append(Conv(f"encoder/block{i + 1}/conv1", "conv", 3, cin, c, hh, ww,
                        first=(i == 0)))
        out.append(Conv(f"encoder/block{i + 1}/conv2", "conv", 3, c, c, hh, ww))
        cin = c
    n = len(widths)
    out.append(Conv("mid/conv1", "conv", 3, cin, mid, h >> n, w >> n))
    out.append(Conv("mid/conv2", "conv", 3, mid, mid, h >> n, w >> n))
    cin = mid
    for i, c in enumerate(reversed(widths)):
        lvl = n - 1 - i
        hh, ww = h >> lvl, w >> lvl
        out.append(Conv(f"decoder/upconv{i + 1}", "upconv", 2, cin, c, hh, ww))
        out.append(Conv(f"decoder/block{i + 1}/conv1", "conv", 3, 2 * c, c, hh, ww))
        out.append(Conv(f"decoder/block{i + 1}/conv2", "conv", 3, c, c, hh, ww))
        cin = c
    out.append(Conv("segmap", "conv", 1, cin, config["n_classes"], h, w))
    return out


def param_shapes(config):
    """``{leaf name: shape}`` of every parameter, from the walk."""
    out = {}
    for c in conv_layers(config):
        out[c.name + "/kernel"] = (c.k, c.k, c.cin, c.cout)
        out[c.name + "/bias"] = (c.cout,)
    return out


def state_shapes(config):
    return {}


def forward(ops, config, p, state, x):
    """(probabilities (B, H, W, 1) in float32, None). Every block is a
    unit that gets its own leaves of ``p``."""
    n = len(config["widths"])

    def block(q, name, x):
        for j in (1, 2):
            x = ops.relu(ops.conv(x, q[f"{name}/conv{j}/kernel"],
                                  q[f"{name}/conv{j}/bias"]))
        return x

    skips = []
    for i in range(n):
        name = f"encoder/block{i + 1}"
        x = ops.unit(lambda q, x, name=name, i=i: block(
            q, name, ops.maxpool(x) if i else x))(sub(p, name), x)
        skips.append(x)
    x = ops.unit(lambda q, x: block(q, "mid", ops.maxpool(x)))(sub(p, "mid"), x)
    for i in range(n):
        up, name = f"decoder/upconv{i + 1}", f"decoder/block{i + 1}"

        def level(q, x, skip, up=up, name=name):
            y = ops.upconv(x, q[up + "/kernel"], q[up + "/bias"])
            return block(q, name, ops.concat(skip, y))

        x = ops.unit(level)({**sub(p, up), **sub(p, name)}, x, skips[n - 1 - i])
    x = ops.conv(x, p["segmap/kernel"], p["segmap/bias"])
    return ops.sigmoid(x), None
