"""``flops.py`` against the numbers the repo already had: the course
UNet's 0.257 TFLOP forward pass at 640x960 (``bench.py``
``ANALYTIC_FWD_FLOPS_PER_IMG``) and its published parameter count, from
the same walk of the convolutions. By hand, on the CPU:

    python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import flops  # noqa: E402


def test_course_unet_forward_flops_and_parameters():
    config = flops.load_config("course_unet")
    convs = flops.conv_layers(config)
    # bench.py's constant takes 2*K*K*Cin*Cout*Hout*Wout for the transposed
    # convolutions too, which counts the zeros of an input-dilated
    # implementation: K*K taps where each output pixel has one. The walk
    # reproduces it when asked to count so, and is 12% lower as it counts.
    as_bench_py = sum(c.fwd_flops * (c.k * c.k if c.kind == "upconv" else 1)
                      for c in convs)
    assert abs(as_bench_py / 0.257e12 - 1) < 0.01
    assert abs(flops.forward_flops_per_image(config) / 0.2263e12 - 1) < 0.001
    assert flops.param_count(config) == 7_760_097 == config["parameters"]


def test_train_flops_are_three_passes_but_for_the_first_convolution():
    config = flops.load_config("course_unet")
    convs = flops.conv_layers(config)
    first = [c for c in convs if c.first]
    assert len(first) == 1
    expect = 3 * flops.forward_flops_per_image(config) - first[0].fwd_flops
    assert flops.train_flops_per_image(config) == expect


def test_roofline_names_its_bound():
    config = flops.load_config("course_unet")
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    r = flops.conv_roofline_seconds(config, 16, peak)
    assert abs(sum(r["by_bound"].values()) - r["seconds"]) < 1e-12
    by_name = {name: bound for name, _, bound in r["rows"]}
    # 3 -> 32 channels at full resolution moves far more bytes than it
    # multiplies; 512 -> 512 at 1/16 is the other way round
    assert by_name["encoder/block1/conv1"] == "bytes"
    assert by_name["mid/conv2"] == "compute"
