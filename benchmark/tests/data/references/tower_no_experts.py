"""Test-only reference of ``tower_no_experts``: hands a pattern that has
no expert block to the plain reference of ``nemotron_twotower_30b_a3b``
(the same blocks, the same leaves) and brings only the hooks that a model
without routers has: no ``balanced_biases``, no ``routed_left_out``. The
configuration's file has no ``deployment`` and no expert's size; what the
tower's reference reads of them for every configuration is filled in
here, and none of it is used."""

import flops

_tower = flops.load_reference({"reference": "nemotron_twotower_30b_a3b"})
_UNUSED = {"n_routed_experts": 1, "num_experts_per_tok": 1,
           "moe_intermediate_size": 8, "moe_shared_expert_intermediate_size": 8,
           "deployment": {"experts_total": 1, "first_held": 0}}
stateful = False


def _handed(name):
    hook = getattr(_tower, name)
    return lambda config, *args, **kw: hook({**_UNUSED, **config}, *args, **kw)


param_shapes = _handed("param_shapes")
make_loss_and_grad = _handed("make_loss_and_grad")
program_overrides = _handed("program_overrides")
expert_blocks = _handed("expert_blocks")
matmul_layers = _handed("matmul_layers")
train_flops_per_sample = _handed("train_flops_per_sample")
