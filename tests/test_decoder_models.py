"""The decoder models' Programs are what they were, and the seams a
new architecture crosses stay where they are.

`decoder_case.program_digest` holds a Program op for op (type, attributes,
the name scope among them, the shapes it writes, the persistable variables it reads and
writes by name) and parameter for parameter (name, shape, dtype, trainable), main and
startup: what a checkpoint and the benchmark's `trace_scopes` reader find
things by. `decoder_case.DIGESTS` and `CENSUS` were taken on the commit before
`models/_decoder.py`, `observe/census.py` and `ops/_kernels.py` existed
(PR 58's parent; `olmo_hybrid`'s on PR 63, which added the model and gave the
census of a program with `gated_delta_rule` ops the key
`linear_attention_head_dims`; `granite_hybrid`'s on PR 65, which added the
model, moved the Mamba-2 mixer from `nemotron_h.py` to `_decoder.py` with
Nemotron-H's digest unmoved, and gave the census of a program with `ssd_scan`
ops its groups, heads a group and chunk; `lfm2_moe`'s on PR 69, which added
the model, `layers.causal_conv1d(activation=)`, `noaux_router(norm_eps=)` and
the census kind `short_conv`, with every other digest and census unmoved;
`phi4_flash`'s on PR 73, which added the model, the op `selective_scan` and the
census kinds `selective_scan`, `differential_attention`,
`cross_decoder_attention` and `gated_memory`, likewise) at
the models' own tests' tiny sizes (`decoder_case.tiny_args`), forward,
backward and Adam; after a deliberate change to a model take them again with
`program_digest(*build_program(model)[:2])` and
`census.program_detail(build_program(model)[0])`.
"""

import ast
import os

import pytest

from paddle_tpu.core import registry
from paddle_tpu.observe import census

from decoder_case import DIGESTS, build_program, program_digest

HERE = os.path.dirname(os.path.abspath(__file__))
PACKAGE = os.path.join(HERE, "..", "paddle_tpu")

CENSUS = {
    "granite_hybrid": {
        "parameters": 128, "parameter_uses": 129, "grad_fanin_max": 2,
        "state_space_groups": 1, "state_space_heads_per_group": 4,
        "state_space_chunk": 64,
        "layer_kinds": {"full_attention": 1, "state_space": 9},
        "state_space_layers": 9, "attention_kv_group": 2,
        "attention_unrotated_layers": 1, "tied_heads": 1,
        "residual_scaled_sublayers": 20},
    "kanana2": {
        "parameters": 43, "parameter_uses": 43, "grad_fanin_max": 1,
        "attention_qk_width": 24, "attention_value_width": 16,
        "moe_experts_routed": 16, "moe_router_score": "sigmoid",
        "moe_experts_held": 4, "layer_kinds": {"latent_attention": 3},
        "dense_ffn_layers": 1, "moe_router_bias_updates": 2,
        "attention_rotary_layers": 3},
    "keye_vl2": {
        "parameters": 54, "parameter_uses": 54, "grad_fanin_max": 1,
        "moe_experts_routed": 16, "moe_experts_held": 4,
        "layer_kinds": {"sparse_attention": 3}, "dsa_layers": 3,
        "frozen_parameters": 15, "attention_rotary_layers": 3},
    "lfm2_moe": {
        "parameters": 53, "parameter_uses": 54, "grad_fanin_max": 2,
        "short_conv_taps": 3, "moe_experts_routed": 16,
        "moe_router_score": "sigmoid", "moe_experts_held": 4,
        "layer_kinds": {"full_attention": 1, "short_conv": 4},
        "short_conv_layers": 4, "short_conv_gates": 8,
        "attention_kv_group": 2, "dense_ffn_layers": 1,
        "moe_router_bias_updates": 4, "attention_rotary_layers": 1,
        "tied_heads": 1},
    "mellum2": {
        "parameters": 51, "parameter_uses": 51, "grad_fanin_max": 1,
        "attention_window": 96, "attention_kv_group": 2,
        "moe_experts_routed": 16, "moe_experts_held": 4,
        "layer_kinds": {"full_attention": 1, "window_attention": 3},
        "attention_window_layers": 3, "attention_rotary_layers": 4},
    "nemotron_h": {
        "parameters": 72, "parameter_uses": 72, "grad_fanin_max": 1,
        "moe_experts_routed": 16, "moe_router_score": "sigmoid",
        "moe_experts_held": 4, "moe_expert_activation": "relu2",
        "layer_kinds": {"full_attention": 1, "state_space": 4},
        "state_space_layers": 4, "attention_kv_group": 2,
        "state_space_groups": 2, "state_space_heads_per_group": 2,
        "state_space_chunk": 128,                   # keys since PR 65
        "moe_router_bias_updates": 4, "attention_unrotated_layers": 1},
    "olmoe": {
        "parameters": 27, "parameter_uses": 27, "grad_fanin_max": 1,
        "moe_experts_routed": 8, "moe_experts_held": 8,
        "layer_kinds": {"full_attention": 2}, "attention_rotary_layers": 2},
    "olmo_hybrid": {
        "parameters": 62, "parameter_uses": 62, "grad_fanin_max": 1,
        "delta_rule_beta_scale": 2.0, "linear_attention_head_dims": [12, 24],
        "attention_heads_held": 2, "attention_heads": 4,
        "layer_kinds": {"linear_attention": 3, "full_attention": 1},
        "residual_out_norms": 8},
    "ouro": {
        "parameters": 27, "parameter_uses": 103, "grad_fanin_max": 4,
        "layer_kinds": {"full_attention": 8}, "attention_rotary_layers": 8,
        "residual_out_norms": 19},
    "phi4_flash": {
        "parameters": 100, "parameter_uses": 101, "grad_fanin_max": 2,
        "selective_scan_state": 8, "attention_window": 48,
        "attention_window_layers": 1,
        "layer_kinds": {"selective_scan": 2, "differential_attention": 2,
                        "cross_decoder_attention": 1, "gated_memory": 1},
        "selective_scan_layers": 2, "diff_attention_layers": 3,
        "shared_kv_readers": 1, "memory_readers": 1,
        "activation_grad_fanin_max": 4, "tied_heads": 1},
    "qwen3_next": {
        "parameters": 70, "parameter_uses": 70, "grad_fanin_max": 1,
        "moe_experts_routed": 16, "moe_experts_held": 4,
        "layer_kinds": {"linear_attention": 3, "full_attention": 1},
        "linear_attention_head_dims": [8, 8],       # a key since PR 63
        "attention_rotary_layers": 1, "attention_gated_layers": 1},
    "trinity": {
        "parameters": 93, "parameter_uses": 93, "grad_fanin_max": 1,
        "attention_window": 96, "attention_kv_group": 2,
        "moe_experts_routed": 16, "moe_router_score": "sigmoid",
        "moe_experts_held": 4,
        "layer_kinds": {"full_attention": 1, "window_attention": 4},
        "attention_window_layers": 4, "dense_ffn_layers": 1,
        "moe_router_bias_updates": 4, "attention_rotary_layers": 4,
        "attention_unrotated_layers": 1, "attention_gated_layers": 5,
        "residual_out_norms": 10},
}


@pytest.mark.parametrize("model", sorted(DIGESTS))
def test_a_decoder_program_is_unchanged_op_for_op(model):
    assert program_digest(*build_program(model)[:2]) == DIGESTS[model]


@pytest.mark.parametrize("model", sorted(CENSUS))
def test_a_decoder_programs_compile_detail_is_unchanged(model):
    assert census.program_detail(build_program(model)[0]) == CENSUS[model]


# -- the rule: where a new architecture's diff may reach -----------------------------

SHARED = {"models": "_decoder", "ops": "_kernels"}
# the model families' op modules, whose op types the autodiff module may
# not name
FAMILY_OPS = ("pallas_attention", "moe", "linear_attention", "state_space",
              "selective_scan", "decoder_block", "sparse_attention")


def _sources(package):
    folder = os.path.join(PACKAGE, package)
    for name in sorted(os.listdir(folder)):
        if name.endswith(".py"):
            with open(os.path.join(folder, name)) as f:
                yield name, ast.parse(f.read())


@pytest.mark.parametrize("package", sorted(SHARED))
def test_no_module_imports_a_siblings_underscored_name(package):
    """A model or a kernel family shares through `models/_decoder.py` or
    `ops/_kernels.py`, never through a sibling's private names."""
    found = []
    for name, tree in _sources(package):
        for node in ast.walk(tree):
            if not isinstance(node, ast.ImportFrom) or node.level != 1 \
                    or node.module in (None, SHARED[package]):
                continue
            found += [f"{package}/{name}: from .{node.module} import "
                      f"{alias.name}" for alias in node.names
                      if alias.name.startswith("_")]
    assert not found, found


def test_the_autodiff_module_names_no_op_of_a_model_family():
    """`core/backward.py` decides every gradient; what the executors count
    on a compile event is `observe/census.py`'s."""
    modules = {"paddle_tpu.ops." + m for m in FAMILY_OPS}
    family = {t for t in registry.registered_ops()
              if registry.get_op_def(t).lower.__module__ in modules}
    assert {"fused_attention", "moe_router", "gated_delta_rule", "ssd_scan",
            "selective_scan", "rms_norm", "dsa_select"} <= family, \
        sorted(family)
    with open(os.path.join(PACKAGE, "core", "backward.py")) as f:
        tree = ast.parse(f.read())
    named = {node.value for node in ast.walk(tree)
             if isinstance(node, ast.Constant) and isinstance(node.value, str)}
    assert not named & family, sorted(named & family)
