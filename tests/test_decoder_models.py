"""The decoder models' Programs are what they were, and the seams a
new architecture crosses stay where they are.

`program_digest` holds a Program op for op (type, attributes, the name scope
among them, the shapes it writes, the persistable variables it reads and
writes by name) and parameter for parameter (name, shape, dtype, trainable), main and
startup: what a checkpoint and the benchmark's `trace_scopes` reader find
things by. `DIGESTS` and `CENSUS` were taken on the commit before
`models/_decoder.py`, `observe/census.py` and `ops/_kernels.py` existed
(PR 58's parent; `olmo_hybrid`'s on PR 63, which added the model and gave the
census of a program with `gated_delta_rule` ops the key
`linear_attention_head_dims`; `granite_hybrid`'s on PR 65, which added the
model, moved the Mamba-2 mixer from `nemotron_h.py` to `_decoder.py` with
Nemotron-H's digest unmoved, and gave the census of a program with `ssd_scan`
ops its groups, heads a group and chunk; `lfm2_moe`'s on PR 69, which added
the model, `layers.causal_conv1d(activation=)`, `noaux_router(norm_eps=)` and
the census kind `short_conv`, with every other digest and census unmoved) at
the models' own tests' tiny sizes, forward,
backward and Adam; after a deliberate change to a model take them again with
`program_digest(*build_program(model)[:2])` and
`census.program_detail(build_program(model)[0])`.
"""

import ast
import hashlib
import os

import pytest

import paddle_tpu as fluid
from paddle_tpu import models
from paddle_tpu.core import registry
from paddle_tpu.observe import census

from test_granite_hybrid import TINY as GRANITE_HYBRID_TINY
from test_kanana2 import TINY as KANANA2_TINY
from test_lfm2_moe import TINY as LFM2_MOE_TINY
from test_keye_vl2 import TINY as KEYE_VL2_TINY
from test_mellum2 import TINY as MELLUM2_TINY
from test_nemotron_h import TINY as NEMOTRON_H_TINY
from test_olmo_hybrid import TINY as OLMO_HYBRID_TINY
from test_olmoe import TINY as OLMOE_TINY
from test_ouro import TINY as OURO_TINY
from test_qwen3_next import TINY as QWEN3_NEXT_TINY
from test_trinity import TINY as TRINITY_TINY

HERE = os.path.dirname(os.path.abspath(__file__))
PACKAGE = os.path.join(HERE, "..", "paddle_tpu")

SIZES = {"granite_hybrid": GRANITE_HYBRID_TINY, "lfm2_moe": LFM2_MOE_TINY,
         "olmoe": OLMOE_TINY, "olmo_hybrid": OLMO_HYBRID_TINY,
         "ouro": OURO_TINY,
         "qwen3_next": QWEN3_NEXT_TINY, "kanana2": KANANA2_TINY,
         "mellum2": MELLUM2_TINY, "trinity": TRINITY_TINY,
         "keye_vl2": KEYE_VL2_TINY, "nemotron_h": NEMOTRON_H_TINY}


def build_program(model):
    """(main, startup, feeds, fetches) of one training step of
    `models.<model>` at its own tests' tiny sizes: forward, backward and
    Adam."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds, fetches = getattr(models, model).build(**SIZES[model])
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(fetches["loss"])
    return main, startup, feeds, fetches


def program_digest(*programs):
    """The programs' global blocks parameter for parameter and op for op:
    every parameter's name, shape, dtype and whether it trains; every op's
    type, attributes (its name scope is one; but the generated names), the
    shapes of what it writes and the persistable variables it touches, by
    slot."""
    lines = []
    for program in programs:
        block = program.global_block()
        lines += [f"parameter {p.name} {tuple(p.shape)} {p.dtype} "
                  f"{p.trainable}" for p in block.all_parameters()]
        kept = {n for n, v in block.vars.items() if v.persistable}
        for op in block.ops:
            attrs = sorted((k, repr(v)) for k, v in op.attrs.items()
                           if not k.startswith("__") or k == "__role__")
            outs = [tuple(block.var(n).shape) for n in op.output_arg_names
                    if block.has_var(n)]
            held = sorted((way, slot, n) for way, slots in
                          (("in", op.inputs), ("out", op.outputs))
                          for slot, names in slots.items()
                          for n in names if n in kept)
            lines.append(f"{op.type} {attrs} {outs} {held}")
    return len(lines), hashlib.sha256("\n".join(lines).encode()).hexdigest()


DIGESTS = {
    "granite_hybrid": (1670, "b8d7a55da93effa62a1255980f4075f3"   # PR 65's own
                             "07c6385913c2ee7311fc1c317fe9f87d"),
    "kanana2": (618, "7e1a4d0a35d9e8c487e85c5fd2d5ca8f"
                     "8a05a524c1f8174aa3de0527033685df"),
    "keye_vl2": (655, "0649d664f592fadd5d847958da86b217"
                      "95f2689bda87d28ed6e4b2dfe0ed6e23"),
    "lfm2_moe": (700, "232d480348c1f734b566184cf830617a"          # PR 69's own
                      "648afc881d999dcea62b73969ab340ee"),
    "mellum2": (747, "63f94049afbd0dd88ed8281da85f666f"
                     "5379fe7da48e1c9c99f130000d3af739"),
    "nemotron_h": (957, "bb4a9beb840fe95d57a8b1bee2a34470"
                        "a3444ab5db5c1fccba256058024defa9"),
    "olmoe": (397, "d8847a00387005278bfc31daf8556e30"
                   "1eb7d918126f76aafef7dc06b6d29cb9"),
    "olmo_hybrid": (725, "3d6b51bdb3f5b30704e77b84ef566b09"       # PR 63's own
                         "25752028f333b5369d0c325de1a10c8e"),
    "ouro": (790, "6ea230c9082de14ccaa4df13364540aa"
                  "790498e29ea98a844998f066efecfee8"),
    "qwen3_next": (1021, "e431cca320eca95789aee1fbdb3a8e2f"
                         "b2607d1abf037deb504eee331f0fb8e3"),
    "trinity": (1231, "391890374a0cbe3ad429a97497bb07a2"
                      "ab5c431c4f6824d731aeff15d0707e75"),
}

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


@pytest.mark.parametrize("model", sorted(SIZES))
def test_a_decoder_program_is_unchanged_op_for_op(model):
    assert program_digest(*build_program(model)[:2]) == DIGESTS[model]


@pytest.mark.parametrize("model", sorted(SIZES))
def test_a_decoder_programs_compile_detail_is_unchanged(model):
    assert census.program_detail(build_program(model)[0]) == CENSUS[model]


# -- the rule: where a new architecture's diff may reach -----------------------------

SHARED = {"models": "_decoder", "ops": "_kernels"}
# the model families' op modules, whose op types the autodiff module may
# not name
FAMILY_OPS = ("pallas_attention", "moe", "linear_attention", "state_space",
              "decoder_block", "sparse_attention")


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
            "rms_norm", "dsa_select"} <= family, sorted(family)
    with open(os.path.join(PACKAGE, "core", "backward.py")) as f:
        tree = ast.parse(f.read())
    named = {node.value for node in ast.walk(tree)
             if isinstance(node, ast.Constant) and isinstance(node.value, str)}
    assert not named & family, sorted(named & family)
