"""What a Program holds, counted for its compile events: how far it shares
its weights (`parameter_sharing`) and what kinds of mixer and expert layer it
is built from (`layer_census`). Read back from the global block alone, after
`minimize` has written the backward pass and the updates; the executors write
`program_detail` on every compile event (`observe.observatory()`, `detail`),
where the benchmark's `compile_detail` reader finds it key by key. A model
family that wants a count there adds it here; nothing in the autodiff module
(`core/backward.py`) reads any of it."""

from __future__ import annotations

from typing import Dict

from ..core import ir
from ..core.ir import grad_var_name


def parameter_sharing(program: ir.Program) -> Dict[str, int]:
    """How far the program shares its weights, read back from the global
    block: `parameters`; `parameter_uses`, the reads of a parameter by a
    forward op (a parameter counts once an op); `grad_fanin_max`, the most
    gradient contributions summed into one parameter: the longest `X` of the
    `sum` ops `_insert_sum_ops` wrote for a parameter's gradient, 1 where
    every parameter has one contribution, 0 in a program without a backward
    pass. An unrolled loop over shared layers reads `uses = loops x
    parameters` and a fan-in of `loops`; a copy of the weights per pass
    would read a fan-in of 1. PARAMETERS' fan-in only: the contributions
    summed into an activation that several ops read (a layer's keys and
    values or a scan's output read by later layers) are counted by
    `layer_census`'s `activation_grad_fanin_max`, not here. Goes on the
    program's compile events (`observe.observatory()`, `detail`)."""
    block = program.global_block()
    params = {p.name for p in block.all_parameters()}
    grads = {grad_var_name(n) for n in params}
    uses = fanin = 0
    for op in block.ops:
        if op.attrs.get("__role__") is None:
            uses += len(params.intersection(op.input_arg_names))
        elif op.attrs["__role__"] == "backward":
            if op.type == "sum" and op.output("Out")[0] in grads:
                fanin = max(fanin, len(op.input("X")))
            elif not fanin and grads.intersection(op.output_arg_names):
                fanin = 1
    return {"parameters": len(params), "parameter_uses": uses,
            "grad_fanin_max": fanin}


def layer_census(program: ir.Program) -> Dict[str, object]:
    """What kinds of mixer and expert layer the program holds, read back
    from the global block's forward ops: `layer_kinds`, the layers by their
    mixer (`linear_attention`: a `gated_delta_rule` op, `full_attention`: a
    `fused_attention` op, `latent_attention`: a `fused_attention` whose value
    heads are narrower or wider than its key heads, with
    `attention_qk_width` and `attention_value_width` beside it,
    `window_attention`: a `fused_attention` whose `window` is shorter than
    its sequence, with `attention_window_layers`, their count again as a
    flat number, and `attention_window` beside it); `attention_kv_group`
    where a windowed program's keys are a `layers.expand` of fewer heads
    (the query heads one key-value head serves);
    `dense_ffn_layers`, the `swiglu` feed-forwards built under a
    `name_scope` that holds no router, where the program has expert layers
    too; and where it has those,
    `moe_experts_routed` (the router's width), `moe_experts_held` (the
    experts whose weights live here: fewer under a share),
    `moe_router_score` where the router's scores are not a softmax, and
    `moe_router_bias_updates`, the routers whose selection bias a later op
    of the step writes again. Of the softmax-attention layers, by what else
    their `name_scope` holds: `attention_rotary_layers`, those with a
    `rotary_embedding` op, and where the program has such layers,
    `attention_unrotated_layers`, those without (no positions at all);
    `attention_gated_layers`, those with a `sigmoid` op (an output gate on
    the context). `residual_out_norms`: the `rms_norm` ops whose result goes
    straight into a residual `elementwise_add`, a sublayer normed on the way
    out (two a layer where a layer has four norms). Empty for a program with
    none of these. `sparse_attention`: a `fused_attention` that is handed a
    kept set (a learned selection of keys), with `dsa_layers`, their count
    again as a flat number. `frozen_parameters`: the trainable parameters
    that no update op names, in a program that has update ops: what the loss
    cannot reach and `minimize` therefore left alone, without moments (an
    indexer behind a selection that carries no gradient).
    `state_space`: an `ssd_scan` op (a Mamba-2 mixer), with
    `state_space_layers`, their count again as a flat number; a program
    that has them reports its softmax-attention layers' `attention_kv_group`
    and `attention_unrotated_layers` whether or not another layer is
    windowed or turns (its layers are a mixer or a feed-forward part alone,
    and none of its attention layers carries positions).
    With them `state_space_groups`, `state_space_heads_per_group` and
    `state_space_chunk`: the scan's groups of B and C, the heads that read
    one group and the op's `chunk` attribute (8, 8 and 128 for Nemotron-H; 1,
    64 and 256 for Granite 4.0-H, which `ops/state_space.py::_grid` takes in
    eight blocks of 8 heads and steps of 128 tokens; `ssd_plan`, the form
    the scan ran in, is noted by the op's rule on the same event). `tied_heads`: the embedding tables (a
    `lookup_table`'s W) that a `matmul` reads as well, a head tied to its
    embedding. `residual_scaled_sublayers`: the `scale` ops under a
    `name_scope` whose result goes straight into a residual
    `elementwise_add`, a sublayer's branch under a residual multiplier.
    `moe_expert_activation`: `relu2` where the routed experts are two
    matrices around a `relu2` op (absent for gated silu experts).
    `kda`: a `kda_delta_rule` op (a Kimi Delta Attention mixer: the delta
    rule under a decay per key channel), with `kda_layers`, their count
    again as a flat number. `latent_attention_gated_layers`: the latent-
    attention layers whose `name_scope` holds a `sigmoid` op (a head-wise
    gate on the context). `moe_router_groups` and `moe_router_groups_kept`:
    a group-limited router's `n_group` and `topk_group` (absent for one
    group).
    `short_conv`: a `causal_conv1d` op with NO activation under a
    `name_scope` whose layer holds no `ssd_scan`, `gated_delta_rule` or
    `kda_delta_rule` (LFM2's gated short convolution: the mixer itself, not
    the convolution in front of a scan or a delta rule, which takes silu),
    with `short_conv_layers`, their count again as a flat number,
    `short_conv_taps`, the weight's taps a channel, and `short_conv_gates`,
    the `elementwise_mul`s under those scopes with an operand that is a
    `slice` of a projection (two a layer: the gate before the convolution and
    the one after it); a program that has them reports its softmax-attention
    layers' `attention_kv_group` as one with scans does (`causal_conv_plan`,
    the form the convolution ran in, is noted by the op's rule on the same
    event).
    `selective_scan`: a `selective_scan` op (a Mamba-1 mixer: a decay per
    channel and state), with `selective_scan_layers`, their count again as a
    flat number, and `selective_scan_state`, the states a channel
    (`selective_scan_plan`, the form the scan ran in, and
    `selective_scan_grid_steps` are noted by the op's rule on the same
    event). `differential_attention`: a `name_scope` that holds TWO
    `fused_attention` ops and an `elementwise_sub` (two softmax maps a pair of
    heads and their difference), whose keys were made under the same layer,
    with `diff_attention_layers`, every such scope whoever made its keys, as
    a flat number; `cross_decoder_attention`: such a scope whose calls read
    keys made under ANOTHER layer's scope, with `shared_kv_readers`, their
    count again. A differential layer under a window shorter than its
    sequence counts in `attention_window_layers` with `attention_window`
    beside it, as a windowed `fused_attention` does, and is no
    `window_attention`, `latent_attention` or `full_attention` layer.
    `gated_memory`: a `swiglu` whose `Up` is the output of a
    `selective_scan` op of another layer (a gated memory unit), with
    `memory_readers`, their count again. Where the program has either kind of
    reader, `activation_grad_fanin_max`: the most gradient contributions the
    backward pass sums into one of the tensors those readers read (the
    served keys, the served values, the scan's output): the longest `X` of
    the `sum` ops that write their gradients.
    (`moe_row_buffer_rows`, the rows of the expert layer's layout, follows
    the batch: `moe_dispatch`'s rule notes it on the same event under the
    trace, `LoweringContext.note`.)"""
    block = program.global_block()
    kinds = {"linear_attention": 0, "full_attention": 0,
             "latent_attention": 0, "window_attention": 0,
             "sparse_attention": 0, "state_space": 0, "kda": 0,
             "short_conv": 0, "selective_scan": 0,
             "differential_attention": 0, "cross_decoder_attention": 0,
             "gated_memory": 0}
    out: Dict[str, object] = {}
    copies: Dict[str, str] = {}     # an `assign` op's result -> what it copied
    biases = []                     # the routers' selection biases
    gated, routed = [], set()       # name scopes of `swiglu`s, of routers
    mixers = []                     # name scopes of `fused_attention`s
    latent = []                     # of those, the latent-attention ones
    full_keys = []                  # the full-attention ops' K
    held_by = {"rotary_embedding": set(), "sigmoid": set()}  # name scopes
    normed, added = set(), set()    # `rms_norm` results, residual addends
    scaled = set()                  # results of `scale` ops under a scope
    tables, multiplied = set(), set()   # `lookup_table`s' W, `matmul`s' Y
    bare_convs, recurrent = [], set()   # (layer, taps) of the convolutions
    #                                     without an activation, a layer the
    #                                     first part of a scope; the layers
    #                                     of scans and delta rules
    sliced, products = set(), []        # `slice` results; (layer, operands)
    #                                     of the `elementwise_mul`s
    forward = [op for op in block.ops if op.attrs.get("__role__") is None]
    differential = _differential_scopes(forward)
    made_under = {n: op.attrs.get(ir.NAME_SCOPE_ATTR) for op in forward
                  for n in op.output_arg_names}
    scans = {}                          # a `selective_scan`'s Out -> its scope
    shared = set()                      # tensors another layer's ops read
    windowed_pairs = 0                  # differential scopes under a window
    counted = set()                     # differential scopes met already
    for op in forward:
        scope = op.attrs.get(ir.NAME_SCOPE_ATTR)
        layer = scope.split("/")[0] if scope else None
        if op.type in ("gated_delta_rule", "ssd_scan", "kda_delta_rule",
                       "selective_scan"):
            recurrent.add(layer)
        if op.type == "selective_scan":
            kinds["selective_scan"] += 1
            scans[op.output("Out")[0]] = scope
            out["selective_scan_state"] = \
                block.var(op.input("ALog")[0]).shape[-1]
            continue
        if op.type == "swiglu" and scans.get(op.input("Up")[0],
                                             scope) != scope:
            kinds["gated_memory"] += 1
            shared.add(op.input("Up")[0])
        if op.type == "fused_attention" and scope in differential:
            keys = op.input("K")[0]
            borrowed = made_under.get(keys) != scope
            if borrowed:
                shared.update(op.input("K") + op.input("V"))
            if scope not in counted:            # the scope's first call
                counted.add(scope)
                kinds["cross_decoder_attention" if borrowed
                      else "differential_attention"] += 1
                window = op.attrs.get("window")
                if window is not None \
                        and window < block.var(keys).shape[-2]:
                    windowed_pairs += 1
                    out["attention_window"] = window
            continue
        if op.type == "causal_conv1d" and not op.attrs.get("activation",
                                                           "silu"):
            bare_convs.append((layer,
                               block.var(op.input("W")[0]).shape[-1]))
        elif op.type == "slice":
            sliced.update(op.output_arg_names)
        elif op.type == "elementwise_mul":
            products.append((layer, op.input_arg_names))
        elif op.type == "gated_delta_rule":
            kinds["linear_attention"] += 1
            out["linear_attention_head_dims"] = [
                block.var(op.input(slot)[0]).shape[-1] for slot in "KV"]
        elif op.type == "delta_rule_gates" and "beta_scale" in op.attrs:
            out["delta_rule_beta_scale"] = op.attrs["beta_scale"]
        elif op.type == "ssd_scan":
            kinds["state_space"] += 1
            heads = block.var(op.input("X")[0]).shape[2]
            groups = block.var(op.input("B")[0]).shape[2]
            out["state_space_groups"] = groups
            out["state_space_heads_per_group"] = heads // groups
            out["state_space_chunk"] = op.attrs.get("chunk", 128)
        elif op.type == "lookup_table":
            tables.update(op.input("W"))
        elif op.type == "matmul":
            multiplied.update(op.input("Y"))
        elif op.type == "scale" and scope is not None:
            scaled.update(op.output("Out"))
        elif op.type == "kda_delta_rule":
            kinds["kda"] += 1
        elif op.type == "relu2" and scope in routed:
            out["moe_expert_activation"] = "relu2"
        elif op.type in held_by:
            held_by[op.type].add(scope)
        elif op.type == "rms_norm":
            normed.update(op.output("Y"))
        elif op.type == "elementwise_add":
            added.update(op.input_arg_names)
        elif op.type == "fused_attention":
            mixers.append(scope)
            keys = block.var(op.input("K")[0])
            wide = keys.shape[-1]
            value = block.var(op.input("V")[0]).shape[-1]
            window = op.attrs.get("window")
            token_major = op.attrs.get("layout") == "BTHD"
            seq = keys.shape[1 if token_major else -2]
            if "heads_total" in op.attrs:
                out["attention_heads_held"] = keys.shape[2 if token_major
                                                         else 1]
                out["attention_heads"] = op.attrs["heads_total"]
            if op.inputs.get("Kept"):
                kinds["sparse_attention"] += 1
            elif window is not None and window < seq:
                kinds["window_attention"] += 1
                out["attention_window"] = window
                group = _expanded_by(block, op.input("K")[0])
                if group > 1:
                    out["attention_kv_group"] = group
            elif wide == value:
                kinds["full_attention"] += 1
                full_keys.append(op.input("K")[0])
            else:
                kinds["latent_attention"] += 1
                latent.append(scope)
                out["attention_qk_width"] = wide
                out["attention_value_width"] = value
        elif op.type == "swiglu":
            gated.append(scope)
        elif op.type == "assign":
            copies[op.output("Out")[0]] = op.input("X")[0]
        elif op.type == "moe_router":
            out["moe_experts_routed"] = block.var(op.input("W")[0]).shape[-1]
            routed.add(scope)
            if op.attrs.get("score_func"):
                out["moe_router_score"] = op.attrs["score_func"]
            if op.attrs.get("n_group"):
                out["moe_router_groups"] = op.attrs["n_group"]
                out["moe_router_groups_kept"] = op.attrs["topk_group"]
            biases += [copies.get(name, name)
                       for name in op.inputs.get("Bias", [])]
        elif op.type == "moe_dispatch":
            out["moe_experts_held"] = op.attrs.get(
                "experts_held", out.get("moe_experts_routed"))
    if kinds["window_attention"] or windowed_pairs:
        out["attention_window_layers"] = kinds["window_attention"] \
            + windowed_pairs
    if kinds["sparse_attention"]:
        out["dsa_layers"] = kinds["sparse_attention"]
    if kinds["kda"]:
        out["kda_layers"] = kinds["kda"]
    short = dict(c for c in bare_convs if c[0] not in recurrent)
    kinds["short_conv"] = len(short)
    if any(kinds.values()):
        out["layer_kinds"] = {k: n for k, n in kinds.items() if n}
    if short:
        out["short_conv_layers"] = len(short)
        out["short_conv_taps"] = max(short.values())
        out["short_conv_gates"] = sum(
            1 for layer, operands in products
            if layer in short and sliced.intersection(operands))
    if kinds["state_space"]:
        out["state_space_layers"] = kinds["state_space"]
    if kinds["selective_scan"]:
        out["selective_scan_layers"] = kinds["selective_scan"]
    pairs = kinds["differential_attention"] + kinds["cross_decoder_attention"]
    if pairs:
        out["diff_attention_layers"] = pairs
    if kinds["cross_decoder_attention"]:
        out["shared_kv_readers"] = kinds["cross_decoder_attention"]
    if kinds["gated_memory"]:
        out["memory_readers"] = kinds["gated_memory"]
    if shared:
        grads = {grad_var_name(n) for n in shared}
        out["activation_grad_fanin_max"] = max(
            [len(op.input("X")) for op in block.ops
             if op.attrs.get("__role__") == "backward" and op.type == "sum"
             and op.output("Out")[0] in grads], default=1)
    if kinds["state_space"] or kinds["short_conv"]:
        group = max([_expanded_by(block, k) for k in full_keys], default=1)
        if group > 1:
            out["attention_kv_group"] = group
    updated_params = {n for op in block.ops
                      if op.attrs.get("__role__") == "optimize"
                      for n in op.inputs.get("Param", [])}
    frozen = sum(1 for p in block.all_parameters()
                 if p.trainable and p.name not in updated_params)
    if updated_params and frozen:
        out["frozen_parameters"] = frozen
    dense = sum(1 for scope in gated if scope not in routed)
    if routed and dense:
        out["dense_ffn_layers"] = dense
    updated = sum(1 for bias in biases if bias in copies)
    if updated:
        out["moe_router_bias_updates"] = updated
    turned = sum(1 for scope in mixers if scope in held_by["rotary_embedding"])
    if turned:
        out["attention_rotary_layers"] = turned
    if (turned or kinds["state_space"]) and turned < len(mixers):
        out["attention_unrotated_layers"] = len(mixers) - turned
    gated_mixers = sum(1 for scope in mixers if scope in held_by["sigmoid"])
    if gated_mixers:
        out["attention_gated_layers"] = gated_mixers
    gated_latent = sum(1 for scope in latent if scope in held_by["sigmoid"])
    if gated_latent:
        out["latent_attention_gated_layers"] = gated_latent
    out_norms = len(normed & added)
    if out_norms:
        out["residual_out_norms"] = out_norms
    if tables & multiplied:
        out["tied_heads"] = len(tables & multiplied)
    if scaled & added:
        out["residual_scaled_sublayers"] = len(scaled & added)
    return out


def _differential_scopes(forward):
    """Every `name_scope` that holds two `fused_attention` ops and an
    `elementwise_sub`: the two softmax maps of a differential attention layer
    and their difference."""
    calls, subtracts = {}, set()
    for op in forward:
        scope = op.attrs.get(ir.NAME_SCOPE_ATTR)
        if op.type == "fused_attention":
            calls[scope] = calls.get(scope, 0) + 1
        elif op.type == "elementwise_sub":
            subtracts.add(scope)
    return {scope for scope, n in calls.items()
            if scope is not None and n == 2 and scope in subtracts}


def _expanded_by(block, name) -> int:
    """How many times an `expand` op repeated the heads behind `name`: the
    op that wrote it, looked for through the `reshape`s between."""
    by_output = {n: op for op in block.ops for n in op.output_arg_names}
    op = by_output.get(name)
    while op is not None and op.type in ("reshape", "reshape2"):
        op = by_output.get(op.input("X")[0])
    if op is None or op.type != "expand":
        return 1
    times = 1
    for t in op.attrs.get("expand_times", []):
        times *= int(t)
    return times


def program_detail(program: ir.Program) -> Dict[str, object]:
    """What the executors write on a program's compile events."""
    return {**parameter_sharing(program), **layer_census(program)}
