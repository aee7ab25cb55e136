"""Program-level autodiff: `append_backward`.

Capability parity with the reference's program-level backward pass
(reference: python/paddle/fluid/backward.py:450 `append_backward`,
`_append_backward_ops_` :295, `_addup_repetitive_outputs_` :120,
`_remove_no_grad_branch_` :189).

TPU-native redesign: instead of ~200 hand-written GradOpDescMakers
(reference: grad_op_desc_maker.h:34), every forward op gets ONE generic grad
op whose lowering re-traces the forward rule under `jax.vjp`
(core/lowering.py). The graph-level concerns stay explicit in the IR exactly
as in the reference: fan-in gradient accumulation inserts `sum` ops, and
stop_gradient / no_grad_set prune dead branches.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Set, Tuple

from . import ir, registry
from .ir import GRAD_SUFFIX, grad_var_name
from .registry import EMPTY_VAR, FWD_OP_ATTR, GRAD_OP_SUFFIX

# Ops that never need/propagate gradients.
_NON_DIFF_OPS = {"fill_constant", "uniform_random", "gaussian_random", "feed",
                 "fetch", "accuracy", "increment", "assign_value", "shape",
                 "iota", "truncated_gaussian_random"}


def _grad_contrib_name(name: str, k: int) -> str:
    return f"{name}{GRAD_SUFFIX}@RENAME@{k}"


def append_backward(loss: ir.Variable,
                    parameter_list: Optional[Sequence[str]] = None,
                    no_grad_set: Optional[Set[str]] = None,
                    ) -> List[Tuple[ir.Variable, ir.Variable]]:
    """Append gradient ops for `loss` to its program's global block.

    Returns [(parameter, gradient_variable)] pairs, like the reference.
    """
    block = loss.block
    program = block.program
    no_grad = set(no_grad_set or ())

    # 1. d(loss)/d(loss) = 1.
    loss_grad = _ensure_grad_var(block, loss)
    block.append_op(
        "fill_constant",
        outputs={"Out": [loss_grad.name]},
        attrs={"shape": list(loss.shape) if loss.shape else [1],
               "dtype": loss.dtype, "value": 1.0,
               "__role__": "backward"},
    )

    # 2. Reverse walk emitting grad ops; collect per-var grad contributions.
    #
    # Contributions are tracked in EPOCHS: programs are not strictly SSA (a
    # `while` loop or `assign(x, out=y)` re-writes an existing name), and
    # grad contributions to different SSA "versions" of a name must never be
    # summed together. When the reverse walk passes an op that WRITES var n,
    # n's current epoch closes and a fresh one opens; each epoch's
    # contributions are summed separately into the canonical `n@GRAD` name,
    # and ordered execution (grad ops run reverse-fwd) makes the canonical
    # name hold the right epoch's value at every read point. (The reference
    # gets the same effect with per-step scopes, while_op.cc:96.)
    loss_idx = _find_producer_idx(block, loss.name)
    # var -> list of epochs; each epoch is a list of (contrib_name, grad_op)
    contribs: Dict[str, List[List[Tuple[str, Optional[ir.Operator]]]]] = {
        loss.name: [[(loss_grad.name, block.ops[-1])]]}
    rename_counter: Dict[str, int] = {}
    fwd_ops = list(enumerate(block.ops[: loss_idx + 1]))

    def _has_grad(n):
        # only the CURRENT epoch's contributions are reachable by ops at this
        # point of the reverse walk; earlier epochs belong to later SSA
        # versions of the name (severed by a write barrier)
        return n in contribs and bool(contribs[n][-1])

    def _write_barrier(op):
        # this op produced these names; earlier consumers see the previous
        # SSA version, so their grads start a new epoch. Applies to EVERY
        # producing op — a non-diff op (fill_constant out=x) severs the
        # dependency just as thoroughly as a diff one.
        for ns in op.outputs.values():
            for n in ns:
                if n in contribs:
                    contribs[n].append([])

    for idx, op in reversed(fwd_ops):
        if op.type.endswith(GRAD_OP_SUFFIX):
            continue
        if op.type in _NON_DIFF_OPS:
            _write_barrier(op)
            continue
        out_has_grad = any(_has_grad(n) for ns in op.outputs.values() for n in ns)
        if not out_has_grad:
            _write_barrier(op)
            continue
        if op.type == "while":
            raise NotImplementedError(
                "gradients cannot flow through an unbounded `while` loop on "
                "TPU (lax.while_loop is not reverse-differentiable); pass "
                "While(cond, max_iters=N) for a scan-based differentiable "
                "loop, or use layers.StaticRNN / DynamicRNN / dynamic_lstm")
        grad_targets = _grad_needing_inputs(block, op, no_grad, parameter_list)

        # out-grad inputs: canonical @GRAD names.
        out_grad_names = []
        for ns in op.outputs.values():
            for n in ns:
                if _has_grad(n):
                    out_grad_names.append(grad_var_name(n))

        _write_barrier(op)

        if not grad_targets:
            continue

        # in-grad outputs: contribution names within the target's epoch.
        out_names, touched = [], []
        for n in grad_targets:
            epochs = contribs.setdefault(n, [[]])
            epoch = epochs[-1]
            if not epoch:
                cname = grad_var_name(n)
            else:
                k = rename_counter.get(n, 0) + 1
                rename_counter[n] = k
                cname = _grad_contrib_name(n, k)
            out_names.append(cname)
            touched.append(n)
            _ensure_grad_var(block, block.var(n), cname)

        fwd_desc = op.to_dict()
        fwd_desc["__idx__"] = idx
        grad_op = ir.Operator(
            block, op.type + GRAD_OP_SUFFIX,
            inputs={"FwdIn": sorted({n for ns in op.inputs.values() for n in ns}),
                    "OutGrad": out_grad_names},
            outputs={"InGrad": out_names},
            attrs={FWD_OP_ATTR: fwd_desc, "__role__": "backward"},
        )
        block.ops.append(grad_op)
        program._bump()
        for n, cname in zip(touched, out_names):
            contribs[n][-1].append((cname, grad_op))

    # 3. Fan-in accumulation per epoch: rename the epoch's first contribution
    # (which took the canonical name) and insert a `sum` op right after the
    # epoch's last contribution (reference `_addup_repetitive_outputs_`).
    _insert_sum_ops(block, contribs, loss.name, rename_counter)

    # 4. Collect (param, grad) pairs.
    params = block.all_parameters()
    if parameter_list is not None:
        wanted = set(parameter_list)
        params = [p for p in params if p.name in wanted]
    pairs = []
    for p in params:
        if not p.trainable or p.name in no_grad:
            continue
        gname = grad_var_name(p.name)
        if p.name in contribs:
            pairs.append((p, block.var(gname)))
    return pairs


def _insert_sum_ops(block: ir.Block, contribs, loss_name: str,
                    rename_counter: Dict[str, int]):
    # Collect (var, epoch) groups needing a sum, with their op references.
    pending = []  # (n, [(cname, op), ...])
    for n, epochs in contribs.items():
        if n == loss_name:
            continue
        for epoch in epochs:
            if len(epoch) > 1:
                pending.append((n, epoch))
    if not pending:
        return
    for n, epoch in pending:
        canonical = grad_var_name(n)
        # rename the epoch's first contribution (it took the canonical name)
        first_name, first_op = epoch[0]
        k = rename_counter.get(n, 0) + 1
        rename_counter[n] = k
        renamed0 = _grad_contrib_name(n, k)
        for slot, names in first_op.outputs.items():
            for j, out in enumerate(names):
                if out == first_name:
                    names[j] = renamed0
        _ensure_grad_var(block, block.var(n), renamed0)
        srcs = [renamed0] + [c for c, _ in epoch[1:]]
        # insert the sum right after the epoch's last contributing op
        ops_in_epoch = {id(op) for _, op in epoch}
        last_idx = max(i for i, op in enumerate(block.ops)
                       if id(op) in ops_in_epoch)
        block.insert_op(last_idx + 1, "sum",
                        inputs={"X": srcs}, outputs={"Out": [canonical]},
                        attrs={"__role__": "backward"})


def parameter_sharing(program: ir.Program) -> Dict[str, int]:
    """How far the program shares its weights, read back from the global
    block: `parameters`; `parameter_uses`, the reads of a parameter by a
    forward op (a parameter counts once an op); `grad_fanin_max`, the most
    gradient contributions summed into one parameter: the longest `X` of the
    `sum` ops `_insert_sum_ops` wrote for a parameter's gradient, 1 where
    every parameter has one contribution, 0 in a program without a backward
    pass. An unrolled loop over shared layers reads `uses = loops x
    parameters` and a fan-in of `loops`; a copy of the weights per pass
    would read a fan-in of 1. Goes on the program's compile events
    (`observe.observatory()`, `detail`)."""
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
    `moe_expert_activation`: `relu2` where the routed experts are two
    matrices around a `relu2` op (absent for gated silu experts).
    (`moe_row_buffer_rows`, the rows of the expert layer's layout, follows
    the batch: `moe_dispatch`'s rule notes it on the same event under the
    trace, `LoweringContext.note`.)"""
    block = program.global_block()
    kinds = {"linear_attention": 0, "full_attention": 0,
             "latent_attention": 0, "window_attention": 0,
             "sparse_attention": 0, "state_space": 0}
    out: Dict[str, object] = {}
    copies: Dict[str, str] = {}     # an `assign` op's result -> what it copied
    biases = []                     # the routers' selection biases
    gated, routed = [], set()       # name scopes of `swiglu`s, of routers
    mixers = []                     # name scopes of `fused_attention`s
    full_keys = []                  # the full-attention ops' K
    held_by = {"rotary_embedding": set(), "sigmoid": set()}  # name scopes
    normed, added = set(), set()    # `rms_norm` results, residual addends
    for op in block.ops:
        if op.attrs.get("__role__") is not None:
            continue
        scope = op.attrs.get(ir.NAME_SCOPE_ATTR)
        if op.type == "gated_delta_rule":
            kinds["linear_attention"] += 1
        elif op.type == "ssd_scan":
            kinds["state_space"] += 1
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
            seq = keys.shape[1 if op.attrs.get("layout") == "BTHD" else -2]
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
            biases += [copies.get(name, name)
                       for name in op.inputs.get("Bias", [])]
        elif op.type == "moe_dispatch":
            out["moe_experts_held"] = op.attrs.get(
                "experts_held", out.get("moe_experts_routed"))
    if any(kinds.values()):
        out["layer_kinds"] = {k: n for k, n in kinds.items() if n}
    if kinds["window_attention"]:
        out["attention_window_layers"] = kinds["window_attention"]
    if kinds["sparse_attention"]:
        out["dsa_layers"] = kinds["sparse_attention"]
    if kinds["state_space"]:
        out["state_space_layers"] = kinds["state_space"]
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
    out_norms = len(normed & added)
    if out_norms:
        out["residual_out_norms"] = out_norms
    return out


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


def _grad_needing_inputs(block, op, no_grad, parameter_list) -> List[str]:
    """Inputs of `op` that should receive gradients (dedup, order-stable)."""
    seen, out = set(), []
    for ns in op.inputs.values():
        for n in ns:
            if n in seen or n == EMPTY_VAR:
                continue
            seen.add(n)
            if n in no_grad:
                continue
            if not block.has_var(n):
                continue
            v = block.var(n)
            from .types import is_float_dtype
            if v.stop_gradient or not is_float_dtype(v.dtype):
                continue
            out.append(n)
    return out


def _ensure_grad_var(block: ir.Block, fwd_var: ir.Variable, name: Optional[str] = None):
    name = name or grad_var_name(fwd_var.name)
    if name in block.vars:
        return block.vars[name]
    return block.create_var(name=name, shape=fwd_var.shape, dtype=fwd_var.dtype,
                            stop_gradient=True)


def _find_producer_idx(block: ir.Block, name: str) -> int:
    for i in range(len(block.ops) - 1, -1, -1):
        if name in block.ops[i].output_arg_names:
            return i
    raise ValueError(f"loss var {name!r} has no producing op in block")


def calc_gradient(targets, inputs, target_gradients=None, no_grad_set=None):
    """Reference calc_gradient analog (backward.py:667): gradients of
    `targets` w.r.t. `inputs`."""
    targets = targets if isinstance(targets, (list, tuple)) else [targets]
    inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    if len(targets) != 1:
        raise NotImplementedError("calc_gradient currently supports one target")
    pairs = append_backward(targets[0], no_grad_set=no_grad_set,
                            parameter_list=None)
    block = targets[0].block
    outs = []
    for v in inputs:
        gname = grad_var_name(v.name)
        outs.append(block.var(gname) if block.has_var(gname) else None)
    return outs
