"""Block -> XLA lowering.

This replaces the reference's executor hot loop (`for op in ops: op->Run(...)`,
reference: paddle/fluid/framework/executor.cc:321-366) and its per-op kernel
dispatch (operator.cc:635). TPU-native redesign: the whole block is traced
once through each op's JAX lowering rule into ONE jit-compiled XLA
computation; XLA then fuses/schedules what the reference interpreted op by op.

Gradient ops (produced by core/backward.py) are lowered generically: the
forward rule is re-traced under `jax.vjp`. Duplicate forward subexpressions
are eliminated by XLA CSE inside the single jit, so no residual plumbing is
required in the IR.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Set

import jax
import jax.numpy as jnp
import numpy as np

from . import ir, registry, types
from .ir import SEQLEN_SUFFIX
from .registry import EMPTY_VAR, FWD_OP_ATTR, GRAD_OP_SUFFIX, LoweringContext


class BlockLowerer:
    """Lowers a Block's op list into a pure function over an env dict."""

    def __init__(self, program: ir.Program, amp: bool = False,
                 check_nan_inf: bool = False, mesh=None,
                 detail: Optional[dict] = None):
        self.program = program
        # bf16 mixed precision for MXU ops (registry.AMP_OPS); params stay
        # fp32, accumulation is fp32 on the MXU.
        self.amp = amp
        # device mesh when compiling under ParallelExecutor; ops with
        # mesh-aware lowerings (fused_attention -> ring attention over the
        # 'sp' axis) read it via ctx.lowerer.mesh
        self.mesh = mesh
        # reference FLAGS_check_nan_inf (CheckTensorNANOrInf after every op,
        # operator.cc:622-634). XLA programs cannot raise, so each op's
        # float outputs contribute an all-finite flag; the executor checks
        # the flags on the host after the step and raises naming the first
        # offending (op, var).
        self.check_nan_inf = check_nan_inf
        self.nan_flags: List[tuple] = []  # (op_type, var_name, flag) per trace
        # control-flow sub-blocks lower inside lax.scan/while/cond body
        # traces where a recorded flag would be a leaked tracer; interior
        # ops are therefore covered at the control-flow op's boundary
        # (its outputs are checked at depth 1)
        self._block_depth = 0
        # the `detail` of the compile event of the step being lowered: the
        # executor's dict, so what a rule notes under the trace
        # (`LoweringContext.note`) is on the event of this step and no
        # other; a dict nobody reads where no executor gave one
        self.detail: dict = detail if detail is not None else {}
        # what each op counted there, by key (`LoweringContext.tally`)
        self.tallies: Dict[str, dict] = {}
        # AMP's shadows, for the length of one trace of a step
        # (`enter_step`): name -> the bf16 form of a float32 parameter the
        # step updates, and name -> the float32 value that form was made
        # from; and where `master_as` cast one of them the plain way
        self.shadows: Dict[str, Any] = {}
        self._shadow_source: Dict[str, Any] = {}
        self._plain_casts: Set[tuple] = set()

    # -- AMP shadows -----------------------------------------------------
    def enter_step(self, mut_state: Dict[str, Any],
                   shadows: Optional[Dict[str, Any]]):
        """Start one trace of the step: `shadows` are the bf16 forms the
        step was entered with, of the masters in `mut_state` of the same
        names (none: a step lowered without them casts as it always did)."""
        self.shadows = dict(shadows or {})
        self._shadow_source = {n: mut_state[n] for n in self.shadows}

    def shadow_of(self, name, env, value):
        """The shadow of the variable `name`, if the step carries one and
        `env` still binds the name to the value it was made from (the one
        the step was entered with, or the block's last write); else None:
        a sub-block's carried value, a name the rule was not handed by."""
        shadow = self.shadows.get(name)
        if shadow is None or env is None \
                or env.get(name) is not self._shadow_source[name] \
                or shadow.shape != value.shape:
            return None
        return shadow

    def note_plain_cast(self, name, site):
        """`registry.master_as` cast the float32 variable `name` the plain
        way at `site`: counted, once however often the step is traced, if
        the step carries a shadow of it. A step lowered without its
        shadows counts nothing."""
        if name in self.shadows:
            self._plain_casts.add(site)
            self.detail["amp_plain_master_casts"] = len(self._plain_casts)

    def _reshadow(self, op: ir.Operator, env: Dict[str, Any]):
        """After a top-level op: the next step's shadow of every shadowed
        variable the op wrote, cast where it is written (inside the op's
        named scope, so the convert joins the update's own fusion and
        carries its name). The block's last write wins."""
        for name in op.output_arg_names:
            if name in self.shadows \
                    and env[name] is not self._shadow_source[name]:
                self._shadow_source[name] = env[name]
                self.shadows[name] = env[name].astype(jnp.bfloat16)

    def run_block(self, block_idx: int, env: Dict[str, Any], key) -> Dict[str, Any]:
        """Execute all ops of `block_idx` on `env` (name -> jnp array),
        mutating and returning it. `key` is the step's base PRNG key."""
        block = self.program.blocks[block_idx]
        self._block_depth += 1
        try:
            for op_idx, op in enumerate(block.ops):
                self._run_op(block, op, op_idx, env, key)
        finally:
            self._block_depth -= 1
        return env

    # -- single op -------------------------------------------------------
    def _run_op(self, block: ir.Block, op: ir.Operator, op_idx: int,
                env: Dict[str, Any], key):
        if op.type.endswith(GRAD_OP_SUFFIX) and FWD_OP_ATTR in op.attrs:
            # the grad op's own type: whatever the forward rule lowers in
            # here (the generic vjp path re-traces it) is told apart, in the
            # compiled module and a device trace, from the forward op's
            with _named_scope(op.type, op.attrs[FWD_OP_ATTR]["attrs"]):
                self._run_grad_op(block, op, env, key)
                if self.shadows and self._block_depth == 1:
                    self._reshadow(op, env)
            if self.check_nan_inf and self._block_depth == 1:
                self._record_nan_flags_env(op, env)
            return
        opdef = registry.get_op_def(op.type)
        op_key = jax.random.fold_in(key, _op_seed(op, op_idx)) if opdef.needs_rng else None
        ins = _gather_inputs(op.inputs, env, op.type)
        ctx = LoweringContext(op.attrs, key=op_key, lowerer=self, op=op,
                              env=env, inputs=op.inputs)
        with _named_scope(op.type, op.attrs):
            outs = registry.call_rule(opdef, ctx, ins)
            _scatter_outputs(op, outs, env)
            if self.shadows and self._block_depth == 1:
                self._reshadow(op, env)
        if opdef.propagate_seqlen:
            _propagate_seqlen(op, env)
        if self.check_nan_inf and self._block_depth == 1:
            self._record_nan_flags(op, outs)

    def _record_nan_flags(self, op, outs):
        for slot, names in op.outputs.items():
            for name, val in zip(names, outs.get(slot, [])):
                self._record_one_flag(op.type, name, val)

    def _record_nan_flags_env(self, op, env):
        # grad ops scatter their outputs straight into env (vjp path);
        # check whatever actually got written
        for name in op.output_arg_names:
            self._record_one_flag(op.type, name, env.get(name))

    def _record_one_flag(self, op_type, name, val):
        if val is None or not hasattr(val, "dtype"):
            return
        if jnp.issubdtype(jnp.asarray(val).dtype, jnp.floating):
            self.nan_flags.append(
                (op_type, name, jnp.all(jnp.isfinite(val))))

    # -- generic vjp-based grad op --------------------------------------
    def _run_grad_op(self, block: ir.Block, op: ir.Operator,
                     env: Dict[str, Any], key):
        fwd = op.attrs[FWD_OP_ATTR]          # forward OpDesc as dict
        fwd_type, fwd_inputs, fwd_outputs = fwd["type"], fwd["inputs"], fwd["outputs"]
        fwd_attrs, fwd_idx = fwd["attrs"], fwd.get("__idx__", 0)
        opdef = registry.get_op_def(fwd_type)
        op_key = jax.random.fold_in(key, fwd_idx) if opdef.needs_rng else None

        if opdef.grad_lower is not None:
            ins = {s: [env[n] for n in ns] for s, ns in fwd_inputs.items()}
            out_grads = {}
            for slot, names in fwd_outputs.items():
                out_grads[slot] = [env.get(ir.grad_var_name(n)) for n in names]
            # forward OUTPUT values (already materialized in env): grads
            # that consume a saved output (reference convention, e.g.
            # softmax_grad takes Out) read them from ctx.fwd_outs instead
            # of recomputing
            fwd_outs = {slot: [env.get(n) for n in names]
                        for slot, names in fwd_outputs.items()}
            ctx = LoweringContext(fwd_attrs, key=op_key, lowerer=self, op=op,
                                  inputs=fwd_inputs, block_env=env)
            ctx.fwd_outs = fwd_outs
            grads = opdef.grad_lower(ctx, ins, out_grads)
            _write_input_grads(op, fwd_inputs, grads, env)
            return

        # Flatten differentiable fwd inputs; keep the rest closed over.
        diff_entries: List[tuple] = []   # (slot, pos, name)
        for slot, names in fwd_inputs.items():
            for pos, name in enumerate(names):
                val = env[name]
                if jnp.issubdtype(jnp.asarray(val).dtype, jnp.floating):
                    diff_entries.append((slot, pos, name))
        wanted = _wanted_input_grads(op)
        diff_entries = [e for e in diff_entries if e[2] in wanted]
        if not diff_entries:
            return
        diff_vals = [env[name] for _, _, name in diff_entries]
        if fwd_attrs.get("__remat__"):
            # memory_optimize marked this op: barrier the recompute inputs so
            # XLA cannot CSE the backward's re-traced forward with the
            # original — the activation is rematerialized, not kept in HBM
            diff_vals = list(jax.lax.optimization_barrier(tuple(diff_vals)))

        out_slots = [(slot, names) for slot, names in fwd_outputs.items() if names]

        def fwd_fn(*vals):
            ins = {s: [env[n] for n in ns] for s, ns in fwd_inputs.items()}
            # control-flow rules read values through ctx.env, not slot args —
            # patch a shadow env so perturbations flow through jax.vjp
            env2 = dict(env)
            for (slot, pos, name), v in zip(diff_entries, vals):
                ins[slot][pos] = v
                env2[name] = v
            ctx = LoweringContext(fwd_attrs, key=op_key, lowerer=self,
                                  env=env2, inputs=fwd_inputs, block_env=env)
            outs = registry.call_rule(opdef, ctx, ins)
            flat = []
            for slot, names in out_slots:
                flat.extend(outs[slot][: len(names)])
            return tuple(flat)

        declared_by_base = _declared_by_base(op)
        primals, vjp_fn = jax.vjp(fwd_fn, *diff_vals)
        cotangents = []
        i = 0
        for slot, names in out_slots:
            for name in names:
                primal = primals[i]
                i += 1
                g = env.get(ir.grad_var_name(name))
                if g is None:
                    g = _zero_cotangent(primal)
                elif jnp.issubdtype(jnp.asarray(primal).dtype, jnp.floating):
                    g = jnp.asarray(g, jnp.asarray(primal).dtype)
                else:
                    g = _zero_cotangent(primal)
                cotangents.append(g)
        in_grads = vjp_fn(tuple(cotangents))

        # Accumulate per-variable (a var may appear in several input slots).
        acc: Dict[str, Any] = {}
        for (slot, pos, name), g in zip(diff_entries, in_grads):
            if g is None or (hasattr(g, "dtype") and g.dtype == jax.dtypes.float0):
                continue
            acc[name] = g if name not in acc else acc[name] + g
        for name, g in acc.items():
            if name in declared_by_base:
                env[declared_by_base[name]] = g


def cast_masters(program: ir.Program, mut_names: Sequence[str]) -> List[str]:
    """Which of `mut_names` (the persistable variables a step updates) get
    a shadow under AMP, read off the Program alone: a float32 variable that
    an op of `registry.AMP_SHADOW_OPS` (or its grad op) reads, in any
    block, and whose bf16 form is at least `AMP_SHADOW_MIN_BYTES` (why
    those: the comment over them). Dense weights, embedding tables, norm
    and router weights, biases and small expert stacks get none: they are
    cast where they are read."""
    mut = set(mut_names)
    block = program.global_block()
    found: List[str] = []
    for blk in program.blocks:
        for op in blk.ops:
            fwd = op.attrs.get(FWD_OP_ATTR) \
                if op.type.endswith(GRAD_OP_SUFFIX) else None
            op_type, inputs = (fwd["type"], fwd["inputs"]) if fwd \
                else (op.type, op.inputs)
            if op_type not in registry.AMP_SHADOW_OPS:
                continue
            for names in inputs.values():
                for n in names:
                    if n not in mut or n in found:
                        continue
                    var = block._find_var_recursive(n)
                    if var is not None and var.dtype \
                            and jnp.dtype(var.dtype) == jnp.float32 \
                            and 2 * int(np.prod(var.shape)) \
                            >= registry.AMP_SHADOW_MIN_BYTES:
                        found.append(n)
    return found


def _named_scope(op_type: str, attrs: Dict[str, Any]):
    """The op's type, behind the prefixes of the `name_scope` it was
    appended in (a grad op passes its forward op's attributes)."""
    prefix = attrs.get(ir.NAME_SCOPE_ATTR)
    return jax.named_scope(f"{prefix}/{op_type}" if prefix else op_type)


def _op_seed(op: ir.Operator, op_idx: int) -> int:
    return int(op.attrs.get("__idx__", op_idx))


def _gather_inputs(inputs: Dict[str, List[str]], env: Dict[str, Any], op_type: str):
    ins = {}
    for slot, names in inputs.items():
        vals = []
        for n in names:
            if n == EMPTY_VAR:
                vals.append(None)
                continue
            if n not in env:
                raise KeyError(f"op {op_type}: input var {n!r} not materialized")
            vals.append(env[n])
        ins[slot] = vals
    return ins


def _scatter_outputs(op: ir.Operator, outs: Dict[str, List[Any]], env: Dict[str, Any]):
    for slot, names in op.outputs.items():
        if slot not in outs:
            continue
        vals = outs[slot]
        if len(vals) < len(names):
            raise ValueError(f"op {op.type}: slot {slot} produced {len(vals)} values "
                             f"for {len(names)} outputs")
        for name, val in zip(names, vals):
            if name != EMPTY_VAR and val is not None:
                env[name] = val


def _propagate_seqlen(op: ir.Operator, env: Dict[str, Any]):
    """Variable-length (LoD-analog) bookkeeping: elementwise-ish ops carry
    the first input's length companions onto their outputs — the bare
    @SEQLEN (outer level) and, for nested LoD, the @SEQLEN.1 inner
    lengths."""
    for suffix in (SEQLEN_SUFFIX, SEQLEN_SUFFIX + ".1"):
        src = None
        for names in op.inputs.values():
            for n in names:
                if n != EMPTY_VAR and (n + suffix) in env:
                    src = env[n + suffix]
                    break
            if src is not None:
                break
        if src is None:
            continue
        for names in op.outputs.values():
            for n in names:
                if n != EMPTY_VAR and n in env and (n + suffix) not in env:
                    val = env[n]
                    if hasattr(val, "ndim") and val.ndim >= 2 \
                            and val.shape[0] == src.shape[0]:
                        env[n + suffix] = src


def _grad_base(grad_name: str) -> str:
    """`x@GRAD` or `x@GRAD@RENAME@k` -> `x` (fan-in contributions are renamed
    by core/backward.py before a `sum` op re-merges them)."""
    return grad_name.split(ir.GRAD_SUFFIX)[0]


def _declared_by_base(grad_op: ir.Operator) -> Dict[str, str]:
    out = {}
    for names in grad_op.outputs.values():
        for n in names:
            if n != EMPTY_VAR and ir.GRAD_SUFFIX in n:
                out[_grad_base(n)] = n
    return out


def _wanted_input_grads(grad_op: ir.Operator) -> Set[str]:
    return set(_declared_by_base(grad_op))


def _write_input_grads(grad_op, fwd_inputs, grads: Dict[str, Any], env):
    declared = _declared_by_base(grad_op)
    for slot, g in grads.items():
        names = fwd_inputs.get(slot, [])
        gs = g if isinstance(g, (list, tuple)) else [g]
        for name, gv in zip(names, gs):
            if gv is None or name not in declared:
                continue
            gname = declared[name]
            env[gname] = gv if gname not in env else env[gname] + gv


def _zero_cotangent(primal):
    arr = jnp.asarray(primal)
    if jnp.issubdtype(arr.dtype, jnp.floating):
        return jnp.zeros(arr.shape, arr.dtype)
    return np.zeros(arr.shape, jax.dtypes.float0)
