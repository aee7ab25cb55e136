"""Op registry: each op type maps to a JAX lowering rule.

Capability parity with the reference's operator registry + kernel dispatch
(reference: paddle/fluid/framework/op_registry.h:185-217, op_info.h:68,
operator.cc:635-830). TPU-native redesign: an "op kernel" is a pure JAX
function (the *lowering rule*); whole blocks are traced through these rules
into a single XLA computation, so there is no per-op dispatch at runtime, no
OpKernelType keying, and no data-transform insertion — XLA owns layout/fusion.

Shape inference (reference shape_inference.h:30) is derived from the lowering
rule itself via `jax.eval_shape`: the rule is the single source of truth for
both compile-time shapes and runtime values.
"""

from __future__ import annotations

import inspect
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..observe import steplog as _steplog
from . import types

# Sentinel size substituted for -1 (unknown batch) dims during build-time shape
# inference. Prime and large, so it never collides with a real feature dim.
DIM_SENTINEL = 8191
# Second, distinct prime for the confirmation trace: a dim is dynamic iff it
# CHANGES when the sentinel changes (see infer_op_shapes). Divisibility alone
# cannot classify mixed derivations like concat(dynamic, static) = S+k.
DIM_SENTINEL_ALT = 7919

EMPTY_VAR = "@EMPTY@"
GRAD_OP_SUFFIX = "_grad"
FWD_OP_ATTR = "__fwd_op__"  # grad ops carry the forward OpDesc dict here


class OpDef:
    def __init__(self, type: str, lower: Callable, infer: Optional[Callable],
                 needs_rng: bool, propagate_seqlen: bool,
                 grad_lower: Optional[Callable] = None):
        self.type = type
        self.lower = lower
        self.infer = infer
        self.needs_rng = needs_rng
        self.propagate_seqlen = propagate_seqlen
        self.grad_lower = grad_lower
        # parameter names of the rule (minus ctx) = input slot names
        sig = inspect.signature(lower)
        params = list(sig.parameters.values())[1:]
        self.input_slots = [p.name for p in params]
        self.optional_slots = {p.name for p in params if p.default is not inspect.Parameter.empty}


_REGISTRY: Dict[str, OpDef] = {}


def register_op(type: str, infer: Optional[Callable] = None, needs_rng: bool = False,
                propagate_seqlen: bool = True):
    """Decorator registering a lowering rule for op `type`.

    The rule's signature is ``rule(ctx, SlotA, SlotB=None, ...)`` where slot
    parameter names match the OpDesc input slots; each receives a jnp array
    (or a list when the slot holds multiple vars, e.g. `sum`'s X). It returns
    ``{output_slot: array_or_list}``.
    """

    def deco(fn):
        if type in _REGISTRY:
            raise ValueError(f"op {type!r} already registered")
        _REGISTRY[type] = OpDef(type, fn, infer, needs_rng, propagate_seqlen)
        return fn

    return deco


def register_grad(type: str):
    """Optionally register a hand-written grad lowering for op `type`
    (overrides the generic vjp-based grad). Signature:
    ``grad(ctx, ins: dict, out_grads: dict) -> dict[input_slot, grad]``."""

    def deco(fn):
        if type not in _REGISTRY:
            close = close_op_names(type)
            hint = f"; closest registered: {', '.join(close)}" if close else ""
            raise ValueError(
                f"register_grad({type!r}): forward op {type!r} is not "
                f"registered — register_op must run first{hint}")
        _REGISTRY[type].grad_lower = fn
        return fn

    return deco


def close_op_names(name: str, n: int = 3) -> List[str]:
    """Registered op types most similar to `name` (typo hints for
    register_grad and the analysis verifier)."""
    import difflib
    return difflib.get_close_matches(name, _REGISTRY, n=n)


def get_op_def(type: str) -> OpDef:
    if type not in _REGISTRY:
        raise KeyError(f"op type {type!r} is not registered")
    return _REGISTRY[type]


def is_registered(type: str) -> bool:
    return type in _REGISTRY


def registered_ops() -> List[str]:
    return sorted(_REGISTRY)


class LoweringContext:
    """Per-op context handed to lowering rules.

    attrs: the OpDesc attrs; key: a PRNG key unique to (step, op position) for
    random ops, threaded functionally through the compiled step (replacing the
    reference's per-op cuRAND states).
    """

    # Sentinel the CURRENT abstract trace substituted for -1 dims: custom
    # `infer` rules must test dynamicness against this, not the module
    # constant (infer_op_shapes runs a second trace with DIM_SENTINEL_ALT
    # to tell sentinel-derived dims from real ones).
    dim_sentinel = DIM_SENTINEL

    def __init__(self, attrs: Dict[str, Any], key=None, lowerer=None, op=None,
                 env=None, inputs=None, block_env=None):
        self.attrs = attrs
        self.key = key
        self.lowerer = lowerer   # BlockLowerer, for control-flow sub-blocks
        self.op = op
        self.env = env           # live env dict (control-flow ops only)
        # {slot: names} of the variables whose values the rule is handed
        # (a grad op's: its forward op's inputs), and the env of the block
        # the op stands in, which binds them: `env` itself, except under
        # the generic grad's `jax.vjp`, where `env` is a copy patched with
        # the vjp's own tracers. `master_as` looks a master up by them.
        self.inputs = inputs
        self.block_env = block_env if block_env is not None else env

    def attr(self, name: str, default=None):
        return self.attrs.get(name, default)

    def note(self, **facts):
        """What a rule knows only under the trace (a static row count that
        follows the batch), onto the detail of the compile event of the
        step being traced: the lowerer holds that dict. Nothing where a
        rule is called outside a program's lowering."""
        if self.lowerer is not None:
            self.lowerer.detail.update(facts)

    def tally(self, key: str, amount: int = 1):
        """Count this op under `key` on the compile event: how many of the
        program's ops took a path that only their rule knows of, or with an
        `amount` the sum of what each of them counts (the score tiles a
        windowed attention call computes). `note` overwrites, so the lowerer
        keeps what each op has counted: an op counts once however often the
        step is traced."""
        if self.lowerer is None:
            return
        counted = self.lowerer.tallies.setdefault(key, {})
        counted[id(self.op)] = amount
        self.note(**{key: sum(counted.values())})


# AMP policy (torch-autocast style; reference analog:
# paddle/contrib/float16/float16_transpiler.py rewrote programs to fp16).
# MXU-heavy ops cast f32 inputs to bf16 and KEEP bf16 outputs — activations
# flow through the network in bf16 and never round-trip f32 in HBM (a cast
# feeding a conv cannot fuse on TPU, so per-op up/down-casts cost a full
# read+write of every activation).  Numerically sensitive ops upcast bf16
# inputs to f32.  Everything else runs in whatever dtype reaches it.  The
# f32 master params become bf16 in one place, `master_as`, at their point
# of use, so the vjp delivers f32 grads to the optimizer.  One kind of
# parameter is not cast there at all: a large expert stack that a Pallas
# call reads (AMP_SHADOW_OPS and AMP_SHADOW_MIN_BYTES below), whose cast XLA
# can neither fold into its consumer nor keep in fast memory, a whole-array
# pass of 4 bytes read and 2 written a parameter a step.  The step carries
# that parameter's bf16 form (its "shadow") from one run to the next beside
# the donated state (core/executor.py::_StateCache), the op that writes the
# parameter writes the next shadow in the same fusion
# (lowering.py::BlockLowerer._reshadow), and `master_as` hands the shadow
# out under a `custom_vjp` whose backward is the cast's own transpose.
AMP_BF16_OPS = frozenset({"conv2d", "depthwise_conv2d", "conv2d_transpose",
                          "mul", "matmul", "lstm", "gru", "fc",
                          "fused_attention", "grouped_matmul"})
# Which parameters get a shadow (lowering.py::cast_masters reads both off the
# Program). The consumer: an op of AMP_BF16_OPS whose rule hands the bf16
# form to a Pallas call. XLA cannot fold the cast into a custom call, so it
# is a pass of its own over the whole array; a dot or a convolution takes
# the cast into its own fusion or keeps no bf16 copy from forward to
# backward, so a dense parameter's shadow is 2 bytes a parameter of HBM that
# nothing held before (+0.33 to +0.57 GB in three cells for 0.2-1.2% of
# step: PERF.md section 6, PR 59). The size: a bf16 form that the chip's
# fast memory cannot hold (128 MiB of VMEM on a v5e). XLA casts a smaller
# stack straight into fast memory, for the 4 bytes a parameter the cast
# reads, and the kernels read it from there; its shadow lives in HBM, is
# read from HBM by both products and costs the update 2 bytes more
# (Nemotron-3-Nano's 80 MB stacks: +0.92 ms on a 60.96 ms step). A larger
# one is cast HBM to HBM, 6 bytes a parameter, every step (OLMoE's 268 MB
# stacks: 1.22 ms each), which is what its shadow takes out.
AMP_SHADOW_OPS = frozenset({"grouped_matmul"})
AMP_SHADOW_MIN_BYTES = 128 << 20
# NOTE: plain `softmax` deliberately NOT f32-listed: jax.nn.softmax is
# max-subtracted so bf16 is safe, and an f32 round trip on [B,H,T,T]
# attention weights doubles the dominant HBM traffic of unfused attention.
# The loss-adjacent softmaxes (softmax_with_cross_entropy & co) stay f32.
AMP_F32_OPS = frozenset({"log_softmax", "cross_entropy",
                         "softmax_with_cross_entropy",
                         "sigmoid_cross_entropy_with_logits",
                         "square_error_cost", "smooth_l1", "huber_loss",
                         "mean", "reduce_mean", "nce", "hierarchical_sigmoid",
                         "linear_chain_crf", "warpctc", "cos_sim",
                         # router logits, the softmax over all experts and
                         # what both router losses are built from; rms_norm
                         # keeps its statistics in f32 inside its rule and
                         # needs no entry (its output stays in bf16)
                         "moe_router",
                         # a looped LM's gate logit; what is built from it
                         # (log-sigmoids, their running sums, the exit
                         # distribution and its entropy) then stays float32:
                         # elementwise ops keep the dtype that reaches them
                         "exit_gate",
                         # a delta-rule layer's log-decay and write strength
                         # from two bf16 projections: softplus, exp and
                         # sigmoid in float32; `gated_delta_rule` then keeps
                         # its sums, norms and state in float32 inside its
                         # rule, like rms_norm, and needs no entry
                         "delta_rule_gates",
                         # a state-space layer's step size and log-decay
                         # from a bf16 projection, likewise; `ssd_scan`
                         # keeps its sums, decays and state in float32
                         # inside its rule
                         "ssd_gates",
                         # a KDA layer's log-decay per key channel and write
                         # strength from two bf16 projections: exp and both
                         # sigmoids in float32; `kda_delta_rule` keeps its
                         # sums, norms and state in float32 inside its rule
                         "kda_gates",
                         # a Mamba-1 scan whole: x, dt's raw form, B and C
                         # arrive bf16 and are widened before the rule, whose
                         # softplus, decays, state and sums are float32; y
                         # leaves float32
                         "selective_scan"})
# Mixed-dtype elementwise ops downcast the f32 side to bf16 instead of
# letting numpy promotion upcast the bf16 side: one f32 mask/bias/table
# leaking into the residual or attention-score stream would otherwise
# promote every downstream tensor to f32 and double its HBM traffic.
# bf16 keeps the full f32 exponent range, so additive masks (-1e9) and
# scales survive the downcast.
AMP_DOWNCAST_OPS = frozenset({"elementwise_add", "elementwise_sub",
                              "elementwise_mul", "elementwise_div",
                              "elementwise_max", "elementwise_min"})
# Back-compat alias (older tests/tools referenced AMP_OPS).
AMP_OPS = AMP_BF16_OPS


def _cast_to(v, dt_from, dt_to):
    if hasattr(v, "dtype") and v.dtype == dt_from:
        return v.astype(dt_to)
    return v


@jax.custom_vjp
def _shadowed(master, shadow):
    """`master.astype(bf16)`, read from `shadow`, which holds it already."""
    return shadow


_shadowed.defvjp(lambda master, shadow: (shadow, None),
                 lambda _, g: (g.astype(jnp.float32), None))


def master_as(ctx: LoweringContext, slot: str, pos: int, v, dtype):
    """`v`, the value a rule was handed for input `pos` of `slot`, in
    `dtype`. The one place a float32 master becomes bf16: `amp_cast` and
    the registered grads that cast their masters themselves come through
    here. Where the step carries a shadow of the variable of that name and
    the block still binds the name to the value the shadow was made from
    (`BlockLowerer.shadow_of`: by name, since the generic grad re-traces
    the forward rule on `jax.vjp`'s own tracers), the shadow is the result
    and the gradient reaches the master as the cast's transpose delivers
    it. Anything else is a plain cast; one of a shadowed variable is
    counted on the step's compile event (`amp_plain_master_casts`)."""
    if not hasattr(v, "dtype") or v.dtype == dtype:
        return v
    lowerer = ctx.lowerer
    if (lowerer is not None and ctx.inputs is not None
            and v.dtype == jnp.float32 and dtype == jnp.bfloat16):
        names = ctx.inputs.get(slot, ())
        name = names[pos] if pos < len(names) else None
        shadow = lowerer.shadow_of(name, ctx.block_env, v)
        if shadow is not None:
            return _shadowed(v, shadow)
        lowerer.note_plain_cast(name, (id(ctx.inputs), slot, pos))
    return v.astype(dtype)


def amp_cast(opdef: OpDef, ctx: LoweringContext,
             ins_by_slot: Dict[str, List[Any]]) -> Dict[str, List[Any]]:
    """`ins_by_slot` as AMP hands it to the op's rule: float32 -> bf16 for
    AMP_BF16_OPS (and for a mixed-dtype AMP_DOWNCAST_OPS op), bf16 -> float32
    for AMP_F32_OPS, untouched otherwise. `call_rule` applies it; a grad rule
    that works on saved outputs instead of re-tracing the forward rule calls
    it itself, so both see the same dtypes."""
    if ctx.lowerer is None or not getattr(ctx.lowerer, "amp", False):
        return ins_by_slot
    if opdef.type in AMP_BF16_OPS:
        # an MXU op's operands: the masters among them through `master_as`
        return {slot: [master_as(ctx, slot, i, v, jnp.bfloat16)
                       if getattr(v, "dtype", None) == jnp.float32 else v
                       for i, v in enumerate(vals)]
                for slot, vals in ins_by_slot.items() if vals}
    if opdef.type in AMP_F32_OPS:
        pair = (jnp.bfloat16, jnp.float32)
    elif opdef.type in AMP_DOWNCAST_OPS:
        dtypes = {jnp.dtype(v.dtype)
                  for vals in ins_by_slot.values() for v in vals
                  if hasattr(v, "dtype")}
        if not (jnp.dtype(jnp.bfloat16) in dtypes
                and jnp.dtype(jnp.float32) in dtypes):
            return ins_by_slot
        # the float32 side of a mixed elementwise op: the cast fuses into
        # the op itself, so there is no pass over the array to take out
        pair = (jnp.float32, jnp.bfloat16)
    else:
        return ins_by_slot
    return {slot: [_cast_to(v, *pair) for v in vals]
            for slot, vals in ins_by_slot.items() if vals}


def call_rule(opdef: OpDef, ctx: LoweringContext, ins_by_slot: Dict[str, List[Any]]):
    """Dispatch arrays to the rule per its signature; normalize outputs."""
    ins_by_slot = amp_cast(opdef, ctx, ins_by_slot)
    kwargs = {}
    for slot in opdef.input_slots:
        vals = ins_by_slot.get(slot)
        if vals is None or len(vals) == 0:
            if slot not in opdef.optional_slots:
                raise ValueError(f"op {opdef.type}: required input slot {slot!r} missing")
            continue
        kwargs[slot] = vals[0] if len(vals) == 1 else list(vals)
    out = opdef.lower(ctx, **kwargs)
    if out is None:
        out = {}
    return {slot: (list(v) if isinstance(v, (list, tuple)) else [v])
            for slot, v in out.items()}


# ---------------------------------------------------------------------------
# Build-time shape inference via eval_shape (reference: InferShape contexts).
# ---------------------------------------------------------------------------

def _mark_dynamic(shape_a, shape_b):
    """Classify each output dim by comparing the two sentinel traces: a
    dim that moved when the sentinel moved derives from the dynamic input
    dim -> -1. This classifies EVERY arithmetic derivation — identity,
    multiples (flatten), and mixed sums like concat(dynamic, static) =
    S+k, which the old divisible-by-sentinel test left as a bogus
    concrete extent (e.g. 8194) that then poisoned downstream inference."""
    if shape_b is None:
        return tuple(int(d) for d in shape_a)
    return tuple(-1 if int(a) != int(b) else int(a)
                 for a, b in zip(shape_a, shape_b))


def _eval_abstract(opdef, attrs, ins_by_slot, sentinel):
    """One abstract trace with `sentinel` standing in for -1 dims.
    Returns {slot: [ShapeDtypeStruct, ...]}."""
    structs: Dict[str, List[jax.ShapeDtypeStruct]] = {}
    for slot, pairs in ins_by_slot.items():
        ss = []
        for shape, dtype in pairs:
            shp = [sentinel if d == -1 else int(d) for d in shape]
            ss.append(jax.ShapeDtypeStruct(tuple(shp), types.np_dtype(dtype)))
        structs[slot] = ss

    if opdef.infer is not None:
        ctx = LoweringContext(attrs)
        ctx.dim_sentinel = sentinel
        result = opdef.infer(ctx, structs)
    else:
        key = jax.random.key(0)

        def f(ins):
            ctx = LoweringContext(attrs, key=key)
            ctx.dim_sentinel = sentinel
            return call_rule(opdef, ctx, ins)

        result = jax.eval_shape(f, structs)
    return {slot: (list(vals) if isinstance(vals, (list, tuple)) else [vals])
            for slot, vals in result.items()}


# (op_type, attrs json, input signature) -> inferred result. Abstract
# traces are pure functions of the key, and model builders repeat
# identical layers (64 transformer blocks = 64x the same per-op shapes),
# so memoizing collapses the build-time cost of the two-sentinel scheme.
_infer_cache: Dict[tuple, Dict[str, List[tuple]]] = {}
_MAX_INFER_CACHE = 4096


def _infer_cache_key(op_type, attrs, ins_by_slot):
    import json
    try:
        akey = json.dumps(attrs, sort_keys=True, default=repr)
    except (TypeError, ValueError):  # unserializable attr -> don't cache
        return None
    sig = tuple(sorted((slot, tuple((tuple(s), str(d)) for s, d in pairs))
                       for slot, pairs in ins_by_slot.items()))
    return (op_type, akey, sig)


def infer_op_shapes(op_type: str, attrs: Dict[str, Any],
                    ins_by_slot: Dict[str, List[Any]]):
    """Return {output_slot: [(shape, dtype), ...]} for an op given input
    (shape, dtype) pairs. -1 dims are substituted with a sentinel and
    traced through the lowering rule abstractly; a second trace with a
    different sentinel identifies which output dims derive from the
    dynamic inputs (those map back to -1).

    While a set-up phase is open on this thread (the body of a
    `program_guard`) the call's seconds are summed on it: the phase's
    `infer_shapes_s` / `infer_shapes_calls` (observe/steplog.py)."""
    phase = _steplog.open_phase()
    if phase is None:
        return _infer_op_shapes(op_type, attrs, ins_by_slot)
    t0 = time.perf_counter()
    try:
        return _infer_op_shapes(op_type, attrs, ins_by_slot)
    finally:
        phase.add("infer_shapes", time.perf_counter() - t0)


def _infer_op_shapes(op_type, attrs, ins_by_slot):
    opdef = get_op_def(op_type)
    key = _infer_cache_key(op_type, attrs, ins_by_slot)
    hit = _infer_cache.get(key) if key is not None else None
    if hit is not None:
        return {slot: list(pairs) for slot, pairs in hit.items()}
    had_dynamic = any(d == -1 for pairs in ins_by_slot.values()
                      for shape, _ in pairs for d in shape)
    result = _eval_abstract(opdef, attrs, ins_by_slot, DIM_SENTINEL)
    result_alt = (_eval_abstract(opdef, attrs, ins_by_slot, DIM_SENTINEL_ALT)
                  if had_dynamic else None)

    out = {}
    for slot, vals in result.items():
        alts = result_alt[slot] if result_alt is not None else [None] * len(vals)
        out[slot] = [(_mark_dynamic(v.shape, a.shape if a is not None else None),
                      types.canonical_dtype(v.dtype))
                     for v, a in zip(vals, alts)]
    if key is not None:
        if len(_infer_cache) >= _MAX_INFER_CACHE:
            _infer_cache.pop(next(iter(_infer_cache)))
        _infer_cache[key] = {slot: list(pairs) for slot, pairs in out.items()}
    return out
