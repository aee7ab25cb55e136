"""Scope + Executor: run programs as single jit-compiled XLA steps.

Capability parity with the reference's Scope/Executor
(reference: paddle/fluid/framework/scope.h:39, executor.cc:294-366,
python/paddle/fluid/executor.py:224-470).

TPU-native redesign: the reference interprets ops one by one against a
mutable Scope, syncing the device every run (executor.cc:345). Here the
executor lowers the whole block to ONE pure jitted function
`(feeds, mutable_state, const_state, key) -> (fetches, new_mutable_state)`,
compiled once per (program version, feed signature) and cached — the XLA
analog of the reference's `Prepare`/`RunPreparedContext` program cache.
Mutable state (parameters, optimizer accumulators) is donated to XLA so
updates are in-place in HBM; there is no per-step host sync and no per-op
dispatch.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import logging
import weakref
from typing import (Any, Dict, List, NamedTuple, Optional, Sequence, Tuple,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np

from . import ir, registry
from .. import flags as _flags
from ..observe import steplog as _steplog
from ..observe.census import program_detail
from .lowering import BlockLowerer, cast_masters

logger = logging.getLogger(__name__)


class EOFException(Exception):
    """Raised when a py_reader-fed program drains its queue (reference:
    paddle/fluid/framework/reader.h EOF semantics surfaced as
    core.EOFException in python)."""


# ---------------------------------------------------------------------------
# Places (reference: platform/place.h). On TPU these are thin shims over jax
# devices; XLA/PJRT owns device memory and streams.
# ---------------------------------------------------------------------------

class Place:
    def jax_device(self):
        raise NotImplementedError


class CPUPlace(Place):
    def jax_device(self):
        # local_devices: under multi-controller jax, jax.devices()[0] can
        # belong to ANOTHER process — computing there would leave this
        # process holding arrays with no addressable shards
        return jax.local_devices(backend="cpu")[0]

    def __repr__(self):
        return "CPUPlace()"


def _cpu_requested() -> bool:
    """Whether this process asked jax for the CPU backend outright
    (JAX_PLATFORMS=cpu or the equivalent config update — the test rig
    and the rehearsal tools). Only then may a TPUPlace resolve to a CPU
    device."""
    return (jax.config.jax_platforms or "").split(",")[0].strip() == "cpu"


class TPUPlace(Place):
    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def jax_device(self):
        devs = jax.local_devices()
        if devs[0].platform != "tpu" and not _cpu_requested():
            raise RuntimeError(
                f"{self!r}: jax found no TPU (local devices: {devs}) and "
                f"this process did not ask for the CPU backend — set "
                f"JAX_PLATFORMS=cpu to rehearse on CPU")
        if not 0 <= self.device_id < len(devs):
            raise ValueError(
                f"{self!r}: device id out of range, jax sees "
                f"{len(devs)} local device(s)")
        return devs[self.device_id]

    def __repr__(self):
        return f"TPUPlace({self.device_id})"


# Alias so reference scripts using CUDAPlace keep working on TPU.
CUDAPlace = TPUPlace


class Scope:
    """Hierarchical name -> array holder (reference scope.h:39).

    Mutations bump a version counter shared by the whole scope TREE (kept
    on the root): prepared programs cache their state gather against it,
    and find_var walks parents, so a parent mutation must invalidate a
    child-bound cache too. One counter per tree (not per process) keeps
    independent scopes from invalidating each other's caches."""

    _uid_counter = itertools.count()

    def __init__(self, parent: Optional["Scope"] = None):
        self._vars: Dict[str, Any] = {}
        self._parent = parent
        self._kids: List[Scope] = []
        # process-unique id for executor cache keys (id() recycles after GC)
        self._uid = next(Scope._uid_counter)
        self._root = parent._root if parent is not None else self
        if parent is None:
            self._version = 0

    def version(self) -> int:
        return self._root._version

    def new_scope(self) -> "Scope":
        kid = Scope(self)
        self._kids.append(kid)
        return kid

    def drop_kids(self):
        self._kids = []
        self._root._version += 1

    def var(self, name: str):
        """Get a variable from THIS scope only (no parent lookup); returns
        None if absent. Unlike the reference's Scope::Var this does not
        create — arrays are materialized by programs, use set_var."""
        return self._vars.get(name)

    def find_var(self, name: str):
        s: Optional[Scope] = self
        while s is not None:
            if name in s._vars:
                return s._vars[name]
            s = s._parent
        return None

    def set_var(self, name: str, value):
        self._vars[name] = value
        self._root._version += 1

    def has_var(self, name: str) -> bool:
        return self.find_var(name) is not None

    def local_var_names(self) -> List[str]:
        return list(self._vars)

    def erase(self, names: Sequence[str]):
        for n in names:
            self._vars.pop(n, None)
        self._root._version += 1


_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


def _as_feed_array(v, var: Optional[ir.Variable]):
    if isinstance(v, jax.Array):
        # already on device (e.g. AsyncFeeder pre-transfer) — never round-trip
        # through host
        return v
    arr = np.asarray(v)
    if var is not None and var.dtype and arr.dtype != jnp.dtype(var.dtype):
        # Follow the reference DataFeeder's implicit cast for python scalars.
        if arr.dtype.kind in "fiub":
            arr = arr.astype(jnp.dtype(var.dtype))
    return arr


def _convert_feed_dict(block, feed: Dict[str, Any]) -> Dict[str, Any]:
    """User feed dict -> array dict, materializing @SEQLEN companions for
    (data, lengths) LoD feeds. Shared by the unprepared and prepared paths
    so both produce identical feed signatures."""
    feed_arrays = {}
    for name, val in feed.items():
        var = block.vars.get(name)
        if isinstance(val, (tuple, list)) and len(val) == 2 and var is not None \
                and var.lod_level > 0:
            data, lens = val
            feed_arrays[name] = _as_feed_array(data, var)
            if isinstance(lens, (tuple, list)) and len(lens) == 2 \
                    and not np.isscalar(lens[0]):
                # nested LoD: (outer counts [B], inner lengths [B, S])
                feed_arrays[ir.seqlen_var_name(name)] = \
                    np.asarray(lens[0], np.int32)
                feed_arrays[ir.seqlen_var_name(name, 1)] = \
                    np.asarray(lens[1], np.int32)
            else:
                feed_arrays[ir.seqlen_var_name(name)] = \
                    np.asarray(lens, np.int32)
        else:
            feed_arrays[name] = _as_feed_array(val, var)
    return feed_arrays


class _StateCache:
    """Scope-version-keyed cache of a compiled step's (mut, const) state
    gather. The gather is O(state vars) of find_var walks — pure per-step
    host overhead once the program is steady — so it is rebuilt only when
    the scope tree reports a mutation the executor didn't make itself.

    Beside the gathered state it holds the step's AMP shadows (the bf16
    form of the parameters `_CompiledProgram.shadow_names` lists, which the
    step updates and reads in bf16): made from the gathered masters by one
    jitted cast at every gather (the first run, and after any hand other
    than the executor's own moved the scope: `set_var`,
    `load_persistables`, another program's write-back), and from then on
    the ones the step itself wrote. They are never in the `Scope`: nothing
    saves, loads, lists or fetches them."""

    def __init__(self):
        self._entry = None
        self._version = -1
        self._mut: Optional[Dict[str, Any]] = None
        self._const: Optional[Dict[str, Any]] = None
        self._shadows: Optional[Dict[str, Any]] = None

    def get(self, entry: "_CompiledProgram", scope: Scope, device=None):
        if (entry is not self._entry or self._mut is None
                or scope.version() != self._version):
            self._mut, self._const = entry.gather_state(scope)
            with _step_device_ctx(device, entry):
                self._shadows = entry.make_shadows(self._mut)
            self._entry = entry
        return self._mut, self._const, self._shadows

    def commit(self, entry: "_CompiledProgram", scope: Scope, new_state,
               new_shadows):
        """Refresh after a step: the mut arrays and the shadows were
        donated (dead); swap in the step's outputs, then adopt the scope
        version the write-back produced so our own set_var calls don't
        invalidate the cache."""
        mut = self._mut
        for n in entry.mut_names:
            v = new_state.get(n)
            if v is not None:
                mut[n] = v
        self._shadows = new_shadows
        self._version = scope.version()


def resolve_compiler_options(platform: str, program=None):
    """Per-executable XLA options from the `xla_compiler_options` flag.

    "auto" applies a 32 MiB scoped-VMEM budget (room for the fusion merger
    to form larger fusions: fewer HBM round-trips between them) to
    conv-free programs on the TPU and nothing to a program with a
    convolution. The set came from a sweep on an installation this repo
    no longer runs on and is unmeasured on this chip (ROADMAP S6). An
    explicit k=v list applies unconditionally. Non-TPU backends get None
    (the names are TPU-only and other backends reject unknown options)."""
    val = _flags.get_flag("xla_compiler_options")
    if val == "auto":
        if platform != "tpu":
            return None
        if program is not None and _program_has_conv(program):
            return None
        return {"xla_tpu_scoped_vmem_limit_kib": "32768"}
    if not val or val == "none":
        return None
    opts = {}
    for kv in val.split(","):
        if not kv:
            continue
        if "=" not in kv:
            raise ValueError(
                f"xla_compiler_options entry {kv!r} is malformed — expected "
                f"'name=value' pairs separated by commas (full flag value: "
                f"{val!r})")
        k, v = kv.split("=", 1)
        opts[k] = v
    return opts


# program uid -> (program version, has_conv). Keyed by uid with the version
# INSIDE the value so a mutated program replaces its stale entry instead of
# accreting one per version in a long-lived process.
_has_conv_cache: Dict[int, tuple] = {}


def _program_has_conv(program) -> bool:
    """Memoized per program uid (latest version wins): run() calls this on
    bind and a full op walk on a large program is avoidable repeated work."""
    hit = _has_conv_cache.get(program._uid)
    if hit is None or hit[0] != program._version:
        val = any("conv" in op.type
                  for block in program.blocks for op in block.ops)
        if hit is None and len(_has_conv_cache) >= _MAX_TRACKED_PROGRAMS:
            _has_conv_cache.pop(next(iter(_has_conv_cache)))
        _has_conv_cache[program._uid] = (program._version, val)
        return val
    return hit[1]


def donation_safe() -> bool:
    """Whether donate_argnums may be used for compiled steps.

    Buffer donation and the persistent compilation cache are MUTUALLY
    EXCLUSIVE on this jaxlib's CPU backend: a warm-cache hit of a
    donate_argnums executable loses its input-output aliasing on
    deserialization and reuses the donated buffers while still
    referenced — a use-after-free that bus-errors, segfaults, or
    silently corrupts the carried state (minimal repro: a donated jit
    run twice across processes against one cache dir; without donation
    the same cache is bit-deterministic). Donated mutable state is a
    core perf design (in-place HBM updates), so instead of banning the
    cache, the executor drops donation whenever a compilation cache dir
    is configured on a CPU backend. The package configures one for every
    process (paddle_tpu/__init__.py), so CPU runs never donate; on the
    TPU a warm-cache run is checked bit-identical to the cold one by
    chip_smoke.py, and donation stays on."""
    return (not jax.config.jax_compilation_cache_dir
            or jax.default_backend() != "cpu")


def _abstract(x):
    """What the jitted step's cache key sees of an argument: shape, dtype
    and, for a committed array, its sharding."""
    if isinstance(x, jax.ShapeDtypeStruct):
        return x
    sharding = x.sharding if isinstance(x, jax.Array) and x.committed \
        else None
    return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sharding)


def _needs_device_ctx(device, entry: "_CompiledProgram") -> bool:
    """Whether `entry`'s step has to be called (and lowered) under
    `jax.default_device(device)`. Entering that context per step costs
    ~hundreds of µs (it defeats pjit's C++ fast path) and it is part of
    jax's trace-cache key, so it is entered only where it decides
    something: never for a handle without a single device (a mesh's: the
    arguments' shardings place the step), and the ctx can be skipped when
    the device IS the process default and the step reads scope state. jit
    outputs are UNCOMMITTED, so a stateful step with numpy feeds would
    otherwise migrate to jax's global default backend (e.g. CPUPlace
    selected in a TPU-default process): place selection must hold even
    without the per-step ctx."""
    if device is None:
        return False
    try:
        default_dev = (jax.config.jax_default_device
                       or jax.local_devices()[0])
    except Exception:
        default_dev = None
    return device != default_dev or not (entry.mut_names or entry.const_names)


def _step_device_ctx(device, entry: "_CompiledProgram"):
    """The context a run() calls `entry`'s step under: a lowering made
    under the same one finds what jax cached for the running step (its
    trace, its lowering and, through them, its executable)."""
    if _needs_device_ctx(device, entry):
        return jax.default_device(device)
    return contextlib.nullcontext()


def lower_step(entry: "_CompiledProgram", feeds: Dict[str, Any], scope: Scope):
    """`entry`'s step lowered (a `jax.stages.Lowered`) from abstract
    arguments: the signature of `feeds` (arrays, or what `_abstract` made of
    them) and of the state `scope` holds now. No array is read, and the
    entry's own compiler options apply, so `.compile()` of a step that has
    run gives the executable that runs: lowered under the context a run
    calls the step in (`_step_device_ctx`), jax finds the running step's
    own trace, lowering and executable in its in-process caches, and
    nothing is compiled again. The step's AMP shadows are signed as the
    masters they shadow (`abstract_shadows`). The one place the executors
    and the tools get a step's text."""
    mut, const = entry.gather_state(scope)
    mut = {n: _abstract(v) for n, v in mut.items()}
    return entry._step.lower(
        {n: _abstract(feeds[n]) for n in sorted(feeds)},
        mut,
        {n: _abstract(v) for n, v in const.items()},
        jax.ShapeDtypeStruct((), np.uint32),
        entry.abstract_shadows(mut))


def offer_step_text(entry: "_CompiledProgram", feed_arrays: Dict[str, Any],
                    scope: Scope, device=None):
    """Put a lazy `compiled_text()` on `entry.event`, the compile event it
    was built with: the feeds' abstract signature is noted here, once, the
    state's is read from the scope at the ask (by then the committed
    outputs of a step), and nothing is lowered until someone asks. The
    closure holds the entry, the avals and a weak reference to the scope;
    no arrays."""
    entry.feed_avals = {n: _abstract(v) for n, v in feed_arrays.items()}
    scope_ref = weakref.ref(scope)

    def text():
        scope = scope_ref()
        if scope is None:
            return None
        with _step_device_ctx(device, entry):
            return lower_step(entry, entry.feed_avals,
                              scope).compile().as_text()

    entry.event.offer_text(text)


class StepKey(NamedTuple):
    """What a compiled step bakes in: the key of the compile cache, and
    what the recompilation observatory compares to name a compile's cause
    (`steplog.CAUSE_OF_FIELD` has one for every field after the first, and
    a test fails for a field without). Made by `step_key` only; read by
    name everywhere."""
    program_uid: int
    program_version: int
    feeds: Optional[Tuple[str, ...]]     # sorted feed names
    fetches: Tuple[str, ...]
    scope_uid: int
    amp: bool
    check_nan_inf: Optional[bool]
    copts: Optional[Tuple[Tuple[str, str], ...]]   # XLA compiler options
    seed: Optional[int]       # program.random_seed: baked into the trace
    mesh: Any                 # None on one chip


def step_key(program, feeds, fetches, scope, amp, check_nan_inf, copts,
             mesh) -> StepKey:
    """The record of one step. `Executor`'s memo of handles keys on it
    too, with None where a handle binds later (the feed names at its
    first run) or resolves from the flags (`copts`; `check_nan_inf` as
    the executor holds it, None = the flag): the flag registry's version
    is kept on the handle beside it."""
    return StepKey(program._uid, program._version,
                   None if feeds is None else tuple(sorted(feeds)),
                   tuple(fetches), scope._uid, amp, check_nan_inf,
                   tuple(sorted(copts.items())) if copts else None,
                   program.random_seed, mesh)


@jax.jit
def _cast_bf16(masters: Dict[str, Any]) -> Dict[str, Any]:
    return {n: v.astype(jnp.bfloat16) for n, v in masters.items()}


def _fetch_names(fetch_list) -> Tuple[str, ...]:
    return tuple(f.name if isinstance(f, ir.Variable) else str(f)
                 for f in (fetch_list or ()))


def _fetch_numpy(f):
    """A fetch on the host. A global array spanning other processes'
    devices (a multi-host mesh) cannot be np.asarray'd directly — read
    the local copy when replicated, allgather otherwise (every process
    calls fetch symmetrically, so the collective is safe)."""
    if isinstance(f, jax.Array) and not f.is_fully_addressable:
        if f.sharding.is_fully_replicated:
            return np.asarray(f.addressable_shards[0].data)
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(f, tiled=True))
    return np.asarray(f)


class _CompiledProgram:
    """One lowered+jitted step: what `key` (a `StepKey`) says, against
    `scope`. `event` is the step's compile event: the rules write on its
    `detail` what they know only under the trace (`LoweringContext.note`),
    whenever the step is traced, and a run() names it to the observatory,
    so a compile jax reports inside the jitted call lands there too."""

    def __init__(self, program: ir.Program, key: StepKey, scope: Scope,
                 event=None, rng_stream: int = 0):
        self.program = program
        self.key = key
        self.feed_names = list(key.feeds)
        self.fetch_names = list(key.fetches)
        self.check_nan_inf = key.check_nan_inf
        self._nan_meta = []
        self.event = event
        # abstract signature of the feeds the entry was bound with
        # (`offer_step_text`): what `lower_step` needs instead of a feed
        self.feed_avals: Optional[Dict[str, Any]] = None
        # feed signatures the step has run with (`steplog.track_shapes`)
        self.shape_sigs = set()
        # the pace of this entry's steps, handed to each run's `RunSpans`
        self.pace = _steplog.Pace()
        block = program.global_block()
        lowerer = BlockLowerer(program, amp=key.amp,
                               check_nan_inf=key.check_nan_inf,
                               mesh=key.mesh,
                               detail=event.detail if event else None)

        # Statically determine which scope vars the block reads/writes.
        written: List[str] = []
        produced = set(self.feed_names)
        read: List[str] = []
        for op in block.ops:
            in_names = list(op.input_arg_names)
            for si in ir.sub_block_indices(op):
                in_names += ir.external_reads(program, si)
            for n in in_names:
                if n == registry.EMPTY_VAR:
                    continue
                if n not in produced and n not in read:
                    read.append(n)
            for n in op.output_arg_names:
                if n == registry.EMPTY_VAR:
                    continue
                produced.add(n)
                # runtime seqlen propagation (lowering.py) materializes the
                # @SEQLEN companion of sequence outputs without an explicit op
                produced.add(n + ir.SEQLEN_SUFFIX)
                produced.add(n + ir.SEQLEN_SUFFIX + ".1")
                v = block._find_var_recursive(n)
                if v is not None and v.persistable and n not in written:
                    written.append(n)
        missing = [n for n in read if not scope.has_var(n)]
        if missing:
            missing_data = [n for n in missing
                            if (v := block._find_var_recursive(n)) is not None and v.is_data]
            if missing_data:
                raise RuntimeError(
                    f"input variables {missing_data} were not fed — pass them in "
                    f"`feed={{...}}`")
            raise RuntimeError(
                f"variables {missing} are read by the program but not initialized "
                f"in the scope — run the startup program first")
        self.mut_names = [n for n in read if n in set(written)]
        self.const_names = [n for n in read if n not in set(written)]
        # AMP's shadows: the float32 parameters the step updates and whose
        # bf16 form it carries (`lowering.cast_masters`, from the Program)
        self.shadow_names = cast_masters(program, self.mut_names) \
            if key.amp else []
        if event is not None and self.shadow_names:
            held = [scope.find_var(n) for n in self.shadow_names]
            event.detail.update(
                amp_shadowed_params=len(held),
                amp_shadowed_mb=round(
                    sum(2 * int(np.prod(np.shape(v))) for v in held) / 1e6,
                    3),
                amp_plain_master_casts=0)

        seed = key.seed if key.seed is not None else 0
        # unseeded programs additionally fold in their executor-local
        # ordinal (`rng_stream`): with the per-program run counters, two
        # distinct unseeded programs run through ONE executor would
        # otherwise draw IDENTICAL key sequences (fold_in(key(0), 0..n))
        # and e.g. correlate their dropout masks (round-4 advisor). The
        # ordinal — not the global program uid — keeps the stream
        # deterministic for a given executor's usage pattern regardless
        # of how many programs OTHER code built first. Explicitly seeded
        # programs keep the pure-counter derivation — that is the
        # cross-executor reproducibility contract.
        uid_mix = None if key.seed is not None or not rng_stream \
            else np.uint32(rng_stream)

        def step(feeds, mut_state, const_state, counter, shadows=None):
            # key derivation INSIDE the jit: an eager fold_in would
            # dispatch 2-4 tiny device programs per run (visible in the
            # profiler as jit__threefry_* modules), pure host overhead
            key = jax.random.fold_in(jax.random.key(seed), counter)
            if uid_mix is not None:
                key = jax.random.fold_in(key, uid_mix)
            env = {}
            env.update(const_state)
            env.update(mut_state)
            env.update(feeds)
            lowerer.nan_flags = []
            lowerer.enter_step(mut_state, shadows)
            lowerer.run_block(0, env, key)
            fetches = [env[n] for n in self.fetch_names]
            new_state = {n: env[n] for n in written if n in env}
            # trace-time side effect: remember which (op, var) each flag
            # belongs to so the host can name the offender
            self._nan_meta = [(t, n) for t, n, _ in lowerer.nan_flags]
            flags = ([f for _, _, f in lowerer.nan_flags]
                     if lowerer.check_nan_inf else [])
            return fetches, new_state, flags, lowerer.shadows

        # the shadows are donated with the state they shadow: the update
        # writes the next ones over them
        self._step = jax.jit(step,
                             donate_argnums=(1, 4) if donation_safe() else (),
                             compiler_options=dict(key.copts or ()) or None)

    def gather_state(self, scope: Scope):
        mut = {n: scope.find_var(n) for n in self.mut_names}
        const = {n: scope.find_var(n) for n in self.const_names}
        return mut, const

    def make_shadows(self, mut: Dict[str, Any]) -> Dict[str, Any]:
        """The shadows of a freshly gathered `mut`: one jitted cast of the
        masters (under a mesh each takes its master's sharding), and
        nothing for a step that shadows none."""
        if not self.shadow_names:
            return {}
        return _cast_bf16({n: mut[n] for n in self.shadow_names})

    def abstract_shadows(self, mut_avals: Dict[str, Any]) -> Dict[str, Any]:
        """What the step's cache key sees of the shadows, from that of the
        masters (`_abstract`)."""
        return {n: jax.ShapeDtypeStruct(mut_avals[n].shape, jnp.bfloat16,
                                        sharding=mut_avals[n].sharding)
                for n in self.shadow_names}

    def run_with_state(self, scope: Scope, feeds, mut, const, shadows,
                       counter, spans=None):
        """One step against pre-gathered state dicts; returns (fetches,
        new_state, new_shadows) so callers holding a state cache can
        refresh their mut entries and shadows (the mut arrays and the
        shadows were donated to XLA and are dead after the call). `spans`
        (a `steplog.RunSpans` in its jit_call phase) moves to write_back
        once the jitted step has returned."""
        fetches, new_state, flags, new_shadows = self._step(
            feeds, mut, const, counter, shadows)
        if spans is not None:
            spans.phase(_steplog.WRITE_BACK)
        # bulk write-back: one dict update + one version bump (set_var per
        # name costs ~10µs/step on wide optimizers; equality-based cache
        # invalidation only needs the version to CHANGE, not count)
        scope._vars.update(new_state)
        scope._root._version += 1
        if self.check_nan_inf and flags:
            finite = np.asarray(jnp.stack(flags))
            if not finite.all():
                bad = int(np.argmin(finite))
                op_type, var = self._nan_meta[bad]
                raise RuntimeError(
                    f"NaN/Inf detected in output {var!r} of op "
                    f"{op_type!r} (check_nan_inf mode; reference "
                    f"CheckTensorNANOrInf, operator.cc:622)")
        return fetches, new_state, new_shadows


# leak backstop for the per-program uid maps (run counters / rng ordinals):
# a long-lived process churning through distinct Program objects stops
# growing them past this. Evicting a counter only matters if that exact
# program runs AGAIN later (its unseeded rng stream restarts), which after
# 4096 intervening programs is a serving process recycling graphs, not a
# training loop.
_MAX_TRACKED_PROGRAMS = 4096

# run()'s PreparedProgram memo cap: unlike the compile cache (whose
# entries hold no arrays), a prepared handle pins its scope and the
# gathered state dicts, so the memo is kept small — steady-state loops
# use only a few handles, and rebuilding an evicted one is cheap.
_MAX_PREPARED_HANDLES = 64


def _evict_stale_versions(cache: Dict[StepKey, Any], program):
    """Drop a cache's entries for older versions of a (mutated) program
    before the current version's goes in — a cache keyed by `StepKey`
    would otherwise grow one entry per mutation in a long-lived process
    (advisor r5)."""
    stale = [k for k in cache if k.program_uid == program._uid
             and k.program_version != program._version]
    for k in stale:
        del cache[k]


# program uid -> (program version, the (feed names, fetch names, mode) already
# validated at it): Executor.run rebuilds PreparedProgram handles on scope
# churn / flag flips / memo eviction, and re-sweeping an unchanged program
# each time would defeat PR 1's cheap-rebuild contract. Errors are never
# cached (they raise); a mutation bumps the version and replaces the entry.
_validated: Dict[int, tuple] = {}


def _validate_program(program, mode, feed_names, fetch_names):
    """`validate` hook shared by prepare()/run(): mode None follows the
    `validate` flag; "error" raises ProgramVerificationError on ERROR
    findings, "warn" logs everything found (once per program version),
    "off" is free."""
    if mode is None:
        mode = _flags.get_flag("validate")
    if mode == "off":
        return
    if mode not in ("error", "warn"):
        raise ValueError(f"validate must be 'error', 'warn' or 'off', "
                         f"got {mode!r}")
    sig = (tuple(feed_names or ()), tuple(fetch_names or ()), mode)
    hit = _validated.get(program._uid)
    if hit is not None and hit[0] == program._version and sig in hit[1]:
        return
    from .. import analysis
    # listen_and_serv programs are host services, not XLA computations
    if not any(op.type == "listen_and_serv"
               for op in program.global_block().ops):
        diags = analysis.analyze_program(program, feed_targets=feed_names,
                                         fetch_targets=fetch_names or None,
                                         lint=(mode == "warn"))
        if mode == "error" and analysis.has_errors(diags):
            raise analysis.ProgramVerificationError(diags)
        if diags:
            logger.warning("program validation findings:\n%s",
                           analysis.format_diagnostics(diags))
    if hit is None or hit[0] != program._version:
        if hit is None and len(_validated) >= _MAX_TRACKED_PROGRAMS:
            _validated.pop(next(iter(_validated)))
        hit = _validated[program._uid] = (program._version, set())
    hit[1].add(sig)


class PreparedProgram:
    """Bound fast-path handle from `Executor.prepare()` (reference
    Executor::Prepare / RunPreparedContext, executor.cc:294-366; TF's
    session-handle design serves the same purpose).

    Everything resolvable once per (program, fetch list, scope) — compiler
    options, flag reads, the listen_and_serv scan, fetch-name resolution —
    happens at construction; the compiled entry binds lazily on the first
    `run(feed)` (the feed signature, including @SEQLEN companions, is only
    knowable from real feed values). After that, each `run(feed)` does
    only: feed conversion, a scope-version-checked cached state gather, the
    jitted call, and state write-back. `return_numpy=False` returns the
    step's `jax.Array` outputs without forcing a host sync, so dispatch of
    the next step overlaps this step's device execution."""

    def __init__(self, executor: "Executor", program: ir.Program,
                 fetch_list, scope: Scope, feed_names=None, validate=None):
        self._exe = executor
        self.program = program
        self.fetch_names = list(_fetch_names(fetch_list))
        self.feed_names = list(feed_names) if feed_names else None
        self.scope = scope
        self._block = program.global_block()
        # flag-gated static verification (analysis/): runs HERE, before
        # any lowering — a malformed program is rejected with op
        # provenance instead of a tracer error inside XLA at first run
        _validate_program(program, validate, self.feed_names,
                          self.fetch_names)
        # what the executor's owner runs on: one device, or a mesh (then no
        # single device: the arguments' shardings place the step) with its
        # own placement of the feeds, `feed dict -> arrays`
        self._mesh = executor._mesh
        self._device = executor.place.jax_device() \
            if self._mesh is None else None
        self._convert = executor._place_feeds or functools.partial(
            _convert_feed_dict, self._block)
        self._program_version = program._version
        # flag-derived settings are baked at bind time; Executor.run's memo
        # compares the flag-registry version the handle was made at, so a
        # set_flag() flip yields a fresh handle on the next run() (direct
        # handle holders keep the settings they prepared with — re-prepare
        # to pick up flag flips)
        self.flags_version = _flags.version()
        self._check_nan_inf = executor.check_nan_inf
        self._copts = resolve_compiler_options(
            (self._device or self._mesh.devices.flat[0]).platform, program)
        ls = [op for op in self._block.ops if op.type == "listen_and_serv"]
        self._serve_attrs = ls[0].attrs if ls else None
        # telemetry attribution: the serving layer (serve/) re-tags its
        # handles "serving" so step stats and compile events separate
        # request traffic from training, and shape misses attribute as
        # `padding_bucket` (mis-sized bucket ladder) not `feed_shape`
        self.telemetry_source = executor._source
        # the executor-wide compile cache the entries are looked up and
        # kept in; None for a handle that keeps them nowhere
        # (`Executor._run_uncached`)
        self._cache = executor._cache
        self._entries: Dict[tuple, _CompiledProgram] = {}
        self._entry: Optional[_CompiledProgram] = None
        self._entry_keys = frozenset()
        self._feed_plan = None   # bound by _bind (per-name dtype plan)
        self._plan_keys = frozenset()
        self._state = _StateCache()
        # entering jax.default_device() per step costs ~hundreds of µs
        # (the config context defeats pjit's C++ fast path). Steps that
        # read ANY scope state don't need it: the state arrays were
        # committed to the right device at startup/bind, and committed
        # args pin the execution device. Only an all-feed (stateless)
        # step, whose numpy args would follow jax's global default,
        # keeps the context.
        self._use_device_ctx = True

    @property
    def device(self):
        """The jax device this handle dispatches to (AsyncFeeder targets
        pre-step transfers here)."""
        return self._device

    def run(self, feed: Optional[Dict[str, Any]] = None,
            return_numpy: bool = True):
        # A pserver program (one listen_and_serv op) is a HOST service, not
        # an XLA computation: serve until stopped, exactly like the
        # reference's blocking Executor.run on the pserver program
        # (reference listen_and_serv_op.cc:267).
        if self._serve_attrs is not None:
            from ..pserver.server import ParameterServer
            ps = ParameterServer(self._serve_attrs["endpoint"],
                                 trainers=self._serve_attrs.get("trainers", 1))
            ps.serve_forever()
            return []
        program = self.program
        if program._version != self._program_version:
            raise RuntimeError(
                "program was mutated after prepare(); prepare() it again "
                "(Executor.run() re-prepares automatically)")
        # host spans on the profiler's clock at default flags; StepStats on
        # the same boundaries only when observing, so the prepared fast
        # path still performs zero registry writes (observe/steplog.py)
        entry = self._entry
        with _steplog.RunSpans(
                program._uid, self.telemetry_source,
                self._exe._run_counts.get(program._uid, 0),
                None if entry is None else entry.pace) as spans:
            feed = feed or {}
            # py_reader-fed program: no feed -> pop the next queued batch
            # (raises EOFException at end of pass, reference read-op
            # contract); the wait is the reader's own span
            if not feed and getattr(program, "_py_reader", None) is not None:
                feed = program._py_reader.next_feed()
            spans.phase(_steplog.FEED_CONVERT)
            # steady state: the feed-conversion PLAN (per-name target
            # dtype, no LoD) was resolved at bind time, so conversion is
            # one tight loop without block-var lookups or dtype
            # re-resolution
            plan = self._feed_plan
            if plan is not None and feed.keys() == self._plan_keys:
                feed_arrays = {}
                for name, val in feed.items():
                    if type(val) is not np.ndarray:
                        if isinstance(val, jax.Array):
                            feed_arrays[name] = val   # pre-placed: never
                            continue                  # round-trip
                        val = np.asarray(val)
                    dt = plan[name]
                    if dt is not None and val.dtype != dt \
                            and val.dtype.kind in "fiub":
                        val = val.astype(dt)
                    feed_arrays[name] = val
            else:
                feed_arrays = self._convert(feed)
            if entry is None or feed_arrays.keys() != self._entry_keys:
                # binding (validation, feed plan, cache lookup) is its own
                # one-shot phase: it never pollutes steady-state
                # feed_convert numbers
                spans.phase(_steplog.BIND)
                entry = self._bind(feed, feed_arrays)
            if spans.observing:
                # feed_shape observatory: a new shape/dtype signature on a
                # bound entry means jax.jit retraces + XLA recompiles
                _steplog.track_shapes(entry, program._uid, feed_arrays,
                                      source=self.telemetry_source)
            spans.event = entry.event
            spans.phase(_steplog.STATE_GATHER)
            counter = self._exe._count_run(program._uid)
            mut, const, shadows = self._state.get(entry, self.scope,
                                                  self._device)
            # jit_call ends when the jitted step returns (under async
            # dispatch the device runs on); run_with_state opens
            # write_back before its scope update
            spans.phase(_steplog.JIT_CALL)
            if self._use_device_ctx:
                with jax.default_device(self._device):
                    fetches, new_state, shadows = entry.run_with_state(
                        self.scope, feed_arrays, mut, const, shadows,
                        counter, spans)
            else:
                fetches, new_state, shadows = entry.run_with_state(
                    self.scope, feed_arrays, mut, const, shadows, counter,
                    spans)
            self._state.commit(entry, self.scope, new_state, shadows)
            if return_numpy:
                # the host transfer np.asarray forces; no span with
                # return_numpy=False — the async-dispatch overlap the fast
                # path is built on
                spans.phase(_steplog.FETCH)
                fetches = [_fetch_numpy(f) for f in fetches]
        return fetches

    def _build_feed_plan(self, feed):
        """Per-name target dtype for the bound feed set, resolved once.
        LoD feeds ((data, lengths) tuples) keep the generic conversion —
        they expand into @SEQLEN companions the plan doesn't model — and
        so does an owner's own placement of the feeds (a mesh's)."""
        if self._exe._place_feeds is not None:
            return None
        plan = {}
        for name, val in feed.items():
            var = self._block.vars.get(name)
            if var is not None and var.lod_level > 0:
                # a LoD var may be fed as a plain array on one step and a
                # (data, lengths) tuple on another — only the generic
                # conversion models that
                return None
            plan[name] = (jnp.dtype(var.dtype)
                          if var is not None and var.dtype else None)
        return plan

    def _bind(self, feed, feed_arrays) -> _CompiledProgram:
        """Resolve the compiled entry for this feed signature, consulting
        the executor-wide compile cache so re-preparing (e.g. after an
        unrelated flag flip) never recompiles an unchanged step. A miss
        there is a new XLA executable: `_build_entry`."""
        sig = tuple(sorted(feed_arrays))
        entry = self._entries.get(sig)
        if entry is None:
            key = step_key(self.program, sig, self.fetch_names, self.scope,
                           self._exe.amp, self._check_nan_inf, self._copts,
                           self._mesh)
            entry = self._cache.get(key) if self._cache is not None else None
            if entry is None:
                entry = self._build_entry(key, feed_arrays)
            self._entries[sig] = entry
        self._entry = entry
        self._entry_keys = frozenset(sig)
        self._use_device_ctx = _needs_device_ctx(self._device, entry)
        self._feed_plan = self._build_feed_plan(feed)
        self._plan_keys = frozenset(feed)
        return entry

    def _build_entry(self, key: StepKey, feed_arrays) -> _CompiledProgram:
        """The one way a step is built: record the compile with its cause
        and the dict the rules' facts will land on, build the
        `_CompiledProgram`, offer its text to the event, and put it in the
        compile cache. A handle without one (use_program_cache=False) is
        recorded as its own cause, outside the observatory's attribution
        state for cached runs, and its entry is kept nowhere."""
        program, source, cache = self.program, self.telemetry_source, \
            self._cache
        detail = {"version": key.program_version, "feeds": list(key.feeds),
                  "fetches": list(key.fetches), **program_detail(program)}
        if cache is None:
            event = _steplog.observatory().record(key.program_uid, "uncached",
                                                  source, detail)
        else:
            event = _steplog.observatory().note_entry_build(key, source,
                                                            detail)
        if _flags.get_flag("observe"):
            # fluid-pulse memory observatory: a compile costs seconds, the
            # concrete-shape walk costs milliseconds — estimate this
            # program's peak HBM at the shapes it is about to compile for
            # (never raises)
            from ..observe import memory as _obs_memory
            _obs_memory.note_program(program, feed_arrays, source=source)
        entry = _CompiledProgram(
            program, key, self.scope, event,
            rng_stream=self._exe._stream_for(key.program_uid))
        offer_step_text(entry, feed_arrays, self.scope, self._device)
        if cache is not None:
            _evict_stale_versions(cache, program)
            cache[key] = entry
        return entry


class Executor:
    """Program runner (reference executor.py:224).

    `place` selects the device; `exe.run(program, feed=..., fetch_list=...)`
    matches the reference API. Programs are compiled on first run and
    cached. `run()` itself rides a memoized `PreparedProgram` (the
    reference's Prepare/RunPreparedContext split), so steady-state steps
    skip the per-step cache-key rebuild, flag reads, and full scope state
    gather; loops that want the last few µs hold a `prepare()` handle
    directly.
    """

    def __init__(self, place: Optional[Place] = None, amp: bool = False,
                 check_nan_inf: Optional[bool] = None):
        self.place = place or TPUPlace(0)
        self.amp = amp  # bf16 mixed precision (reference float16_transpiler analog)
        # debug mode: per-op finite checks (reference FLAGS_check_nan_inf).
        # None = follow the flag registry at run time, so
        # set_flag("check_nan_inf", True) takes effect on the next run
        # (a new cache entry compiles with the checks baked in).
        self._check_nan_inf = check_nan_inf
        # what a handle is given beside the program. One device (`place`),
        # the feeds converted as they come, and this source on spans and
        # compile events, unless an owner that runs on a mesh
        # (ParallelExecutor) sets, before the first handle: its mesh, its
        # placement of a feed dict on it, and its source.
        self._mesh = None
        self._place_feeds = None
        self._source = "executor"
        self._cache: Dict[StepKey, _CompiledProgram] = {}
        self._prepared: Dict[StepKey, PreparedProgram] = {}
        self._run_counts: Dict[int, int] = {}  # program uid -> runs so far
        self._prog_order: Dict[int, int] = {}  # program uid -> ordinal
        self._next_stream = 0  # monotone ordinal source (survives eviction)

    @property
    def check_nan_inf(self) -> bool:
        if self._check_nan_inf is None:
            return _flags.get_flag("check_nan_inf")
        return self._check_nan_inf

    @check_nan_inf.setter
    def check_nan_inf(self, value):
        self._check_nan_inf = value

    def _stream_for(self, uid: int) -> int:
        """Executor-local program ordinal for unseeded rng streams. A
        monotone counter (not len()) so the leak-backstop eviction can
        never recycle a live ordinal onto a second program."""
        po = self._prog_order
        s = po.get(uid)
        if s is None:
            if len(po) >= _MAX_TRACKED_PROGRAMS:
                po.pop(next(iter(po)))
            s = self._next_stream
            self._next_stream += 1
            po[uid] = s
        return s

    def _count_run(self, uid: int) -> np.uint32:
        """PER-PROGRAM run counter: the PRNG key is fold_in(key(seed),
        runs-of-THIS-program), so a seeded startup re-initializes
        identically no matter what else this executor ran (cross-
        executor/mesh parity), while seeded TRAINING still draws a
        fresh-but-reproducible mask every step (reference random_seed
        reproducibility with per-step variation — the round-3 dropout
        contract, tests/test_amp_perf_ops.py)."""
        rc = self._run_counts
        n = rc.get(uid)
        if n is None:
            n = 0
            if len(rc) >= _MAX_TRACKED_PROGRAMS:
                rc.pop(next(iter(rc)))
        rc[uid] = n + 1
        return np.uint32(n)

    def prepare(self,
                program: Optional[ir.Program] = None,
                feed_names: Optional[Sequence[str]] = None,
                fetch_list: Optional[Sequence[Union[str, ir.Variable]]] = None,
                scope: Optional[Scope] = None,
                validate: Optional[str] = None) -> PreparedProgram:
        """Resolve the per-step-invariant work ONCE and return a bound
        `PreparedProgram` whose `run(feed)` is the fast path (reference
        Executor::Prepare + RunPreparedContext, executor.cc:294-366).
        `feed_names` is advisory (the real feed signature, including LoD
        @SEQLEN companions, binds on the first run's actual values).
        `validate="error"|"warn"|"off"` runs the static verifier
        (analysis/) over the program before anything lowers; None follows
        the `validate` flag (default off)."""
        program = program or ir.default_main_program()
        scope = scope or global_scope()
        return PreparedProgram(self, program, fetch_list, scope,
                               feed_names=feed_names, validate=validate)

    def run(self,
            program: Optional[ir.Program] = None,
            feed: Optional[Dict[str, Any]] = None,
            fetch_list: Optional[Sequence[Union[str, ir.Variable]]] = None,
            scope: Optional[Scope] = None,
            return_numpy: bool = True,
            use_program_cache: bool = True):
        program = program or ir.default_main_program()
        scope = scope or global_scope()
        if not use_program_cache:
            return self._run_uncached(program, feed, fetch_list, scope,
                                      return_numpy)
        return self._handle_for(program, fetch_list, scope).run(
            feed, return_numpy=return_numpy)

    def _handle_for(self, program, fetch_list, scope) -> PreparedProgram:
        """run() is a thin wrapper over a memoized PreparedProgram:
        existing callers get the prepared fast path for free. The memo key
        is everything a handle bakes in — program identity+version and
        seed, fetch set, scope, executor settings (`step_key`, less what
        binds later) — and the handle keeps the flag registry version it
        was made at (one int compare standing in for the per-step flag
        reads the old path did)."""
        key = step_key(program, None, _fetch_names(fetch_list), scope,
                       self.amp, self._check_nan_inf, None, self._mesh)
        prepared = self._prepared.get(key)
        if prepared is None or prepared.flags_version != _flags.version():
            prepared = PreparedProgram(self, program, key.fetches, scope)
            # a flag flip keeps the key: the new handle takes the old one's
            # place (the compiled entries live in self._cache and reuse)
            _evict_stale_versions(self._prepared, program)
            # hard cap (FIFO): a handle pins its scope AND the gathered
            # state arrays, so per-call temporary scopes (exe.run(prog,
            # scope=Scope()) in a serving loop) would otherwise keep one
            # full parameter set alive per call. Evicted handles rebuild
            # cheaply — the compiled entries stay in self._cache.
            if len(self._prepared) >= _MAX_PREPARED_HANDLES:
                self._prepared.pop(next(iter(self._prepared)))
            self._prepared[key] = prepared
        return prepared

    def _run_uncached(self, program, feed, fetch_list, scope, return_numpy):
        """use_program_cache=False: compile fresh, bypass both caches
        (reference semantics; used by tests probing recompilation): a
        handle of its own, whose entry goes into no cache."""
        handle = PreparedProgram(self, program, fetch_list, scope)
        handle._cache = None
        return handle.run(feed, return_numpy=return_numpy)

    def compiled_step(self, program: Optional[ir.Program] = None,
                      scope: Optional[Scope] = None):
        """The `jax.stages.Compiled` of a step this executor has run
        against `scope`: `.as_text()` is its optimized HLO,
        `.cost_analysis()` XLA's own count. Built from the abstract
        signature noted when the step was bound and the state the scope
        holds now (`lower_step`), so no feed is needed, and answered from
        jax's in-process caches once the step has run with that
        signature."""
        program = program or ir.default_main_program()
        scope = scope or global_scope()
        entries = [e for k, e in self._cache.items()
                   if (k.program_uid, k.program_version, k.scope_uid)
                   == (program._uid, program._version, scope._uid)]
        if not entries:
            raise RuntimeError("compiled_step requires a prior run() of the "
                               "program against this scope")
        entry = entries[-1]     # the newest feed signature and fetch set
        with _step_device_ctx(self.place.jax_device(), entry):
            return lower_step(entry, entry.feed_avals, scope).compile()

    def compiled_text(self, program: Optional[ir.Program] = None,
                      scope: Optional[Scope] = None) -> str:
        """Optimized HLO of `compiled_step(program, scope)`."""
        return self.compiled_step(program, scope).as_text()

    def close(self):
        self._cache.clear()
        self._prepared.clear()


def _switch_scope(scope: Scope) -> Scope:
    """Swap the process-global scope, returning the previous one
    (reference executor.py _switch_scope)."""
    global _global_scope
    prev = _global_scope
    _global_scope = scope
    return prev


@contextlib.contextmanager
def scope_guard(scope: Scope):
    """Run a `with` region against `scope` as the global scope (reference
    executor.py scope_guard)."""
    prev = _switch_scope(scope)
    try:
        yield
    finally:
        _switch_scope(prev)


def fetch_var(name: str, scope: Optional[Scope] = None, return_numpy: bool = True):
    """Read a variable's current value from a scope (reference
    executor.py fetch_var)."""
    scope = scope or global_scope()
    val = scope.find_var(name)
    if val is None:
        raise KeyError(f"fetch_var: variable {name!r} not found in scope")
    return np.asarray(val) if return_numpy else val
