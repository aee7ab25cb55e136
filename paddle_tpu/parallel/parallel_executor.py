"""ParallelExecutor: data-parallel training via GSPMD sharding.

Capability parity with the reference ParallelExecutor (reference:
paddle/fluid/framework/parallel_executor.cc:118-330 + details/ SSA graph,
python/paddle/fluid/parallel_executor.py).

TPU-native redesign: the reference replicates the program per GPU, builds an
SSA dependency graph, and hand-inserts NCCL AllReduce ops on gradients
(details/all_reduce_op_handle.cc:47). Here the SAME single-program lowering
used by Executor is compiled once under a `jax.sharding.Mesh`: feeds are
placed batch-sharded over the 'dp' axis, parameters replicated (kAllReduce
analog), and XLA GSPMD inserts the gradient all-reduces over ICI. The
`BuildStrategy.ReduceStrategy.Reduce` mode (sharded optimizer updates,
reference details/reduce_op_handle.cc) maps to sharding optimizer state over
'dp' — XLA then emits reduce-scatter + all-gather, the ZeRO-style pattern.
"""

from __future__ import annotations

import enum
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core import ir
from ..core.backward import program_detail
from ..core.executor import (Scope, _CompiledProgram, _StateCache,
                             _evict_stale_versions, _evict_superseded,
                             global_scope, lower_step, offer_step_text)
from ..observe import steplog as _steplog
from . import mesh as mesh_lib


class ExecutionStrategy:
    """Accepted for reference API parity (execution_strategy.h:21); XLA owns
    scheduling so only `num_threads` is meaningful (host callback pool)."""

    def __init__(self):
        self.num_threads = 0
        self.allow_op_delay = False
        self.num_iteration_per_drop_scope = 100


class BuildStrategy:
    class ReduceStrategy(enum.Enum):
        AllReduce = 0
        Reduce = 1

    class GradientScaleStrategy(enum.Enum):
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        self.debug_graphviz_path = ""
        # TPU extensions: name-pattern -> PartitionSpec for model parallelism,
        # and bf16 mixed precision for the MXU ops.
        self.sharding_rules = []
        self.amp = False
        # fluid-wire: "int8" / "bf16" inserts comm_quant_dequant ops with
        # persistent error feedback before every optimizer op
        # (wire/graph.py), quantizing each dp shard's gradient
        # contribution at the GSPMD all-reduce boundary — still ONE
        # jitted steady-state program (zero extra recompiles). None (the
        # default) keeps full-precision gradients.
        self.comm_quant = None


class ParallelExecutor:
    """Drop-in ParallelExecutor over a TPU mesh.

    `use_cuda` is accepted for reference parity and ignored. Feeds are split
    along the batch dim across the mesh 'dp' axis (the reference split feed
    lists per device in parallel_executor.py:run).
    """

    def __init__(self, use_cuda=None, loss_name=None, main_program=None,
                 share_vars_from=None, exec_strategy=None, build_strategy=None,
                 num_trainers=1, trainer_id=0, scope=None, mesh: Optional[Mesh] = None,
                 use_tpu=True):
        self._program = main_program or ir.default_main_program()
        self._scope = scope or (share_vars_from._scope if share_vars_from
                                else global_scope())
        self._mesh = mesh or mesh_lib.get_default_mesh()
        self._build_strategy = build_strategy or BuildStrategy()
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        self._loss_name = loss_name
        self._cache: Dict[tuple, _CompiledProgram] = {}
        # prepared fast path (the Executor.prepare analog): memoizes the
        # full cache-key build + flag reads per (program version, feed
        # signature, fetch set, flag registry version), and caches the
        # O(params) scope state gather against the scope version counter
        self._fast: Dict[tuple, _CompiledProgram] = {}
        self._state_cache = _StateCache()
        self._last_key = None
        self._run_counter = 0
        self._replicated = NamedSharding(self._mesh, PartitionSpec())
        # fluid-wire: rewrite BEFORE the first compile/bcast — the
        # residual vars are materialized straight into this executor's
        # scope (the startup program typically already ran) and ride
        # _bcast_params onto the mesh like any other state
        if getattr(self._build_strategy, "comm_quant", None):
            from ..wire.graph import apply_comm_quant
            apply_comm_quant(self._program,
                             codec=self._build_strategy.comm_quant,
                             scope=self._scope)
        self._bcast_params()

    # reference BCastParamsToDevices (parallel_executor.cc:204): replicate
    # host/chip0 params across the mesh.
    def _bcast_params(self):
        sharding_for = self._sharding_for_state
        for name in list(self._scope.local_var_names()):
            val = self._scope.find_var(name)
            if val is None or not hasattr(val, "shape"):
                continue
            self._scope.set_var(name, self._place_global(
                val, sharding_for(name, val)))

    def _place_global(self, val, sharding):
        """Place a host-local value under `sharding`. Single-controller:
        plain device_put. Multi-host: device_put cannot target remote
        devices, so the global array is assembled from each process's
        local copy (every host initialized identical params from the same
        seeded startup program — the reference broadcasts from dev0
        instead, parallel_executor.cc:204)."""
        if jax.process_count() == 1:
            return jax.device_put(val, sharding)
        if isinstance(val, jax.Array) and not val.is_fully_addressable:
            # already a world-spanning array (multi-controller jit outputs
            # are): keep it if the sharding already matches, else localize
            if val.sharding == sharding:
                return val
            val = self._fetch_numpy(val)
        val = np.asarray(val)
        idx_map = sharding.addressable_devices_indices_map(val.shape)
        shards = [jax.device_put(val[idx], d) for d, idx in idx_map.items()]
        return jax.make_array_from_single_device_arrays(val.shape, sharding,
                                                        shards)

    def _sharding_for_state(self, name, val):
        # 1. Parameter-level annotations (ParamAttr.sharding, e.g. the
        #    transformer's Megatron-style 'mp' specs).
        var = self._program.global_block().vars.get(name)
        spec = getattr(var, "sharding", None)
        if spec:
            names = set(self._mesh.axis_names)
            spec = [s if (s in names) else None for s in spec]
            shape = getattr(val, "shape", ())
            ok = len(shape) == len(spec)
            if ok:
                for d, s in zip(shape, spec):
                    if s is not None and d % self._mesh.shape[s] != 0:
                        ok = False
            if ok and any(s is not None for s in spec):
                return NamedSharding(self._mesh, PartitionSpec(*spec))
        # 2. BuildStrategy pattern rules.
        for pattern, spec in self._build_strategy.sharding_rules:
            if pattern in name:
                return NamedSharding(self._mesh, PartitionSpec(*spec))
        if (self._build_strategy.reduce_strategy
                is BuildStrategy.ReduceStrategy.Reduce):
            # ZeRO-style: shard state along dim 0 over 'dp' when divisible.
            shape = getattr(val, "shape", ())
            ndev = self._mesh.devices.size
            if shape and shape[0] % ndev == 0 and shape[0] >= ndev:
                spec = [None] * len(shape)
                spec[0] = "dp"
                return NamedSharding(self._mesh, PartitionSpec(*spec))
        return self._replicated

    @property
    def device_count(self):
        return self._mesh.devices.size

    def run(self, fetch_list, feed=None, feed_dict=None, return_numpy=True):
        from .. import flags as _flags
        # host spans at default flags, StepStats when observing: the same
        # helper and boundaries as PreparedProgram.run (observe/steplog.py)
        with _steplog.RunSpans(self._program._uid, "parallel",
                               self._run_counter) as spans:
            spans.phase(_steplog.FEED_CONVERT)
            feed = feed if feed is not None else feed_dict or {}
            if isinstance(feed, (list, tuple)):
                merged: Dict[str, np.ndarray] = {}
                for d in feed:
                    for k, v in d.items():
                        merged.setdefault(k, []).append(np.asarray(v))
                feed = {k: np.concatenate(v, axis=0)
                        for k, v in merged.items()}
            fetch_names = [f.name if isinstance(f, ir.Variable) else str(f)
                           for f in fetch_list]
            feed_arrays = self._convert_feeds(feed)

            fast_key = (self._program._uid, self._program._version,
                        frozenset(feed_arrays), tuple(fetch_names),
                        _flags.version())
            hit = self._fast.get(fast_key)
            if hit is None:
                # one-shot memo resolution / build, kept out of the
                # steady-state feed_convert numbers
                spans.phase(_steplog.BIND)
                hit = self._fast[fast_key] = self._bind(
                    fast_key, feed_arrays, fetch_names)
            compiled, self._last_key = hit

            if spans.observing:
                _steplog.track_shapes(compiled, self._program._uid,
                                      feed_arrays, source="parallel")
            spans.phase(_steplog.STATE_GATHER)
            # per-program run counter (see Executor.run): deterministic
            # trajectories from seeded init, per-step mask variation
            counter = np.uint32(self._run_counter)
            self._run_counter += 1
            mut, const = self._state_cache.get(compiled, self._scope)
            spans.phase(_steplog.JIT_CALL)  # run_with_state: -> write_back
            fetches, new_state = compiled.run_with_state(
                self._scope, feed_arrays, mut, const, counter, spans)
            self._state_cache.commit(compiled, self._scope, new_state)
            if return_numpy:
                spans.phase(_steplog.FETCH)
                fetches = [self._fetch_numpy(f) for f in fetches]
        return fetches

    def _bind(self, fast_key, feed_arrays, fetch_names):
        """(compiled step, its cache key) for a feed signature and fetch
        set the fast memo has not seen: from the compile cache, or built."""
        from ..core.executor import resolve_compiler_options
        copts = resolve_compiler_options(
            self._mesh.devices.flat[0].platform, self._program)
        copts_sig = tuple(sorted(copts.items())) if copts else None
        feed_sig = tuple(sorted(feed_arrays))
        key = (self._program._uid, self._program._version, feed_sig,
               tuple(fetch_names), copts_sig)
        compiled = self._cache.get(key)
        if compiled is None:
            _steplog.observatory().note_entry_build(
                self._program._uid, self._program._version, feed_sig,
                tuple(fetch_names), copts_sig, source="parallel",
                scope_uid=self._scope._uid,
                detail=program_detail(self._program))
            compiled = _CompiledProgram(self._program, sorted(feed_arrays),
                                        fetch_names, self._scope,
                                        donate=True,
                                        amp=self._build_strategy.amp,
                                        mesh=self._mesh,
                                        compiler_options=copts)
            offer_step_text(self._program._uid, compiled, feed_arrays,
                            self._scope)
            _evict_stale_versions(self._cache, self._program._uid,
                                  self._program._version)
            self._cache[key] = compiled
        _evict_stale_versions(self._fast, self._program._uid,
                              self._program._version)
        # a flag flip re-keys the memo for the same (program, feed
        # signature, fetch set) — drop the superseded entry
        _evict_superseded(self._fast, fast_key)
        return compiled, key

    @staticmethod
    def _fetch_numpy(f):
        """Multi-host fetch: a global array spanning remote devices cannot
        be np.asarray'd directly — read the local copy when replicated,
        allgather otherwise (every process calls fetch symmetrically, so
        the collective is safe)."""
        if isinstance(f, jax.Array) and not f.is_fully_addressable:
            if f.sharding.is_fully_replicated:
                return np.asarray(f.addressable_shards[0].data)
            from jax.experimental import multihost_utils
            return np.asarray(multihost_utils.process_allgather(f,
                                                                tiled=True))
        return np.asarray(f)

    def _convert_feeds(self, feed):
        block = self._program.global_block()
        feed_arrays = {}
        for name, val in feed.items():
            var = block.vars.get(name)
            if isinstance(val, (tuple, list)) and len(val) == 2 and var is not None \
                    and var.lod_level > 0:
                data, lens = val
                feed_arrays[name] = self._shard_feed(data, var)
                if isinstance(lens, (tuple, list)) and len(lens) == 2 \
                        and not np.isscalar(lens[0]):
                    # nested LoD: (outer counts [B], inner lengths [B, S])
                    feed_arrays[ir.seqlen_var_name(name)] = self._shard_feed(
                        np.asarray(lens[0], np.int32), var)
                    feed_arrays[ir.seqlen_var_name(name, 1)] = \
                        self._shard_feed(np.asarray(lens[1], np.int32), var)
                else:
                    feed_arrays[ir.seqlen_var_name(name)] = self._shard_feed(
                        np.asarray(lens, np.int32), var)
            else:
                feed_arrays[name] = self._shard_feed(val, var)
        return feed_arrays

    def _entry_for(self, feed, what):
        """(cache entry, converted feeds) of the step a run() with this
        feed's names went through."""
        if not self._cache:
            raise RuntimeError(f"{what} requires a prior run()")
        feeds = self._convert_feeds(feed)
        names = tuple(sorted(feeds))
        cands = [k for k in self._cache
                 if k[2] == names and k[1] == self._program._version]
        if not cands:
            raise RuntimeError(
                f"no compiled step matches feed names {sorted(feeds)}; "
                f"run() with this feed first")
        # prefer the step the LAST run used (disambiguates fetch lists)
        key = self._last_key if self._last_key in cands else cands[-1]
        return self._cache[key], feeds

    def lowered_text(self, feed) -> str:
        """StableHLO text of the step this feed shape ran through — the
        supported way to inspect what GSPMD emitted (tests/dryrun assert
        on collective ops here instead of poking privates). Requires a
        prior run() with the same feed names (and fetch list)."""
        compiled, feeds = self._entry_for(feed, "lowered_text")
        return lower_step(compiled, feeds, self._scope).as_text()

    def compiled_text(self, feed) -> str:
        """Optimized-HLO text of the compiled step — AFTER GSPMD
        partitioning, so the collectives XLA actually inserted
        (all-reduce / all-gather / collective-permute / reduce-scatter)
        are visible and countable. Same contract as lowered_text: run()
        with this feed first."""
        compiled, feeds = self._entry_for(feed, "compiled_text")
        # memoize: the AOT compile below is a second full GSPMD+XLA
        # compile of a step run() already compiled where the compile cache
        # is cold (the jit-internal executable is not publicly reachable);
        # callers probing the inventory repeatedly must not pay it
        # repeatedly
        if getattr(compiled, "_hlo_text", None) is None:
            compiled._hlo_text = lower_step(
                compiled, feeds, self._scope).compile().as_text()
        return compiled._hlo_text

    def _shard_feed(self, arr, var=None):
        # already-global arrays (dist.shard_local_batch on multi-host, or a
        # re-fed fetch) pass through untouched
        if isinstance(arr, jax.Array) and getattr(arr, "sharding", None) is not None \
                and isinstance(arr.sharding, NamedSharding) \
                and arr.sharding.mesh == self._mesh:
            return arr
        arr = np.asarray(arr)
        if arr.ndim == 0:
            return self._place_global(arr, self._replicated)
        dp = self._mesh.shape.get("dp", 1)  # no 'dp' axis -> replicated dim 0
        if arr.shape[0] % dp != 0:
            if var is None or var.is_data:
                # a silently replicated DATA feed would train every device
                # on the SAME rows — a correctness bug, not a fallback
                # (reference PE enforces divisibility via data_balance)
                raise ValueError(
                    f"feed batch dim {arr.shape[0]} is not divisible by the "
                    f"{dp}-way data-parallel mesh axis; pad or drop the "
                    f"tail batch (reader.batch(..., drop_last=True))")
            # non-data feeds (lr schedules, class weights, ...) have no
            # batch dimension — replicate
            return self._place_global(arr, self._replicated)
        spec = [None] * arr.ndim
        spec[0] = "dp" if "dp" in self._mesh.axis_names else None
        # sequence parallelism: shard the seq dim of data feeds over 'sp'
        # so ring attention's Q/K/V shards arrive pre-placed
        if ("sp" in self._mesh.axis_names and arr.ndim >= 2
                and var is not None and var.is_data
                and arr.shape[1] % self._mesh.shape["sp"] == 0):
            spec[1] = "sp"
        return self._place_global(arr, NamedSharding(self._mesh,
                                                     PartitionSpec(*spec)))


def collective_inventory(hlo_text: str) -> dict:
    """Count the collective ops in an optimized-HLO module (one compiled
    step): which collectives GSPMD actually inserted for a mesh, per
    step. Async pairs (`-start`/`-done`) count once."""
    inv = {}
    for kind in ("all-reduce", "all-gather", "collective-permute",
                 "reduce-scatter", "all-to-all"):
        n = hlo_text.count(f" {kind}(") + hlo_text.count(f" {kind}-start(")
        if n:
            inv[kind] = n
    return inv
