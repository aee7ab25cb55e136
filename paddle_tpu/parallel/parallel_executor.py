"""ParallelExecutor: data-parallel training via GSPMD sharding.

Capability parity with the reference ParallelExecutor (reference:
paddle/fluid/framework/parallel_executor.cc:118-330 + details/ SSA graph,
python/paddle/fluid/parallel_executor.py).

TPU-native redesign: the reference replicates the program per GPU, builds an
SSA dependency graph, and hand-inserts NCCL AllReduce ops on gradients
(details/all_reduce_op_handle.cc:47). Here the SAME single-program lowering
used by Executor is compiled once under a `jax.sharding.Mesh`, by the same
code (`core/executor.py`: an `Executor` of its own that is told the mesh and
how feeds are placed on it): feeds are
placed batch-sharded over the 'dp' axis, parameters replicated (kAllReduce
analog), and XLA GSPMD inserts the gradient all-reduces over ICI. The
`BuildStrategy.ReduceStrategy.Reduce` mode (sharded optimizer updates,
reference details/reduce_op_handle.cc) maps to sharding optimizer state over
'dp' — XLA then emits reduce-scatter + all-gather, the ZeRO-style pattern.
"""

from __future__ import annotations

import enum
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core import ir
from ..core.executor import (Executor, _convert_feed_dict, _fetch_numpy,
                             global_scope, lower_step)
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
        # the one way from a Program to a running step: an Executor's
        # handles, memo and compile cache, given what only a mesh has
        self._exe = Executor(amp=self._build_strategy.amp)
        self._exe._mesh = self._mesh
        self._exe._place_feeds = self._convert_feeds
        self._exe._source = "parallel"
        self._last = None       # the handle of the last run()
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
            val = _fetch_numpy(val)
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
        feed = feed if feed is not None else feed_dict or {}
        if isinstance(feed, (list, tuple)):     # one dict per device
            merged = {}
            for d in feed:
                for k, v in d.items():
                    merged.setdefault(k, []).append(np.asarray(v))
            feed = {k: np.concatenate(v, axis=0) for k, v in merged.items()}
        self._last = self._exe._handle_for(self._program, fetch_list,
                                           self._scope)
        return self._last.run(feed, return_numpy=return_numpy)

    def _convert_feeds(self, feed):
        """The mesh's feed placement: the Executor's conversion (the
        implicit cast, @SEQLEN companions of LoD feeds), then each array
        under its sharding."""
        block = self._program.global_block()
        return {name: self._shard_feed(
                    val, block.vars.get(name.split(ir.SEQLEN_SUFFIX)[0]))
                for name, val in _convert_feed_dict(block, feed).items()}

    def _entry_for(self, feed, what):
        """(cache entry, converted feeds) of the step a run() with this
        feed's names went through."""
        cache = self._exe._cache
        if not cache:
            raise RuntimeError(f"{what} requires a prior run()")
        feeds = self._convert_feeds(feed)
        names = tuple(sorted(feeds))
        cands = [k for k in cache if k.feeds == names
                 and k.program_version == self._program._version]
        if not cands:
            raise RuntimeError(
                f"no compiled step matches feed names {sorted(feeds)}; "
                f"run() with this feed first")
        # prefer the step the LAST run used (disambiguates fetch lists)
        last = self._last._entry.key
        return cache[last if last in cands else cands[-1]], feeds

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
