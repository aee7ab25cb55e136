"""The system under test, built from a configuration file, a cell file and
the lengths of the cell's traffic mix, through the program's public entry
points only: `<model>.build`, an optimizer's `minimize`, `fluid.Executor(fluid.TPUPlace(0), amp=...)` or
`fluid.ParallelExecutor` on a mesh, and `run(..., return_numpy=False)`.

Copied from `chip_smoke.py` (PR 21), which ran these calls on the chip; the
yardstick keeps its own copy so that a later PR cannot change what is
measured by changing a helper.
"""

import importlib
import time

import numpy as np

# jax.random.key(program.random_seed) is a constant inside the compiled
# programs (core/executor.py), so a program seed that followed --seed would
# be a new executable in every run: a full compile of the step, and 17.6 s
# for the startup program alone (20.7 s against 3.0 s from the cache, chip
# run, PR 24). Both seeds are therefore fixed: every run starts from the
# same weights, made on the device by the startup program, and --seed makes
# the batches (make_pool). A step's time does not depend on either.
PROGRAM_SEED = 7


def _resolve(dotted):
    module, _, attr = dotted.partition(":")
    return getattr(importlib.import_module(module), attr)


def make_pool(feeds, ranges, batch, n_batches, seed):
    """`n_batches` distinct host batches from the seed: for every feed the
    program declares, its declared shape with the batch dimension filled in,
    integers uniform in the configuration's `[low, high)`, floats uniform in
    [0, 1). Every seed gives the same shapes; only the values differ."""
    rng = np.random.RandomState(seed % (2 ** 32))
    pool = []
    for _ in range(n_batches):
        one = {}
        for name in sorted(feeds):
            var = feeds[name]
            shape = [batch if d in (-1, None) else int(d) for d in var.shape]
            if np.dtype(str(var.dtype)).kind in "iu":
                low, high = ranges[name]
                one[name] = rng.randint(low, high, shape).astype(np.int32)
            else:
                one[name] = rng.rand(*shape).astype(np.float32)
        pool.append(one)
    return pool


class System:
    """One training program on one chip or on a mesh, ready to step."""

    def __init__(self, config, cell, traffic, devices, batch, tiny=False):
        import jax
        import paddle_tpu as fluid

        self.jax = jax
        t0 = time.perf_counter()
        self.devices = devices
        self.batch = batch
        self.mesh_shape = cell.get("mesh")
        build_args = dict(config["build_args"])
        build_args.update(traffic.get("build_args", {}))   # the mix's lengths
        if tiny:
            build_args.update(config["tiny"]["build_args"])
        self.build_args = build_args
        main_p, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_p, startup), fluid.unique_name.guard():
            feeds, fetches = _resolve(config["builder"])(**build_args)
            self.loss = fetches["loss"]
            opt = config["optimizer"]
            getattr(fluid.optimizer, opt["type"])(**opt["args"]).minimize(
                self.loss)
        main_p.random_seed = startup.random_seed = PROGRAM_SEED
        self.main, self.startup, self.feeds = main_p, startup, feeds
        self.scope = fluid.Scope()
        self.exe = fluid.Executor(fluid.TPUPlace(0), amp=config["amp"])
        self.build_s = time.perf_counter() - t0
        self.exe.run(startup, scope=self.scope)
        self.jax.block_until_ready(
            [self.scope.find_var(n) for n in self.scope.local_var_names()])
        self.startup_s = time.perf_counter() - t0 - self.build_s
        self.pe = None
        if self.mesh_shape:
            from paddle_tpu.parallel.mesh import make_mesh
            names = list(self.mesh_shape)
            self.mesh = make_mesh([self.mesh_shape[n] for n in names], names,
                                  devices)
            strategy = fluid.BuildStrategy()
            strategy.amp = config["amp"]
            self.pe = fluid.ParallelExecutor(
                main_program=main_p, loss_name=self.loss.name,
                scope=self.scope, mesh=self.mesh, build_strategy=strategy)

    def place(self, host_batch):
        """One host batch onto the device(s), as a user's input pipeline
        would hand it over: whole on one chip, split along the batch over
        the mesh's `dp` axis (the form ParallelExecutor passes through
        without a copy)."""
        jax = self.jax
        if self.pe is None:
            return {k: jax.device_put(v, self.devices[0])
                    for k, v in host_batch.items()}
        from jax.sharding import NamedSharding, PartitionSpec
        sharding = NamedSharding(self.mesh, PartitionSpec("dp"))
        return {k: jax.device_put(v, sharding) for k, v in host_batch.items()}

    def step(self, feed):
        """Dispatch one training step; returns the loss on the device,
        without waiting for it."""
        if self.pe is None:
            return self.exe.run(self.main, feed=feed, fetch_list=[self.loss],
                                return_numpy=False, scope=self.scope)[0]
        return self.pe.run(fetch_list=[self.loss.name], feed=feed,
                           return_numpy=False)[0]

    def compiled_text(self, feed):
        """Optimized HLO of the mesh step (ParallelExecutor's public
        `compiled_text`); None on one chip, where the program offers none."""
        return None if self.pe is None else self.pe.compiled_text(feed)

    def close(self):
        self.exe.close()
