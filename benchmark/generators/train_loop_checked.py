"""`train_loop` and then, outside the timed window and outside `setup_s`,
one more step compared with the configuration's plain reference.

`train_loop.run(...)` is called as it is and its observations are returned
with the comparison added: clocks, warm-up, window and profile are its own.
Afterwards, with the queue drained: the current float32 weights are read
from the scope, the
reference (`references/<name>.py`, float32, every product at "highest")
computes the loss of the next pool batch on the device, the system takes
that step, and the two losses are compared under the traffic file's
`reference_check.loss_atol` (its reason is written beside it): the gap
goes beside its limit into `obs["compared"]`, where `run.py` reads it with
the numbers of its own, so a miss is `correct: false` in a result line that
holds both numbers.

The reference's keyword arguments are taken from the configuration's
`build_args` by name (whatever `loss_parts` accepts).
"""

import importlib
import inspect
import time

import numpy as np

from generators import train_loop


def reference_args(system, ref):
    """(the system's current parameters by name, the keyword arguments of
    the reference's `loss_parts` that the configuration's `build_args`
    name)."""
    names = [p.name for p in system.main.global_block().all_parameters()]
    accepted = inspect.signature(ref.loss_parts).parameters
    return ({n: system.scope.find_var(n) for n in names},
            {k: v for k, v in system.build_args.items() if k in accepted})


def reference_loss(system, ref, feed):
    """The reference's loss parts for one placed batch on the system's
    current weights, as Python floats. Op by op, not under one `jit`: the
    reference's loop over experts repeats a few small programs, which
    compile in seconds, where the whole unrolled pass took 147 s to compile
    cold on the chip (chip run, PR 28)."""
    params, kw = reference_args(system, ref)
    out = ref.loss_parts(params, feed["tokens"], feed["labels"], **kw)
    return {k: float(out[k]) for k in ("loss", "ce", "load_balance",
                                       "z_loss")}


def run(system, host_pool, traffic, seconds, trace_dir, t_process_start,
        counter):
    obs = train_loop.run(system, host_pool, traffic, seconds, trace_dir,
                         t_process_start, counter)
    check = traffic["reference_check"]
    ref = importlib.import_module("references." + check["reference"])
    t0 = time.perf_counter()
    batch = host_pool[len(obs["all_losses"]) % len(host_pool)]
    feed = system.place(batch)
    want = reference_loss(system, ref, feed)     # before the step: it
    step_loss = system.step(feed)                # donates these weights
    got = float(np.asarray(step_loss).reshape(-1)[0])
    diff = abs(got - want["loss"])
    print(f"benchmark: reference check after {len(obs['all_losses'])} steps: "
          f"system loss {got:.6f}, float32 reference {want['loss']:.6f} "
          f"(ce {want['ce']:.6f}, load_balance {want['load_balance']:.6f}, "
          f"z_loss {want['z_loss']:.6f}), |difference| {diff:.6f} against "
          f"{check['loss_atol']}; {time.perf_counter() - t0:.1f} s, outside "
          f"every clock", flush=True)
    obs["compared"] = {"reference_loss_gap": [diff, check["loss_atol"]]}
    return obs
