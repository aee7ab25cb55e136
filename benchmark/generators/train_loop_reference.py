"""`train_loop` and then, outside the timed window and outside `setup_s`,
one more step compared with the configuration's plain reference: what
`train_loop_checked` does, for any reference.

What it does differently from `train_loop_checked.py`, and why that file
could not be reused: that one reads the keys `ce`, `load_balance` and
`z_loss` of the reference's result by name, which only OLMoE's reference
has, and hands `loss_parts` nothing but the configuration's `build_args`.
This one prints every scalar the reference's `loss_parts` returns, under
whatever keys it returns them (arrays of a few numbers, such as a value per
pass, are printed whole), and hands it the traffic file's
`reference_check.reference_args` as well (how the reference is computed so
that it fits beside the system's state: a block of queries at a time). The
comparison is the same: `train_loop.run(...)` is called as it is and its
observations are returned with the comparison added; afterwards, with the
queue drained, the current float32 weights are read from the scope, the
reference (`references/<name>.py`, float32, every product at "highest")
computes the loss of the next pool batch on the device, the system takes
that step, and the two losses are compared under the traffic file's
`reference_check.loss_atol` (its reason is written beside it): the gap goes
beside its limit into `obs["compared"]`, where `run.py` reads it with the
numbers of its own, so a miss is `correct: false` in a result line that
holds both numbers.
"""

import importlib
import time

import numpy as np

from generators import train_loop
from generators.train_loop_checked import reference_args


def reference_parts(system, ref, feed, extra):
    """{key: float or list of floats} of everything small the reference's
    `loss_parts` returns for one placed batch on the system's current
    weights. Not under one `jit` of the whole pass: the reference jits what
    it repeats (a layer application, a head), so a few small programs
    compile in seconds and run many times."""
    params, kw = reference_args(system, ref)
    out = ref.loss_parts(params, feed["tokens"], feed["labels"], **kw,
                         **extra)
    return {k: np.asarray(v, np.float64).reshape(-1).tolist()
            for k, v in out.items() if np.size(v) <= 16}


def run(system, host_pool, traffic, seconds, trace_dir, t_process_start,
        counter):
    obs = train_loop.run(system, host_pool, traffic, seconds, trace_dir,
                         t_process_start, counter)
    check = traffic["reference_check"]
    ref = importlib.import_module("references." + check["reference"])
    t0 = time.perf_counter()
    batch = host_pool[len(obs["all_losses"]) % len(host_pool)]
    feed = system.place(batch)
    want = reference_parts(system, ref, feed,       # before the step: it
                           check.get("reference_args", {}))
    step_loss = system.step(feed)                   # donates these weights
    got = float(np.asarray(step_loss).reshape(-1)[0])
    want_loss = want.pop("loss")[0]
    diff = abs(got - want_loss)
    others = ", ".join(
        f"{k} " + (f"{v[0]:.6f}" if len(v) == 1 else
                   "[" + " ".join(f"{x:.6f}" for x in v) + "]")
        for k, v in sorted(want.items()))
    print(f"benchmark: reference check after {len(obs['all_losses'])} steps: "
          f"system loss {got:.6f}, float32 reference {want_loss:.6f} "
          f"({others}), |difference| {diff:.6f} against "
          f"{check['loss_atol']}; {time.perf_counter() - t0:.1f} s, outside "
          f"every clock", flush=True)
    obs["compared"] = {"reference_loss_gap": [diff, check["loss_atol"]]}
    return obs
