"""`train_loop_reference`, and in the same compared step the UPDATE of a few
named parameters against the reference's: a second number beside the loss,
one that a lower precision cannot pass by a lucky draw.

Why a second number: `train_loop_reference` compares one scalar, the loss.
A loss computed in bfloat16 lies on a grid (0.0625 wide between 8 and 16), so
the reference computed in bfloat16 throughout misses the float32 loss by a
draw from that grid, and about one draw in fifteen falls under any limit that
leaves the system room (`PERF.md` section 7). What bfloat16 cannot do at all
is move its state by a step that is far under its own spacing: at the cells'
learning rates a step changes a weight by ~1e-6 where a bfloat16 weight of
0.02 is spaced 1.2e-4, so a bfloat16 state is left where it was. The number:

    update_gap(name) = |d_system - d_reference| / |d_reference|   (Frobenius)

    d_system     the parameter after the system's step minus before it
    d_reference  what the optimizer's rule (`adam_delta`: Adam as the
                 Program's `adam` op states it) makes of the REFERENCE's
                 float32 gradient on the same weights and batch, from the
                 system's own moments and step count before the step

and `reference_update_gap` is the largest over the traffic file's
`reference_check.update.parameters`, under `reference_check.update.rel_atol`.
A state left unchanged reads 1 (so does a step of the wrong sign or a missing
one), the system reads what its bf16 gradient differs from the float32 one in
this step's tenth of the first moment. The parameters are chosen where the
reference's gradient costs a forward pass and little more (the head, the last
norm, the last layer's way out): differentiating with respect to them alone
leaves every earlier layer a plain forward call.

Everything else is `train_loop_reference`'s: `train_loop.run(...)` as it is,
the loss of the next pool batch under `reference_check.loss_atol`, every
scalar of the reference's `loss_parts` printed, all of it after the window
and outside every clock.
"""

import importlib
import time

import numpy as np

from generators import train_loop
from generators.train_loop_checked import reference_args


def adam_delta(param, grad, moment1, moment2, beta1_pow, beta2_pow, lr, *,
               beta1, beta2, epsilon, dtype="float32"):
    """The change one Adam step makes to `param`, as the Program's `adam`
    op states the rule (`paddle_tpu/ops/optimizer_ops.py`), with every array
    and every product in `dtype`; returned as float32 numpy. In bfloat16 this
    is what a state held in that precision does: the new value rounds back
    onto the old one wherever the step is under half its spacing."""
    import jax.numpy as jnp
    cast = lambda a: jnp.asarray(a, jnp.float32).astype(dtype)
    p, g, m1, m2 = cast(param), cast(grad), cast(moment1), cast(moment2)
    b1p, b2p = cast(beta1_pow).reshape(()), cast(beta2_pow).reshape(())
    lr = cast(lr).reshape(())
    m1 = beta1 * m1 + (1 - beta1) * g
    m2 = beta2 * m2 + (1 - beta2) * g * g
    lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
    new = (p - lr_t * m1 / (jnp.sqrt(m2) + epsilon)).astype(dtype)
    return np.asarray((new - p).astype(jnp.float32))


def update_gap(got, want):
    """|got - want| / |want| in the Frobenius norm; 1 where `got` is 0."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def optimizer_state(system, names):
    """{parameter: (its `adam` op's attributes, {slot: the scope's value,
    copied off the buffers that the next step donates})} for `names`."""
    ops = {op.input("Param")[0]: op
           for op in system.main.global_block().ops if op.type == "adam"}
    slots = ("Param", "Moment1", "Moment2", "Beta1Pow", "Beta2Pow",
             "LearningRate")
    return {n: (ops[n].attrs, {
        s: np.array(system.scope.find_var(ops[n].input(s)[0]))
        for s in slots}) for n in names}


def reference_delta(attrs, state, grad, dtype="float32"):
    return adam_delta(
        state["Param"], grad, state["Moment1"], state["Moment2"],
        state["Beta1Pow"], state["Beta2Pow"], state["LearningRate"],
        beta1=attrs["beta1"], beta2=attrs["beta2"],
        epsilon=attrs["epsilon"], dtype=dtype)


def run(system, host_pool, traffic, seconds, trace_dir, t_process_start,
        counter):
    obs = train_loop.run(system, host_pool, traffic, seconds, trace_dir,
                         t_process_start, counter)
    check = traffic["reference_check"]
    names = list(check["update"]["parameters"])
    ref = importlib.import_module("references." + check["reference"])
    t0 = time.perf_counter()
    batch = host_pool[len(obs["all_losses"]) % len(host_pool)]
    feed = system.place(batch)
    params, kw = reference_args(system, ref)        # before the step: it
    before = optimizer_state(system, names)         # donates these weights
    parts, grads = ref.loss_and_grads(
        params, feed["tokens"], feed["labels"], wrt=names, **kw,
        **check.get("reference_args", {}))
    want = {k: np.asarray(v, np.float64).reshape(-1).tolist()
            for k, v in parts.items() if np.size(v) <= 16}
    step_loss = system.step(feed)
    got = float(np.asarray(step_loss).reshape(-1)[0])
    want_loss = want.pop("loss")[0]
    diff = abs(got - want_loss)
    gaps = {}
    for n in names:
        attrs, state = before[n]
        moved = np.asarray(system.scope.find_var(n)) - state["Param"]
        gaps[n] = update_gap(moved, reference_delta(attrs, state, grads[n]))
    worst = max(gaps.values())
    others = ", ".join(
        f"{k} " + (f"{v[0]:.6f}" if len(v) == 1 else
                   "[" + " ".join(f"{x:.6f}" for x in v) + "]")
        for k, v in sorted(want.items()))
    print(f"benchmark: reference check after {len(obs['all_losses'])} steps: "
          f"system loss {got:.6f}, float32 reference {want_loss:.6f} "
          f"({others}), |difference| {diff:.6f} against "
          f"{check['loss_atol']}; the step's update against Adam on the "
          f"reference's gradient, |difference| / |reference's|: "
          + ", ".join(f"{n} {g:.6f}" for n, g in gaps.items())
          + f", against {check['update']['rel_atol']} (a state left "
          f"unchanged reads 1); {time.perf_counter() - t0:.1f} s, outside "
          f"every clock", flush=True)
    obs["compared"] = {
        "reference_loss_gap": [diff, check["loss_atol"]],
        "reference_update_gap": [worst, check["update"]["rel_atol"]]}
    return obs
