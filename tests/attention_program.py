"""One `fused_attention` op in a program of its own, for the tests that hold
its grad op (tests/test_flash_attention.py on the CPU under the Pallas
interpreter, tests/test_flash_grad_tpu.py on the chip), and the flash calls'
grids and index maps with nothing run (`flash_calls`)."""

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core import registry
from paddle_tpu.layer_helper import LayerHelper
from paddle_tpu.ops import pallas_attention


def float32_grad_layer(monkeypatch):
    """Registers, for one test, an identity op whose grad rule hands a
    float32 gradient to a bf16 input, as a hand-written grad rule may: what
    makes `Out@GRAD` float32 beside a bf16 `Out` under AMP. Returns the
    layer function."""
    opdef = registry.OpDef(
        "float32_grad_identity", lambda ctx, X: {"Out": X}, None, False,
        False, grad_lower=lambda ctx, ins, out_grads: {
            "X": out_grads["Out"][0].astype(jnp.float32)})
    monkeypatch.setitem(registry._REGISTRY, opdef.type, opdef)

    def layer(x):
        helper = LayerHelper(opdef.type)
        out = helper.create_variable_for_type_inference(x.dtype)
        helper.append_op(opdef.type, inputs={"X": [x.name]},
                         outputs={"Out": [out.name]})
        return out
    return layer


def qkv_feed(names, shape=(2, 2, 256, 64), seed=5, dtype=np.float32):
    rng = np.random.RandomState(seed)
    feed = {n: rng.randn(*shape).astype(np.float32).astype(dtype)
            for n in names}
    feed["probe"] = rng.randn(*shape).astype(np.float32)
    return feed


def attention_grads(feed, causal, amp, rate=0.0, after=None, strip_lse=False,
                    place=None, layout="BHTD"):
    """A program of one `fused_attention` over data vars (one var in all
    three slots if the feed has only `q`), loss = sum(after(out) * probe):
    returns Out, the fetched input gradients by name, and the traced step's
    text. `layout` is the op's: how the feed's arrays lie."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        data = {n: layers.data(name=n, shape=list(x.shape),
                               dtype=str(x.dtype), append_batch_size=False,
                               stop_gradient=False)
                for n, x in feed.items() if n != "probe"}
        # an op in front, so that the attention op is not the block's op 0
        q = layers.scale(data["q"], scale=1.0)
        out = layers.fused_attention(q, data.get("k", q), data.get("v", q),
                                     causal=causal, dropout_rate=rate,
                                     layout=layout)
        if strip_lse:
            del main.global_block().ops[-1].outputs["Lse"]
        probe = layers.data(name="probe", shape=list(feed["probe"].shape),
                            dtype="float32", append_batch_size=False)
        loss = layers.reduce_sum(layers.elementwise_mul(
            after(out) if after else out, probe))
        fluid.append_backward(loss)
    main.random_seed = 7
    scope = fluid.Scope()
    exe = fluid.Executor(place or fluid.CPUPlace(), amp=amp)
    exe.run(startup, scope=scope)
    wrt = sorted(data)
    fetched = exe.run(main, feed=feed, scope=scope,
                      fetch_list=[out] + [n + "@GRAD" for n in wrt])
    return fetched[0], dict(zip(wrt, fetched[1:])), step_text(exe, main,
                                                              scope, feed)


class _StepText(str):
    """A step's jaxpr as text, the jaxpr itself beside it (`kernel_calls`
    counts call sites on it: the text prints a jitted function's body once
    however many equations call it)."""
    jaxpr = None


def step_text(exe, main, scope, feed):
    """The jaxpr of the executor's jitted step for `main`, as text: every
    `pallas_call` appears in it with its `name=`."""
    compiled, = [c for c in exe._cache.values() if c.program is main]
    closed = compiled._step.trace(
        feed, {n: scope.find_var(n) for n in compiled.mut_names},
        {n: scope.find_var(n) for n in compiled.const_names},
        np.uint32(0)).jaxpr
    text = _StepText(closed)
    text.jaxpr = closed
    return text


def _pallas_calls(jaxpr, kernel):
    """Equations of `jaxpr` that are a `pallas_call` named `kernel`, those
    of the jaxprs its equations call counted once for each call."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params.get("name_and_src_info") or eqn.params.get(
                "name")
            count += getattr(name, "name", name) == kernel
            continue
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    count += _pallas_calls(sub, kernel)
    return count


def kernel_calls(text, kernel):
    """Call sites of the `pallas_call` named `kernel` in a step
    (`step_text`), or its occurrences in a jaxpr's plain text."""
    if getattr(text, "jaxpr", None) is not None:
        return _pallas_calls(text.jaxpr, kernel)
    return text.count(f"name={kernel}\n") + text.count(f"name={kernel} ")


def flash_calls(monkeypatch, T, tiles, causal=True, token_major=False,
                kept=False, split=False, every_step=False):
    """name -> (grid, input specs) of the forward and the backward calls of
    one row length, as `_forward` and `_bwd_specs` build them: `pallas_call`
    is stood in for, nothing runs. `every_step`: the maps without the hold,
    every operand's own block on every grid step."""
    from jax.experimental import pallas as pl

    calls = {}

    def pallas_call(kernel, *, grid, in_specs, out_shape, name, **kw):
        calls[name] = (grid, in_specs)
        return lambda *operands: jax.tree.map(
            lambda x: jnp.zeros(x.shape, x.dtype), out_shape)

    B, H, D = 2, 2, 128
    x = jax.ShapeDtypeStruct((B, T, H, D) if token_major else (B, H, T, D),
                             jnp.float32)
    the_set = jnp.ones((B, T, T), jnp.int8) if kept else None

    def both(q, k, v, g):
        out, lse = pallas_attention._flash_forward(
            q, k, v, causal, 1.0, token_major=token_major, kept=the_set)
        return pallas_attention._flash_backward(
            q, k, v, out, lse, g, causal, 1.0, 0.0, 0,
            token_major=token_major, kept=the_set)

    with monkeypatch.context() as patch:
        patch.setattr(pl, "pallas_call", pallas_call)
        patch.setattr(pallas_attention, "_BLOCK_OVERRIDE", tiles)
        # a jitted call keeps its trace: the stand-in has to be called
        patch.setattr(pallas_attention, "_jitted_forward",
                      pallas_attention._forward)
        patch.setattr(pallas_attention, "_jitted_backward",
                      pallas_attention._backward)
        patch.setattr(pallas_attention, "_bwd_plan",
                      lambda *a: "split" if split else "fused")
        if every_step:
            patch.setattr(pallas_attention, "_dead_steps", lambda *a: 0)
        jax.eval_shape(both, x, x, x, x)
    return calls
