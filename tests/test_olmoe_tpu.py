"""TPU-only: OLMoE's expert layer as the chip compiles it. The CPU suite
(tests/test_olmoe.py) holds the mathematics to the reference in float32;
what only the chip can say is that the Pallas grouped-matmul kernels
compile under Mosaic and keep their names (`%gmm`, `%tgmm`), that each
forward product runs once a step, and how far bf16 AMP on the MXU moves a
small model, gradients included, from the float32 reference."""

import re

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import models

import olmoe_reference as ref

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="Mosaic custom calls need real TPU hardware")

# heads of 128 and a sequence of 256: inside the flash kernels' envelope
SMALL = dict(vocab_size=512, seq_len=256, n_layer=1, d_model=256, n_head=2,
             n_expert=8, top_k=2, d_expert=128)
REF_KW = dict(n_layer=1, n_head=2, top_k=2)


def _program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds, fetches = models.olmoe.build(**SMALL)
        fluid.optimizer.Adam(learning_rate=4e-4).minimize(fetches["loss"])
    main.random_seed = startup.random_seed = 7
    return main, startup, fetches


def _feed(seed=0, batch=2):
    rng = np.random.RandomState(seed)
    shape = (batch, SMALL["seq_len"])
    return {"tokens": rng.randint(0, 512, shape).astype(np.int32),
            "labels": rng.randint(0, 512, shape).astype(np.int32)}


def _custom_calls(text):
    return re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = .* custom-call\(.*"
                      r"custom_call_target=\"tpu_custom_call\"", text, re.M)


def test_grouped_matmul_kernels_keep_their_names_each_forward_once():
    main, startup, fetches = _program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0), amp=True)
    exe.run(startup, scope=scope)
    feed = _feed()
    out, = exe.run(main, feed=feed, fetch_list=[fetches["loss"]],
                   scope=scope)
    assert np.isfinite(out).all()
    compiled, = [c for c in exe._cache.values() if c.program is main]
    text = compiled._step.lower(
        feed, {n: scope.find_var(n) for n in compiled.mut_names},
        {n: scope.find_var(n) for n in compiled.const_names},
        np.uint32(0)).compile().as_text()
    names = _custom_calls(text)
    # gate, up, down: forward and input gradient are %gmm, the weight
    # gradient %tgmm; a forward product run again inside the grad op would
    # make it 9 %gmm
    assert sum(n.startswith("gmm") for n in names) == 6, names
    assert sum(n.startswith("tgmm") for n in names) == 3, names
    assert any(n.startswith("flash_fwd") for n in names), names
    assert any(n.startswith("flash_dq_flash_dkv") for n in names), names


def test_small_model_under_amp_is_within_bf16_of_the_reference():
    """bf16 operands carry 8 bits of mantissa (2^-9 = 0.002 relative); the
    logits are sums of 256 such products with std about 0.3, and a loss is a
    mean over 512 positions: logits within 0.03, losses within 2e-3. The
    reference is given the system's routing: where bf16 flips a near-tie a
    token goes to another expert and its logits move by far more."""
    main, startup, fetches = _program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0), amp=True)
    exe.run(startup, scope=scope)
    params = {p.name: np.asarray(scope.find_var(p.name))
              for p in main.global_block().all_parameters()}
    feed = _feed(seed=1)
    names = ["loss", "ce", "load_balance", "z_loss", "logits"]
    router, = [op for op in main.global_block().ops
               if op.type == "moe_router"]
    got = dict(zip(names + ["index"], exe.run(
        main, feed=feed, fetch_list=[fetches[n] for n in names]
        + [router.outputs["TopKIndex"][0]], scope=scope)))
    want = ref.loss_parts(params, feed["tokens"], feed["labels"],
                          routing=[np.asarray(got["index"])], **REF_KW)
    worst = np.max(np.abs(np.asarray(got["logits"], np.float32)
                          - np.asarray(want["logits"])))
    assert worst < 0.03, worst
    for n in names[:4]:
        diff = abs(float(np.asarray(got[n]).reshape(-1)[0]) - float(want[n]))
        assert diff < 2e-3, (n, diff)
