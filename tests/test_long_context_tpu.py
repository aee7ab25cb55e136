"""TPU-only: long-context evidence (SURVEY §5.7). The Pallas flash path
must run fwd+bwd at sequence lengths where materializing the [B,H,T,T]
score tensor cannot fit: at seq 16384 with 4 heads the scores alone would
be 4 x 16384^2 x 2B = 2 GiB per batch element — the O(T) kernel trains
through the DSL regardless."""

import numpy as np
import pytest

import jax

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="long-context flash kernels need real TPU hardware")


def test_flash_seq32k_kernel_grad():
    """Raw kernels at 32k context (streamed K/V grid): fwd+bwd finite."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_attention import flash_attention

    rng = np.random.RandomState(0)
    T, D = 32768, 64
    q, k, v = (jnp.asarray(rng.randn(1, 1, T, D).astype(np.float32) * 0.1)
               for _ in range(3))

    def loss(q, k, v):
        return flash_attention(q, k, v, jnp.int32(0), causal=True,
                               sm_scale=D ** -0.5).sum()

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    assert all(bool(jnp.isfinite(x.sum())) for x in g)


def test_flash_seq16k_trains():
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.models.transformer import multi_head_attention

    SEQ, D = 16384, 256
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[-1, SEQ, D], dtype="float32",
                        append_batch_size=False)
        h = multi_head_attention(x, x, D, num_heads=4, dropout_rate=0.1,
                                 causal=True, name="long", fused=True)
        loss = layers.mean(h)
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0), amp=True)
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    xb = rng.randn(1, SEQ, D).astype(np.float32)
    vals = []
    for _ in range(2):
        out, = exe.run(main, feed={"x": xb}, fetch_list=[loss], scope=scope)
        vals.append(float(np.asarray(out).reshape(-1)[0]))
    assert all(np.isfinite(v) for v in vals), vals
    assert vals[1] != vals[0], "no parameter movement at seq 16k"
