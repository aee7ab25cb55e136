"""AsyncFeeder overlap proof on the CPU backend (round-4 verdict item 4).

An on-chip feeder A/B has not been taken on the current installation.
This demo measures the property the feeder actually provides — OVERLAP
of host-side batch production with device compute — on the in-process
CPU backend:

  sync loop  : produce(batch) then step(batch), serially
  async loop : AsyncFeeder produces on its thread while the consumer steps

With production cost ~= step cost, perfect overlap halves the loop time;
the demo asserts >= 1.3x (tests/test_data_plane.py runs it).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(sleep_factor=1.0):
    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.async_feeder import AsyncFeeder

    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup), fluid.unique_name.guard():
        img = layers.data(name="img", shape=[-1, 32, 32, 3], dtype="float32",
                          append_batch_size=False)
        lab = layers.data(name="lab", shape=[-1, 1], dtype="int64",
                          append_batch_size=False)
        h = layers.conv2d(input=img, num_filters=32, filter_size=3, padding=1,
                          act="relu", data_format="NHWC")
        h = layers.pool2d(input=h, pool_size=2, pool_stride=2,
                          data_format="NHWC")
        h = layers.conv2d(input=h, num_filters=64, filter_size=3, padding=1,
                          act="relu", data_format="NHWC")
        p = layers.fc(input=h, size=10, act="softmax")
        loss = layers.mean(layers.cross_entropy(input=p, label=lab))
        fluid.optimizer.Momentum(learning_rate=0.01, momentum=0.9) \
            .minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)

    rng = np.random.RandomState(0)
    base = rng.rand(64, 32, 32, 3).astype(np.float32)
    labs = rng.randint(0, 10, (64, 1)).astype(np.int64)

    def step(feed):
        # return_numpy=True: the loop reads the loss every step, as the
        # reference trainers do — each step SYNCHRONIZES on its result,
        # which is exactly when reader latency shows up in the loop time
        # (a fully-async loop is already overlapped by PJRT dispatch)
        return exe.run(main_p, feed=feed, fetch_list=[loss],
                       return_numpy=True, scope=scope)

    # calibrate device-step cost, then give the producer comparable work
    step({"img": base, "lab": labs})
    t0 = time.perf_counter()
    for _ in range(10):
        step({"img": base, "lab": labs})
    step_ms = (time.perf_counter() - t0) / 10 * 1e3

    N = 30

    def produce():
        # I/O-bound reader stand-in (the double_buffer use case: RecordIO
        # from disk/network — waits release the GIL and burn no CPU, so
        # they CAN overlap with compute; on this backend the "device" is
        # the same CPU, so compute-bound production could never overlap)
        time.sleep(sleep_factor * step_ms / 1e3)
        a = (base * 1.0001).astype(np.float32)
        return {"img": a, "lab": labs}

    def reader():
        for _ in range(N):
            yield [produce()]

    # sync: produce then step, serially
    t0 = time.perf_counter()
    for batch in reader():
        step(batch[0])
    t_sync = time.perf_counter() - t0

    # async: producer thread overlaps with the stepping consumer
    feeder = AsyncFeeder(lambda b: b[0], reader, capacity=4)
    t0 = time.perf_counter()
    for feed in feeder:
        step(feed)
    t_async = time.perf_counter() - t0

    speedup = t_sync / t_async
    print(json.dumps({"feeder_overlap_speedup_cpu_demo": round(speedup, 2),
                      "sleep_factor": sleep_factor,
                      "sync_s": round(t_sync, 3),
                      "async_s": round(t_async, 3),
                      "step_ms": round(step_ms, 1)}))
    return speedup


if __name__ == "__main__":
    s = main()
    if "--assert" in sys.argv and s < 1.3:
        sys.exit(f"feeder overlap speedup {s:.2f} < 1.3")
