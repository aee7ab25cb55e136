#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives both main paths once, through the entry points a user calls, at the
full width of models the repo supports (weights random from a seed):

  train  layers -> Program -> minimize -> Executor(TPUPlace(0)) -> exe.run
         transformer-base seq 256 (unfused, then flash), seq 2048 flash at
         the tuned long-context tiles, ResNet-50 bs128
  serve  tiny_lm.save_tiny_lm at a chip-shaped signature -> InferenceServer
         -> add_model -> concurrent submit_generate over the paged-KV
         cache, float32 and int8 residency; the paged kernels against
         their references at op level
  mesh   transformer-base through ParallelExecutor on dp=4 and dp2 x mp2,
         only where >= 4 devices are visible

    python chip_smoke.py               # on a TPU, or it fails
    python chip_smoke.py --rehearsal   # tiny sizes on CPU, kernels interpreted

One process: the chip belongs to whoever touched jax first, so nothing here
starts a child. No phase is wrapped in a handler that swallows its failure:
the first one that fails ends the run with a non-zero exit code. Each phase
prints its cold-compile seconds, a warm step/request time (taken with
block_until_ready; information for the next PR, not a claim), a `CHECK`
line carrying the exact losses/tokens (diff two runs' CHECK lines to see
that a warm compile cache changes nothing), and PASS. The rehearsal prints
REHEARSAL wherever the real run prints PASS, and never prints the result
line.
"""

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# Full sizes, and the tiny ones the CPU rehearsal swaps in. Widths on the
# chip are the models' own (transformer.build defaults, ResNet-50); only
# what the rehearsal overrides is listed.
REAL = {
    "transformer": {},
    "seq": 256, "batch": 64, "steps": 8,
    "long_seq": 2048, "long_batch": 8, "long_steps": 3,
    "resnet": {"depth": 50, "class_dim": 1000,
               "image_shape": (3, 224, 224)},
    "resnet_batch": 128, "resnet_steps": 3,
    # the existing generative producer at a real width: d_model 1024 =
    # 8 heads x 128, so a [block_size * heads, head_dim] cache tile is
    # (128, 128) — whole int8 and float32 tiles
    "lm": {"vocab": 32000, "d_model": 1024, "n_heads": 8, "n_layers": 4,
           "max_slots": 4, "block_size": 16, "max_context": 1024,
           "prefill_rows": (1,), "prefill_seq_rungs": (128, 256)},
    "prompt_lens": (5, 40, 100, 128, 129, 200, 250, 17),
    "max_new": (24, 8, 16, 12, 20, 8, 10, 24),
    "mesh_steps": 4,
}
TINY = dict(
    REAL,
    transformer={"src_vocab_size": 128, "trg_vocab_size": 128, "n_layer": 1,
                 "n_head": 2, "d_model": 32, "d_inner": 64},
    seq=128, batch=4, long_seq=256, long_batch=1, long_steps=2,
    resnet={"depth": 18, "class_dim": 10, "image_shape": (3, 32, 32)},
    resnet_batch=2, resnet_steps=2,
    lm={"vocab": 32, "d_model": 16, "n_heads": 2, "n_layers": 2,
        "max_slots": 4, "block_size": 4, "max_context": 32,
        "prefill_rows": (1,), "prefill_seq_rungs": (8, 16)},
    prompt_lens=(2, 5, 8, 9, 12, 16, 3, 7), max_new=(6, 3, 5, 4, 6, 3, 4, 6),
    mesh_steps=3,
)

# paged kernel vs reference, outputs O(1): the reference runs at "highest"
# matmul precision, the kernel at Mosaic's default for float32 operands
PAGED_ATOL = 2e-2
# one-chip vs sharded loss under bf16 AMP: same math, other reduction order
MESH_RTOL = 2e-2


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU backend with the Pallas "
                         "kernels interpreted; prints REHEARSAL, not PASS")
    rehearsal = ap.parse_args().rehearsal
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()

    import jax
    import numpy as np

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    print(f"jax {jax.__version__}  devices: {dev}", flush=True)
    if not rehearsal and any(d.platform != "tpu" for d in devices):
        print(f"chip_smoke: jax found no TPU ({devices}); nothing was run. "
              f"--rehearsal runs the tiny CPU version.", file=sys.stderr)
        return 2

    import paddle_tpu as fluid
    from paddle_tpu import models, observe, serve
    from paddle_tpu.models import tiny_lm
    from paddle_tpu.ops import paged_attention as pa
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.parallel_executor import collective_inventory
    from tools._common import compile_main_step

    print(f"compile cache: {jax.config.jax_compilation_cache_dir}",
          flush=True)
    fluid.set_flag("observe", True)   # recompile + shape tracking on
    sz = TINY if rehearsal else REAL
    ok_word = "REHEARSAL" if rehearsal else "PASS"

    @contextlib.contextmanager
    def phase(name):
        print(f"--- {name}", flush=True)
        t0 = time.perf_counter()
        try:
            yield
        except BaseException as e:
            print(f"FAIL {name}: {e!r}", flush=True)
            raise
        print(f"{ok_word} {name}  ({time.perf_counter() - t0:.1f}s)",
              flush=True)

    def check(name, values):
        """The exact values a second run must reproduce."""
        print(f"CHECK {name} {json.dumps(values)}", flush=True)

    def recompiles():
        return sum(observe.observatory().counts().values())

    def train_steps(run_step, n):
        """n steps of `run_step() -> device loss`; returns (losses, cold
        first-step seconds, warm seconds/step) and asserts that nothing
        compiled after the first step."""
        t0 = time.perf_counter()
        first = run_step()
        first.block_until_ready()
        cold = time.perf_counter() - t0
        after_first = recompiles()
        losses, t0 = [first], time.perf_counter()
        for _ in range(n - 1):
            losses.append(run_step())
        losses[-1].block_until_ready()
        warm = (time.perf_counter() - t0) / (n - 1)
        assert recompiles() == after_first, (
            f"recompiled after the first step: "
            f"{observe.observatory().counts()}")
        losses = [float(np.asarray(l).reshape(-1)[0]) for l in losses]
        assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
        print(f"  cold first step {cold:.1f}s, warm {warm * 1e3:.1f} ms/step"
              f" (informational), losses {losses[0]:.4f} -> "
              f"{losses[-1]:.4f}", flush=True)
        return losses

    def build_transformer(seq, fused, dropout=0.1):
        main_p, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_p, startup), \
                fluid.unique_name.guard():
            _, fetches = models.transformer.build(
                seq_len=seq, fused_attention=fused, dropout_rate=dropout,
                **sz["transformer"])
            loss = fetches["loss"]
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
        main_p.random_seed = startup.random_seed = 7
        return main_p, startup, loss

    def token_batch(batch, seq):
        vocab = sz["transformer"].get("trg_vocab_size", 30000)
        rng = np.random.RandomState(0)
        return {k: rng.randint(1, vocab, (batch, seq)).astype(np.int32)
                for k in ("src_word", "trg_word", "lbl_word")}

    def train_transformer(name, seq, batch, fused, steps):
        main_p, startup, loss = build_transformer(seq, fused)
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.TPUPlace(0), amp=True)
        exe.run(startup, scope=scope)
        feed = {k: jax.device_put(v)
                for k, v in token_batch(batch, seq).items()}
        losses = train_steps(
            lambda: exe.run(main_p, feed=feed, fetch_list=[loss],
                            return_numpy=False, scope=scope)[0], steps)
        assert losses[-1] < losses[0], f"loss did not fall: {losses}"
        if fused and not rehearsal:
            # a quiet reference path cannot pass: the kernels must be IN
            # the compiled step
            text = compile_main_step(exe, scope, main_p).as_text()
            n = text.count("tpu_custom_call")
            assert n, "no Mosaic custom call in the compiled fused step"
            print(f"  {n} tpu_custom_call mentions in the compiled step",
                  flush=True)
        check(name, losses)
        exe.close()
        return losses

    # -- train ------------------------------------------------------------
    with phase("transformer-base seq256 unfused"):
        train_transformer("transformer256_unfused", sz["seq"], sz["batch"],
                          False, sz["steps"])
    with phase("transformer-base seq256 fused (flash fwd + fused bwd)"):
        train_transformer("transformer256_fused", sz["seq"], sz["batch"],
                          True, sz["steps"])
    with phase("transformer-base long-context fused (tuned tiles)"):
        train_transformer("transformer_long_fused", sz["long_seq"],
                          sz["long_batch"], True, sz["long_steps"])
    jax.clear_caches()

    with phase("ResNet-50 NHWC AMP Momentum"):
        main_p, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_p, startup), \
                fluid.unique_name.guard():
            _, fetches = models.resnet.build(data_format="NHWC",
                                             **sz["resnet"])
            loss = fetches["loss"]
            fluid.optimizer.Momentum(learning_rate=0.01,
                                     momentum=0.9).minimize(loss)
        main_p.random_seed = startup.random_seed = 7
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.TPUPlace(0), amp=True)
        exe.run(startup, scope=scope)
        rng = np.random.RandomState(0)
        c, h, w = sz["resnet"]["image_shape"]
        n = sz["resnet_batch"]
        feed = {"image": jax.device_put(
                    rng.rand(n, h, w, c).astype(np.float32)),
                "label": jax.device_put(rng.randint(
                    0, sz["resnet"]["class_dim"], (n, 1)).astype(np.int32))}
        check("resnet", train_steps(
            lambda: exe.run(main_p, feed=feed, fetch_list=[loss],
                            return_numpy=False, scope=scope)[0],
            sz["resnet_steps"]))
        exe.close()
    jax.clear_caches()

    # -- serve ------------------------------------------------------------
    with phase("paged kernels vs references (op level)"):
        lm = sz["lm"]
        H, Dh = lm["n_heads"], lm["d_model"] // lm["n_heads"]
        bs, S = lm["block_size"], lm["max_slots"]
        max_b = lm["max_context"] // bs
        nblk = 1 + S * max_b
        rng = np.random.RandomState(1)
        q = jax.device_put(rng.randn(S, H, Dh).astype(np.float32))
        kc = jax.device_put(rng.randn(nblk, bs, H, Dh).astype(np.float32))
        vc = jax.device_put(rng.randn(nblk, bs, H, Dh).astype(np.float32))
        # slot s owns blocks 1 + s*max_b ...; ragged lengths incl. an
        # inactive slot, a mid-block end and a full context
        bt = jax.device_put(
            (1 + np.arange(S * max_b).reshape(S, max_b)).astype(np.int32))
        seq = jax.device_put(np.asarray(
            [lm["max_context"], 0, bs + 3, 1][:S], np.int32))
        sm = 1.0 / float(np.sqrt(Dh))
        ks = jax.numpy.max(jax.numpy.abs(kc), axis=(1, 2, 3)) / 127.0
        vs = jax.numpy.max(jax.numpy.abs(vc), axis=(1, 2, 3)) / 127.0
        kq = jax.numpy.rint(kc / ks[:, None, None, None]).astype("int8")
        vq = jax.numpy.rint(vc / vs[:, None, None, None]).astype("int8")
        with jax.default_matmul_precision("highest"):
            ref = pa.paged_attention_reference(q, kc, vc, bt, seq, sm)
            ref8 = pa.paged_attention_q8_reference(q, kq, vq, ks, vs, bt,
                                                   seq, sm)
        ker = jax.jit(pa._paged_attention_pallas, static_argnums=5)(
            q, kc, vc, bt, seq, sm)
        ker8 = jax.jit(pa._paged_attention_q8_pallas, static_argnums=7)(
            q, kq, vq, ks, vs, bt, seq, sm)
        err = float(jax.numpy.max(jax.numpy.abs(ker - ref)))
        err8 = float(jax.numpy.max(jax.numpy.abs(ker8 - ref8)))
        print(f"  max |kernel - reference|: float32 {err:.2e}, int8 "
              f"{err8:.2e} (tolerance {PAGED_ATOL:.0e}); cache "
              f"[{nblk}, {bs}, {H}, {Dh}]", flush=True)
        assert err <= PAGED_ATOL and err8 <= PAGED_ATOL
        assert not np.asarray(ker)[1].any() and not np.asarray(ker8)[1].any()

    def serve_requests(kv_dtype):
        lm = sz["lm"]
        with tempfile.TemporaryDirectory(prefix="chip_smoke_lm_") as tmp:
            mdir = os.path.join(tmp, "model")
            t0 = time.perf_counter()
            sig = tiny_lm.save_tiny_lm(mdir, kv_dtype=kv_dtype, **lm)
            srv = serve.InferenceServer(fluid.TPUPlace(0))
            try:
                # a name per residency: the serve metrics are per process
                # and keyed by model name
                name = f"lm_{kv_dtype}"
                srv.add_model(name, mdir)     # loads, warm-compiles
                cold = time.perf_counter() - t0
                warmed = len(observe.observatory().unexpected())
                rng = np.random.RandomState(2)
                prompts = [rng.randint(1, sig["vocab"], n).tolist()
                           for n in sz["prompt_lens"]]
                # more requests than slots, unequal lengths: the later
                # ones can only be admitted when an earlier one vacates
                # its slot mid-flight
                assert len(prompts) > sig["max_slots"]
                t0 = time.perf_counter()
                futs = [srv.submit_generate(name, p, max_new_tokens=m)
                        for p, m in zip(prompts, sz["max_new"])]
                results = [f.result(timeout=600) for f in futs]
                wall = time.perf_counter() - t0
                stats = srv.stats()["models"][name]
            finally:
                srv.close()
        for res, m in zip(results, sz["max_new"]):
            assert len(res.tokens) == m and res.finish_reason == "length", \
                res
        tokens = sum(len(r.tokens) for r in results)
        late = observe.observatory().unexpected()[warmed:]
        assert not late, f"recompiled after warm-up: {late}"
        steps = stats["steps"]
        assert 0 < steps < tokens, (steps, tokens)   # slots were shared
        print(f"  save+load+warm {cold:.1f}s; {len(results)} requests, "
              f"{tokens} tokens in {steps:.0f} decode steps, "
              f"{wall * 1e3 / len(results):.1f} ms/request wall "
              f"(informational)", flush=True)
        check(f"serve_{kv_dtype}", [list(map(int, r.tokens))
                                    for r in results])

    with phase("serve paged-KV float32"):
        serve_requests("fp32")
    with phase("serve paged-KV int8"):
        serve_requests("int8")
    jax.clear_caches()

    # -- mesh -------------------------------------------------------------
    if len(devices) < 4:
        print(f"SKIPPED: {len(devices)} device(s) — the four-chip phase "
              f"needs 4", flush=True)
    else:
        with phase("four chips: dp=4 and dp2 x mp2 vs one chip"):
            # unfused, dropout 0: a deterministic trajectory to compare
            main_p, startup, loss = build_transformer(
                sz["seq"], fused=False, dropout=0.0)
            feed = token_batch(sz["batch"], sz["seq"])
            n = sz["mesh_steps"]

            def trajectory(run):
                return [float(np.asarray(run()).reshape(-1)[0])
                        for _ in range(n)]

            scope = fluid.Scope()
            exe = fluid.Executor(fluid.TPUPlace(0), amp=True)
            exe.run(startup, scope=scope)
            one = trajectory(lambda: exe.run(
                main_p, feed=feed, fetch_list=[loss], scope=scope)[0])
            exe.close()
            print(f"  one chip: {one}", flush=True)
            strategy = fluid.BuildStrategy()
            strategy.amp = True
            for shape, names in (([4], ["dp"]), ([2, 2], ["dp", "mp"])):
                scope = fluid.Scope()
                exe = fluid.Executor(fluid.TPUPlace(0), amp=True)
                exe.run(startup, scope=scope)
                mesh = make_mesh(shape, names, devices[:4])
                pe = fluid.ParallelExecutor(
                    main_program=main_p, loss_name=loss.name, scope=scope,
                    mesh=mesh, build_strategy=strategy)
                got = trajectory(lambda: pe.run(
                    fetch_list=[loss.name], feed=feed)[0])
                print(f"  mesh {dict(mesh.shape)}: {got}", flush=True)
                np.testing.assert_allclose(got, one, rtol=MESH_RTOL)
                inv = collective_inventory(pe.compiled_text(feed))
                assert inv.get("all-reduce", 0) > 0, inv
                print(f"  collectives: {inv}", flush=True)
                if "mp" in names:
                    w = scope.find_var(next(
                        v for v in scope.local_var_names()
                        if "_ffn1" in v and ".w" in v))
                    assert "mp" in tuple(w.sharding.spec), w.sharding
                    held = {s.device for s in w.addressable_shards
                            if s.index != (slice(None),) * w.ndim}
                    assert len(held) == 4, held
                if not rehearsal:   # CPU devices report no memory stats
                    used = [d.memory_stats()["bytes_in_use"]
                            for d in devices[:4]]
                    print(f"  bytes_in_use per device: {used}", flush=True)
                    assert all(used), used
                check("mesh_" + "x".join(map(str, shape)), got)
                exe.close()

    if rehearsal:
        print("REHEARSAL complete on CPU: nothing here is a chip result")
        return 0
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
