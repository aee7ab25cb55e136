"""fluid-decode: paged KV cache + continuous batching (ISSUE 9).

Pins the invariants the decode path lives on:

- allocator: reserve-at-admission / allocate-on-append / free-on-finish
  round-trips, deterministic placement, retriable exhaustion;
- math: paged attention bit-identical to dense attention on the valid
  region (the reference path tier-1 runs on), the Pallas kernel matching
  the reference under the interpreter, trash-block isolation;
- serving: registry loads a generative dir from its MANIFEST decode
  signature alone (warm decode compile, zero steady-state recompiles),
  continuous batching + slot recycling produce token-for-token the same
  generations as solo runs, hot swap pins in-flight sequences to their
  version, deadlines/backpressure stay retriable;
- observability: decode token/TTFT/occupancy metrics and the
  kv_cache_exhaustion detector.

The model is models/tiny_lm.py — small enough that a full load+warm is
~2 s on the CPU backend, and greedy decode makes every parity assert
exact instead of statistical.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observe, serve
from paddle_tpu.models import tiny_lm
from paddle_tpu.ops import paged_attention as pa

SIG_KW = dict(max_slots=4, block_size=4, max_context=32,
              prefill_rows=(1, 2), prefill_seq_rungs=(8, 16))


@pytest.fixture(scope="session")
def lm_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("tiny_lm") / "model")
    tiny_lm.save_tiny_lm(d, **SIG_KW)
    return d


def _server(**cfg):
    return serve.InferenceServer(fluid.CPUPlace(),
                                 serve.ServeConfig(**cfg))


# ---------------------------------------------------------------------------
# allocator invariants
# ---------------------------------------------------------------------------

class TestPagedKVCache:
    def test_reserve_ensure_free_round_trip(self):
        kv = serve.PagedKVCache(num_blocks=9, block_size=4,
                                max_blocks_per_seq=4, max_slots=2,
                                model="t")
        assert kv.capacity == 8 and kv.available() == 8
        kv.reserve(0, 13)                   # ceil(13/4) = 4 blocks
        assert kv.available() == 4 and kv.in_use() == 4
        bt = kv.ensure(0, 5)                # 2 blocks materialize
        # deterministic ascending placement, block 0 never handed out
        assert list(bt[0][:2]) == [1, 2] and bt[0][2] == 0
        kv.ensure(0, 13)
        assert list(kv.block_tables[0]) == [1, 2, 3, 4]
        assert kv.in_use() == 4             # reservation became blocks
        kv.free_slot(0)
        assert kv.available() == 8 and kv.in_use() == 0
        assert not kv.block_tables.any()    # vacant rows -> trash block
        # recycling re-hands the same ids (deterministic replay)
        kv.reserve(1, 8)
        kv.ensure(1, 8)
        assert list(kv.block_tables[1][:2]) == [1, 2]

    def test_exhaustion_is_retriable_and_reserves_nothing(self):
        kv = serve.PagedKVCache(num_blocks=5, block_size=4,
                                max_blocks_per_seq=4, max_slots=2)
        kv.reserve(0, 12)                   # 3 of 4 blocks
        with pytest.raises(serve.CacheExhaustedError) as ei:
            kv.reserve(1, 8)                # needs 2, only 1 left
        assert ei.value.retriable
        assert kv.available() == 1          # failed reserve left no debris
        kv.free_slot(0)
        kv.reserve(1, 8)                    # now fits

    def test_growth_beyond_reservation_is_a_bug_not_backpressure(self):
        kv = serve.PagedKVCache(num_blocks=9, block_size=4,
                                max_blocks_per_seq=4, max_slots=1)
        kv.reserve(0, 4)
        kv.ensure(0, 4)
        with pytest.raises(RuntimeError, match="reservation"):
            kv.ensure(0, 5)

    def test_re_reserve_charges_only_the_delta(self):
        kv = serve.PagedKVCache(num_blocks=9, block_size=4,
                                max_blocks_per_seq=8, max_slots=1)
        kv.reserve(0, 12)                   # 3 blocks
        kv.ensure(0, 5)                     # 2 materialize, 1 reserved
        kv.reserve(0, 20)                   # grow to 5: delta = 2
        assert kv.in_use() == 5 and kv.available() == 3
        kv.free_slot(0)
        assert kv.in_use() == 0 and kv.available() == 8

    def test_over_long_sequence_rejected_at_the_door(self):
        kv = serve.PagedKVCache(num_blocks=99, block_size=4,
                                max_blocks_per_seq=4, max_slots=1)
        with pytest.raises(serve.CacheExhaustedError):
            kv.reserve(0, 17)               # 5 blocks > max_blocks_per_seq


# ---------------------------------------------------------------------------
# attention math
# ---------------------------------------------------------------------------

def _random_cache(rng, S=4, H=2, Dh=8, BS=4, MAXB=4, NB=12):
    import jax.numpy as jnp
    kc = jnp.asarray(rng.randn(NB, BS, H, Dh).astype(np.float32))
    vc = jnp.asarray(rng.randn(NB, BS, H, Dh).astype(np.float32))
    bt = np.zeros((S, MAXB), np.int32)
    bt[0, :2] = [1, 2]
    bt[2] = [3, 4, 5, 6]
    bt[3, 0] = 7
    seq = np.asarray([5, 0, 16, 1], np.int32)
    q = jnp.asarray(rng.randn(S, H, Dh).astype(np.float32))
    return q, kc, vc, jnp.asarray(bt), jnp.asarray(seq), bt


class TestPagedAttentionMath:
    def test_paged_bit_identical_to_dense_on_valid_region(self):
        import jax.numpy as jnp
        rng = np.random.RandomState(0)
        q, kc, vc, btj, seqj, bt = _random_cache(rng)
        BS = kc.shape[1]
        sm = 1.0 / np.sqrt(q.shape[-1])
        ref = np.asarray(pa.paged_attention_reference(q, kc, vc, btj,
                                                      seqj, sm))
        for slot, n in [(0, 5), (2, 16), (3, 1)]:
            # dense attention: the slot's K/V laid out CONTIGUOUSLY (no
            # block indirection), same softmax composition
            ks = np.stack([np.asarray(kc)[bt[slot, t // BS], t % BS]
                           for t in range(n)])
            vs = np.stack([np.asarray(vc)[bt[slot, t // BS], t % BS]
                           for t in range(n)])
            s = jnp.einsum("shd,sthd->sht", q[slot][None],
                           jnp.asarray(ks)[None]) * sm
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            dense = np.asarray(
                jnp.einsum("sht,sthd->shd", p, jnp.asarray(vs)[None])
                / jnp.maximum(l, 1e-20)[..., 0][..., None])[0]
            np.testing.assert_array_equal(ref[slot], dense)

    def test_inactive_slot_outputs_exact_zeros(self):
        rng = np.random.RandomState(1)
        q, kc, vc, btj, seqj, _ = _random_cache(rng)
        out = np.asarray(pa.paged_attention_reference(
            q, kc, vc, btj, seqj, 0.35))
        assert np.array_equal(out[1], np.zeros_like(out[1]))

    def test_kernel_matches_reference_under_interpreter(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        rng = np.random.RandomState(2)
        q, kc, vc, btj, seqj, _ = _random_cache(rng)
        sm = 1.0 / np.sqrt(q.shape[-1])
        ref = np.asarray(pa.paged_attention_reference(q, kc, vc, btj,
                                                      seqj, sm))
        ker = np.asarray(pa._paged_attention_pallas(q, kc, vc, btj, seqj,
                                                    sm))
        # same math, different (online-softmax) accumulation order
        np.testing.assert_allclose(ker, ref, atol=1e-5, rtol=1e-5)

    def test_q8_kernel_matches_reference_under_interpreter(self,
                                                           monkeypatch):
        import jax.numpy as jnp
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        rng = np.random.RandomState(4)
        q, kc, vc, btj, seqj, _ = _random_cache(rng, H=3)
        sm = 1.0 / np.sqrt(q.shape[-1])
        ks = jnp.max(jnp.abs(kc), axis=(1, 2, 3)) / 127.0
        vs = jnp.max(jnp.abs(vc), axis=(1, 2, 3)) / 127.0
        kq = jnp.rint(kc / ks[:, None, None, None]).astype(jnp.int8)
        vq = jnp.rint(vc / vs[:, None, None, None]).astype(jnp.int8)
        ref = np.asarray(pa.paged_attention_q8_reference(
            q, kq, vq, ks, vs, btj, seqj, sm))
        ker = np.asarray(pa._paged_attention_q8_pallas(
            q, kq, vq, ks, vs, btj, seqj, sm))
        np.testing.assert_allclose(ker, ref, atol=1e-5, rtol=1e-5)
        assert np.array_equal(ker[1], np.zeros_like(ker[1]))  # inactive

    def test_append_places_kv_and_trash_isolates_inactive(self):
        import jax.numpy as jnp
        rng = np.random.RandomState(3)
        NB, BS, H, Dh = 6, 4, 2, 8
        kc = jnp.zeros((NB, BS, H, Dh), jnp.float32)
        vc = jnp.zeros((NB, BS, H, Dh), jnp.float32)
        bt = np.zeros((2, 2), np.int32)
        bt[0, :] = [2, 5]
        k_new = jnp.asarray(rng.randn(2, H, Dh).astype(np.float32))
        v_new = jnp.asarray(rng.randn(2, H, Dh).astype(np.float32))
        # slot 0 at seq_len 6 -> block 5 (=bt[0,1]), offset 1;
        # slot 1 inactive -> trash block 0
        kc2, _ = pa.kv_cache_append(kc, vc, k_new, v_new,
                                    jnp.asarray(bt),
                                    jnp.asarray([6, 0], np.int32))
        kc2 = np.array(kc2)
        np.testing.assert_array_equal(kc2[5, 1], np.asarray(k_new)[0])
        # nothing outside block 5 pos 1 and the trash block changed
        kc2[5, 1] = 0
        kc2[0] = 0
        assert not kc2.any()

    def test_prefill_write_pads_to_trash(self):
        import jax.numpy as jnp
        rng = np.random.RandomState(4)
        NB, BS, H, Dh, T = 6, 4, 1, 4, 8
        kc = jnp.zeros((NB, BS, H, Dh), jnp.float32)
        vc = jnp.zeros((NB, BS, H, Dh), jnp.float32)
        bt = np.asarray([[1, 3]], np.int32)
        k = jnp.asarray(rng.randn(1, T, H, Dh).astype(np.float32))
        kc2, _ = pa.kv_cache_prefill_write(
            kc, vc, k, k, jnp.asarray(bt),
            jnp.asarray([5], np.int32))
        kc2 = np.array(kc2)
        np.testing.assert_array_equal(kc2[1], np.asarray(k)[0, :4])
        np.testing.assert_array_equal(kc2[3, 0], np.asarray(k)[0, 4])
        assert not kc2[3, 1:].any()        # positions 5.. went to trash
        kc2[[1, 3]] = 0
        kc2[0] = 0
        assert not kc2.any()


# ---------------------------------------------------------------------------
# generative model dir + registry
# ---------------------------------------------------------------------------

class TestGenerativeModelDir:
    def test_manifest_carries_decode_signature_and_decode_file(self,
                                                               lm_dir):
        with open(os.path.join(lm_dir, fluid.io.MODEL_MANIFEST)) as f:
            manifest = json.load(f)
        sig = manifest["decode"]
        assert sig["max_slots"] == 4 and sig["block_size"] == 4
        assert sig["max_context"] == 32
        assert fluid.io.DECODE_FILENAME in manifest["files"]
        # cache state is never serialized
        assert not [p for p in os.listdir(lm_dir) if "@KV_CACHE" in p]
        assert all("@KV_CACHE" not in p for p in manifest["files"])

    def test_registry_warms_decode_from_manifest_zero_steady_state(
            self, lm_dir):
        flag = fluid.get_flag("observe")
        fluid.set_flag("observe", True)
        srv = _server()
        try:
            ver = srv.add_model("g", lm_dir)    # no ladder, no probe
            assert ver.generative
            assert ver.decode.signature["max_slots"] == 4
            t0 = time.time()
            res = srv.generate("g", [3, 1, 4], max_new_tokens=6)
            assert len(res.tokens) == 6
            fresh = [e for e in observe.observatory().unexpected()
                     if e.ts >= t0]
            assert fresh == [], fresh
        finally:
            fluid.set_flag("observe", flag)
            srv.close()

    def test_re_register_flips_model_kind_and_request_path(self, lm_dir,
                                                           tmp_path):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            out = fluid.layers.fc(input=x, size=2)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        mlp_dir = str(tmp_path / "mlp")
        fluid.io.save_inference_model(mlp_dir, ["x"], [out], exe,
                                      main_program=main, scope=scope)
        srv = _server()
        try:
            srv.add_model("m", mlp_dir,
                          ladder=serve.BucketLadder(rows=(1, 2)))
            srv.infer("m", {"x": np.zeros((1, 4), "f4")})
            # one-shot -> generative: the stale batcher must go
            srv.add_model("m", lm_dir)
            assert len(srv.generate("m", [1, 2],
                                    max_new_tokens=3).tokens) == 3
            with pytest.raises(serve.BadRequestError):
                srv.infer("m", {"x": np.zeros((1, 4), "f4")})
            # and back again
            srv.add_model("m", mlp_dir,
                          ladder=serve.BucketLadder(rows=(1, 2)))
            out_, = srv.infer("m", {"x": np.zeros((1, 4), "f4")})
            assert out_.shape == (1, 2)
            with pytest.raises(serve.BadRequestError, match="one-shot"):
                srv.generate("m", [1, 2])
        finally:
            srv.close()

    def test_legacy_oneshot_dir_is_not_generative(self, tmp_path):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            out = fluid.layers.fc(input=x, size=2)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        mdir = str(tmp_path / "mlp")
        fluid.io.save_inference_model(mdir, ["x"], [out], exe,
                                      main_program=main, scope=scope)
        srv = _server()
        try:
            ver = srv.add_model("m", mdir,
                                ladder=serve.BucketLadder(rows=(1, 2)))
            assert not ver.generative
            with pytest.raises(serve.BadRequestError):
                srv.generate("m", [1, 2])
            out_, = srv.infer("m", {"x": np.zeros((1, 4), "f4")})
            assert out_.shape == (1, 2)
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# serving semantics
# ---------------------------------------------------------------------------

class TestDecodeServing:
    def test_solo_generation_deterministic_and_bounded(self, lm_dir):
        srv = _server()
        try:
            srv.add_model("g", lm_dir)
            a = srv.generate("g", [5, 9, 2], max_new_tokens=7)
            b = srv.generate("g", [5, 9, 2], max_new_tokens=7)
            assert a.tokens == b.tokens and len(a.tokens) == 7
            assert a.finish_reason == "length"
            assert a.prompt_len == 3 and a.ttft_us > 0
        finally:
            srv.close()

    def test_continuous_admission_matches_solo_tokens(self, lm_dir):
        """Mid-batch admission + slot recycling vs solo runs: with 2
        slots and 8 staggered ragged generations, every sequence is
        admitted into a recycled slot while others are decoding — each
        must still produce exactly its solo tokens."""
        prompts = [([(i * 7 + j) % 31 + 1 for j in range(2 + i % 5)],
                    3 + (i * 5) % 10)
                   for i in range(8)]
        solo = {}
        srv = _server()
        try:
            srv.add_model("g", lm_dir)
            for p, n in prompts:
                solo[tuple(p) + (n,)] = srv.generate(
                    "g", p, max_new_tokens=n).tokens
        finally:
            srv.close()
        small = _server()
        try:
            # fresh server, smaller slot count -> queueing + recycling
            small.add_model("g", lm_dir)
            futs = []
            for i, (p, n) in enumerate(prompts):
                futs.append(small.submit_generate("g", p,
                                                  max_new_tokens=n))
                if i % 3 == 0:
                    time.sleep(0.01)      # stagger: admit mid-batch
            for (p, n), f in zip(prompts, futs):
                got = f.result(timeout=120).tokens
                assert got == solo[tuple(p) + (n,)], (p, n)
        finally:
            small.close()

    def test_slot_recycle_no_cross_sequence_aliasing(self, lm_dir):
        """After a slot (and its blocks) are recycled, a new sequence
        must read only its own K/V: its generation equals a fresh-server
        solo run even though its blocks held another sequence's data."""
        srv = _server()
        try:
            srv.add_model("g", lm_dir)
            first = srv.generate("g", [7] * 8, max_new_tokens=10)
            second = srv.generate("g", [3, 1], max_new_tokens=10)
        finally:
            srv.close()
        srv2 = _server()
        try:
            srv2.add_model("g", lm_dir)
            fresh = srv2.generate("g", [3, 1], max_new_tokens=10)
            assert second.tokens == fresh.tokens
            assert first.tokens != second.tokens   # sanity: distinct seqs
        finally:
            srv2.close()

    def test_streaming_yields_exactly_the_result_tokens(self, lm_dir):
        srv = _server()
        try:
            srv.add_model("g", lm_dir)
            st = srv.submit_stream("g", [11, 4], max_new_tokens=6)
            toks = list(st)
            res = st.future.result(timeout=60)
            assert toks == res.tokens and len(toks) == 6
        finally:
            srv.close()

    def test_queued_deadline_expires_retriable(self, lm_dir):
        srv = _server()
        try:
            srv.add_model("g", lm_dir)
            # occupy every slot with long generations, then a deadlined
            # request behind them
            sig_slots = srv.registry.get("g").decode.signature["max_slots"]
            futs = [srv.submit_generate("g", [2, 3], max_new_tokens=28)
                    for _ in range(sig_slots + 2)]
            with pytest.raises(serve.DeadlineExceededError) as ei:
                srv.generate("g", [1], max_new_tokens=28, deadline_ms=1)
            assert ei.value.retriable
            for f in futs:
                f.result(timeout=120)
        finally:
            srv.close()

    def test_mid_decode_deadline_stops_the_generation(self, lm_dir):
        srv = _server()
        try:
            srv.add_model("g", lm_dir)
            # a 1 ms deadline cannot outlive a 30-token generation: it
            # expires either in the queued sweep or at the first decode
            # step's mid-decode check — both deterministic, both the
            # retriable deadline error, never a hung future and never a
            # completed generation
            t0 = time.monotonic()
            with pytest.raises(serve.DeadlineExceededError):
                srv.generate("g", [4, 2], max_new_tokens=30,
                             deadline_ms=1)
            assert time.monotonic() - t0 < 30
        finally:
            srv.close()

    def test_bad_requests_rejected_at_the_door(self, lm_dir):
        srv = _server()
        try:
            srv.add_model("g", lm_dir)
            with pytest.raises(serve.BadRequestError):
                srv.generate("g", [])                     # empty
            with pytest.raises(serve.BadRequestError):
                srv.generate("g", [99])                   # vocab
            with pytest.raises(serve.BadRequestError):
                srv.generate("g", [1] * 17)               # > max rung
            with pytest.raises(serve.BadRequestError):
                srv.generate("g", [1, 2], max_new_tokens=31)  # > context
        finally:
            srv.close()

    def test_hot_swap_pins_inflight_to_old_version(self, lm_dir,
                                                   tmp_path):
        import shutil
        mdir = str(tmp_path / "model")
        shutil.copytree(lm_dir, mdir)
        srv = _server()
        try:
            srv.add_model("g", mdir)
            v0 = srv.registry.get("g").version_id
            before = srv.generate("g", [6, 6, 6], max_new_tokens=8)
            assert before.version_id == v0
            inflight = srv.submit_generate("g", [6, 6, 6],
                                           max_new_tokens=24)
            tiny_lm.save_tiny_lm(mdir, scale=1.7, **SIG_KW)
            assert srv.reload("g") is True
            old = inflight.result(timeout=120)
            assert old.version_id == v0
            assert old.tokens[:8] == before.tokens
            after = srv.generate("g", [6, 6, 6], max_new_tokens=8)
            assert after.version_id != v0
            assert after.tokens != before.tokens   # swapped weights
        finally:
            srv.close()

    def test_decode_metrics_emitted(self, lm_dir):
        srv = _server()
        try:
            srv.add_model("g", lm_dir)
            n0 = observe.counter("serve_decode_tokens_total").value(
                model="g")
            srv.generate("g", [2, 4, 6], max_new_tokens=5)
            assert observe.counter("serve_decode_tokens_total").value(
                model="g") == n0 + 5
            ttft = observe.histogram("serve_ttft_us").summary(model="g")
            assert ttft and ttft["count"] >= 1 and ttft["mean"] > 0
            occ = observe.histogram("serve_decode_occupancy").summary(
                model="g")
            assert occ and occ["count"] >= 4
            st = srv.stats()["models"]["g"]
            assert st["generative"] and st["tokens"] >= 5
            assert st["kv"]["blocks_capacity"] > 0
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# kv_cache_exhaustion detector
# ---------------------------------------------------------------------------

class TestKvCacheExhaustionDetector:
    def test_fires_before_admission_stalls_and_self_clears(self):
        from paddle_tpu.observe import health
        eng = health.get_engine()
        eng.install_default_detectors()
        kv = serve.PagedKVCache(num_blocks=11, block_size=4,
                                max_blocks_per_seq=10, max_slots=2,
                                model="g")
        kv.reserve(0, 37)                  # 10 of 10 blocks -> >= 90%
        alerts = {a.rule for a in eng.evaluate()}
        assert "kv_cache_exhaustion" in alerts
        # surfaced on the /healthz verdict body
        v = eng.verdict()
        assert v["status"] == "unready"
        det = v["checks"]["detectors"]["detail"]["kv_cache_exhaustion"]
        assert det["firing"] and "blocks" in det["alert"]["message"]
        kv.free_slot(0)                    # finish-frees clear it
        assert not [a for a in eng.evaluate()
                    if a.rule == "kv_cache_exhaustion"]

    def test_engine_rejects_unadmittable_request_with_cache_error(
            self, tmp_path):
        mdir = str(tmp_path / "small")
        # cache deliberately too small for a full-context generation:
        # 3 allocatable blocks = 12 positions < 8 prompt + 9 new
        tiny_lm.save_tiny_lm(mdir, max_slots=2, block_size=4,
                             max_context=32, num_blocks=4,
                             prefill_rows=(1, 2),
                             prefill_seq_rungs=(8, 16))
        srv = _server()
        try:
            srv.add_model("g", mdir)
            with pytest.raises(serve.CacheExhaustedError) as ei:
                srv.generate("g", [1] * 8, max_new_tokens=9)
            assert ei.value.retriable
            # a fitting request still serves
            res = srv.generate("g", [1, 2], max_new_tokens=4)
            assert len(res.tokens) == 4
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# CI wrapper: the full decode drill (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_decode_loadgen_drill():
    """Open-loop generative traffic + mid-run hot swap, gated on zero
    steady-state recompiles, exact solo parity, and the swap landing
    (the ISSUE 9 acceptance drill)."""
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "serve_loadgen.py")
    out = subprocess.run(
        [sys.executable, tool, "--workload", "generate",
         "--duration", "8", "--qps", "60"],
        capture_output=True, text=True, timeout=590,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, (out.stdout, out.stderr)
    rec = json.loads([l for l in out.stdout.splitlines()
                      if l.startswith("{")][-1])
    assert rec["decode_recompiles"] == 0
    assert rec["decode_failed"] == 0
    assert rec["decode_mismatches"] == 0
    assert rec["decode_hot_swap_ok"] is True
    assert rec["decode_tokens_per_s"] > 0 and rec["ttft_p50_us"] > 0
