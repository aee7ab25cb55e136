"""One harness for a decoder model's checks: what the thirteen
`tests/test_<model>.py` files share, written once. pytest does not collect
this module (as it does not `attention_program.py`); no test module imports
another.

A new `tests/test_<model>.py` starts from
  - `TINY = tiny_args("<model>")` and its `REF_KW`, and one `DecoderCase`
    (the build function, the reference module, the fetch names, the names
    that are state and not trained, the model's own `seeded_values(shapes)`,
    the gradients a planted fault is judged on);
  - a module fixture `tiny` that returns `CASE.tiny_model()`, and for each
    shared check a stub of the check's usual name that parametrises over the
    file's own lists and calls the one body here
    (`CASE.output_matches_reference(tiny, name)`);
  - the tests of its own mechanism: the pieces through `run_piece`, the
    kernels under the interpreter, the shares, the config's published widths.
A check that differs from a sibling's in a tolerance or a name takes it as an
argument; one that differs in kind stays in the model's file and says why."""

import collections
import dataclasses
import filecmp
import hashlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, models, observe
from paddle_tpu.core import ir

import nemotron_h_reference

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# float32 against float32 highest: the two sides differ by the order of
# their sums (chunks and a triangular solve against a recurrence)
RTOL = 2e-5


def rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, (got.shape, want.shape)
    return np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30)


def frob(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got.reshape(want.shape) - want) \
        / (np.linalg.norm(want) + 1e-30)


def run_piece(build, feed, params=None):
    """Build a few layers on data vars, take the mean of the first output
    times a fixed random tensor as a loss, and return the outputs and the
    gradients of every float feed and every parameter."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        data = {}
        for name, value in feed.items():
            is_float = value.dtype.kind == "f"
            data[name] = layers.data(name=name, shape=list(value.shape),
                                     dtype=str(value.dtype),
                                     append_batch_size=False,
                                     stop_gradient=not is_float)
        outs = build(data)
        first = outs[0]
        probe = layers.data(name="probe", shape=list(first.shape),
                            dtype="float32", append_batch_size=False)
        loss = layers.reduce_sum(layers.elementwise_mul(first, probe))
        fluid.append_backward(loss)
    run_piece.program_uid = main._uid       # whose event `piece_noted` reads
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    for name, value in (params or {}).items():
        scope.set_var(name, jnp.asarray(value))
    rng = np.random.RandomState(99)
    probe_value = rng.randn(*first.shape).astype(np.float32)
    wrt = [n for n, v in feed.items() if v.dtype.kind == "f"] \
        + sorted(params or {})
    fetched = exe.run(main, feed={**feed, "probe": probe_value},
                      fetch_list=list(outs) + [n + "@GRAD" for n in wrt],
                      scope=scope)
    return (fetched[:len(outs)], dict(zip(wrt, fetched[len(outs):])),
            probe_value)


def piece_noted(key):
    """What the rules noted under `key` on the compile event of the program
    `run_piece` ran last, None where none did: that program's own event, not
    the last of the observatory's list, which is the process's (every test
    file of an xdist worker writes it, and it holds 256 events)."""
    return observe.observatory().latest(
        run_piece.program_uid).detail.get(key)


def _planted(name, value):
    """A bias that starts at `value`: `run_piece` takes the gradient of every
    parameter it is handed, and the bias has none."""
    return fluid.ParamAttr(
        name=name, initializer=fluid.initializer.NumpyArrayInitializer(value))


def _forward_ops_by_scope(main):
    scopes = {}
    for op in main.global_block().ops:
        if op.attrs.get("__role__") is None:
            scopes.setdefault(op.attrs.get(ir.NAME_SCOPE_ATTR), []) \
                .append(op.type)
    return scopes


# -- the delta rule's regimes, the state-space scan's pieces, a kernel's trace line --------------

REGIMES = {
    # (scale and offset of g's pre-activation, of beta's logit)
    "mixed": ((1.0, 0.0), (1.0, 0.0)),
    "g_near_0": ((0.1, -9.0), (1.0, 0.0)),          # g ~ -1e-4
    "g_strongly_negative": ((1.0, 3.0), (1.0, 0.0)),  # g ~ -30 a token
    "beta_near_0": ((1.0, 0.0), (0.3, -7.0)),
    "beta_near_1": ((1.0, 0.0), (0.3, 7.0)),
}

SCAN_NAMES = ["x", "b", "c", "dt_raw", "A_log", "dt_bias", "D"]


def _scan_inputs(B, T, H, P, G, N, seed=0):
    rng = np.random.RandomState(seed)
    f = np.float32
    return ({"x": rng.randn(B, T, H, P).astype(f),
             "b": rng.randn(B, T, G, N).astype(f) * 0.5,
             "c": rng.randn(B, T, G, N).astype(f) * 0.5,
             "dt_raw": rng.randn(B, T, H).astype(f)},
            {"A_log": np.log(rng.uniform(1, 8, H)).astype(f),
             "dt_bias": (rng.randn(H) * 0.5 - 1.0).astype(f),
             "D": rng.uniform(0.5, 1.5, H).astype(f)})


def _recurrence(x, b, c, dt_raw, A_log, dt_bias, D):
    dt = jax.nn.softplus(dt_raw + dt_bias)
    r = x.shape[2] // b.shape[2]
    return nemotron_h_reference.selective_scan(
        x, dt, -jnp.exp(A_log) * dt, jnp.repeat(b, r, axis=2),
        jnp.repeat(c, r, axis=2), D)


def _scan_layer(chunk):
    def build(d):
        return [layers.ssd_scan(
            d["x"], d["b"], d["c"], d["dt_raw"], chunk=chunk,
            a_log_attr=fluid.ParamAttr(name="A_log"),
            dt_bias_attr=fluid.ParamAttr(name="dt_bias"),
            d_attr=fluid.ParamAttr(name="D"))]
    return build


def _published_scan(seed=2, T=256, heads=8):
    """One group at the published head shapes: 8 heads of 64 over a state of
    128, chunk 128."""
    rng = np.random.RandomState(seed)
    f = jnp.float32
    x = jnp.asarray(rng.randn(1, T, heads, 64), f)
    b = jnp.asarray(rng.randn(1, T, 1, 128) * 0.3, f)
    c = jnp.asarray(rng.randn(1, T, 1, 128) * 0.3, f)
    dt = jax.nn.softplus(jnp.asarray(rng.randn(1, T, heads) - 1.0, f))
    a = -jnp.asarray(rng.uniform(1, 8, heads), f) * dt
    D = jnp.asarray(rng.uniform(0.5, 1.5, heads), f)
    return x, dt, a, b, c, D


def _instruction(eqn):
    """The line a TPU trace names a `pallas_call` by: its name, then the
    tuple of its results in row-major layouts."""
    def result(aval):
        dtype = {"float32": "f32", "bfloat16": "bf16"}[str(aval.dtype)]
        dims = ",".join(str(d) for d in aval.shape)
        order = ",".join(str(i) for i in reversed(range(len(aval.shape))))
        return f"{dtype}[{dims}]{{{order}}}"

    name = eqn.params["name"]
    results = ", ".join(result(v.aval) for v in eqn.outvars)
    return f"%{name}.1 = ({results}) custom-call(%reshape.8, %reshape.9)"


# -- each model's tiny size ----------------------------------------------------------------------

# the models whose tiny size is their configuration's `tiny` block
CONFIGS = {"granite_hybrid": "granite_4_0_h_micro",
           "keye_vl2": "keye_vl_2_30b_a3b", "lfm2_moe": "lfm2_8b_a1b",
           "ling3": "ling_3_0_flash_vl",
           "nemotron_h": "nemotron_3_nano_30b_a3b",
           "phi4_flash": "phi_4_mini_flash_reasoning",
           "trinity": "trinity_mini_26b_a3b"}
# a YaRN block that bends the frequencies of a 16-wide head: low 0, high 3
TINY_YARN = {"factor": 4.0, "original_max_position_embeddings": 64,
             "beta_fast": 32.0, "beta_slow": 1.0}
# the models whose tests wrote their tiny size out
LITERAL = {
    "olmoe": dict(vocab_size=128, seq_len=128, n_layer=2, d_model=64,
                  n_head=2, n_expert=8, top_k=2, d_expert=32),
    "ouro": dict(vocab_size=128, seq_len=128, n_layer=2, d_model=64,
                 n_head=2, d_ff=96, n_loop=4),
    "qwen3_next": dict(
        vocab_size=64, seq_len=128, n_layer=4, d_model=32,
        full_attention_interval=4, n_head=4, n_kv_head=2, head_dim=16,
        rotary_dim=4, rope_theta=1e4, n_key_head=2, n_value_head=4,
        key_dim=8, value_dim=8, conv_kernel=4, n_expert=16, top_k=4,
        d_expert=16, d_shared=16, first_expert=4, experts_held=4),
    "kanana2": dict(
        vocab_size=64, seq_len=128, n_layer=3, n_dense_layer=1, d_model=32,
        d_dense=48, n_head=4, kv_rank=16, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16, rope_theta=1e4, n_expert=16, top_k=3, d_expert=16,
        n_shared=2, routed_scaling_factor=2.448, bias_update_rate=0.001,
        first_expert=4, experts_held=4),
    # the published pattern; a window shorter than the sequence and no
    # multiple of 128; a group of 2; a share that starts above expert 0
    "mellum2": dict(
        vocab_size=64, seq_len=256, n_layer=4, d_model=32, n_head=4,
        n_kv_head=2, head_dim=16, sliding_window=96, rope_theta=1e4,
        rope_scaling=TINY_YARN, n_expert=16, top_k=3, d_expert=16,
        first_expert=4, experts_held=4),
    "olmo_hybrid": dict(
        vocab_size=128, seq_len=128, n_layer=4, d_model=64, d_ff=96,
        n_head=4, heads_held=2, head_dim=16, key_dim=12, value_dim=24,
        conv_kernel=4),
}


def config(model):
    """The model's file under `benchmark/configs/`."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIGS[model] + ".json")) as f:
        return json.load(f)


def tiny_args(model):
    """The build arguments of `models.<model>` at its tests' tiny size."""
    if model in LITERAL:
        return dict(LITERAL[model])
    conf = config(model)
    args, block = conf["build_args"], conf["tiny"]["build_args"]
    if model == "keye_vl2":     # the tiny block at a narrower model
        return dict({k: block[k] for k in (
            "seq_len", "topk", "n_index_head", "index_dim", "index_tile",
            "n_expert", "top_k", "first_expert", "experts_held")},
            vocab_size=64, n_layer=3, d_model=32, n_head=4, n_kv_head=2,
            head_dim=16, rope_theta=1e4, d_expert=16)
    if model == "trinity":      # of the published arguments, what is no size
        args = {k: args[k] for k in (
            "layer_types", "rope_theta", "n_shared", "route_scale",
            "bias_update_rate", "rms_eps")}
    return {**args, **block}


# -- a Program held op for op (`tests/test_decoder_models.py` says when they were taken) ---------

def build_program(model):
    """(main, startup, feeds, fetches) of one training step of
    `models.<model>` at its own tests' tiny sizes: forward, backward and
    Adam."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds, fetches = getattr(models, model).build(**tiny_args(model))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(fetches["loss"])
    return main, startup, feeds, fetches


def program_digest(*programs):
    """The programs' global blocks parameter for parameter and op for op:
    every parameter's name, shape, dtype and whether it trains; every op's
    type, attributes (its name scope is one; but the generated names), the
    shapes of what it writes and the persistable variables it touches, by
    slot."""
    lines = []
    for program in programs:
        block = program.global_block()
        lines += [f"parameter {p.name} {tuple(p.shape)} {p.dtype} "
                  f"{p.trainable}" for p in block.all_parameters()]
        kept = {n for n, v in block.vars.items() if v.persistable}
        for op in block.ops:
            attrs = sorted((k, repr(v)) for k, v in op.attrs.items()
                           if not k.startswith("__") or k == "__role__")
            outs = [tuple(block.var(n).shape) for n in op.output_arg_names
                    if block.has_var(n)]
            held = sorted((way, slot, n) for way, slots in
                          (("in", op.inputs), ("out", op.outputs))
                          for slot, names in slots.items()
                          for n in names if n in kept)
            lines.append(f"{op.type} {attrs} {outs} {held}")
    return len(lines), hashlib.sha256("\n".join(lines).encode()).hexdigest()


DIGESTS = {
    "granite_hybrid": (1670, "b8d7a55da93effa62a1255980f4075f3"   # PR 65's own
                             "07c6385913c2ee7311fc1c317fe9f87d"),
    "kanana2": (618, "7e1a4d0a35d9e8c487e85c5fd2d5ca8f"
                     "8a05a524c1f8174aa3de0527033685df"),
    "keye_vl2": (655, "0649d664f592fadd5d847958da86b217"
                      "95f2689bda87d28ed6e4b2dfe0ed6e23"),
    "lfm2_moe": (700, "232d480348c1f734b566184cf830617a"          # PR 69's own
                      "648afc881d999dcea62b73969ab340ee"),
    "mellum2": (747, "63f94049afbd0dd88ed8281da85f666f"
                     "5379fe7da48e1c9c99f130000d3af739"),
    "nemotron_h": (957, "bb4a9beb840fe95d57a8b1bee2a34470"
                        "a3444ab5db5c1fccba256058024defa9"),
    "olmoe": (397, "d8847a00387005278bfc31daf8556e30"
                   "1eb7d918126f76aafef7dc06b6d29cb9"),
    "olmo_hybrid": (725, "3d6b51bdb3f5b30704e77b84ef566b09"       # PR 63's own
                         "25752028f333b5369d0c325de1a10c8e"),
    "ouro": (790, "6ea230c9082de14ccaa4df13364540aa"
                  "790498e29ea98a844998f066efecfee8"),
    "phi4_flash": (1249, "3478f951db8a660db86da3280297bdda"       # PR 73's own
                         "e3611e1ec7f2fb1ba68140a85673fa88"),
    "qwen3_next": (1021, "e431cca320eca95789aee1fbdb3a8e2f"
                         "b2607d1abf037deb504eee331f0fb8e3"),
    "trinity": (1231, "391890374a0cbe3ad429a97497bb07a2"
                      "ab5c431c4f6824d731aeff15d0707e75"),
}


# -- the case: what a model's file hands over, and the checks over it ----------------------------

def runs_through_the_benchmark(workload):
    """`run.py --tiny` on the cell: the configuration's tiny block through
    the harness's own rehearsal, the in-run reference comparison
    included."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", workload, "--seed", "3000000019", "--seconds", "1",
         "--trace", "0", "--tiny"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "REHEARSAL" in out.stdout and "reference check after" in out.stdout
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True


def layers_are_built_under_their_scopes(main, present, absent=(), holds=None,
                                        lacks=None):
    """The forward ops' name scopes: those that are there, those that are
    not, and op types a scope holds or lacks."""
    scopes = {k: set(v) for k, v in _forward_ops_by_scope(main).items()}
    assert set(present) <= set(scopes), sorted(set(present) - set(scopes))
    assert not set(absent) & set(scopes)
    for scope, ops in (holds or {}).items():
        assert set(ops) <= scopes[scope], (scope, set(ops) - scopes[scope])
    for scope, ops in (lacks or {}).items():
        assert not set(ops) & scopes[scope], scope
    return scopes


def carries_the_census(details, expected, absent=(), startup_lacks=None):
    """`details` is `CASE.compile_detail()`'s pair: the step's compile event
    holds `expected` and none of `absent`; the startup program holds no
    layer, so none of these keys (its sharing counts read 0)."""
    detail, startup_detail = details
    for key, value in expected.items():
        assert detail[key] == value, key
    for key in absent:
        assert key not in detail, key
    for key in expected if startup_lacks is None else startup_lacks:
        if key == "grad_fanin_max":
            assert not startup_detail.get(key)
        else:
            assert key not in startup_detail, key


Run = collections.namedtuple("Run", "main params feed got grads after")


@dataclasses.dataclass
class DecoderCase:
    build: object               # models.<model>.build
    tiny: dict                  # the file's TINY itself: a test may patch it
    ref: object                 # the plain reference's module
    ref_kw: dict                # the file's REF_KW
    fetches: list               # names of `build`'s fetches, in `got`
    state: tuple = ()           # parameters no gradient reaches: router
    #                             biases the step rewrites, a frozen indexer
    seeded_values: object = None    # shapes -> weights far from the initial
    #                                 ones; None: the initial ones
    fault_wrt: tuple = ()       # the gradients a planted fault is judged on
    interpreted: bool = False   # `tiny_model` under the Pallas interpreter
    also_fetch: object = None   # main -> {name in `got`: [variable names]}
    out_tol: float = 1e-4       # a fetch, of its largest value
    grad_tol: float = 2e-4      # a gradient, by `grad_err`
    grad_err: object = frob     # or `rel_err`: the largest entry's

    def program(self, optimizer=None, **sizes):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            feeds, fetches = self.build(**{**self.tiny, **sizes})
            if optimizer is None:
                pairs = fluid.append_backward(fetches["loss"])
            else:
                optimizer.minimize(fetches["loss"])
                pairs = []
        main.random_seed = startup.random_seed = 7
        return main, startup, fetches, pairs

    def batch(self, seed=0, batch=2, seq_len=None):
        rng = np.random.RandomState(seed)
        shape = (batch, seq_len or self.tiny["seq_len"])
        return {"tokens": rng.randint(0, self.tiny["vocab_size"], shape)
                .astype(np.int32),
                "labels": rng.randint(0, self.tiny["vocab_size"], shape)
                .astype(np.int32)}

    def run_tiny(self, amp, seeded=True, weights=None, seed=3, batch_seed=0,
                 **sizes):
        """One step of the program with its gradients fetched: `params` as
        the step found them, `after` the state it left."""
        main, startup, fetches, pairs = self.program(**sizes)
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.CPUPlace(), amp=amp)
        exe.run(startup, scope=scope)
        names = [p.name for p in main.global_block().all_parameters()]
        if weights is None and seeded and self.seeded_values:
            weights = self.seeded_values(
                {n: np.shape(scope.find_var(n)) for n in names}, seed)
        if weights:
            for name in names:
                scope.set_var(name, jnp.asarray(weights[name]))
        params = {n: np.asarray(scope.find_var(n)) for n in names}
        feed = self.batch(batch_seed)
        more = self.also_fetch(main) if self.also_fetch else {}
        out = exe.run(main, feed=feed,
                      fetch_list=[fetches[n] for n in self.fetches]
                      + [v for held in more.values() for v in held]
                      + [g for _, g in pairs], scope=scope)
        got = dict(zip(self.fetches, out))
        at = len(self.fetches)
        for name, held in more.items():
            got[name], at = out[at:at + len(held)], at + len(held)
        grads = dict(zip((p.name for p, _ in pairs), out[at:]))
        after = {n: np.asarray(scope.find_var(n)) for n in self.state
                 if n in params}
        return Run(main, params, feed, got, grads, after)

    def tiny_model(self, run=None, **ref_kw):
        """The `tiny` fixture's dictionary: the float32 step and the
        reference's loss parts and gradients on the same weights and batch
        (`last=None`: a reference that takes no `last`)."""
        if run is None:
            with pytest.MonkeyPatch.context() as patch:
                if self.interpreted:
                    patch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
                run = self.run_tiny(amp=False)
        tokens = jnp.asarray(run.feed["tokens"])
        labels = jnp.asarray(run.feed["labels"])
        kw = {"last": self.tiny["seq_len"], **self.ref_kw, **ref_kw}
        if kw["last"] is None:
            del kw["last"]
        want, want_grads = self.ref.loss_and_grads(run.params, tokens, labels,
                                                   **kw)
        return dict(run._asdict(), tokens=tokens, labels=labels, want=want,
                    want_grads=want_grads)

    def compile_detail(self, **sizes):
        """(the step's, the startup program's) compile event's detail, of
        one SGD step."""
        main, startup, fetches, _ = self.program(
            fluid.optimizer.SGD(learning_rate=1e-3), **sizes)
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        exe.run(main, feed=self.batch(seq_len=sizes.get("seq_len")),
                fetch_list=[fetches["loss"]], scope=scope)
        latest = observe.observatory().latest
        return latest(main._uid).detail, latest(startup._uid).detail

    # -- the checks: a model's file has a stub of the usual name for each ------------------------

    def has_the_reference_parameters(self, tiny, trained, shapes=None):
        """The reference's names, a gradient for every trained parameter and
        for no other, and the shapes named."""
        assert sorted(tiny["params"]) == sorted(list(trained)
                                                + list(self.state))
        assert sorted(tiny["grads"]) == sorted(trained)
        for name, shape in (shapes or {}).items():
            assert tiny["params"][name].shape == shape, name

    def output_matches_reference(self, tiny, name):
        want = np.asarray(tiny["want"][name])
        if name == "tokens_per_expert":
            assert np.array_equal(tiny["got"][name], want)
        else:
            assert rel_err(np.reshape(tiny["got"][name], want.shape),
                           want) < self.out_tol

    def gradient_matches_reference(self, tiny, name):
        assert self.grad_err(tiny["grads"][name],
                             tiny["want_grads"][name]) < self.grad_tol

    def routing_sends_most_assignments_elsewhere(self, tiny, routed_layers):
        t = self.tiny
        counts = tiny["got"]["tokens_per_expert"]
        assigned = tiny["tokens"].shape[0] * t["seq_len"] * t["top_k"]
        assert counts.shape == (routed_layers, t["n_expert"])
        assert np.all(counts.sum(1) == assigned)
        first = t["first_expert"]
        held = counts[:, first:first + t["experts_held"]].sum(1)
        assert np.all(held > 0) and np.all(held < counts.sum(1) / 2)

    def one_step_moves_the_bias_as_next_bias_does(self, tiny, name, rate):
        """The step leaves the router bias `name` where the reference's
        `next_bias` puts it from the step's own counts: every entry moved by
        `rate` or stayed."""
        counts = tiny["got"]["tokens_per_expert"][list(self.state).index(name)]
        want = self.ref.next_bias(tiny["params"][name], counts, rate)
        assert np.array_equal(tiny["after"][name], np.asarray(want))
        moved = tiny["after"][name] - tiny["params"][name]
        assert np.all(np.isclose(np.abs(moved), rate, rtol=1e-3)
                      | (moved == 0)) and np.any(moved != 0)

    def planted_fault_is_refused(self, tiny, fault, factor=50, loss=None):
        """The comparison that passes the reference refuses the fault: the
        logits or a gradient of `fault_wrt` (or, where `loss` is given, the
        loss) moves by far more than the system's distance from the true
        reference."""
        wrt = list(self.fault_wrt)
        bad, bad_grads = self.ref.loss_and_grads(
            tiny["params"], tiny["tokens"], tiny["labels"], wrt=wrt,
            last=self.tiny["seq_len"], fault=fault, **self.ref_kw)
        moved = [rel_err(tiny["got"]["logits"], bad["logits"])] \
            + [frob(tiny["grads"][n], bad_grads[n]) for n in wrt]
        held = [rel_err(tiny["got"]["logits"], tiny["want"]["logits"])] \
            + [frob(tiny["grads"][n], tiny["want_grads"][n]) for n in wrt]
        assert max(held) < self.grad_tol
        # a fault that overflows (a step size below 0 makes the decay a
        # growth) reads nan: not within any limit, as `run.py::misses` has it
        assert not max(np.nan_to_num(moved, nan=np.inf)) \
            <= factor * self.grad_tol, (fault, moved)
        if loss is not None:
            assert not abs(float(bad["loss"])
                           - float(tiny["want"]["loss"])) <= loss

    def unknown_fault_is_refused(self, tiny):
        with pytest.raises(ValueError, match="fault is one of"):
            self.ref.loss_parts(tiny["params"], tiny["tokens"],
                                tiny["labels"], fault="no_such",
                                **self.ref_kw)

    def reference_in_blocks_is_the_reference(self, tiny, wrt, tol=1e-5,
                                             **blocks):
        """`q_block`, `token_block` and `remat` are the reference's memory,
        not its mathematics."""
        parts, grads = self.ref.loss_and_grads(
            tiny["params"], tiny["tokens"], tiny["labels"], wrt=wrt,
            remat=True, **blocks, **self.ref_kw)
        assert abs(float(parts["loss"]) - float(tiny["want"]["loss"])) < 1e-5
        for name, g in grads.items():
            assert frob(g, tiny["want_grads"][name]) < tol, name

    def reference_last_positions_equal_the_full_pass(self, tiny, tol=1e-6):
        parts = self.ref.loss_parts(tiny["params"], tiny["tokens"],
                                    tiny["labels"], last=16, **self.ref_kw)
        assert rel_err(parts["logits"],
                       tiny["want"]["logits"][:, -16:]) < tol

    def reference_in_bfloat16_is_another_number(self, tiny):
        low = self.ref.loss_parts(tiny["params"], tiny["tokens"],
                                  tiny["labels"], dtype=jnp.bfloat16,
                                  **self.ref_kw)
        assert low["loss"].dtype == jnp.bfloat16
        assert abs(float(low["loss"]) - float(tiny["want"]["loss"])) > 1e-4

    def amp_within_bf16_of_reference(self, grads, loss=0.002, mean=0.02,
                                     most=0.1, of_std=True, seeded=False,
                                     **sizes):
        """The step under AMP against the float32 reference on the same
        weights: the loss within `loss`, the bf16 logits within `mean` on
        average and `most` at most (`of_std`: times the reference's logits'
        std), each gradient of `grads` ({limit: names}) float32 and within
        its limit in the Frobenius norm, the state float32."""
        t = self.tiny_model(self.run_tiny(amp=True, seeded=seeded, **sizes),
                            **sizes)
        got, want = t["got"], t["want"]
        assert abs(float(got["loss"][0]) - float(want["loss"])) < loss
        assert got["logits"].dtype == jnp.bfloat16
        err = np.abs(np.asarray(got["logits"], np.float32)
                     - np.asarray(want["logits"]))
        unit = float(np.std(want["logits"])) if of_std else 1.0
        assert err.mean() < mean * unit and err.max() < most * unit
        for limit, names in grads.items():
            for name in names:
                assert t["grads"][name].dtype == np.float32, name
                assert frob(t["grads"][name],
                            t["want_grads"][name]) < limit, name
        for name in self.state:
            assert t["after"][name].dtype == np.float32, name

    def adam_steps_lower_the_loss(self, lr=3e-3, seed=0, steps=6):
        main, startup, fetches, _ = self.program(
            fluid.optimizer.Adam(learning_rate=lr))
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        feed = self.batch(seed)
        losses = [float(np.asarray(exe.run(
            main, feed=feed, fetch_list=[fetches["loss"]],
            scope=scope)[0]).reshape(-1)[0]) for _ in range(steps)]
        assert np.all(np.isfinite(losses))
        assert losses[-1] < losses[0] - 0.05, losses

    def two_copies_of_the_reference_are_identical(self):
        name = os.path.basename(self.ref.__file__)
        assert filecmp.cmp(
            os.path.join(HERE, name),
            os.path.join(ROOT, "benchmark", "references", name),
            shallow=False)
