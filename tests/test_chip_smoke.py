"""chip_smoke.py cannot rot between chip runs, and the device-side
fallbacks it exists to catch stay loud: the rehearsal runs every phase at
tiny sizes on CPU, the plain command refuses a machine without a TPU, a
Place means what it says, the interpreter switch and unsupported kernel
shapes raise on a TPU backend, and the compile cache is placed by one
rule."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as fluid
from paddle_tpu.analysis import planner
from paddle_tpu.core import executor
from paddle_tpu.ops import _kernels
from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.ops import pallas_attention as fa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, env=None, timeout=600):
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=timeout, cwd=REPO,
                          env=env or dict(os.environ))


def test_rehearsal_runs_every_phase_and_never_claims_a_pass():
    out = _run(SMOKE, "--rehearsal")
    assert out.returncode == 0, (out.stdout + out.stderr)[-3000:]
    lines = out.stdout.splitlines()
    passed = [l for l in lines if l.startswith("REHEARSAL ")]
    for name in ("seq256 unfused", "seq256 fused", "long-context fused",
                 "ResNet-50", "paged kernels", "paged-KV float32",
                 "paged-KV int8", "four chips"):
        assert any(name in l for l in passed), (name, passed)
    assert not any(l.startswith(("PASS", "FAIL", "SKIPPED")) for l in lines)
    assert '"ok"' not in out.stdout   # the result line is the chip's alone


def test_plain_command_refuses_a_machine_without_a_tpu():
    out = _run(SMOKE, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode not in (0, None)
    assert "no TPU" in out.stderr
    assert "---" not in out.stdout and '"ok"' not in out.stdout


def test_tpuplace_out_of_range_raises():
    with pytest.raises(ValueError, match="out of range"):
        fluid.TPUPlace(len(jax.local_devices())).jax_device()
    with pytest.raises(ValueError, match="out of range"):
        fluid.TPUPlace(-1).jax_device()


def test_tpuplace_without_tpu_raises_unless_cpu_was_asked_for(monkeypatch):
    assert fluid.TPUPlace(0).jax_device().platform == "cpu"   # the rig
    monkeypatch.setattr(executor, "_cpu_requested", lambda: False)
    with pytest.raises(RuntimeError, match="no TPU.*CpuDevice"):
        fluid.TPUPlace(0).jax_device()


def test_cpuplace_is_a_cpu_device():
    assert fluid.CPUPlace().jax_device() \
        == jax.local_devices(backend="cpu")[0]
    assert not fluid.is_compiled_with_tpu()


def test_interpreter_switch_is_refused_off_cpu(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert _kernels.interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="CPU rehearsal switch"):
        _kernels.interpret()


def test_unsupported_kernel_shapes_raise_on_a_tpu_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="T % 128"):
        fa._pallas_ok(jnp.zeros((1, 2, 100, 64)))
    assert fa._pallas_ok(jnp.zeros((1, 2, 256, 64)), 0.1) is True
    q = jnp.zeros((4, 2, 8))
    with pytest.raises(ValueError, match="head_dim"):
        pa._pallas_ok(q, jnp.zeros((9, 4, 2, 8)))
    q = jnp.zeros((4, 8, 128))
    assert pa._pallas_ok(q, jnp.zeros((9, 16, 8, 128))) is True
    with pytest.raises(ValueError, match="int8"):   # 2 * 8 rows < 32
        pa._pallas_ok(q, jnp.zeros((9, 2, 8, 128), jnp.int8))


def test_unknown_accelerator_has_no_hardware_spec(monkeypatch):
    class Dev:
        platform, device_kind = "tpu", "TPU v9 imaginary"
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(RuntimeError, match="TPU v9 imaginary"):
        planner.detect_hardware()


def test_compile_cache_is_placed_by_one_rule(tmp_path):
    probe = ("import jax, paddle_tpu; "
             "print(jax.config.jax_compilation_cache_dir)")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    out = _run("-c", probe, env=env)
    assert out.stdout.strip().splitlines()[-1] == \
        os.path.join(REPO, ".jax_compile_cache"), out.stderr[-2000:]
    outside = str(tmp_path / "cache")
    out = _run("-c", probe,
               env=dict(env, JAX_COMPILATION_CACHE_DIR=outside))
    assert out.stdout.strip().splitlines()[-1] == outside


def test_a_new_chip_file_is_selected_on_the_chip_by_its_name(monkeypatch):
    """No list names the chip's test files: under PADDLE_TPU_TEST_ON_TPU on
    a one-chip backend `conftest.py` keeps every `tests/test_*_tpu.py`, one
    that was never typed anywhere too, and skips the rest."""
    import glob

    import conftest

    class Item:
        def __init__(self, path):
            self.fspath, self.name, self.marks = path, "test_it", []

        def add_marker(self, mark):
            self.marks.append(mark.name)

    here = os.path.dirname(os.path.abspath(__file__))
    on_disk = sorted(glob.glob(os.path.join(here, "test_*_tpu.py")))
    assert len(on_disk) >= 12
    others = [os.path.join(here, name) for name in (
        "test_olmoe.py", "test_tpu_place.py", "olmoe_tpu.py",
        "test_olmoe_tpu_helpers.py")]
    items = [Item(p) for p in on_disk + others
             + [os.path.join(here, "test_never_typed_tpu.py")]]
    monkeypatch.setattr(conftest, "_ON_TPU", True)
    monkeypatch.setattr(conftest.jax, "devices", lambda: [object()])
    conftest.pytest_collection_modifyitems(None, items)
    assert {i.fspath for i in items if "skip" in i.marks} == set(others)
