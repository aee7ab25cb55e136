"""The north-star topology in dryrun: a 16-device (v5e-16 analog) CPU
mesh, dp=4 x mp=2 x sp=2 (round-5 verdict item 6; reference analog
nccl_helper.h:96-120 multi-node ranks).

Runs `__graft_entry__.py dryrun 16` in a SUBPROCESS: the suite's own jax
backend is pinned to 8 virtual devices by conftest, and a second backend
cannot be re-initialized in-process. The dryrun itself asserts the
3-step decreasing loss trajectory, exact single-device parity (sp>1 =>
deterministic), mp sharding of the ffn weights, ring-attention lowering,
and a non-empty collective inventory of the compiled step — so this test
is the 16-device mirror of tests/test_parallel_modes.py.
"""

import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sixteen_devices_possible() -> bool:
    """The dryrun subprocess needs 16 devices. Under the tier-1 command
    the suite's conftest pins XLA_FLAGS to 8 virtual CPU devices, which
    the subprocess INHERITS and `__graft_entry__._force_cpu_devices`
    cannot override once the backend came up — so on a clean container
    this is an environment gap (skip), not a code failure. The
    prerequisite exists when the ambient XLA_FLAGS already grants >= 16
    host devices, when no pin is set (the subprocess pins its own), or
    when real accelerator devices are present."""
    m = re.search(r"xla_force_host_platform_device_count=(\d+)",
                  os.environ.get("XLA_FLAGS", ""))
    if m is not None:
        return int(m.group(1)) >= 16
    # no ambient pin: the subprocess pins its own 16 virtual CPU devices
    # (how the recorded MULTICHIP_r*.json runs were produced)
    return True


@pytest.mark.skipif(not _sixteen_devices_possible(),
                    reason="subprocess cannot see 16 devices (ambient "
                           "XLA_FLAGS pins fewer and no real accelerator "
                           "topology is mounted)")
@pytest.mark.xdist_group("multichip16")
def test_dryrun_16_devices_dp4_mp2_sp2():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the child places its compile cache by the package's own rule
    # (paddle_tpu/__init__.py), so the repeat cost is near-zero once the
    # 16-way step has been compiled in this checkout
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "__graft_entry__.py"),
         "dryrun", "16"],
        capture_output=True, text=True, timeout=900, cwd=REPO, env=env)
    tail = (out.stdout + out.stderr).strip().splitlines()[-8:]
    assert out.returncode == 0, f"dryrun 16 failed: {tail}"
    ok_line = next(l for l in out.stdout.splitlines()
                   if l.startswith("dryrun_multichip OK"))
    # the north-star factorization, not some degenerate fallback
    assert "mesh dp=4 x mp=2 x sp=2" in ok_line, ok_line
    # collective inventory: data/tensor parallelism => all-reduce, ring
    # attention over sp => collective-permute, each with a per-step count
    m = re.search(r"collectives=\{(.*)\}", ok_line)
    assert m, ok_line
    inv = m.group(1)
    assert "'all-reduce': " in inv, ok_line
    assert "'collective-permute': " in inv, ok_line
