"""What decides `correct`: `run.compared_numbers` / `run.misses` on hand-made
observations (every number beside its limit, worked out in the comments), and
`generators/train_loop_reference.py` driven end to end over a fake system and
a fake reference, sound and with the timed path broken underneath (a step
that returns its state unchanged, a loss altered where it is produced): the
miss has to arrive in `compared`, not as an exit without a result line.
The limits of the two transformer cells are held to the chip's readings that
`last_losses_slack_why` records."""

import json
import math
import os
import re
import sys
import types

import pytest

import run
from generators import train_loop_reference

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF = {"classes": 30000, "first_loss_atol": 0.05, "last_losses_slack": 0.6}
LN = math.log(30000)        # 10.30895...
# the fake system's first loss is 10: ln(classes) to the digit
FAKE_REF = {"classes": math.exp(10.0), "first_loss_atol": 0.05,
            "last_losses_slack": 0.6}


def obs_of(first, window, compiles=0, extra=None, warmup=None):
    warmup = [first] if warmup is None else warmup
    out = {"first_loss": first, "losses": window,
           "all_losses": warmup + window, "compiles_window": compiles}
    if extra:
        out["compared"] = extra
    return out


def test_compared_numbers_of_a_sound_run_by_hand():
    # the parent's traced seq2048 run at seed 207300833: first 10.3259, last
    # ten max 10.5778 -> excess 0.2519, which the old slack of 0.1 refused
    window = [10.3540] + [10.4] * 39 + [10.5778] + [10.3] * 8 + [10.2718]
    got = run.compared_numbers(obs_of(10.3259, window), REF)
    assert list(got) == ["compilations_in_window", "losses_not_finite",
                         "first_loss_gap", "last_ten_excess"]
    assert got["compilations_in_window"] == [0, 0]
    assert got["losses_not_finite"] == [0, 0]
    assert got["first_loss_gap"] == [pytest.approx(10.3259 - LN), 0.05]
    assert got["last_ten_excess"] == [pytest.approx(0.2519), 0.6]
    assert run.misses(got) == []
    assert run.misses(run.compared_numbers(
        obs_of(10.3259, window), dict(REF, last_losses_slack=0.1))) == [
        "last_ten_excess"]


@pytest.mark.parametrize("obs,missed", [
    # a compilation inside the window
    (obs_of(10.32, [10.3] * 12, compiles=1), ["compilations_in_window"]),
    # a sum in place of a mean: the first loss is not ln(classes)
    (obs_of(10.32 * 96, [10.0] * 12), ["first_loss_gap"]),
    # a run that diverges: the last ten sit above the first by over the slack
    (obs_of(10.32, [10.4] * 5 + [11.0] * 10), ["last_ten_excess"]),
    # a NaN in the warm-up, and one in the window's last ten (Python's max
    # passes over a NaN that is not first: the count is what names it)
    (obs_of(10.32, [10.3] * 12, warmup=[10.32, float("nan")]),
     ["losses_not_finite"]),
    (obs_of(10.32, [10.3] * 11 + [float("nan")]), ["losses_not_finite"]),
    # the generator's own comparison, handed over beside its limit
    (obs_of(10.32, [10.3] * 12,
            extra={"reference_loss_gap": [0.0151, 0.002]}),
     ["reference_loss_gap"]),
    (obs_of(10.32, [10.3] * 12,
            extra={"reference_loss_gap": [float("nan"), 0.002]}),
     ["reference_loss_gap"]),
])
def test_each_kind_of_miss_is_named(obs, missed):
    assert run.misses(run.compared_numbers(obs, REF)) == missed


def test_mesh_numbers_ride_along():
    got = run.compared_numbers(
        obs_of(10.32, [10.3] * 12), REF,
        {"all_reduce_missing": [1, 0], "devices_without_bytes": [0, 0]})
    assert run.misses(got) == ["all_reduce_missing"]


# -- the reference generator over fakes -----------------------------------------

class FakeLoss(float):
    """What `system.step` returns: a device array as far as the loop cares."""

    def block_until_ready(self):
        return self


class FakeSystem:
    """A 'model' of one weight w: loss = w, and a step moves w down by
    `rate` (0: the step returns its state unchanged). `tamper` is added to
    the loss where it is produced."""

    batch = 1
    build_args = {}

    def __init__(self, rate=0.01, tamper=0.0):
        self.w, self.rate, self.tamper = 10.0, rate, tamper
        param = types.SimpleNamespace(name="w")
        block = types.SimpleNamespace(all_parameters=lambda: [param])
        self.main = types.SimpleNamespace(global_block=lambda: block)
        self.scope = types.SimpleNamespace(find_var=lambda name: self.w)

    def place(self, batch):
        return batch

    def step(self, feed):
        loss = FakeLoss(self.w + self.tamper)
        self.w -= self.rate
        return loss


@pytest.fixture
def fake_reference(monkeypatch):
    mod = types.ModuleType("references.fake_reference")

    def loss_parts(params, tokens, labels):
        return {"loss": params["w"], "ce": params["w"],
                "per_pass": [1.0, 2.0]}
    mod.loss_parts = loss_parts
    monkeypatch.setitem(sys.modules, "references.fake_reference", mod)


def drive(system):
    traffic = {"feed": "device", "in_flight": 1,
               "warmup": {"min_steps": 3, "max_steps": 4,
                          "agree_within": 1.0},
               "reference_check": {"reference": "fake_reference",
                                   "loss_atol": 0.002}}
    counter = types.SimpleNamespace(n=0, cache_misses=0)
    pool = [{"tokens": 0, "labels": 0}] * 3
    return train_loop_reference.run(system, pool, traffic, 0.05, None, 0.0,
                                    counter)


def test_a_sound_step_agrees_with_its_reference(fake_reference, capsys):
    obs = drive(FakeSystem())
    gap, limit = obs["compared"]["reference_loss_gap"]
    assert (gap, limit) == (pytest.approx(0.0, abs=1e-9), 0.002)
    assert run.misses(run.compared_numbers(obs, FAKE_REF)) == []
    assert "reference check after" in capsys.readouterr().out


def test_a_loss_altered_where_it_is_produced_is_a_miss(fake_reference):
    obs = drive(FakeSystem(tamper=0.0151))      # a bfloat16 loss's miss
    assert obs["compared"]["reference_loss_gap"][0] == pytest.approx(0.0151)
    assert run.misses(run.compared_numbers(obs, FAKE_REF)) == [
        "reference_loss_gap"]


def test_what_the_in_run_numbers_cannot_see_is_said(fake_reference):
    """A step that returns its state unchanged passes every in-run number:
    the loss is the reference's on the same weights, and the last ten do not
    rise. The reference checks at the initial weights hold the step's
    function; nothing in a run holds that the parameters move
    (PERF.md section 7)."""
    obs = drive(FakeSystem(rate=0.0))
    assert run.misses(run.compared_numbers(obs, FAKE_REF)) == []
    assert len(set(obs["all_losses"])) == 1


# -- the transformer cells' limit is what its reason records ----------------------

def test_transformer_slack_is_held_to_the_readings_beside_it():
    with open(os.path.join(BENCH, "configs", "transformer_base.json")) as f:
        ref = json.load(f)["reference"]
    why = ref["last_losses_slack_why"]
    slack = ref["last_losses_slack"]
    # the two seeds that refused PRs 38 and 43, and the large one
    for seed in ("1123568343", "207300833", "2147483659"):
        assert seed in why, seed
    largest = float(re.search(
        r"largest excess read: \+([0-9.]+)", why).group(1))
    diverging = float(re.search(
        r"a run that diverges.*?last-ten excess of \+([0-9.]+)", why,
        re.S).group(1))
    # room of 1.5 times over the sound runs, and the diverging trial refused
    assert 1.5 * largest <= slack < diverging
    assert slack < 1.0
