"""`moe_share_bounded_ops.train`: the count that the ops between a share's
dispatch and combine leave on the main program's compile event (`swiglu` with
`GroupSizes`, its grad and the two-weight `grouped_matmul`'s grad, lowered
over the rows the held groups use), read by `compile_detail` from a hand-made
observatory, and left out where the program wrote none (every expert held,
no expert layer, or a program older than the counter)."""

import json
import os
import sys
import types

from readers import compile_detail

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = "moe_share_bounded_ops.train"
SHARE_CELLS = ["qwen3_next_80b_a3b.bs1", "kanana_2_30b_a3b.bs1",
               "mellum2_12b_a2_5b.s8192"]


def read(system):
    with open(os.path.join(BENCH, "metrics", NAME + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "compile_detail" and "\n" not in spec["what"]
    return compile_detail.read({"system": system}, **spec["args"])


def test_reads_the_count_and_nothing_where_there_is_none(monkeypatch):
    def event(uid, detail):
        return types.SimpleNamespace(program_uid=uid, detail=detail)
    events = [event(3, {"version": 1, "grad_fanin_max": 0}),     # startup
              # a share: three ops in each of four expert layers
              event(5, {"version": 2, "moe_experts_held": 8,
                        "moe_row_buffer_rows": 66560,
                        "moe_share_bounded_moves": 16,
                        "moe_share_bounded_ops": 12}),
              # every expert held: neither count
              event(8, {"version": 2, "moe_experts_routed": 64}),
              # the parent's program: the movements' count alone
              event(9, {"version": 2, "moe_experts_held": 32,
                        "moe_row_buffer_rows": 45056,
                        "moe_share_bounded_moves": 16})]
    sys.path.insert(0, ROOT)
    from paddle_tpu import observe
    monkeypatch.setattr(observe.observatory(), "events", lambda: events)
    system = types.SimpleNamespace(main=types.SimpleNamespace(_uid=5))
    assert read(system) == 12.0
    for uid in (3, 8, 9):
        system.main._uid = uid
        assert read(system) is None


def test_benchmark_json_lists_it_for_the_three_share_cells_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "count", "better": "higher",
                     "source": "program_counter", "layer": "expert layer",
                     "moves": "train_examples_per_s",
                     "workloads": SHARE_CELLS}
    # the share cells are the ones whose configuration holds fewer experts
    # than its router chooses among
    held = []
    for cell in bench["workloads"]:
        with open(os.path.join(BENCH, "configs",
                               cell["config"] + ".json")) as f:
            if "experts_held" in json.load(f)["build_args"]:
                held.append(cell["name"])
    assert held == SHARE_CELLS
