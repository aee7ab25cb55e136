"""Device milliseconds a step by Fluid op: the profiled window joined with
the compiled step's own `op_name` metadata, which says for every instruction
which op's lowering rule emitted it (`core/lowering.py` lowers each rule
under `jax.named_scope(<name_scope>/<op type>)`).

The program makes the join (`paddle_tpu/profiler.py`): the main program's
newest compile event offers `compiled_text()`; `profiler.op_map` reads it
into {instruction line: owner op, members of a fusion}; `device_table`
joins that with the busiest device's "XLA Ops" events on the whole
instruction line, each event at its self time (a `%while` less its body's
ops). This reader loads those events with `trace_reduce`'s own functions
and sums what the metric asks for:

  op, scope   regular expressions on the owner's op type and on its
              `name_scope` prefix: device ms a step of the instructions the
              matching ops own (a fusion is owned by the op of its own
              `op_name`; the other ops fused into it are its members, and
              the log says how much time an op only sits in)
  share       the percentage of the device's busy ns whose instruction has
              a Fluid owner at all

On its first call in a run it prints the whole table. Nothing where the
trace holds no TPU plane (the rehearsal: nothing is lowered for it), where
the program offers no `compiled_text()` (a program older than the join), or
where no instruction matches.
"""

import functools
import time

import trace_reduce


@functools.lru_cache(maxsize=1)
def _ops(trace_dir, device):
    """[(start, end, name)] of the device's executed instructions."""
    plane = f"/device:TPU:{device}"
    events = trace_reduce.load_xplane(
        trace_dir, keep_line=lambda p, line:
            p == plane and line == trace_reduce.OPS_LINE)
    return trace_reduce.device_ops(events).get(device, [])


def main_op_map(program_uid):
    """The op map of the program's newest compile event that offers a
    compiled text; None where none does."""
    from paddle_tpu import observe, profiler
    if not hasattr(profiler, "op_map"):
        return None
    for event in reversed(observe.observatory().events()):
        if event.program_uid == program_uid \
                and hasattr(event, "compiled_text"):
            found = profiler.op_map(event)
            if found:
                return found
    return None


@functools.lru_cache(maxsize=1)
def _table(trace_dir, device, program_uid, steps, busy_ns):
    from paddle_tpu import profiler
    t0 = time.perf_counter()
    op_map = main_op_map(program_uid)
    if op_map is None:
        return None
    t1 = time.perf_counter()
    table = profiler.device_table(_ops(trace_dir, device), op_map)
    print(f"benchmark: device time by Fluid op, device {device}, {steps} "
          f"steps (ms are of the whole window; / {steps} for a step); the "
          f"rows sum to {table.total_ns / 1e6 / steps:.3f} ms a step, the "
          f"busy union is {busy_ns / 1e6 / steps:.3f}; the op map of "
          f"{len(op_map)} instructions took {t1 - t0:.2f} s (the compiled "
          f"text asked of the program and read), the events and the join "
          f"{time.perf_counter() - t1:.2f} s\n"
          + profiler.format_table(table, "total", busy_ns), flush=True)
    return table


def read(ctx, op=None, scope=None, share=False):
    trace = ctx["trace"]()
    if trace is None or trace["device"] is None:
        return None
    device, steps = trace["device"], trace["steps"]
    busy_ns = trace["summary"][device]["busy_ns"]
    table = _table(ctx["obs"]["profile"]["dir"], device,
                   ctx["system"].main._uid, steps, busy_ns)
    if table is None:
        return None
    owned = table.owned(op, scope)
    if not owned:
        return None
    ns = sum(st.total for _, _, st in owned)
    if share:
        return 100.0 * ns / busy_ns
    top = sorted(owned, key=lambda o: -o[2].total)[:3]
    print(f"benchmark: trace_scopes op={op!r} scope={scope!r}: "
          f"{len(owned)} instructions, {ns / 1e6 / steps:.3f} ms a step; "
          f"largest: " + "; ".join(
              f"{st.total / 1e6 / steps:.3f} ms {ins.owner[1]} "
              f"{trace_reduce.short_name(line)}" for line, ins, st in top),
          flush=True)
    return ns / 1e6 / steps
