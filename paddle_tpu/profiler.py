"""Profiler (reference: python/paddle/fluid/profiler.py + platform/profiler.cc).

TPU-native redesign: the reference's CUPTI device tracer + event profiler map
onto the JAX/XLA profiler, which captures both host events and device (TPU)
trace timelines into TensorBoard/perfetto format. The `profiler` context
manager keeps the reference API shape (state, sorted_key, output path), and
like the reference's it ends by printing a table of device time per op type,
sorted by `sorted_key`.

Where that table comes from. Under jit there is no kernel launch per Fluid
op to time; there is one compiled step of a few thousand XLA instructions,
and a TPU trace event is an instruction's text without its metadata. But the
lowering writes every op rule under `jax.named_scope(<name_scope>/<op type>)`
(`core/lowering.py::_named_scope`), so each instruction of the compiled
step's text says in its `op_name` which Fluid op emitted it. `build_op_map`
reads the text a compile event offers (`RecompileEvent.compiled_text()`)
into {instruction line: owner op, member ops of a fusion}; `device_table`
joins that with the device track on the whole instruction line, each event
at its self time; `format_table` prints it. `benchmark/readers/
trace_scopes.py` reads the same join.

The host-event table behind `record_event` / `print_host_events` /
`export_chrome_tracing` is the `observe.tracer` ring buffer (fluid-scope,
round 8): events are BOUNDED (old ones fall off the back instead of
growing host memory across a long run), nested spans carry depth/parent,
and executor step phases, trainer epoch marks and RPC spans share the
same timeline + export path.
"""

from __future__ import annotations

import collections
import contextlib
import glob
import os
import re
import time
import warnings
from typing import Dict, Iterable, List, Optional, Tuple

import jax

from .core import registry as _registry
from .observe import steplog as _steplog
from .observe import tracer as _tracer_mod

# TPU-native states. "GPU" is accepted as a deprecated alias (reference
# scripts pass it); there is no CUDA device here — the XLA trace simply
# captures whatever accelerator backend is active.
_STATES = ("CPU", "TPU", "All")
_DEPRECATED_STATES = ("GPU",)


def _check_state(state: str) -> str:
    if state in _DEPRECATED_STATES:
        warnings.warn(
            f"profiler state {state!r} is a deprecated alias on the "
            f"TPU-native build; use 'TPU' (or 'All')", DeprecationWarning,
            stacklevel=3)
        return state
    if state not in _STATES:
        raise ValueError(
            f"state must be CPU / TPU / All (got {state!r}; 'GPU' is "
            f"accepted as a deprecated alias)")
    return state


def _host_tracer() -> _tracer_mod.Tracer:
    return _tracer_mod.get_tracer()


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile"):
    """reference profiler.py:profiler — a `jax.profiler` capture around the
    block, then the reference's table: device time per Fluid op type of the
    programs that ran inside it, sorted by `sorted_key` (`calls`, `total`,
    `max`, `min`, `ave`; default `total`). See `print_device_table`."""
    _check_state(state)
    sorted_key = _check_sorted_key(sorted_key)
    os.makedirs(profile_path, exist_ok=True)
    jax.profiler.start_trace(profile_path)
    t0 = time.time()
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        dt = time.time() - t0
        print(f"[paddle_tpu.profiler] trace written to {profile_path} "
              f"(wall {dt:.3f}s); view with TensorBoard or perfetto")
        if state != "CPU":
            print_device_table(profile_path, sorted_key)


@contextlib.contextmanager
def record_event(name: str):
    """reference platform::RecordEvent analog -> jax named annotation.
    Events also land in the bounded host-event ring (print_host_events)
    and the chrome trace export (export_chrome_tracing). Recorded even
    when the body raises — the failing iteration is usually the one being
    profiled."""
    with jax.profiler.TraceAnnotation(name):
        with _host_tracer().span(name, cat="host"):
            yield


def start_profiler(state="All", profile_path="/tmp/profile"):
    _check_state(state)
    os.makedirs(profile_path, exist_ok=True)
    jax.profiler.start_trace(profile_path)


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    """End the capture `start_profiler` began and print the table of device
    time per Fluid op type (`print_device_table`), sorted by `sorted_key`.
    An unknown key is refused before anything is stopped."""
    sorted_key = _check_sorted_key(sorted_key)
    jax.profiler.stop_trace()
    print_device_table(profile_path, sorted_key)


def reset_profiler():
    """Clear the host-event ring (reference ResetProfiler)."""
    _host_tracer().clear()


@contextlib.contextmanager
def cuda_profiler(*a, **kw):
    """Accepted for reference API parity; TPU traces are captured by
    `profiler` above."""
    yield


def print_host_events(sorted_key="total"):
    """Aggregated host-event table (reference DisableProfiler's printed
    table, profiler.cc:448): the `record_event` spans, on the host's clock.
    Under jit there are no per-op kernel launches to time on the host;
    device time per Fluid op is the table `profiler` / `stop_profiler`
    print from the device track (`print_device_table`)."""
    agg = _host_tracer().aggregate(cat="host")
    keyfn = {"total": lambda kv: -kv[1][1], "calls": lambda kv: -kv[1][0],
             "max": lambda kv: -kv[1][2], "min": lambda kv: kv[1][3],
             "ave": lambda kv: -kv[1][1] / kv[1][0]}.get(
        sorted_key, lambda kv: -kv[1][1])
    rows = sorted(agg.items(), key=keyfn)
    print(f"{'Event':<40} {'Calls':>8} {'Total(s)':>12} {'Avg(ms)':>10} "
          f"{'Max(ms)':>10} {'Min(ms)':>10}")
    for name, (calls, total, mx, mn) in rows:
        print(f"{name:<40} {calls:>8} {total:>12.4f} "
              f"{1000 * total / calls:>10.3f} {1000 * mx:>10.3f} "
              f"{1000 * mn:>10.3f}")
    return rows


def export_chrome_tracing(path: str):
    """Write recorded host events as chrome://tracing JSON (reference
    tools/timeline.py:21 converts the profiler proto the same way; device
    timelines come from the perfetto trace jax.profiler writes). Exports
    the WHOLE telemetry timeline — record_event spans plus executor step
    phases and any other tracer category."""
    return _host_tracer().export_chrome(path)


# ---------------------------------------------------------------------------
# Device time by Fluid op
# ---------------------------------------------------------------------------

# (scope, type) of a Fluid op: its `name_scope` prefix ("" if none) and its
# op type. owner: the op of an instruction's own `op_name`, or None (XLA's
# own copies, a bare `reduce_sum`). members: for a fusion, the distinct ops
# among the instructions of the computation it calls (XLA fuses across op
# boundaries), owner included. short: the instruction's XLA name without its
# number, what a row of instructions with no Fluid op is called.
Instr = collections.namedtuple("Instr", "owner members short")

# jax's own components of an `op_name` path. `jit(fn)` / `pjit(fn)` hold a
# function's name and go; any other `wrapper(...)` (jvp, transpose, vmap,
# checkpoint, custom_jvp, ...) holds the scopes it was entered under, which
# stay; `while/body`, `cond/branch_1_fun` and the bare words go.
_FN_WRAPPERS = ("jit", "pjit")
_WRAPPER = re.compile(r"^(\w+)\((.*)\)$")
_JAX_PAIRS = {"while": re.compile(r"^(body|cond)(_fun)?$"),
              "cond": re.compile(r"^branch_\d+(_fun)?$")}
_JAX_WORDS = frozenset({"checkpoint", "remat", "closed_call", "core_call",
                        "custom_jvp_call", "custom_vjp_call"})
# Fluid ops that run a sub-block: the ops inside are lowered under them, and
# the table wants the inner op
_CONTAINER_OPS = frozenset({"while", "static_rnn", "conditional_block"})


def _split_path(path: str) -> List[str]:
    """Components of an `op_name`, split at the slashes outside parentheses."""
    out, depth, start = [], 0, 0
    for i, c in enumerate(path):
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        elif c == "/" and depth == 0:
            out.append(path[start:i])
            start = i + 1
    out.append(path[start:])
    return out


def _peel(components: List[str]) -> List[str]:
    out = []
    for comp in components:
        m = _WRAPPER.match(comp)
        if m is None:
            out.append(comp)
        elif m.group(1) not in _FN_WRAPPERS and m.group(2):
            out += _peel(_split_path(m.group(2)))
    kept, i = [], 0
    while i < len(out):
        pair = _JAX_PAIRS.get(out[i])
        if pair is not None and i + 1 < len(out) and pair.match(out[i + 1]):
            i += 2
        elif out[i] in _JAX_WORDS:
            i += 1
        else:
            kept.append(out[i])
            i += 1
    return kept


def _is_op_type(name: str) -> bool:
    return _registry.is_registered(name) or (
        name.endswith(_registry.GRAD_OP_SUFFIX)
        and _registry.is_registered(name[:-len(_registry.GRAD_OP_SUFFIX)]))


def parse_op_name(op_name: str) -> Optional[Tuple[str, str]]:
    """(name_scope prefix, op type) of the Fluid op whose lowering rule
    emitted an instruction with this `op_name`, or None.

    `jit(step)/l0.gdn/rms_norm_grad/transpose(jvp())/mul`: what jax adds is
    peeled, then the path is read from the left. The first component that is
    a registered op type (`<type>_grad` included) is the op, what stands
    before it its `name_scope`: `("l0.gdn", "rms_norm_grad")`. An op that
    runs a sub-block (`while`) is passed over for the op inside it. The last
    component, where it is no wrapper, is the primitive's own name and never
    the op: XLA's bare `reduce_sum` is no Fluid `reduce_sum`."""
    raw = _split_path(op_name)
    if raw and not _WRAPPER.match(raw[-1]):
        raw = raw[:-1]
    path = _peel(raw)
    found = None
    for i, comp in enumerate(path):
        if _is_op_type(comp):
            found = ("/".join(path[:i]), comp)
            if comp not in _CONTAINER_OPS:
                break
    return found


_NAME = re.compile(r"^(?:ROOT )?(%?[\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_METADATA = re.compile(r", metadata=\{(?:[^{}\"]|\"(?:[^\"\\]|\\.)*\")*\}")
_COMPUTATION = re.compile(r"^(ENTRY )?(%?[\w.\-]+) .*\{$")
_BRACKETS = re.compile(r"[(){}]")
_OPERAND_TOKENS = re.compile(r"[(){}\[\]]|, ")
_COMMENT = re.compile(r"/\*.*?\*/")     # `/*index=5*/` in a long operand list
# attributes that name the computations whose instructions the device runs as
# events of their own, beside the instruction that calls them
_BODIES = re.compile(r"\b(?:body|condition|true_computation|false_computation"
                     r"|branch_computations|to_apply|calls)="
                     r"(\{[^{}]*\}|%?[\w.\-]+)")
_NESTING_OPCODES = ("while", "conditional", "call")


def _close(text: str, start: int) -> int:
    """Index just past the bracket that closes the one at `start`."""
    depth = 0
    for m in _BRACKETS.finditer(text, start):
        depth += 1 if m.group() in "({" else -1
        if depth == 0:
            return m.end()
    return len(text)


def _split_instruction(line: str):
    """(head `%name = type `, opcode, operands, rest) of an instruction
    line, or None: `%a = f32[8]{0} add(f32[8]{0} %b, f32[8]{0} %c), x=1`
    gives `("%a = f32[8]{0} ", "add", "f32[8]{0} %b, f32[8]{0} %c",
    ", x=1")`."""
    m = _NAME.match(line)
    if m is None:
        return None
    at = m.end()
    # the result type: a tuple in parentheses, or one token
    at = _close(line, at) if line.startswith("(", at) else line.find(" ", at)
    if at < 0:
        return None
    paren = line.find("(", at)
    if paren < 0:
        return None
    end = _close(line, paren)
    return (line[m.start(1):at + 1], line[at + 1:paren],
            line[paren + 1:end - 1], line[end:])


def _operand_names(operands: str) -> str:
    """`f32[8]{0:T(8)} %b, (s32[], f32[2]{0}) %c` -> `%b, %c`: a trace
    event's name carries each operand's type, jax's compiled text does
    not."""
    out, depth, start = [], 0, 0
    for m in _OPERAND_TOKENS.finditer(operands):
        tok = m.group()
        if tok == ", ":
            if depth == 0:
                out.append(operands[start:m.start()])
                start = m.end()
        else:
            depth += 1 if tok in "({[" else -1
    if depth:               # not what this reads: leave it as it is
        return operands
    out.append(operands[start:])
    return ", ".join(piece.rsplit(" ", 1)[-1] for piece in out)


def canonical_line(line: str) -> Optional[str]:
    """The form an instruction is joined on: the whole line as the compiled
    text and a device event's name both have it, so without `ROOT`, the
    operands' types, `metadata={..}` and `backend_config=..`. None for a
    line that is no instruction."""
    line = line.strip()
    if "/*" in line:
        line = _COMMENT.sub("", line)
    parts = _split_instruction(line)
    if parts is None:
        return None
    head, opcode, operands, rest = parts
    rest = _METADATA.sub("", rest)
    at = rest.find(", backend_config=")
    if at >= 0:
        end = at + len(", backend_config=")
        end = _close(rest, end) if rest.startswith("{", end) else len(rest)
        rest = rest[:at] + rest[end:]
    return f"{head}{opcode}({_operand_names(operands)}){rest}"


def build_op_map(hlo_text: str) -> Dict[str, Instr]:
    """{canonical instruction line: Instr} of a compiled step's text, for
    the instructions the device track shows as events: those of the ENTRY
    computation and of the `while` / `conditional` / `call` bodies reached
    from it. A fusion's `members` come from the computation it `calls=`."""
    computations: Dict[str, list] = {}      # name -> [(line, opcode, owner)]
    owners_of: Dict[str, tuple] = {}        # name -> distinct owners inside
    entry = current = None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            if m:
                current = computations.setdefault(m.group(2).lstrip("%"), [])
                if m.group(1):
                    entry = m.group(2).lstrip("%")
            continue
        parts = _split_instruction(line.strip())
        if parts is None or current is None:
            continue
        op = _OP_NAME.search(parts[3])
        owner = parse_op_name(op.group(1).replace("\\'", "'")) if op else None
        current.append((line, parts[1], owner))
    for name, instrs in computations.items():
        owners_of[name] = tuple(dict.fromkeys(
            o for _, _, o in instrs if o is not None))
    out: Dict[str, Instr] = {}
    todo, seen = [entry], set()
    while todo:
        name = todo.pop()
        if name is None or name in seen:
            continue
        seen.add(name)
        for line, opcode, owner in computations.get(name, ()):
            key = canonical_line(line)      # its metadata is cut: no
            called = [c.lstrip("%")         # `body=` inside an op_name
                      for grp in _BODIES.findall(key)
                      for c in grp.strip("{}").split(", ")]
            members = ()
            if opcode == "fusion" and called:
                members = owners_of.get(called[0], ())
            elif opcode in _NESTING_OPCODES:
                todo += called
            short = re.sub(r"\.\d+$", "", key.split(" = ", 1)[0].lstrip("%"))
            out[key] = Instr(owner, members, short)
    return out


def op_map(event) -> Optional[Dict[str, Instr]]:
    """The op map of a compile event's step (`observe.observatory()`),
    built at the first ask (`RecompileEvent.compiled_text()`: no compile
    once the step has run) and kept on the event; None where the event
    offers no text."""
    return event.op_map(build_op_map)


def self_times(ops: Iterable[Tuple[int, int, str]]):
    """[(name, self ns)] of one device's events `(start, end, name)`: an
    event's duration less that of the events nested inside it (a `%while`
    spans its body's ops; counted whole they would count twice)."""
    out, stack = [], []         # stack: [end, index into out]
    for start, end, name in sorted(ops, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack and end <= stack[-1][0]:
            out[stack[-1][1]][1] -= end - start
        stack.append((end, len(out)))
        out.append([name, end - start])
    return out


class _Stat:
    """calls, total, max, min of the self times added."""
    __slots__ = ("calls", "total", "max", "min")

    def __init__(self):
        self.calls, self.total, self.max, self.min = 0, 0, 0, None

    def add(self, ns, calls=1, mx=None, mn=None):
        self.calls += calls
        self.total += ns
        self.max = max(self.max, ns if mx is None else mx)
        mn = ns if mn is None else mn
        self.min = mn if self.min is None else min(self.min, mn)

    def merge(self, other):
        self.add(other.total, other.calls, other.max, other.min)


class DeviceTable:
    """Device time of one program's step by Fluid op, from `device_table`.

    `lines`: {canonical instruction line: (Instr or None, _Stat)}, None for
    an event whose line the op map does not hold (another executable's, or a
    text that is not the running one's). Everything else is summed from it:
    rows of owned time by op type, by `name_scope`, by XLA short name where
    there is no Fluid op, the unattributed rest, and for each op type the
    time of fusions it sits in without owning them."""

    def __init__(self):
        self.lines: Dict[str, Tuple[Optional[Instr], _Stat]] = {}

    @property
    def total_ns(self) -> int:
        return sum(st.total for _, st in self.lines.values())

    def owned(self, op: Optional[str] = None, scope: Optional[str] = None):
        """[(line, Instr, _Stat)] of the instructions with a Fluid owner,
        of those whose op type matches the regular expression `op` and
        whose `name_scope` matches `scope` where they are given."""
        op_rx = re.compile(op) if op else None
        scope_rx = re.compile(scope) if scope else None
        return [(line, ins, st) for line, (ins, st) in self.lines.items()
                if ins is not None and ins.owner is not None
                and (op_rx is None or op_rx.search(ins.owner[1]))
                and (scope_rx is None or scope_rx.search(ins.owner[0]))]

    def rows(self):
        """(by op type, by name_scope, no Fluid op by short name,
        unattributed, shared ns by op type): the first three
        {name: _Stat}."""
        types, scopes, xla = {}, {}, {}
        unattributed, shared = _Stat(), collections.Counter()
        for ins, st in self.lines.values():
            if ins is None:
                unattributed.merge(st)
            elif ins.owner is None:
                xla.setdefault(ins.short, _Stat()).merge(st)
            else:
                types.setdefault(ins.owner[1], _Stat()).merge(st)
                if ins.owner[0]:
                    scopes.setdefault(ins.owner[0], _Stat()).merge(st)
            if ins is not None:
                for t in {m[1] for m in ins.members if m != ins.owner}:
                    shared[t] += st.total
        for t in shared:        # an op that owns nothing still has its row
            types.setdefault(t, _Stat())
        return types, scopes, xla, unattributed, shared


def device_table(ops: Iterable[Tuple[int, int, str]],
                 op_map: Dict[str, Instr],
                 canon: Optional[Dict[str, str]] = None) -> DeviceTable:
    """Join one device's events `(start, end, name)` (a TPU plane's "XLA Ops"
    line: an event's name is its instruction) with a step's op map, each
    event at its self time. `canon`: {event name: canonical line} where the
    caller has made some already."""
    table = DeviceTable()
    canon = {} if canon is None else canon
    for name, ns in self_times(ops):
        key = canon.get(name)
        if key is None:
            key = canon[name] = canonical_line(name) or name
        hit = table.lines.get(key)
        if hit is None:
            hit = table.lines[key] = (op_map.get(key), _Stat())
        hit[1].add(ns)
    return table


SORT_KEYS = {"total": lambda s: -s.total, "calls": lambda s: -s.calls,
             "max": lambda s: -s.max, "min": lambda s: s.min or 0,
             "ave": lambda s: -s.total / max(s.calls, 1)}


def _check_sorted_key(sorted_key):
    if sorted_key is None:
        return "total"
    if sorted_key not in SORT_KEYS:
        raise ValueError(f"sorted_key must be one of {sorted(SORT_KEYS)} "
                         f"(got {sorted_key!r})")
    return sorted_key


def format_table(table: DeviceTable, sorted_key: Optional[str] = None,
                 busy_ns: Optional[int] = None, top_xla: int = 15) -> str:
    """The reference's profiling report for one program's step on one
    device: a row a Fluid op type, then a row a `name_scope` prefix where
    any is set, then the instructions with no Fluid op by XLA short name,
    then the unattributed rest. Columns: calls (device events), total, min,
    max, ave in ms at self time, the share of busy time, and `in fusions`:
    ms of fusions the op type has instructions in without owning them (XLA
    fuses across op boundaries; a fusion is owned by the op of its own
    `op_name`)."""
    key = SORT_KEYS[_check_sorted_key(sorted_key)]
    types, scopes, xla, unattributed, shared = table.rows()
    busy = busy_ns or table.total_ns or 1
    head = (f"{'':<34} {'Calls':>8} {'Total(ms)':>11} {'Min(ms)':>10} "
            f"{'Max(ms)':>10} {'Ave(ms)':>10} {'Busy%':>7}")

    def row(name, st, extra=""):
        return (f"{name[:34]:<34} {st.calls:>8} {st.total / 1e6:>11.3f} "
                f"{(st.min or 0) / 1e6:>10.4f} {st.max / 1e6:>10.4f} "
                f"{st.total / max(st.calls, 1) / 1e6:>10.4f} "
                f"{100.0 * st.total / busy:>7.2f}{extra}")

    def block(title, stats, limit=None):
        ranked = sorted(stats.items(), key=lambda kv: key(kv[1]))
        out = [title]
        out += [row(n, st) for n, st in ranked[:limit]]
        if limit is not None and len(ranked) > limit:
            rest = _Stat()
            for _, st in ranked[limit:]:
                rest.merge(st)
            out.append(row(f"({len(ranked) - limit} more)", rest))
        return out

    attributed = sum(st.total for st in types.values())
    out = [f"{'Fluid op type':<34}" + head[34:] + f" {'in fusions(ms)':>15}"]
    out += [row(n, st, f" {shared.get(n, 0) / 1e6:>15.3f}") for n, st in
            sorted(types.items(), key=lambda kv: key(kv[1]))]
    if scopes:
        out += block(f"{'name_scope':<34}" + head[34:], scopes)
    if xla:
        out += block(f"{'no Fluid op (XLA short name)':<34}" + head[34:],
                     xla, top_xla)
    out.append(f"attributed to a Fluid op {100.0 * attributed / busy:.2f}% "
               f"of busy time, XLA's own "
               f"{100.0 * sum(s.total for s in xla.values()) / busy:.2f}%, "
               f"unattributed (no such line in the compiled text) "
               f"{100.0 * unattributed.total / busy:.2f}% "
               f"({unattributed.calls} events, "
               f"{unattributed.total / 1e6:.3f} ms)")
    return "\n".join(out)


# -- from a capture to the tables -------------------------------------------

_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_RUN_SPAN = "paddle_tpu:run"


def _newest_xplane(profile_path: str) -> Optional[str]:
    found = glob.glob(os.path.join(profile_path, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def read_capture(xplane_path: str):
    """(programs, devices) of a `jax.profiler` capture. programs: {program
    uid: source} of the `paddle_tpu:run` spans in it, in order of first run.
    devices: {TPU index: (modules, ops)}, each a list of `(start ns, end ns,
    name)` sorted by start: the plane's "XLA Modules" line (one event an
    execution of a compiled module) and its "XLA Ops" line (one event an
    executed instruction, named by its text)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    programs, devices = {}, {}
    for plane in data.planes:
        m = _TPU_PLANE.match(plane.name)
        if m:
            lines = {"XLA Modules": [], "XLA Ops": []}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name] = sorted(
                        (int(e.start_ns), int(e.start_ns + e.duration_ns),
                         e.name) for e in line.events if e.duration_ns > 0)
            devices[int(m.group(1))] = (lines["XLA Modules"],
                                        lines["XLA Ops"])
            continue
        runs = []
        for line in plane.lines:
            for e in line.events:
                if e.name == _RUN_SPAN:
                    stats = dict(e.stats)
                    runs.append((e.start_ns, stats.get("program"),
                                 stats.get("source")))
        for _, uid, source in sorted(runs):
            if uid is not None:
                programs.setdefault(int(uid), source)
    return programs, devices


def _union_ns(intervals) -> int:
    """ns covered by `(start, end, ...)` intervals sorted by start."""
    total, upto = 0, None
    for start, end, *_ in intervals:
        if upto is None or start > upto:
            total, upto = total + end - start, end
        elif end > upto:
            total, upto = total + end - upto, end
    return total


def tables_by_module(modules, ops, maps: Dict[int, Dict[str, Instr]]):
    """[(module name, executions, busy ns, program uid or None, DeviceTable
    or None)] of one device, busy being the union of the module's ops. An
    op belongs to the module execution it starts in, and a module to the
    program whose op map holds the most of its distinct instruction lines
    (at least half: the whole line is compared, so another executable's
    lines do not match by accident): two programs in one capture, startup
    and main or train and test, are not mixed."""
    import bisect
    starts = [m[0] for m in modules]
    by_module: Dict[str, list] = {}
    for op in ops:
        i = bisect.bisect_right(starts, op[0]) - 1
        if i >= 0 and op[0] < modules[i][1]:
            by_module.setdefault(modules[i][2], []).append(op)
    out = []
    for name, mod_ops in by_module.items():
        runs = sum(1 for m in modules if m[2] == name)
        canon = {n: canonical_line(n) or n for n in {op[2] for op in mod_ops}}
        lines = set(canon.values())
        best, hits = None, 0
        for uid, op_map_ in maps.items():
            n = sum(1 for line in lines if line in op_map_)
            if n > hits:
                best, hits = uid, n
        if best is not None and 2 * hits < len(lines):
            best = None
        out.append((name, runs, _union_ns(mod_ops), best,
                    device_table(mod_ops, maps[best], canon)
                    if best is not None else None))
    return out


def print_device_table(profile_path="/tmp/profile", sorted_key=None):
    """Print, from the newest capture under `profile_path`, device time per
    Fluid op type (`format_table`) for every program whose `paddle_tpu:run`
    spans fall inside it, on the busiest TPU. Each program's newest compile
    event is asked for its op map here (`op_map`). A capture without a TPU
    plane (a CPU run) has no device track to read and nothing is lowered
    for it."""
    sorted_key = _check_sorted_key(sorted_key)
    path = _newest_xplane(profile_path)
    if path is None:
        print(f"[paddle_tpu.profiler] no capture under {profile_path}")
        return None
    programs, devices = read_capture(path)
    devices = {d: v for d, v in devices.items() if v[0] and v[1]}
    if not devices:
        print("[paddle_tpu.profiler] the capture holds no TPU plane: device "
              "time per Fluid op is read from a TPU's track")
        return None
    dev = max(devices, key=lambda d: sum(e - s for s, e, _ in devices[d][0]))
    maps = {}
    for uid in programs:
        event = _steplog.observatory().latest(uid)
        built = op_map(event) if event is not None else None
        if built:
            maps[uid] = built
    result = tables_by_module(*devices[dev], maps)
    for name, runs, busy, uid, table in result:
        what = (f"program {uid} ({programs[uid]})" if uid is not None else
                "no program of this capture's run spans")
        print(f"[paddle_tpu.profiler] /device:TPU:{dev} module {name}: "
              f"{runs} executions, busy {busy / 1e6:.3f} ms "
              f"({busy / runs / 1e6:.3f} ms each): {what}"
              + (f"; device time by Fluid op, sorted by {sorted_key}"
                 if table else ""))
        if table is not None:
            print(format_table(table, sorted_key, busy))
    return result
