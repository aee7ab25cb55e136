"""Runtime flag registry (reference: gflags end-to-end — FLAGS_check_nan_inf
etc. in C++, forwarded from `FLAGS_*` environment variables at import by
python/paddle/fluid/__init__.py; SURVEY.md §5.6).

Flags initialize from `PADDLE_TPU_<NAME>` (or legacy `FLAGS_<name>`)
environment variables and can be flipped at runtime with `set_flag`:
executors read the registry at run time (the flag value is part of the
compile-cache key), so a flip takes effect on the next `run` call."""

from __future__ import annotations

import os
from typing import Any, Dict

_DEFS: Dict[str, tuple] = {
    # name: (default, type)
    "check_nan_inf": (False, bool),   # reference FLAGS_check_nan_inf
    # XLA compile options for the jitted step (round-5 flag sweep,
    # docs/PERF.md): "auto" = the measured-good TPU set (scoped VMEM
    # 32 MiB — bigger fusion budget, worth ~9% on transformer-base);
    # "" / "none" = compiler defaults; or an explicit comma-separated
    # k=v list (e.g. "xla_tpu_scoped_vmem_limit_kib=65536")
    "xla_compiler_options": ("auto", str),
    # static program verification on Executor.prepare()/run() (analysis/):
    # "error" rejects malformed programs before any XLA lowering, "warn"
    # logs the diagnostics and proceeds, "off" (default) skips the sweep
    "validate": ("off", str),
    # runtime telemetry (observe/): per-step phase timings, feeder queue
    # gauges, pserver RPC counters, recompile-cause metrics. Off (default)
    # keeps the prepared fast path free of registry writes; compile-time
    # recompile events are recorded regardless (they are never hot)
    "observe": (False, bool),
    # the distributed-tracing half of the observe plane (observe/xray):
    # span ids, span recording, and the traceparent element on outbound
    # RPC frames. Only consulted while "observe" is on; turning it off
    # leaves metrics/pulse armed but makes every wire frame legacy-shaped
    # and every span a no-op
    "trace": (True, bool),
}

_FLAGS: Dict[str, Any] = {}

# bumped on every set_flag: executors key their prepared-program memo on
# this, turning the per-step "did any flag change?" check into one int
# compare instead of N registry reads (the flag registry stays the source
# of truth — a flip still takes effect on the next run call)
_VERSION = 0


def version() -> int:
    return _VERSION


def _coerce(val: str, typ):
    if typ is bool:
        return val.lower() in ("1", "true", "yes", "on")
    return typ(val)


def _init():
    for name, (default, typ) in _DEFS.items():
        env = os.environ.get(f"PADDLE_TPU_{name.upper()}",
                             os.environ.get(f"FLAGS_{name}"))
        val = _coerce(env, typ) if env is not None else default
        if name in _CHOICES and env is not None:
            val = str(val).lower()
            if val not in _CHOICES[name]:
                raise ValueError(f"flag {name!r} must be one of "
                                 f"{_CHOICES[name]}, got {val!r}")
        _FLAGS[name] = val


def get_flag(name: str):
    if name not in _FLAGS:
        raise KeyError(f"unknown flag {name!r}; known: {sorted(_FLAGS)}")
    return _FLAGS[name]


# enumerated string flags: value must be one of the choices (a typo like
# validate=eror would otherwise silently select another behaviour)
_CHOICES: Dict[str, tuple] = {
    "validate": ("error", "warn", "off"),
}


def set_flag(name: str, value):
    global _VERSION
    if name not in _FLAGS:
        raise KeyError(f"unknown flag {name!r}; known: {sorted(_FLAGS)}")
    if name in _CHOICES:
        value = str(value).lower()
        if value not in _CHOICES[name]:
            raise ValueError(
                f"flag {name!r} must be one of {_CHOICES[name]}, got {value!r}")
    _FLAGS[name] = value
    _VERSION += 1


def all_flags() -> Dict[str, Any]:
    return dict(_FLAGS)


_init()
