"""paddle_tpu: a TPU-native deep-learning framework with the capabilities of
Fluid-era PaddlePaddle (reference: coslian/Paddle v0.14.0).

Architecture (see SURVEY.md for the reference blueprint):
  - Program/Block/Op IR built from a layers DSL (core/ir.py)
  - ops are JAX lowering rules; shape inference via eval_shape (core/registry.py)
  - program-level autodiff emitting generic vjp grad ops (core/backward.py)
  - Executor compiles whole blocks into single XLA computations (core/executor.py)
  - data parallelism via pjit/GSPMD over a device Mesh (parallel/)
"""

import os as _os

import jax as _jax

# The one place a compile cache is configured: JAX_COMPILATION_CACHE_DIR
# (jax reads it into this config value itself) or a caller's own
# config.update wins; otherwise a fixed path inside the checkout. The
# directory is part of the cache key, so it is never a temp name.
if not _jax.config.jax_compilation_cache_dir:
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_compile_cache"))

from .core import ir as _ir
from .core.ir import (Program, program_guard, default_main_program,  # noqa: F401
                      default_startup_program, Variable, Parameter, Operator,
                      name_scope)
from .core.executor import (Executor, PreparedProgram, Scope,  # noqa: F401
                            global_scope, CPUPlace, TPUPlace, CUDAPlace,
                            EOFException, scope_guard, _switch_scope,
                            fetch_var)
from .core.backward import append_backward, calc_gradient  # noqa: F401

from . import ops  # noqa: F401  (registers all lowering rules)
from . import wire  # noqa: F401  (fluid-wire codecs + comm_quant op)
from . import layers  # noqa: F401
from . import initializer  # noqa: F401
from . import optimizer  # noqa: F401
from . import regularizer  # noqa: F401
from . import clip  # noqa: F401
from . import unique_name  # noqa: F401
from . import nets  # noqa: F401
from . import metrics  # noqa: F401
from . import io  # noqa: F401
from . import profiler  # noqa: F401
from . import debugger  # noqa: F401
from . import evaluator  # noqa: F401
from . import average  # noqa: F401
from . import annotations  # noqa: F401
from . import contrib  # noqa: F401
from . import graphviz  # noqa: F401
from . import net_drawer  # noqa: F401
from . import op  # noqa: F401
from . import default_scope_funcs  # noqa: F401
from . import recordio_writer  # noqa: F401
from .recordio_writer import (convert_reader_to_recordio_file,  # noqa: F401
                              convert_reader_to_recordio_files)
from . import ir_pass  # noqa: F401
from . import analysis  # noqa: F401
from .analysis import ProgramVerificationError  # noqa: F401
from . import enforce  # noqa: F401
from . import lod_tensor  # noqa: F401
from .lod_tensor import create_lod_tensor, create_random_int_lodtensor  # noqa: F401
from .enforce import EnforceNotMet  # noqa: F401
from . import flags  # noqa: F401
from .flags import get_flag, set_flag  # noqa: F401
from . import observe  # noqa: F401  (fluid-scope runtime telemetry)
from .param_attr import ParamAttr, WeightNormParamAttr  # noqa: F401
from .data_feeder import DataFeeder  # noqa: F401
from .async_feeder import AsyncFeeder  # noqa: F401
from . import reader  # noqa: F401
from . import dataset  # noqa: F401
from .parallel.parallel_executor import (ParallelExecutor,  # noqa: F401
                                         BuildStrategy, ExecutionStrategy)
from . import backward  # noqa: F401
from . import transpiler  # noqa: F401
from .transpiler import DistributeTranspiler, DistributeTranspilerConfig  # noqa: F401
from .transpiler import memory_optimize, release_memory, InferenceTranspiler  # noqa: F401
from . import distributed  # noqa: F401
from . import pserver  # noqa: F401
from . import ark  # noqa: F401  (fluid-ark fault-tolerant training)
from . import serve  # noqa: F401  (fluid-serve TPU inference serving)
from . import fleet  # noqa: F401  (fluid-fleet multi-replica serving tier)
from . import haven  # noqa: F401  (fluid-haven replicated PS plane)
from . import master  # noqa: F401
from . import recordio  # noqa: F401
from .trainer import (Trainer, Inferencer, CheckpointConfig,  # noqa: F401
                      BeginEpochEvent, EndEpochEvent, BeginStepEvent,
                      EndStepEvent, save_checkpoint, load_checkpoint)

__version__ = "0.1.0"


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in _jax.devices())


def tpu_device_count() -> int:
    return len(_jax.devices())


def get_var(name, program=None):
    """Look up a Variable by name in a program's global block (reference
    framework.py get_var)."""
    program = program or default_main_program()
    v = program.global_block()._find_var_recursive(name)
    if v is None:
        raise ValueError(f"get_var: no variable named {name!r}")
    return v
