"""paddle_tpu: a TPU-native deep learning framework.

A from-scratch rebuild of the capabilities of PaddlePaddle Fluid (~1.3,
reference at /root/reference) designed TPU-first:

- declarative Program IR in Python (framework.py), lowered whole-program to
  XLA via JAX tracing (core/lowering.py) — no per-op interpreter;
- autodiff by JAX reverse-mode AD behind the reference append_backward API;
- data/model parallelism via jax.sharding Mesh + SPMD partitioner (parallel/)
  instead of NCCL op-handles and transpilers;
- ragged sequences via static LoD + segment ops (core/lod.py, ops/sequence_ops.py);
- host-side input pipeline (reader/) instead of reader ops.
"""
import time as _time
_import_t0 = _time.perf_counter()

from . import core
from . import ops  # registers all op lowerings
from . import framework
from .framework import (Program, Block, Operator, Variable, Parameter,
                        default_main_program, default_startup_program,
                        program_guard, CPUPlace, TPUPlace, CUDAPlace,
                        cpu_places, tpu_places, cuda_places)
from .executor import (Executor, Scope, StepFuture, global_scope,
                       scope_guard)
from .backward import append_backward, calc_gradient, gradients
from . import layers
from . import initializer
from . import optimizer
from . import regularizer
from . import clip
from . import unique_name
from .param_attr import ParamAttr, WeightNormParamAttr
from . import io
from .io import (export_stablehlo_model, load_stablehlo_model,
                 save_vars, save_params, save_persistables, load_vars,
                 load_params, load_persistables, save_inference_model,
                 load_inference_model)
from . import nets
from . import metrics
from . import lod_tensor
from .lod_tensor import (LoDTensor, create_lod_tensor,
                         create_random_int_lodtensor)
from . import reader
from . import pipeline
from .pipeline import DataLoader, train_loop
from . import dataset
from . import models
from . import transpiler
from . import ps
from . import parallel
from . import monitor
from . import coldstart
from . import trace
from . import analysis
from . import goodput
from . import health
from . import resilience
from .resilience import TrainingGuard, elastic_train_loop
from . import profiler
from . import flags
from .flags import get_flags, set_flags
from . import debugger
from . import recordio
from . import imperative
from . import evaluator
from . import compat
from . import net_drawer
from . import default_scope_funcs
from . import checkpoint
from .checkpoint import CheckpointManager
from . import average
from .average import WeightedAverage
from . import contrib
from . import async_executor
from .async_executor import AsyncExecutor, DataFeedDesc, MultiSlotDataFeed
from .data_feeder import DataFeeder
from . import compiler
from .compiler import CompiledProgram
from .parallel_executor import ParallelExecutor
from .parallel_executor import ExecutionStrategy, BuildStrategy
from . import inference
from .inference import Predictor, PredictorConfig, create_predictor
from . import serving
from .serving import ServingConfig, ServingEngine

__version__ = '0.1.0'

# set-up's `import` stage (coldstart.py): this package's own import, and
# jax's where the process had not imported it yet
coldstart.book('import', _time.perf_counter() - _import_t0)
