"""paddle_tpu — a TPU-native deep-learning framework with the capabilities
of Fluid-era PaddlePaddle (reference: /root/reference).

Compute path: JAX/XLA (+ Pallas kernels); runtime around it: Python + C++
(native data loader / recordio). See SURVEY.md and ARCHITECTURE.md.

Usage mirrors the reference:

    import paddle_tpu as paddle
    import paddle_tpu.fluid as fluid

    x = fluid.layers.data(name='x', shape=[13], dtype='float32')
    y = fluid.layers.fc(input=x, size=1)
    ...
    exe = fluid.Executor(fluid.TPUPlace(0))
"""
from .core.compile_cache import configure_compile_cache

configure_compile_cache()   # before anything below can compile

from . import framework  # noqa: E402
from . import ops  # noqa: E402  (registers all kernels)
from .framework import (Program, Block, Variable, Operator,  # noqa
                        default_startup_program, default_main_program,
                        program_guard, switch_startup_program,
                        switch_main_program, get_var)
from .core.places import (TPUPlace, CPUPlace, CUDAPlace,  # noqa
                          CUDAPinnedPlace, is_compiled_with_cuda,
                          is_compiled_with_tpu)
from .executor import (Executor, Scope, global_scope, scope_guard,  # noqa
                       switch_scope, fetch_var)
from . import layers  # noqa
from . import initializer  # noqa
from . import regularizer  # noqa
from . import clip  # noqa
from . import optimizer  # noqa
from . import backward  # noqa
from .backward import append_backward, calc_gradient, gradients  # noqa
from .param_attr import ParamAttr, WeightNormParamAttr  # noqa
from . import unique_name  # noqa
from .data_feeder import DataFeeder  # noqa
from .lod import (SequenceTensor, create_lod_tensor,  # noqa
                  create_random_int_lodtensor)
from . import io  # noqa
from . import nets  # noqa
from . import metrics  # noqa
from . import evaluator  # noqa
from . import average  # noqa
from . import profiler  # noqa
from . import reader  # noqa
from . import dataset  # noqa
from .reader import batch  # noqa
from . import parallel  # noqa
from . import trainer  # noqa
from .trainer import Trainer  # noqa
from . import inferencer  # noqa
from .inferencer import Inferencer  # noqa
from . import serving  # noqa
from .serving import ModelServer  # noqa
from . import fleet  # noqa
from . import debugger  # noqa
from . import debugger as debuger  # noqa  (reference spelling)
from . import graphviz  # noqa
from . import net_drawer  # noqa
from . import concurrency  # noqa
from .parallel.parallel_executor import (ParallelExecutor,  # noqa
                                         ExecutionStrategy, BuildStrategy)
from .parallel.transpiler import (DistributeTranspiler,  # noqa
                                  InferenceTranspiler,
                                  SimpleDistributeTranspiler,
                                  memory_optimize, release_memory)
from . import transpiler  # noqa
from . import compiler  # noqa
from . import recordio_writer  # noqa
from . import contrib  # noqa
from . import resilience  # noqa
from .clip import ErrorClipByValue  # noqa

Tensor = SequenceTensor  # loose alias for scripts touching fluid.Tensor
# reference __init__.py:46 re-exports core.LoDTensor; SequenceTensor
# carries the imperative surface (set/set_lod/lod)
LoDTensor = SequenceTensor

__version__ = '0.1.0'

__all__ = [
    'Program', 'Block', 'Variable', 'Operator', 'default_startup_program',
    'default_main_program', 'program_guard', 'get_var', 'TPUPlace',
    'CPUPlace', 'CUDAPlace', 'CUDAPinnedPlace', 'Executor', 'global_scope',
    'scope_guard', 'fetch_var', 'layers', 'initializer', 'regularizer',
    'clip', 'optimizer', 'backward', 'append_backward', 'calc_gradient', 'gradients', 'ParamAttr',
    'WeightNormParamAttr', 'unique_name', 'DataFeeder', 'SequenceTensor',
    'LoDTensor', 'Tensor',
    'create_lod_tensor', 'create_random_int_lodtensor', 'io', 'nets',
    'metrics', 'evaluator', 'profiler', 'reader', 'dataset', 'batch',
    'ParallelExecutor', 'ExecutionStrategy', 'BuildStrategy',
    'DistributeTranspiler', 'SimpleDistributeTranspiler',
    'InferenceTranspiler', 'transpiler', 'recordio_writer', 'contrib',
    'memory_optimize', 'release_memory', 'resilience',
]
