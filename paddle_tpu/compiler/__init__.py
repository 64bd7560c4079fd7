"""``paddle_tpu.compiler`` — the program-level optimizing pass pipeline.

Runs between user-program construction and ``core/lowering``
(COMPILER.md). The reference Fluid stack rewrote ProgramDesc through
one-off transpilers; here the rewrites are registered passes composed
into pipelines with per-pass timing, journal events, and jit-cache
integration:

- ``default_pipeline()`` — exact rewrites, applied by ``Executor`` on
  every compile: constant folding, dead-op elimination, elementwise
  chain fusion, liveness buffer-release annotation.
- ``inference_pipeline()`` — adds BN/scale folding into conv/fc
  weights (needs the scope; <= 1e-5 drift) at the head. Reached via
  ``optimize_inference`` / the legacy ``InferenceTranspiler`` facade.

The executor folds :func:`pipeline_signature` into every program-cache
key, so toggling the pipeline (``set_enabled``/``set_default_passes``)
invalidates exactly the affected compiled programs — never serving a
program compiled under a different config.
"""
import contextlib

from .pass_base import (Pass, PassContext, PassResult, PassRegistry,  # noqa
                        PassPipeline, register_pass, get_pass,
                        registered_passes)
from . import passes  # noqa  (registers canonical passes + fused kernel)
from . import zero  # noqa  (registers the ZeRO-2 grad-tail pass)
from .passes import DEFAULT_PASSES, INFERENCE_PASSES  # noqa

__all__ = ['Pass', 'PassContext', 'PassResult', 'PassRegistry',
           'PassPipeline', 'register_pass', 'get_pass',
           'registered_passes', 'enabled', 'set_enabled', 'disabled',
           'default_pipeline', 'inference_pipeline',
           'set_default_passes', 'pipeline_signature',
           'optimize', 'optimize_inference', 'zero']

_STATE = {'enabled': True, 'pass_names': tuple(DEFAULT_PASSES),
          'pipeline': None}


def enabled():
    return _STATE['enabled']


def set_enabled(on):
    """Master switch for the executor-integrated pipeline. Flipping it
    changes :func:`pipeline_signature`, forcing a recompile (never a stale
    program)."""
    _STATE['enabled'] = bool(on)


@contextlib.contextmanager
def disabled():
    """Temporarily run raw (unoptimized) lowering — benchmarks use this
    for optimized-vs-raw comparisons."""
    prev = _STATE['enabled']
    _STATE['enabled'] = False
    try:
        yield
    finally:
        _STATE['enabled'] = prev


def set_default_passes(names):
    """Reconfigure the canonical pipeline (ordered pass names). Pass
    None to restore :data:`DEFAULT_PASSES`."""
    names = tuple(names) if names is not None else tuple(DEFAULT_PASSES)
    for n in names:
        get_pass(n)          # validate early
    _STATE['pass_names'] = names
    _STATE['pipeline'] = None


def default_pipeline():
    pipe = _STATE['pipeline']
    if pipe is None or pipe.signature() != _STATE['pass_names']:
        pipe = _STATE['pipeline'] = PassPipeline(
            list(_STATE['pass_names']), name='default')
    return pipe


def inference_pipeline():
    return PassPipeline(list(INFERENCE_PASSES), name='inference')


def pipeline_signature():
    """The active config as a stable tuple: (enabled, pass names)."""
    if not _STATE['enabled']:
        return ('off',)
    return _STATE['pass_names']


def optimize(program, fetch_names=(), scope=None, clone=True):
    """Run the canonical pipeline. Returns ``(program, results)``; with
    ``clone=True`` (default) the input program is untouched."""
    return default_pipeline().run(program, scope=scope,
                                  protected=frozenset(fetch_names),
                                  clone=clone)


def optimize_inference(program, scope=None, fetch_names=(), clone=False):
    """BN folding + the canonical passes, for inference programs whose
    weights are resident in ``scope``. In place by default — the
    contract of the legacy ``InferenceTranspiler.transpile``."""
    return inference_pipeline().run(program, scope=scope,
                                    protected=frozenset(fetch_names),
                                    clone=clone)
