"""Where this program keeps what it generates and reuses across
processes: JAX's persistent compilation cache.

One rule, one place. If ``JAX_COMPILATION_CACHE_DIR`` is set, the
compile cache lives there — JAX reads that variable itself, and no code
sets another directory. If it is not, the cache lives at one fixed,
git-ignored path inside the checkout, resolved from the package
location: never from the cwd, a temp name, a pid or the time, because
the path is part of what makes a second process hit what the first one
compiled. Nothing else in the tree configures a jax cache directory
(``PTPU_AOT_CACHE`` is a different, opt-in store of serialized
executables: fleet/coldstart.py).
"""
import os

__all__ = ['CACHE_ENV', 'compile_cache_dir', 'configure_compile_cache']

CACHE_ENV = 'JAX_COMPILATION_CACHE_DIR'

_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), '.ptpu_cache')


def compile_cache_dir():
    """The directory JAX's persistent compilation cache uses."""
    return os.environ.get(CACHE_ENV) or os.path.join(_ROOT, 'jax')


def configure_compile_cache():
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir`, and start listening to what jax says of
    its traces, lowerings, compiles and cache loads
    (``observability.tracing.listen_to_jax``). Called once, when the
    package is imported — before anything can compile. Returns the
    directory."""
    from ..observability.tracing import listen_to_jax
    path = compile_cache_dir()
    if not os.environ.get(CACHE_ENV):
        import jax
        jax.config.update('jax_compilation_cache_dir', path)
    listen_to_jax()
    return path
