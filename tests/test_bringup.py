"""Bring-up contract, checked where there is no chip (PERF.md "Bring-up
on the chip"): a place means its device or raises, nothing names a
device's peaks it does not know, the compile cache has one home, the
chip-only entry points refuse the CPU before building anything, and
generated binaries are not committed."""
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.core import compile_cache
from paddle_tpu.core.places import PlaceUnavailableError, default_place
from paddle_tpu.observability import perf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _git(*args):
    """Run git in the repo; skips the test where the tree under test is
    an export without a .git (``git archive``)."""
    inside = subprocess.run(['git', 'rev-parse', '--is-inside-work-tree'],
                            cwd=REPO, capture_output=True, text=True)
    if inside.returncode != 0:
        pytest.skip('not a git work tree')
    return subprocess.run(['git'] + list(args), cwd=REPO,
                          capture_output=True, text=True)


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.update(extra)
    return env


# ---- places ---------------------------------------------------------------
def test_tpu_place_raises_typed_error_without_a_tpu():
    with pytest.raises(PlaceUnavailableError, match="'tpu'"):
        fluid.TPUPlace(0).jax_device()
    # no modulo: an index past the last device is an error, not device 0
    with pytest.raises(PlaceUnavailableError, match='out of range'):
        fluid.CPUPlace(10 ** 6).jax_device()
    assert fluid.CUDAPlace(0).jax_device().platform == 'cpu'


def test_executor_without_place_runs_on_the_default_backend():
    assert default_place() == fluid.CPUPlace(0)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name='x', shape=[4], dtype='float32')
        y = fluid.layers.fc(input=x, size=2)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        assert exe.place == fluid.CPUPlace(0)
        exe.run(startup)
        out, = exe.run(main, feed={'x': np.ones((3, 4), 'float32')},
                       fetch_list=[y], return_numpy=False)
    assert {d.platform for d in out.data.devices()} == {'cpu'}


# ---- peaks ----------------------------------------------------------------
@pytest.mark.parametrize('kind', ['cpu', '', None, 'TPU v7x', 'mystery'])
def test_unknown_device_kind_has_no_peaks(kind):
    with pytest.raises(perf.UnknownDeviceKindError):
        perf.peak_flops_for(kind)
    with pytest.raises(perf.UnknownDeviceKindError):
        perf.hbm_gbps_for(kind)


# ---- the compile cache ----------------------------------------------------
_PRINT_DIR = ('import paddle_tpu\n'
              'from paddle_tpu.core.compile_cache import compile_cache_dir\n'
              'import jax\n'
              'print(compile_cache_dir())\n'
              'print(jax.config.jax_compilation_cache_dir)\n')


def _cache_dir_in_child(cwd, env):
    env = dict(env, PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, '-c', _PRINT_DIR], cwd=cwd,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    ours, jaxs = out.stdout.split()
    assert ours == jaxs      # what we report is what JAX was given
    return ours


def test_cache_dir_follows_the_environment_when_set(tmp_path, monkeypatch):
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path / 'placed'))
    assert compile_cache.compile_cache_dir() == str(tmp_path / 'placed')
    assert _cache_dir_in_child(str(tmp_path), _cpu_env()) == \
        str(tmp_path / 'placed')


def test_cache_dir_is_one_fixed_path_in_the_checkout(tmp_path):
    env = _cpu_env()
    env.pop(compile_cache.CACHE_ENV, None)
    other = tmp_path / 'elsewhere'
    other.mkdir()
    a = _cache_dir_in_child(str(tmp_path), env)
    b = _cache_dir_in_child(str(other), env)
    assert a == b == os.path.join(REPO, '.ptpu_cache', 'jax')
    ignored = _git('check-ignore', '-q', os.path.join('.ptpu_cache', 'jax'))
    assert ignored.returncode == 0, '.ptpu_cache/ must be git-ignored'


# ---- chip-only entry points -----------------------------------------------
@pytest.mark.parametrize('argv', [
    ['chip_smoke.py'],
    [os.path.join('benchmark', 'fluid', 'fluid_benchmark.py'),
     '--model', 'resnet', '--batch_size', '128', '--device', 'TPU'],
])
def test_chip_entry_points_refuse_the_cpu(argv):
    """Non-zero, within seconds — i.e. before any model is built (a
    ResNet-50 build plus CPU compile takes far longer) — naming the
    backend found, and with no result line."""
    out = subprocess.run([sys.executable] + argv, cwd=REPO, env=_cpu_env(),
                         capture_output=True, text=True, timeout=60)
    assert out.returncode != 0
    assert 'cpu' in out.stderr
    assert '"ok"' not in out.stdout and '"value"' not in out.stdout


# ---- generated binaries ---------------------------------------------------
def test_no_shared_object_is_committed():
    files = _git('ls-files')
    assert files.returncode == 0, files.stderr
    assert [f for f in files.stdout.split() if f.endswith('.so')] == []
