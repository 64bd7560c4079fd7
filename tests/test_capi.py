"""Inference C API (native/capi.cc).

Parity: paddle/capi + inference/io.cc — a C-linkage predictor over
save_inference_model output. Two consumers are tested:

1. in-process via ctypes (the embedded API detects the already-running
   interpreter and GIL-attaches), outputs vs the Python Executor path;
2. a REAL compiled C driver binary linking libptpu_capi.so that
   initializes the interpreter itself — proving a from-C++ serving
   process works end to end.
"""
import ctypes
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.native import capi


@pytest.fixture(scope='module')
def saved_model(tmp_path_factory):
    d = str(tmp_path_factory.mktemp('capi_model'))
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 9
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[4], dtype='float32')
        h = fluid.layers.fc(input=x, size=8, act='relu')
        pred = fluid.layers.fc(input=h, size=3, act='softmax')
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    fluid.io.save_inference_model(d, ['x'], [pred], exe,
                                  main_program=main)
    xv = np.random.RandomState(0).randn(5, 4).astype('float32')
    prog2, feeds, fetches = fluid.io.load_inference_model(d, exe)
    want, = exe.run(prog2, feed={feeds[0]: xv}, fetch_list=fetches)
    return d, xv, np.asarray(want)


def test_capi_in_process_matches_python(saved_model):
    model_dir, xv, want = saved_model
    lib = capi.load()
    pred = lib.ptpu_predictor_create(model_dir.encode())
    assert pred, lib.ptpu_last_error().decode()
    try:
        assert lib.ptpu_predictor_num_inputs(pred) == 1
        assert lib.ptpu_predictor_num_outputs(pred) == 1
        buf = ctypes.create_string_buffer(64)
        n = lib.ptpu_predictor_input_name(pred, 0, buf, 64)
        assert n == 1 and buf.value == b'x'

        data = np.ascontiguousarray(xv)
        shape = (ctypes.c_int64 * 2)(*data.shape)
        out = (ctypes.c_float * 64)()
        out_shape = (ctypes.c_int64 * 8)()
        out_ndim = ctypes.c_int()
        count = lib.ptpu_predictor_run_f32(
            pred, b'x',
            data.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            shape, 2, 0, out, 64, out_shape, 8,
            ctypes.byref(out_ndim))
        assert count == want.size, lib.ptpu_last_error().decode()
        assert out_ndim.value == want.ndim
        assert tuple(out_shape[:out_ndim.value]) == want.shape
        got = np.ctypeslib.as_array(out)[:count].reshape(want.shape)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    finally:
        lib.ptpu_predictor_destroy(pred)


_DRIVER_SRC = r'''
#include <stdio.h>
#include <stdint.h>
#include <stdlib.h>

extern void* ptpu_predictor_create(const char*);
extern int ptpu_predictor_num_outputs(void*);
extern int64_t ptpu_predictor_run_f32(void*, const char*, const float*,
                                      const int64_t*, int, int, float*,
                                      int64_t, int64_t*, int, int*);
extern void ptpu_predictor_destroy(void*);
extern const char* ptpu_last_error(void);

int main(int argc, char** argv) {
  void* p = ptpu_predictor_create(argv[1]);
  if (!p) { fprintf(stderr, "create: %s\n", ptpu_last_error());
            return 1; }
  float in[20];
  for (int i = 0; i < 20; ++i) in[i] = (float)(i % 7) * 0.25f - 0.5f;
  int64_t shape[2] = {5, 4};
  float out[64];
  int64_t out_shape[8];
  int out_ndim = 0;
  int64_t n = ptpu_predictor_run_f32(p, NULL, in, shape, 2, 0, out, 64,
                                     out_shape, 8, &out_ndim);
  if (n < 0) { fprintf(stderr, "run: %s\n", ptpu_last_error());
               return 2; }
  printf("COUNT=%lld NDIM=%d\n", (long long)n, out_ndim);
  for (int64_t i = 0; i < n; ++i) printf("%.6f ", out[i]);
  printf("\n");
  ptpu_predictor_destroy(p);
  return 0;
}
'''


def test_capi_from_compiled_c_driver(saved_model, tmp_path):
    """A pure C program (interpreter initialized BY the C API) serves
    the model and matches the Python path."""
    model_dir, _, want = saved_model
    src = tmp_path / 'driver.c'
    src.write_text(_DRIVER_SRC)
    exe_path = str(tmp_path / 'driver')
    lib_dir = os.path.dirname(capi._LIB_PATH)
    pyldflags = subprocess.run(
        ['python3-config', '--ldflags', '--embed'],
        capture_output=True, text=True)
    if pyldflags.returncode != 0:
        pyldflags = subprocess.run(['python3-config', '--ldflags'],
                                   capture_output=True, text=True)
    cc = (['gcc', str(src), '-o', exe_path, '-L' + lib_dir,
           '-lptpu_capi', '-Wl,-rpath,' + lib_dir] +
          pyldflags.stdout.split())
    r = subprocess.run(cc, capture_output=True, text=True)
    assert r.returncode == 0, r.stderr

    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    env['PYTHONPATH'] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))] +
        env.get('PYTHONPATH', '').split(os.pathsep))
    r = subprocess.run([exe_path, model_dir], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, (r.stdout, r.stderr)
    lines = r.stdout.strip().splitlines()
    assert lines[0].startswith('COUNT=%d' % want.size), lines[0]
    got = np.array([float(v) for v in lines[1].split()],
                   dtype='float32')
    # the driver feeds its own fixed input; recompute the expectation
    xin = (np.arange(20) % 7).astype('float32') * 0.25 - 0.5
    exe = fluid.Executor(fluid.CPUPlace())
    prog2, feeds, fetches = fluid.io.load_inference_model(model_dir,
                                                          exe)
    want2, = exe.run(prog2, feed={feeds[0]: xin.reshape(5, 4)},
                     fetch_list=fetches)
    # the driver's embedded interpreter picks this image's default
    # backend (the TPU when visible — serving on-chip from C is the
    # point); MXU default precision rounds f32 matmul inputs to bf16,
    # so compare at the documented TPU-vs-CPU band
    np.testing.assert_allclose(got.reshape(np.asarray(want2).shape),
                               np.asarray(want2), rtol=2e-2, atol=2e-3)
