"""Automatic mixed precision for the MXU path.

The reference's fp16 story is per-kernel CUDA half support
(paddle/fluid/operators/*_op.cu float16 registrations); the TPU-native
equivalent is bf16 compute on the MXU with f32 accumulation and f32
master weights: matmul/conv kernels cast their operands to bfloat16 and
request ``preferred_element_type=float32``, so XLA emits bf16 MXU ops
with f32 accumulators. Gradients flow through the casts and arrive f32;
optimizer state stays f32 throughout.

Enabled by default on the chip (``places.on_tpu()``), off elsewhere
(CPU tests compare against f64-ish numpy references). Override with
PADDLE_TPU_AMP=0/1.
"""
import os

_STATE = {'mode': None}


def amp_enabled():
    if _STATE['mode'] is None:
        env = os.environ.get('PADDLE_TPU_AMP', 'auto').lower()
        if env in ('auto', ''):
            from .places import on_tpu
            _STATE['mode'] = on_tpu()
        else:
            _STATE['mode'] = env not in ('0', 'off', 'false', 'no')
    return _STATE['mode']


def set_amp(on):
    """Force AMP on/off (None -> re-derive from env/backend)."""
    _STATE['mode'] = on


def conv_layout():
    """'NCHW' (default, reference layout) or 'NHWC'. On TPU the vector
    lane dim wants channels minor; set PADDLE_TPU_CONV_LAYOUT=NHWC to
    run convs channels-last (the kernel transposes at op boundaries and
    XLA cancels the transposes between adjacent convs)."""
    mode = _STATE.get('conv_layout')
    if mode is None:
        mode = os.environ.get('PADDLE_TPU_CONV_LAYOUT', 'NCHW').upper()
        _STATE['conv_layout'] = mode if mode in ('NCHW', 'NHWC') \
            else 'NCHW'
    return _STATE['conv_layout']


def set_conv_layout(layout):
    if layout is None:
        _STATE['conv_layout'] = None
        return
    layout = layout.upper()
    if layout not in ('NCHW', 'NHWC'):
        raise ValueError("conv layout must be NCHW or NHWC, got %r"
                         % layout)
    _STATE['conv_layout'] = layout


def act_bf16():
    """True when activations FLOW in bf16 between ops (AMP v2, default
    under AMP). The r2 design cast every MXU output back to f32, so each
    activation lived in HBM at 4 bytes and BN/relu did f32 traffic; on
    v5e-class chips (197 bf16 TFLOP/s vs 819 GB/s -> ~240 flops/byte to
    be compute-bound) ResNet-shaped training is HBM-bound, and halving
    activation bytes is the single biggest lever (measured r3: 69 ->
    ~50 ms/step). f32 master weights, f32 BN/moving stats, f32 losses
    and optimizer state are unchanged. PADDLE_TPU_AMP_ACT=f32 restores
    the r2 behavior.

    The rule, stated here once. Ops that RETURN TO THE INPUT DTYPE, so a
    bf16 activation stays bf16 through them whatever float32 math they
    do inside: ``mxu_compute`` (mul, matmul, conv2d: bf16 out),
    ``batch_norm`` and ``layer_norm`` (f32 statistics and affine, ops/
    nn_ops.py), ``flash_attention`` (through ``mxu_compute``), and the
    binary ``elementwise_*`` ops where a bf16 X meets an f32 Y that
    broadcasts (not of X's own shape: an fc's bias, a per-channel
    scale; ops/math_ops.py::_amp_flow). Unary activations keep their
    input's dtype by themselves. What KEEPS A STREAM FLOAT32 is an f32
    operand of the stream's own shape: an f32 X stays f32 whatever Y
    is, and a bf16 X against an f32 Y shaped like itself widens, which
    is the residual stream (x + branch(x), x float32 from the
    embedding). The rule reads the two operands' dtypes and shapes and
    nothing else (not their element counts: a batch of one, [1, H]
    against a bias [H], must not widen where a batch of two does not);
    ``compiler.passes.amp_elementwise_counts()`` says how often each
    side of it was taken.

    What STAYS FLOAT32 inside the hybrid blocks' kernels (ops/
    hybrid_ops.py), whatever the stream's dtype: ``rms_norm``'s
    statistics (output in the input's dtype, as ``layer_norm``);
    ``router_scores``' logits and scores (``mxu_operand`` inputs, f32
    accumulation AND result: a top-k over scores rounded to bf16 would
    be decided by ties); ``ssd_scan``'s ``dt``, decays, cumulative
    sums and the state carried between chunks (only the operands of
    its four products go through ``mxu_operand``); ``routed_experts``'
    routing weights, its activation (for a gated expert the silu, the
    gate's product with the up projection and both projections'
    float32 outputs), the rows it gathers and scatters (float32 rows
    picked, float32 rows summed back, so their transposes add in
    float32 too) and the sum over experts (the grouped products, two or
    three forward, take ``mxu_operand`` inputs); ``rotary_embedding``'s
    angles, cos, sin and the rotation itself (a bf16 angle at position
    8191 is off by whole turns; output in the input's dtype)."""
    mode = _STATE.get('act')
    if mode is None:
        env = os.environ.get('PADDLE_TPU_AMP_ACT', 'bf16').lower()
        mode = _STATE['act'] = env not in ('f32', 'fp32', 'float32')
    return amp_enabled() and mode


def set_amp_act(on):
    _STATE['act'] = on


def mxu_operand(x):
    """``x`` as an operand of an MXU product with f32 accumulation
    inside a kernel that keeps its other state float32: f32 -> bf16
    under AMP, unchanged otherwise. The product itself asks for
    ``preferred_element_type=float32``."""
    import jax.numpy as jnp
    if amp_enabled() and x.dtype == jnp.float32:
        return x.astype(jnp.bfloat16)
    return x


def mxu_compute(fn, *operands):
    """Run ``fn(*operands)`` on the MXU in bf16 under AMP.

    Operands are cast f32 -> bf16; the result stays bf16 when act_bf16()
    (activations flow at 2 bytes; loss/normalization kernels upcast
    where f32 math matters) or is cast back to f32 otherwise. The TPU
    MXU accumulates partial products in f32 internally regardless of the
    bf16 I/O dtype, and JAX's conv/dot grad rules stay uniform-dtyped
    (mixed-dtype preferred_element_type breaks them).
    """
    import jax.numpy as jnp
    if not amp_enabled():
        return fn(*operands)
    cast = [o.astype(jnp.bfloat16) if o.dtype == jnp.float32 else o
            for o in operands]
    out = fn(*cast)
    if out.dtype == jnp.bfloat16 and not act_bf16():
        return out.astype(jnp.float32)
    return out
