"""The low-precision control: the reference's matmul and convolution
operands rounded to fp8 at a per-tensor scale, as fp8 training rounds
them (Micikevicius et al., arXiv:2209.05433): e4m3 for the forward
operands, e5m2 for the gradient that flows back into each product. It is
the nearest precision below the bf16 the configurations state and the
step that would tempt a later PR. Used by the control runs and their
test, never by a benchmark run.
"""
import jax
import jax.numpy as jnp


def _round(x, dtype):
    """Round to ``dtype`` at a per-tensor scale to its range, and back."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(x.dtype) * scale


def fq8(x):
    """A forward operand in float8_e4m3fn; the backward pass sees the
    rounded value through a straight-through estimate."""
    return x + jax.lax.stop_gradient(_round(x, jnp.float8_e4m3fn) - x)


@jax.custom_vjp
def fq8_grad(y):
    """A product's output, unchanged; the gradient that comes back to it
    is rounded to float8_e5m2 before the backward products use it."""
    return y


fq8_grad.defvjp(lambda y: (y, None),
                lambda _, g: (_round(g, jnp.float8_e5m2),))
