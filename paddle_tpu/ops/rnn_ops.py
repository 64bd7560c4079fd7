"""Recurrent op kernels: dynamic_lstm(p), dynamic_gru, gru_unit, lstm_unit.

Parity: paddle/fluid/operators/{lstm,lstmp,gru,gru_unit,lstm_unit}_op.*.
The reference sorts sequences by length into batches and steps a CPU/CUDA
cell kernel; here each RNN is one ``lax.scan`` over the padded time axis
with a carried mask — XLA fuses the per-step gate math into the recurrent
matmul, and the whole scan lives on-device (no host round trips).

Gate layouts follow the reference exactly:
  lstm   Weight [H, 4H] = {W_ch, W_ih, W_fh, W_oh} — gate chunks are
         (candidate, input, forget, output) (ref lstm_op.cc:125,
         lstm_kernel.h: state = in*ig + prev*fg). Peephole bias [1, 7H] =
         [b_c b_i b_f b_o | W_ic W_fc W_oc]. candidate_activation acts on
         the candidate chunk; cell_activation on the cell state feeding
         the output (ref lstm_compute.cc active_node/active_state).
  gru    Weight [H, 3H] = {W_uh W_rh | W_ch}; h = (1-u)*h_prev + u*c
         (ref gru_kernel.h:62: out = prev - u*prev + u*c).
  lstm_unit  X chunks are (i, f, o, g) (ref lstm_unit_op.h:63-67).
"""
import jax
import jax.numpy as jnp

from ..core.registry import register_kernel
from ..lod import SequenceTensor
from .common import unwrap
from .sequence_ops import masked_reverse

_ACT = {
    'sigmoid': jax.nn.sigmoid,
    'tanh': jnp.tanh,
    'relu': jax.nn.relu,
    'identity': lambda x: x,
    None: lambda x: x,
}


def _projected(st, w):
    """The pre-projected input [B, T, nH] in the dtype the recurrence
    computes in, its weights': a bf16 projection (an fc under AMP's bf16
    activation flow against f32 master weights) widens once here, so the
    scan's carry and its update have one dtype."""
    x = jnp.asarray(st.data)
    return x.astype(jnp.result_type(x.dtype, w.dtype))


def _mask_t(lengths, T, dtype):
    """[T, B, 1] time-major step mask."""
    return (jnp.arange(T)[:, None] <
            jnp.asarray(lengths)[None, :]).astype(dtype)[..., None]


def _lstm_scan(x, lengths, w, b, h0, c0, use_peep, gact, cact, candact,
               proj=None, pact=None):
    """Shared lstm/lstmp scan. x: [B, T, 4H] pre-projected inputs.
    Returns (recurrent_out [B,T,R], cell [B,T,H])."""
    H = w.shape[1] // 4
    gate_b = b[:, :4 * H]
    if use_peep:
        w_ic, w_fc, w_oc = (b[0, 4 * H:5 * H], b[0, 5 * H:6 * H],
                            b[0, 6 * H:7 * H])
    B, T = x.shape[0], x.shape[1]
    xt = jnp.swapaxes(x, 0, 1) + gate_b           # [T, B, 4H]
    mask = _mask_t(lengths, T, x.dtype)

    # Default-activation, non-peephole, non-projected cells take the fused
    # Pallas kernel (ops/pallas_kernels.py): recurrent matmul + all gates
    # in one kernel launch per step.
    fused_ok = (not use_peep and proj is None
                and gact is jax.nn.sigmoid and cact is jnp.tanh
                and candact is jnp.tanh)

    def step(carry, inp):
        r_prev, c_prev = carry
        xg, m = inp
        if fused_ok:
            from .pallas_kernels import fused_lstm_cell
            h, c = fused_lstm_cell(xg, r_prev, c_prev, w)
            r = h
        else:
            g = xg + r_prev @ w
            gc, gi, gf, go = jnp.split(g, 4, axis=-1)  # (c, i, f, o)
            if use_peep:
                gi = gi + c_prev * w_ic
                gf = gf + c_prev * w_fc
            i = gact(gi)
            f = gact(gf)
            c = candact(gc) * i + c_prev * f
            if use_peep:
                go = go + c * w_oc
            o = gact(go)
            h = o * cact(c)
            r = pact(h @ proj) if proj is not None else h
        r = m * r + (1 - m) * r_prev
        c = m * c + (1 - m) * c_prev
        return (r, c), (r, c)

    (_, _), (rs, cs) = jax.lax.scan(step, (h0, c0), (xt, mask))
    return jnp.swapaxes(rs, 0, 1), jnp.swapaxes(cs, 0, 1)


@register_kernel('dynamic_lstm')
def _dynamic_lstm(ctx):
    st = ctx.input('Input')
    if not isinstance(st, SequenceTensor):
        raise TypeError("dynamic_lstm needs a SequenceTensor input")
    w = jnp.asarray(unwrap(ctx.input('Weight')))  # [H, 4H]
    x = _projected(st, w)                         # [B, T, 4H]
    b = jnp.asarray(unwrap(ctx.input('Bias')))    # [1, 4H] or [1, 7H]
    H = w.shape[0]
    use_peep = bool(ctx.attr('use_peepholes', True)) and b.shape[-1] == 7 * H
    is_rev = bool(ctx.attr('is_reverse', False))
    gact = _ACT[ctx.attr('gate_activation', 'sigmoid')]
    cact = _ACT[ctx.attr('cell_activation', 'tanh')]
    candact = _ACT[ctx.attr('candidate_activation', 'tanh')]

    if is_rev:
        x = masked_reverse(x, st.lengths)
    B = x.shape[0]
    h0 = jnp.asarray(unwrap(ctx.input('H0'))) if ctx.has_input('H0') \
        else jnp.zeros((B, H), x.dtype)
    c0 = jnp.asarray(unwrap(ctx.input('C0'))) if ctx.has_input('C0') \
        else jnp.zeros((B, H), x.dtype)
    hs, cs = _lstm_scan(x, st.lengths, w, b, h0, c0, use_peep, gact, cact,
                        candact)
    if is_rev:
        hs = masked_reverse(hs, st.lengths)
        cs = masked_reverse(cs, st.lengths)
    ctx.set_output('Hidden', SequenceTensor(hs, st.lengths))
    ctx.set_output('Cell', SequenceTensor(cs, st.lengths))
    if ctx.output_names('BatchGate'):
        ctx.set_output('BatchGate', jnp.zeros((1,), x.dtype))
    if ctx.output_names('BatchCellPreAct'):
        ctx.set_output('BatchCellPreAct', jnp.zeros((1,), x.dtype))


@register_kernel('dynamic_lstmp')
def _dynamic_lstmp(ctx):
    st = ctx.input('Input')
    w = jnp.asarray(unwrap(ctx.input('Weight')))      # [P, 4H]
    x = _projected(st, w)                             # [B, T, 4H]
    wp = jnp.asarray(unwrap(ctx.input('ProjWeight')))  # [H, P]
    b = jnp.asarray(unwrap(ctx.input('Bias')))
    H, P = wp.shape
    use_peep = bool(ctx.attr('use_peepholes', True)) and b.shape[-1] == 7 * H
    is_rev = bool(ctx.attr('is_reverse', False))
    gact = _ACT[ctx.attr('gate_activation', 'sigmoid')]
    cact = _ACT[ctx.attr('cell_activation', 'tanh')]
    candact = _ACT[ctx.attr('candidate_activation', 'tanh')]
    pact = _ACT[ctx.attr('proj_activation', 'tanh')]

    if is_rev:
        x = masked_reverse(x, st.lengths)
    B = x.shape[0]
    r0 = jnp.asarray(unwrap(ctx.input('H0'))) if ctx.has_input('H0') \
        else jnp.zeros((B, P), x.dtype)
    c0 = jnp.asarray(unwrap(ctx.input('C0'))) if ctx.has_input('C0') \
        else jnp.zeros((B, H), x.dtype)
    rs, cs = _lstm_scan(x, st.lengths, w, b, r0, c0, use_peep, gact, cact,
                        candact, proj=wp, pact=pact)
    if is_rev:
        rs = masked_reverse(rs, st.lengths)
        cs = masked_reverse(cs, st.lengths)
    ctx.set_output('Projection', SequenceTensor(rs, st.lengths))
    ctx.set_output('Cell', SequenceTensor(cs, st.lengths))


@register_kernel('dynamic_gru')
def _dynamic_gru(ctx):
    st = ctx.input('Input')
    w = jnp.asarray(unwrap(ctx.input('Weight')))  # [H, 3H]
    x = _projected(st, w)                         # [B, T, 3H]
    b = jnp.asarray(unwrap(ctx.input('Bias'))) if ctx.has_input('Bias') \
        else 0.0
    H = w.shape[0]
    is_rev = bool(ctx.attr('is_reverse', False))
    gact = _ACT[ctx.attr('gate_activation', 'sigmoid')]
    cact = _ACT[ctx.attr('activation', 'tanh')]
    w_g, w_c = _gru_weight_chunks(w, H)

    if is_rev:
        x = masked_reverse(x, st.lengths)
    B, T = x.shape[0], x.shape[1]
    xt = jnp.swapaxes(x, 0, 1) + b                # [T, B, 3H]
    mask = _mask_t(st.lengths, T, x.dtype)
    h0 = jnp.asarray(unwrap(ctx.input('H0'))) if ctx.has_input('H0') \
        else jnp.zeros((B, H), x.dtype)

    def step(h_prev, inp):
        xg, m = inp
        g = gact(xg[:, :2 * H] + h_prev @ w_g)
        u, r = g[:, :H], g[:, H:]
        c = cact(xg[:, 2 * H:] + (r * h_prev) @ w_c)
        h = (1 - u) * h_prev + u * c   # ref gru_kernel.h:62
        h = m * h + (1 - m) * h_prev
        return h, h

    _, hs = jax.lax.scan(step, h0, (xt, mask))
    hs = jnp.swapaxes(hs, 0, 1)
    if is_rev:
        hs = masked_reverse(hs, st.lengths)
    ctx.set_output('Hidden', SequenceTensor(hs, st.lengths))


def _gru_weight_chunks(w, H):
    """Reference gru weight layout (gru_op.h / gru_unit_op.h, mirrored
    by the unittests' w.flatten() chunking): the [H, 3H] parameter is
    a CONTIGUOUS [H, 2H] update/reset block followed by an [H, H]
    candidate block — not column slices."""
    flat = w.reshape(-1)
    return (flat[:2 * H * H].reshape(H, 2 * H),
            flat[2 * H * H:].reshape(H, H))


@register_kernel('gru_unit')
def _gru_unit(ctx):
    x = jnp.asarray(unwrap(ctx.input('Input')))        # [B, 3H]
    h_prev = jnp.asarray(unwrap(ctx.input('HiddenPrev')))
    w = jnp.asarray(unwrap(ctx.input('Weight')))       # [H, 3H]
    H = w.shape[0]
    b = jnp.asarray(unwrap(ctx.input('Bias'))) if ctx.has_input('Bias') \
        else 0.0
    gact = _ACT[ctx.attr('gate_activation', 'sigmoid')]
    cact = _ACT[ctx.attr('activation', 'tanh')]
    w_ur, w_cand = _gru_weight_chunks(w, H)
    xg = x + b
    g = gact(xg[:, :2 * H] + h_prev @ w_ur)
    u, r = g[:, :H], g[:, H:]
    rhp = r * h_prev
    c = cact(xg[:, 2 * H:] + rhp @ w_cand)
    h = (1 - u) * h_prev + u * c   # ref gru_unit_op.h: u*(c-h_p)+h_p
    ctx.set_output('Gate', jnp.concatenate([u, r, c], axis=-1))
    ctx.set_output('ResetHiddenPrev', rhp)
    ctx.set_output('Hidden', h)


@register_kernel('lstm_unit')
def _lstm_unit(ctx):
    """Single LSTM step. X = fc([x_t, h_prev]) [B, 4H]; gate chunks
    (i, f, o, g) per ref lstm_unit_op.h:63-67; forget_bias added to f."""
    x = jnp.asarray(unwrap(ctx.input('X')))
    c_prev = jnp.asarray(unwrap(ctx.input('C_prev')))
    fb = float(ctx.attr('forget_bias', 0.0))
    gi, gf, go, gg = jnp.split(x, 4, axis=-1)
    i = jax.nn.sigmoid(gi)
    f = jax.nn.sigmoid(gf + fb)
    c = f * c_prev + i * jnp.tanh(gg)
    o = jax.nn.sigmoid(go)
    h = o * jnp.tanh(c)
    ctx.set_output('C', c)
    ctx.set_output('H', h)
