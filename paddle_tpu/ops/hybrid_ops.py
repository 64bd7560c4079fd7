"""Kernels of hybrid state-space / mixture-of-experts blocks: RMS
normalisation, a causal depthwise conv1d, the chunked Mamba-2 scan,
router scores and dropless routed experts over the experts held here.

paddle_tpu-native additions (the reference has none of them); gradients
come from the lowering's ``value_and_grad`` as for every op. What each
keeps in float32 under AMP is stated once, in core/amp.py::act_bf16.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from .. import observability as _obs
from ..core.amp import mxu_operand
from ..core.registry import register_kernel
from .common import unwrap, f32
from .math_ops import _prod


# ---- RMS normalisation ------------------------------------------------------
@register_kernel('rms_norm')
def _rms_norm(ctx):
    """y = x / sqrt(mean(x^2) + eps) * scale over the dims from
    ``begin_norm_axis``; with ``group_size`` each run of that many
    channels is normalised apart (a gated norm split over tensor-
    parallel groups). Statistics in f32, output in the input's dtype, as
    layer_norm."""
    x_in = unwrap(ctx.input('X'))
    begin = ctx.attr('begin_norm_axis', 1)
    eps = ctx.attr('epsilon', 1e-5)
    x = f32(x_in)
    lead, d = x.shape[:begin], _prod(x.shape[begin:])
    group = int(ctx.attr('group_size', 0) or 0) or d
    xg = x.reshape(lead + (d // group, group))
    y = xg * lax.rsqrt(jnp.mean(jnp.square(xg), axis=-1, keepdims=True)
                       + eps)
    y = y.reshape(lead + (d,)) * unwrap(ctx.input('Scale')).reshape((d,))
    ctx.set_output('Y', y.reshape(x.shape).astype(x_in.dtype))


# ---- causal depthwise conv1d ------------------------------------------------
@register_kernel('causal_conv1d')
def _causal_conv1d(ctx):
    """y[t, c] = sum_k w[c, k] x[t - (K-1) + k, c] + b[c] on [B, T, C]
    (zeros before t = 0), then ``act`` ('silu' or none). K shifted
    multiply-adds that XLA fuses; f32 inside, the input's dtype out."""
    x_in = unwrap(ctx.input('X'))
    w = unwrap(ctx.input('Filter'))
    x = f32(x_in)
    T, K = x.shape[1], w.shape[1]
    pad = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(pad[:, k:k + T, :] * w[:, k] for k in range(K))
    if ctx.has_input('Bias'):
        y = y + unwrap(ctx.input('Bias'))
    act = ctx.attr('act', None)
    if act == 'silu':
        y = y * jax.nn.sigmoid(y)
    elif act:
        raise ValueError('causal_conv1d: unknown act %r' % (act,))
    ctx.set_output('Out', y.astype(x_in.dtype))


# ---- the Mamba-2 scan, chunked (state-space duality) ------------------------
def ssd_chunked(x, dt, a_log, b, c, chunk):
    """The selective scan S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,
    y_t = S_t C_t of Mamba-2 (Dao & Gu, arXiv:2405.21060), computed in
    chunks of ``chunk`` positions: inside a chunk one masked
    [chunk, chunk] product a head, between chunks a carried state.

    x [B, T, H, P]; dt [B, T, H] (after softplus); a_log [H] (A =
    -exp(a_log)); b, c [B, T, G, N], head h reads group h // (H // G).
    Returns y [B, T, H, P] in float32. dt, the decays, their cumulative
    sums and the carried state are float32; only the operands of the
    four products go through ``mxu_operand``. T need not be a multiple
    of ``chunk``: the tail is padded with dt = 0, which neither decays
    nor feeds the state."""
    Bsz, T, H, P = x.shape
    G, N = b.shape[2:]
    r = H // G
    Q = int(chunk)
    pad = (-T) % Q
    if pad:
        def grow(t):
            return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        x, dt, b, c = grow(x), grow(dt), grow(b), grow(c)
    nc = (T + pad) // Q
    dt = dt.astype(jnp.float32)
    a = dt * -jnp.exp(a_log.astype(jnp.float32))          # log decay a step
    # heads before positions: the [Q, Q] planes lie on sublanes x lanes
    dtc = dt.reshape(Bsz, nc, Q, G, r).transpose(0, 1, 3, 4, 2)
    cum = jnp.cumsum(a.reshape(Bsz, nc, Q, G, r).transpose(0, 1, 3, 4, 2),
                     axis=-1)                             # [B, nc, G, r, Q]
    xc = x.reshape(Bsz, nc, Q, G, r, P)
    bc = mxu_operand(b.reshape(Bsz, nc, Q, G, N))
    cc = mxu_operand(c.reshape(Bsz, nc, Q, G, N))

    def dot(spec, u, v):
        return jnp.einsum(spec, u, v, preferred_element_type=jnp.float32)

    # inside a chunk: y_i += sum_{j<=i} (C_i.B_j) exp(cum_i - cum_j) dt_j x_j
    keep = jnp.tril(jnp.ones((Q, Q), bool))
    seg = cum[..., :, None] - cum[..., None, :]
    decay = jnp.where(keep, jnp.exp(jnp.where(keep, seg, 0.0)), 0.0)
    cb = dot('bcign,bcjgn->bcgij', cc, bc)                # [B, nc, G, Q, Q]
    m = cb[:, :, :, None] * decay * dtc[..., None, :]     # [B, nc, G, r, Q, Q]
    y = dot('bcgrij,bcjgrp->bcigrp', mxu_operand(m), mxu_operand(xc))
    # what a chunk leaves behind: sum_j exp(cum_end - cum_j) dt_j x_j (x) B_j
    left = jnp.exp(cum[..., -1:] - cum) * dtc             # [B, nc, G, r, Q]
    xw = xc.astype(jnp.float32) * left.transpose(0, 1, 4, 2, 3)[..., None]
    s_chunk = dot('bcjgrp,bcjgn->bcgrpn', mxu_operand(xw), bc)
    # between chunks: the carried state, float32
    through = jnp.exp(cum[..., -1])                       # [B, nc, G, r]

    def carry(s, inp):
        dec, add = inp
        return dec[..., None, None] * s + add, s

    _, entering = lax.scan(
        carry, jnp.zeros((Bsz, G, r, P, N), jnp.float32),
        (through.transpose(1, 0, 2, 3), s_chunk.transpose(1, 0, 2, 3, 4, 5)))
    entering = entering.transpose(1, 0, 2, 3, 4, 5)       # [B, nc, G, r, P, N]
    y_in = dot('bcign,bcgrpn->bcigrp', cc, mxu_operand(entering))
    y = y + y_in * jnp.exp(cum).transpose(0, 1, 4, 2, 3)[..., None]
    return y.reshape(Bsz, T + pad, H, P)[:, :T]


@register_kernel('ssd_scan')
def _ssd_scan(ctx):
    """The mixer's scan: dt = softplus(Dt + DtBias), y = scan(x, dt, A, B,
    C) + D x. X [B, T, H*P], Dt [B, T, H], B / C [B, T, G*N]; Out in X's
    dtype. Each lowering counts once in ``ssd_lowerings_total{route=,
    chunk=}`` (compiler/passes.py::ssd_counts)."""
    x_in = unwrap(ctx.input('X'))
    H, P = int(ctx.attr('num_heads')), int(ctx.attr('head_dim'))
    G, N = int(ctx.attr('n_groups', 1)), int(ctx.attr('state_size'))
    chunk = int(ctx.attr('chunk_size', 128))
    Bsz, T = x_in.shape[:2]
    x = x_in.reshape(Bsz, T, H, P)
    dt = jax.nn.softplus(
        unwrap(ctx.input('Dt')).astype(jnp.float32)
        + unwrap(ctx.input('DtBias')).astype(jnp.float32))
    b = unwrap(ctx.input('B')).reshape(Bsz, T, G, N)
    c = unwrap(ctx.input('C')).reshape(Bsz, T, G, N)
    _obs.default_registry().counter(
        'ssd_lowerings_total',
        help='ssd_scan op lowerings, by the route taken (xla: the '
             'chunked state-space-duality form in jax.numpy) and the '
             'chunk length',
        route='xla', chunk=str(chunk)).inc()
    y = ssd_chunked(x, dt, unwrap(ctx.input('ALog')), b, c, chunk)
    y = y + unwrap(ctx.input('D')).astype(jnp.float32)[:, None] * f32(x)
    ctx.set_output('Out', y.reshape(x_in.shape).astype(x_in.dtype))


# ---- router scores ----------------------------------------------------------
@register_kernel('router_scores')
def _router_scores(ctx):
    """sigmoid(x W) with float32 logits: the operands take one bf16 MXU
    pass under AMP, accumulation and result stay float32 whatever the
    stream's dtype, because the expert choice is a top-k over them."""
    x = mxu_operand(f32(unwrap(ctx.input('X'))))
    w = mxu_operand(unwrap(ctx.input('W')))
    ctx.set_output('Out', jax.nn.sigmoid(
        jnp.matmul(x, w, preferred_element_type=jnp.float32)))


# ---- rotary positions ---------------------------------------------------------
def rotary(x, head_dim, base):
    """Rotary position embedding of x [B, T, H * head_dim] at positions
    0..T-1, all ``head_dim`` dimensions of every head, the half-split
    (rotate_half) pairing: dimension i < head_dim / 2 turns with
    dimension i + head_dim / 2 by the angle t * base^(-2 i / head_dim).
    Angles, cos and sin and the rotation are float32 whatever x is (a
    bf16 angle at position 8191 is off by whole turns); returns
    float32."""
    B, T, D = x.shape
    half = head_dim // 2
    inv = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) * 2.0
                          / head_dim))
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    xh = f32(x).reshape(B, T, D // head_dim, 2, half)
    x1, x2 = xh[..., 0, :], xh[..., 1, :]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-2)
    return out.reshape(B, T, D)


@register_kernel('rotary_embedding')
def _rotary_embedding(ctx):
    """Out = rotary(X) (above) in X's dtype; X [B, T, heads * head_dim].
    No parameter. Each lowering counts once in
    ``rotary_lowerings_total{dim=, dtype=}`` (compiler/passes.py::
    rotary_counts): the head size turned and the stream's dtype."""
    x_in = unwrap(ctx.input('X'))
    head_dim = int(ctx.attr('head_dim'))
    _obs.default_registry().counter(
        'rotary_lowerings_total',
        help='rotary_embedding op lowerings, by the head size turned and '
             'the dtype of the stream (the angles are float32 in all)',
        dim=str(head_dim), dtype=x_in.dtype.name).inc()
    out = rotary(x_in, head_dim, float(ctx.attr('base', 10000.0)))
    ctx.set_output('Out', out.astype(x_in.dtype))


# ---- routed experts, dropless, over the experts held ------------------------
def route_held(scores, bias, top_k, first, count, scale):
    """Route [N, E] float32 scores over all E experts; for the ``count``
    experts from ``first`` return ``chosen`` [count, N] (bool) and the
    routing weight ``weight`` [count, N] (0 where not chosen): the top
    ``top_k`` of scores + bias choose, the scores themselves weigh,
    normalised over the chosen and scaled. The chosen experts become a
    [N, E] mask by comparison (a gather of the chosen scores, and the
    scatter that is its transpose, cost a millisecond each on the TPU);
    a held expert's score is a static slice."""
    E = scores.shape[1]
    _, idx = lax.top_k(scores + bias, top_k)
    mask = jnp.any(idx[:, :, None] == jnp.arange(E)[None, None, :], axis=1)
    total = jnp.sum(jnp.where(mask, scores, 0.0), axis=1, keepdims=True)
    held = mask[:, first:first + count]
    weight = jnp.where(held, scores[:, first:first + count], 0.0) \
        / (total + 1e-20) * scale
    return held.T, weight.T


def _expert_hidden(xs, ws, sizes, act):
    """A held expert's hidden rows, float32: relu(x W1)^2 ('relu2': ws =
    (W1, W2)) or silu(x W_gate) * (x W_up) ('swiglu': ws = (W_gate,
    W_up, W_down)); the activation and the gate's product are float32
    on the float32 outputs of the grouped products."""
    from .pallas_kernels import grouped_matmul
    h = grouped_matmul(xs, mxu_operand(ws[0]), sizes)
    if act == 'relu2':
        return jnp.square(jnp.maximum(h, 0.0))
    return h * jax.nn.sigmoid(h) * grouped_matmul(xs, mxu_operand(ws[1]),
                                                  sizes)


def pairs_in_row_order(place, chunk):
    """The (expert, token) pairs, as flat indices e * N + n into [held,
    N], in the order of their rows (``place``; -1: not chosen, sorted
    behind every row), padded so that any chunk of ``chunk`` rows that
    starts inside the routed pairs can be sliced out. One sort of
    held * N keys an op (0.24 ms for 131 k on a v5e chip)."""
    held, n = place.shape
    flat = place.reshape(-1)
    key = jnp.where(flat < 0, jnp.int32(held * n), flat)
    _, order = lax.sort((key, jnp.arange(held * n, dtype=jnp.int32)),
                        num_keys=1)
    return jnp.pad(order, (0, chunk))


def _place_rows(u, weight, order, counts, rows):
    """(the tokens of ``rows`` picked from u [N, L], their routing
    weights, the function that sums rows back to their tokens), through
    the pairs in row order: a row gather, a gather of scalars and a
    sum of rows into their tokens, each linear in rows. Float32 rows
    both ways, so that the transposes (a sum of the rows' gradients
    into their tokens, a gather of the output's) add in float32 too. A
    row past the routed pairs reads a zero row and adds to nothing.

    The sum back, in the forward and as the pick's transpose, takes one
    of two routes (pallas_kernels.row_sum_plan is the rule): on the TPU,
    with the width L in whole 128-lane tiles, the Pallas kernel that
    adds only the chunk's live rows into a VMEM-resident column block
    of the result, a token's rows in row (expert) order
    (pallas_kernels.row_sum, row_pick); anywhere else (the CPU, odd
    widths) XLA's scatter-add and autodiff's transposes of it and of
    the gather. On the kernel's route the sum's transpose, a gather of
    float32 rows, takes the Pallas kernel that copies only the live
    rows from a VMEM-resident column block of the tokens where L is at
    least 2048 lanes (pallas_kernels.row_gather_plan), ``jnp.take``
    where it is narrower; the pick stays ``jnp.take``, which XLA turns
    into a gather of the bf16 copy of u the products read (rounding
    commutes with the pick), faster than the kernel at both shapes the
    benchmark runs (PERF.md section 6, PR 42).

    Why not a 0/1 [chunk, N] matrix on the MXU, which moves rows
    without a gather: its cost is the product of rows and tokens.
    Measured on one v5e chip (PERF.md section 6, PR 33; the op alone,
    forward / forward and backward, ms): 16,640 rows of 8192 tokens,
    2048 wide, gated experts 13.56 / 21.87 by the matrix (537 MB of
    float32 comparisons to make it, a 268 MB operand, 2.95 to pick bf16
    rows, 6.42 to sum float32 rows back in two bf16 halves) and 5.12 /
    11.40 this way (the gather 0.23-0.34, XLA's scatter-add 1.59); 3072
    rows of 4096 tokens, 1024 wide 1.76 / 2.83 and 1.52 / 2.47: this
    way is faster at both shapes the benchmark runs."""
    from .pallas_kernels import (row_gather_plan, row_pick, row_sum,
                                 row_sum_plan)
    n, chunk = u.shape[0], rows.shape[0]
    ids = lax.dynamic_slice(order, (rows[0],), (chunk,))
    pairs = jnp.sum(counts)
    live = rows < pairs
    tok = jnp.where(live, ids % n, n)
    row_w = jnp.where(live, jnp.take(weight.reshape(-1),
                                     jnp.where(live, ids, 0)), 0.0)
    f32_rows = jax.ShapeDtypeStruct((chunk, u.shape[1]), jnp.float32)
    plan = row_sum_plan(f32_rows, n)
    if plan is None:
        xs = jnp.take(f32(u), tok, axis=0, mode='fill', fill_value=0)

        def back(y):
            return jnp.zeros((n, y.shape[1]), jnp.float32).at[tok].add(
                y, mode='drop')
    else:
        gather = row_gather_plan(f32_rows, n)
        n_live = jnp.clip(pairs - rows[0], 0, chunk)
        xs = row_pick(f32(u), tok, n_live, n, plan, gather)

        def back(y):
            return row_sum(y, tok, n_live, n, plan, gather)
    return xs.astype(mxu_operand(u).dtype), row_w, back


def _expert_chunk(u, ws, weight, order, counts, chunk, c, act):
    """The held experts' part for rows ``c * chunk .. (c + 1) * chunk``
    of the expert-sorted list of (token, expert) pairs: ``weight``
    [held, N] is a pair's routing weight, ``order`` the pairs in row
    order (pairs_in_row_order). Pick the rows' tokens (_place_rows),
    the grouped products with the activation between (_expert_hidden;
    pallas_kernels.grouped_matmul: the Pallas kernels or
    ``lax.ragged_dot``, one layout for both), weigh, sum back to
    [N, L] float32.

    The rows past the routed pairs are zero rows that belong to no
    expert (``sizes`` sums to the pairs this chunk holds): grouped_matmul
    gives them an exactly zero product and gradient on either route, and
    on the Pallas route spends a grid step without an MXU pass on a row
    tile of them, so a chunk's products cost what the routing sends it:
    the first chunk of a balanced step about half its row tiles, the
    last chunk of a skewed one what it holds. The placement and the
    float32 work between the products still move a chunk's rows,
    whoever they belong to."""
    from .pallas_kernels import grouped_matmul
    lo = c * chunk
    rows = lo + jnp.arange(chunk, dtype=jnp.int32)
    ends = jnp.cumsum(counts)
    sizes = jnp.clip(ends, lo, lo + chunk) \
        - jnp.clip(ends - counts, lo, lo + chunk)
    xs, row_w, back = _place_rows(u, weight, order, counts, rows)
    h = _expert_hidden(xs, ws, sizes, act)
    y = grouped_matmul(mxu_operand(h).astype(xs.dtype), mxu_operand(ws[-1]),
                       sizes)
    return back(y * row_w[:, None])


def _chunks(counts, chunk):
    """Chunks of ``chunk`` rows the routed pairs fill (a traced count)."""
    return (jnp.sum(counts) + chunk - 1) // chunk


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _held_experts(u, ws, weight, order, counts, chunk, act):
    """Sum of ``_expert_chunk`` over as many chunks as the routed pairs
    fill: the first always; the others, which only a skewed routing
    fills, in a loop whose trip count is read from ``counts``, behind a
    ``cond`` so that a balanced step does not pay for the loop's
    carried copies. ``ws``: the held experts' stacked matrices."""
    return _held_experts_fwd(u, ws, weight, order, counts, chunk, act)[0]


def _held_experts_fwd(u, ws, weight, order, counts, chunk, act):
    def one(c):
        return lambda *t: _expert_chunk(*t, order, counts, chunk, c, act)

    out, vjp_first = jax.vjp(one(0), u, ws, weight)

    n = _chunks(counts, chunk)

    def rest(out):
        return lax.fori_loop(
            1, n, lambda c, acc: acc + one(c)(u, ws, weight), out)
    out = lax.cond(n > 1, rest, lambda out: out, out)
    return out, (vjp_first, u, ws, weight, order, counts)


def _held_experts_bwd(chunk, act, res, g):
    # the first chunk's products were saved; a further chunk (a skewed
    # routing) is computed again for its gradient, into the same sums.
    # The first chunk's gradient is taken once, before the cond (inside
    # both branches each held its own temporaries: 0.46 GB of the
    # trinity cell's step); the barrier keeps XLA from sinking the
    # optimizer's work on the weight gradients into the branches, where
    # it leaves its fusions (PERF.md section 6)
    vjp_first, u, ws, weight, order, counts = res

    def more(c, grads):
        _, vjp = jax.vjp(
            lambda *t: _expert_chunk(*t, order, counts, chunk, c, act),
            u, ws, weight)
        return jax.tree_util.tree_map(jnp.add, grads, vjp(g))

    n = _chunks(counts, chunk)
    grads = lax.optimization_barrier(lax.cond(
        n > 1, lambda grads: lax.fori_loop(1, n, more, grads),
        lambda grads: grads, vjp_first(g)))
    return tuple(grads) + (None, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


_ROW_QUANTUM = 256


def expert_chunk_rows(tokens, top_k, held, experts):
    """Rows of (token, held expert) pairs a chunk takes: twice the
    balanced load and no more than any routing can send (a token takes
    a held expert at most once), in whole row quanta either way. The
    Pallas products need no row beyond the pairs (a row tile may
    straddle experts), so a chunk holds as many pairs as it has rows in
    any split."""
    def quanta(n):
        return -(-n // _ROW_QUANTUM) * _ROW_QUANTUM
    return min(quanta(2 * tokens * top_k * held // experts + 1),
               quanta(tokens * min(top_k, held)))


@register_kernel('routed_experts')
def _routed_experts(ctx):
    """Out = sum over the experts e that are chosen AND held of
    w_e f_e(x): f_e = W2_e relu(W1_e x)^2 (attr ``act`` 'relu2') or the
    gated W2_e (silu(W1_e x) * (W3_e x)) ('swiglu': W1 the gate, W3 the
    up projection). X [B, T, L], Scores [B, T, E] float32 over all E
    experts, Bias [E] (added for the choice only), W1 (and W3) [held,
    L, F], W2 [held, F, L] for experts ``first`` .. ``first + held``.

    Dropless: the (token, held expert) pairs are laid out expert by
    expert by counting (a token takes an expert at most once, so a
    cumulative sum over [held, N] places every pair; one sort of those
    places puts the pairs in row order), their rows picked by a row
    gather and summed back into their tokens, both linear in rows
    (_place_rows), multiplied group by group and weighed between. The
    sum back (and the pick's transpose) takes the Pallas kernel that
    adds only a chunk's live rows on the TPU where L is whole 128-lane
    tiles, XLA's scatter-add anywhere else (pallas_kernels.row_sum_plan
    is the rule); on that route the sum's transpose, a row gather,
    takes the kernel that copies only the live rows where L is at least
    2048 lanes (pallas_kernels.row_gather_plan). The grouped
    products take one of two routes, chosen from what the op sees
    (pallas_kernels.grouped_plan): on the TPU, with bf16 operands (AMP)
    and L, F multiples of 128, the Pallas grouped matmul ('pallas');
    anywhere else ``lax.ragged_dot`` ('ragged_dot': the CPU, float32
    without AMP, odd widths). The rows go in chunks of twice the
    balanced load N top_k held / E: one under a balanced routing, as
    many more as a skewed one fills (a loop with a run-time trip count,
    so XLA's static shapes hold any routing). A chunk's rows past the
    routed pairs belong to no expert: the Pallas products pass over
    their row tiles (pallas_kernels.live_row_tiles counts them from
    TokensPerExpert), the row pick and the activation still sweep
    them; the kernels that sum rows back and gather their gradients
    read none of them. TokensPerExpert [held] is the second output.
    What the experts held elsewhere would add is left out. Each
    lowering counts once in ``moe_lowerings_total{experts=, held=,
    top_k=, route=, act=, row_sum=, row_gather=}``
    (compiler/passes.py::moe_counts; ``row_sum`` 'pallas' or 'xla':
    moe_row_sum_counts; ``row_gather`` the same for the sum's transpose,
    pallas_kernels.row_gather_plan the rule: moe_row_gather_counts)."""
    from .pallas_kernels import grouped_plan, row_gather_plan, row_sum_plan
    x_in = unwrap(ctx.input('X'))
    E, K = int(ctx.attr('num_experts')), int(ctx.attr('top_k'))
    first, held = int(ctx.attr('first_expert', 0)), int(ctx.attr('held'))
    L = x_in.shape[-1]
    u = x_in.reshape(-1, L)
    N = u.shape[0]
    scores = unwrap(ctx.input('Scores')).astype(jnp.float32).reshape(N, E)
    bias = unwrap(ctx.input('Bias')).astype(jnp.float32) \
        if ctx.has_input('Bias') else jnp.zeros((E,), jnp.float32)
    chosen, weight = route_held(
        scores, lax.stop_gradient(bias), K, first, held,
        float(ctx.attr('routed_scaling_factor', 1.0)))
    chunk = expert_chunk_rows(N, K, held, E)
    act = ctx.attr('act', 'relu2') or 'relu2'
    if act not in ('relu2', 'swiglu'):
        raise ValueError('routed_experts: unknown act %r' % (act,))
    w1 = unwrap(ctx.input('W1'))
    ws = (w1,) + ((unwrap(ctx.input('W3')),) if act == 'swiglu' else ()) \
        + (unwrap(ctx.input('W2')),)
    plan = grouped_plan(
        jax.ShapeDtypeStruct((chunk, L), mxu_operand(u).dtype),
        jax.ShapeDtypeStruct(w1.shape, mxu_operand(w1).dtype))
    summed = row_sum_plan(jax.ShapeDtypeStruct((chunk, L), jnp.float32), N)
    gathered = row_gather_plan(jax.ShapeDtypeStruct((chunk, L), jnp.float32),
                               N)
    _obs.default_registry().counter(
        'moe_lowerings_total',
        help='routed_experts op lowerings, by the experts routed over, '
             'the experts held here, the experts a token takes, the '
             'grouped product (pallas: the Pallas grouped matmul; '
             'ragged_dot: lax.ragged_dot), the expert\'s activation '
             '(relu2 / swiglu), the sum of rows back into their '
             'tokens (pallas: the live-row kernel; xla: a scatter-add) '
             'and the gather of rows that transposes that sum (pallas: '
             'the live-row kernel; xla: jnp.take)',
        experts=str(E), held=str(held), top_k=str(K),
        route='ragged_dot' if plan is None else 'pallas', act=act,
        row_sum='xla' if summed is None else 'pallas',
        row_gather='xla' if gathered is None else 'pallas').inc()
    counts = jnp.sum(chosen, axis=1, dtype=jnp.int32)       # [held]
    place = (jnp.cumsum(counts) - counts)[:, None] \
        + jnp.cumsum(chosen, axis=1, dtype=jnp.int32) - 1
    order = pairs_in_row_order(jnp.where(chosen, place, -1), chunk)
    out = _held_experts(u, ws, weight, order, counts, chunk, act)
    ctx.set_output('Out', out.reshape(x_in.shape).astype(x_in.dtype))
    ctx.set_output('TokensPerExpert', counts)
