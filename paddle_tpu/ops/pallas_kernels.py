"""Pallas TPU kernels for the hot paths: flash attention, fused LSTM
cell, and fused conv epilogues.

Parity intent: the reference accelerates attention/LSTM with cuDNN and
hand-written CUDA (paddle/fluid/operators/{lstm_op,math/lstm_compute}.*,
scaled_dot_product_attention composed from cuBLAS matmuls). The TPU
equivalents are written in Pallas:

- ``flash_attention``: blockwise online-softmax attention that never
  materialises the [T, T] score matrix; q/k/v blocks stream HBM->VMEM and
  the inner matmuls hit the MXU. The kernels read [B, T, H*dh] as the
  projections write it; grid = (batch, head groups of 128 lanes,
  q-blocks, k-blocks).
- ``fused_lstm_cell``: one kernel for the recurrent matmul + all four gate
  nonlinearities + state update, so per-step HBM traffic is just the
  carried state (XLA would otherwise split matmul and VPU work).

Both carry a pure-jnp fallback (identical math) used off-TPU and for
odd shapes; tests run the Pallas path with ``interpret=True`` on CPU.
"""
import contextlib
import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.places import on_tpu as _on_tpu

_NEG_INF = -1e30


# ---- flash attention ------------------------------------------------------------
def attention_reference_with_lse(q, k, v, causal=True, q_off=0, k_off=0,
                                 window=None):
    """Masked-softmax attention + per-row logsumexp, plain XLA.
    q,k,v: [B, T, H, D] -> (out [B, T, H, D], lse [B, H, T]). The lse
    output is what lets ring attention merge per-block partial results
    exactly (see models/transformer.py::ring_attention). ``window``
    (causal only): a query also drops the keys ``window`` or more
    positions behind it."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = q_off + jnp.arange(q.shape[1])
        kpos = k_off + jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        if window:
            mask = mask & (qpos[:, None] - kpos[None, :] < window)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)     # [B, H, Tq]
    p = jnp.exp(s - lse[..., None]).astype(q.dtype)
    out = jnp.einsum('bhqk,bkhd->bqhd', p, v,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return out, lse


def attention_reference(q, k, v, causal=True, q_off=0, k_off=0, window=None):
    """Canonical masked-softmax attention, plain XLA. q,k,v: [B, T, H, D].

    Single source of truth for the math: the Pallas kernel's parity tests,
    flash_attention's off-TPU fallback, AND the transformer model's
    blockwise/ring path (which passes q_off/k_off for the global positions
    of local blocks) all call this."""
    return attention_reference_with_lse(q, k, v, causal, q_off, k_off,
                                        window)[0]


# exp2-based softmax (VERDICT r4 #4): fold log2(e) into the score
# scale so the VPU evaluates exp2 directly instead of exp's extra
# multiply per element. Saved lse stays NATURAL-log so the
# backward/ring-merge contract is unchanged.
_LOG2E = 1.4426950408889634


def _lane_heads(H, dh):
    """Heads to a program, ``hp``, such that a block of ``hp * dh`` lanes
    of a [B, T, H*dh] array is whole 128-lane tiles: 2 at head size 64,
    1 at a multiple of 128. None for a head shape the addressing cannot
    take (odd H at head size 64, any other head size): flash_plan sends
    those to the XLA reference."""
    if dh % 128 == 0:
        return 1
    if dh == 64 and H % 2 == 0:
        return 2
    return None


def _head_lanes(shape, dh):
    """For a [rows, hp*dh] block, which lanes are head i's, a mask a
    head, made once a tile part and shared by every select of it; None
    where the block is one head."""
    if shape[-1] == dh:
        return None
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return [(lane >= i * dh) & (lane < (i + 1) * dh)
            for i in range(shape[-1] // dh)]


def _only_head(x, heads, i):
    """``x`` [rows, hp*dh] with every lane outside head ``i`` of the
    program's lane block zeroed, so a dot that contracts all the lanes
    sees head ``i`` alone and one that keeps them leaves the other
    heads' lanes 0. No lane is sliced: Mosaic gets a full-width
    select."""
    if heads is None:
        return x
    return jax.lax.select(heads[i], x, jnp.zeros_like(x))


def _by_head(xs, heads, shape):
    """[rows, hp*dh] that takes head i's lanes from ``xs[i]`` (each
    [rows, hp*dh], or [rows, 1] to spread a per-row statistic over its
    head's lanes)."""
    out = jnp.broadcast_to(xs[-1], shape)
    for i in range(len(xs) - 2, -1, -1):
        out = jax.lax.select(heads[i], jnp.broadcast_to(xs[i], shape), out)
    return out


def _row_chunks(block_q, block_k):
    """Row chunks ``r`` a causal tile of square blocks that straddles
    the diagonal (at offset 0 there, a static shape) is cut into: of
    512 rows each, or of the largest 128-row multiple below that which
    divides the block. Chunk j reads only the (j + 1) * block_q / r
    columns at or left of its own diagonal, so the tile issues
    (r + 1) / 2r of a whole tile's dots, exp2s, casts and sums for one
    update of each row's state, as a whole tile makes; and no score
    value is larger than [512, block_k], which is what lets a 2048-row
    block into VMEM. Measured on v5e, bf16 B2 H32 T2048 dh64, ms a
    layer, forward / merged backward (PERF.md section 6, PR 28):
    1024x1024 whole 0.90 / 1.50, r 2 0.90 / 1.26, r 4 0.92 / 1.17, r 8
    0.88 / 1.21; 2048x2048 r 4 0.61 / 1.17, r 8 0.63 / 1.07, r 16
    0.74 / 1.07. The forward wants the larger chunk (a state update of
    every row a chunk); the backward read 9 % faster at r 8, which is
    not taken: every chunk is a body of its own that each process
    traces and lowers before its first step, and the backward's eight
    bodies put warm set-up 9-11 % over the parent's (section 6). 1 is
    the whole tile under a mask over all of it: a block that is one
    such chunk, and block_q != block_k, whose diagonal offset is a
    grid value."""
    if block_q != block_k:
        return 1
    return block_q // math.gcd(block_q, 512)


def _window_chunked(block_q, block_k, window):
    """Whether a window's lower edge falls on tile corners of square
    blocks that go in row chunks: the tiles it cuts are then one kind,
    kb = qi - window / block, of a static shape (the strict upper
    triangle), as the diagonal's are."""
    return block_q == block_k and window % block_q == 0 \
        and _row_chunks(block_q, block_k) > 1


def _tile_parts(qi, kb, block_q, block_k, T, causal, part, window=None):
    """Run ``part(rows, cols, edges)`` over what is live of tile (q
    block qi, k block kb): the q rows ``rows`` against the k rows
    ``cols`` (each a pl.ds of its block), under the mask ``edges`` =
    (diag, low) of _causal_keep ((None, None): no mask). THE one place that
    tells the kinds of tile apart, for the forward and every backward
    kernel: a tile wholly above the diagonal runs nothing (its DMA is
    skipped by the index maps); one wholly at or below it runs whole
    and unmasked, as every tile does without ``causal``; one that
    straddles it runs each row chunk against the columns left of that
    chunk's end, a shape of its own each (all the columns, masked,
    where the tile is one chunk: _row_chunks). Where the ``T``
    positions hold no tile below the diagonal (one block of them, the
    OPT cell's) that body is left out of the kernel: Mosaic unrolls
    what a kernel holds, run or not, and every set-up lowers it.

    With a ``window`` (a query keeps the ``window`` keys up to its own)
    the live tiles are a band: a tile wholly below the band runs
    nothing either (skipped, not masked: the index maps hold the first
    live block through those steps); the tiles the band's lower edge
    cuts are a second kind of partly-masked tile. Where that edge falls
    on tile corners (_window_chunked) such a tile is the strict upper
    triangle and goes in the diagonal's row chunks, each against the
    columns from its own start on; any other window (narrower than a
    block, or not a multiple of it) runs every cut tile whole under
    both edges, whose offsets are grid values."""
    def _full():
        part(pl.ds(0, block_q), pl.ds(0, block_k), (None, None))

    if not causal:
        _full()
        return
    live = kb * block_k <= (qi + 1) * block_q - 1
    full = (kb + 1) * block_k - 1 <= qi * block_q
    r = _row_chunks(block_q, block_k)
    c = block_q // r

    def _diagonal_chunks():
        for j in range(r):
            part(pl.ds(j * c, c), pl.ds(0, (j + 1) * c), (j * c, None))

    if window is None:
        if T - block_q >= block_k:  # a q block starts past a k block's end
            pl.when(full)(_full)

        @pl.when(jnp.logical_and(live, jnp.logical_not(full)))
        def _diagonal():
            if r == 1:
                part(pl.ds(0, block_q), pl.ds(0, block_k),
                     (qi * block_q - kb * block_k, None))
                return
            _diagonal_chunks()
        return

    # inside the band: below the diagonal and above the lower edge
    inside = jnp.logical_and(
        full, kb * block_k >= (qi + 1) * block_q - window)
    if T - block_q >= block_k and window >= block_q + block_k - 1:
        pl.when(inside)(_full)
    if _window_chunked(block_q, block_k, window):
        pl.when(kb == qi)(_diagonal_chunks)

        @pl.when(kb == qi - window // block_q)
        def _lower_edge():
            # row i of the tile keeps the columns past i
            for j in range(r):
                part(pl.ds(j * c, c), pl.ds(j * c, block_k - j * c),
                     (None, 1))
        return
    live = jnp.logical_and(
        live, (kb + 1) * block_k - 1 + window > qi * block_q)

    @pl.when(jnp.logical_and(live, jnp.logical_not(inside)))
    def _edges():
        diag = qi * block_q - kb * block_k
        part(pl.ds(0, block_q), pl.ds(0, block_k),
             (diag, diag - window + 1))


def _causal_keep(shape, diag, low=None):
    """Which scores [rows, cols] the mask keeps, for q rows that start
    ``diag`` positions after the k columns do: row i keeps column j <=
    i + diag (the causal edge; None: no such edge) and column j >= i +
    ``low`` (a window's lower edge, low = diag - window + 1; None: no
    window). None: all of them."""
    if diag is None and low is None:
        return None
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if low is None:
        return row + diag >= col
    if diag is None:
        return col >= row + low
    return jnp.logical_and(row + diag >= col, col >= row + low)


def _masked(s, keep):
    """Scores ``s`` with those outside ``keep`` (_causal_keep) at
    -inf."""
    if keep is None:
        return s
    return jax.lax.select(keep, s, jnp.full_like(s, _NEG_INF))


def _dot(x, y, dims):
    """x @ y contracting ``dims`` = (dim of x, dim of y), at the INPUT
    precision (bf16 inputs -> full-rate MXU), accumulated in float32."""
    return jax.lax.dot_general(x, y, ((dims[:1], dims[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                  acc_scr, *, block_q, block_k, T, causal, dh, window=None):
    """One (batch, head group, q-block, k-block) grid step on blocks
    [block, hp*dh] of [B, T, H*dh] arrays: the ``hp`` heads whose lanes
    fill the block (two at head size 64) are attended one after the
    other, each by zeroing the other's lanes of q and contracting all
    the lanes (_only_head), and share one lane-selected accumulator.

    The k-block index is the innermost grid dim, so Mosaic streams k/v
    blocks HBM->VMEM with automatic double-buffering while the online
    softmax state (m and l per head, acc) persists in VMEM scratch
    across steps. No dynamic_slice on values anywhere — Mosaic can't
    lower it; all block movement is done by the BlockSpec index maps,
    and a diagonal tile's row chunks are static slices of the refs.
    """
    qi = pl.program_id(2)
    kb = pl.program_id(3)
    hp = m_scr.shape[0]
    n_kb = T // block_k

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _part(rows, cols, edges):
        # the dots take their operands as they come (_dot); the
        # online-softmax state stays f32 (r4 perf: the f32 upcast
        # halved MXU throughput on the AMP path). A row a window's tile
        # masks whole keeps m at the finite _NEG_INF, its p are 1s, and
        # the first tile with a live key (the diagonal's at the latest)
        # rescales them to nothing.
        q = q_ref[0, rows, :]                     # [rows, hp*dh]
        k = k_ref[0, cols, :]                     # [cols, hp*dh]
        v = v_ref[0, cols, :]
        scale = 1.0 / math.sqrt(dh) * _LOG2E  # scores live in log2 units
        heads = _head_lanes(q.shape, dh)
        keep = _causal_keep((rows.size, cols.size), *edges)
        stat = (rows.size,) + m_scr.shape[2:]
        alphas, pvs = [], []
        for i in range(hp):
            s = _masked(_dot(_only_head(q, heads, i), k, (1, 1)) * scale,
                        keep)                         # [rows, cols]
            m_prev = m_scr[i, rows, :1]               # [rows, 1]
            l_prev = l_scr[i, rows, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp2(m_prev - m_new)
            p = jnp.exp2(s - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            # head i in its own lanes, p @ (the other heads' v) in theirs
            pvs.append(_dot(p.astype(v.dtype), v, (1, 0)))
            alphas.append(alpha)
            m_scr[i, rows, :] = jnp.broadcast_to(m_new, stat)
            l_scr[i, rows, :] = jnp.broadcast_to(l_new, stat)
        acc_scr[rows, :] = (
            acc_scr[rows, :] * _by_head(alphas, heads, q.shape)
            + _by_head(pvs, heads, q.shape))

    _tile_parts(qi, kb, block_q, block_k, T, causal, _part, window)

    if causal:
        last_kb = jnp.minimum(n_kb - 1, ((qi + 1) * block_q - 1) // block_k)
    else:
        last_kb = n_kb - 1

    @pl.when(kb == last_kb)
    def _finalize():
        ls = [jnp.maximum(l_scr[i, :, :1], 1e-30) for i in range(hp)]
        heads = _head_lanes(acc_scr.shape, dh)
        o_ref[0] = (acc_scr[:] / _by_head(ls, heads, acc_scr.shape)) \
            .astype(o_ref.dtype)
        # logsumexp row stats (NATURAL log: m is in log2 units), saved
        # for the blockwise backward and the ring-attention merge
        for i in range(hp):
            lse_ref[0, i] = m_scr[i, :, :1] / _LOG2E + jnp.log(ls[i])


def _live_kb(causal, block_q, block_k, n_kb, window=None):
    """(q-block i, step j) -> the k block that step reads. Causal: dead
    (fully-masked) steps re-reference the last live block, so Pallas
    skips their HBM DMA entirely (an index map that repeats the
    previous indices is a no-op fetch); under a window the steps below
    the band hold the first live block likewise."""
    if not causal:
        return lambda i, j: j

    def first(i):
        if window is None:
            return 0
        return jnp.maximum(i * block_q - window + 1, 0) // block_k
    return lambda i, j: jnp.clip(
        j, first(i),
        jnp.minimum(n_kb - 1, ((i + 1) * block_q - 1) // block_k))


def _live_qi(causal, block_q, block_k, window=None, n_qb=None):
    """(k-block j, step i) -> the q block that step of a kv-major sweep
    reads: steps before the diagonal re-reference the first live q
    block (no-op DMA), steps past a window's band the last."""
    if not causal:
        return lambda j, i: i
    if window is None:
        return lambda j, i: jnp.maximum(i, (j * block_k) // block_q)
    return lambda j, i: jnp.clip(
        i, (j * block_k) // block_q,
        jnp.minimum(n_qb - 1, ((j + 1) * block_k + window - 2) // block_q))


def _outer(x, y):
    """Row-block map of the operand that stays put while the inner grid
    dimension sweeps: the outer index."""
    return x


def _wide(rows, lanes, at):
    """BlockSpec of a [rows, lanes] block of a [B, T, H*dh] array under
    a (batch, head group, outer, inner) grid: ``at(outer, inner)`` is
    the row block, the head group is the lane block. This is where the
    heads are addressed — no copy splits them off."""
    return pl.BlockSpec((1, rows, lanes),
                        lambda b, g, x, y: (b, at(x, y), g))


def _cols(hp, rows, at):
    """BlockSpec of lse [B, H, T, 1], the layout the forward can write
    without two programs sharing a block: the ``hp`` columns of a
    program's heads, ``rows`` rows each."""
    return pl.BlockSpec((1, hp, rows, 1),
                        lambda b, g, x, y: (b, g, at(x, y), 0))


# Two heads a program with a whole 1024x1024 tile at once needed 16.5 MB
# of scoped VMEM in the merged backward (compiled for v5e inside the
# OPT step), half a megabyte over Mosaic's default limit of 16 MB; the
# chip has 128 MB. 2048x2048 blocks in row chunks (_row_chunks) compile
# under the same 32 MB, a whole 2048x2048 tile would not (37 MB).
_FLASH_COMPILER_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=32 * 1024 * 1024)


# The raw calls are jitted with everything but the arrays static: a
# model's layers share one shape, so the step traces and lowers each
# kernel once, not once a layer. Two heads a program double a kernel's
# trace, and the step is traced two or three times before its first
# timed run.
_RAW_STATICS = ('H', 'causal', 'block_q', 'block_k', 'interpret', 'window')


@functools.partial(jax.jit, static_argnames=_RAW_STATICS)
def _flash_pallas_call(q, k, v, *, H, causal, block_q, block_k, interpret,
                       window=None):
    """Raw Pallas forward on [B, T, H*dh] -> (out [B, T, H*dh],
    lse [B, H, T, 1])."""
    B, T, HD = q.shape
    dh = HD // H
    hp = _lane_heads(H, dh)
    lanes = hp * dh
    n_kb = T // block_k
    kb_at = _live_kb(causal, block_q, block_k, n_kb, window)
    return pl.pallas_call(
        functools.partial(_flash_kernel, block_q=block_q, block_k=block_k,
                          T=T, causal=causal, dh=dh, window=window),
        grid=(B, H // hp, T // block_q, n_kb),
        in_specs=[
            _wide(block_q, lanes, _outer),
            _wide(block_k, lanes, kb_at),
            _wide(block_k, lanes, kb_at),
        ],
        out_specs=[
            _wide(block_q, lanes, _outer),
            _cols(hp, block_q, _outer),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, HD), q.dtype),
            jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((hp, block_q, 128), jnp.float32),  # running max m
            pltpu.VMEM((hp, block_q, 128), jnp.float32),  # running sum l
            pltpu.VMEM((block_q, lanes), jnp.float32),    # unnormalised acc
        ],
        name='_flash_kernel',
        compiler_params=_FLASH_COMPILER_PARAMS,
        interpret=interpret,
    )(q, k, v)


def _bwd_p_ds(q, k, v, do, lse, delta, keep, dh):
    """Shared backward recompute for ONE head: normalised probs ``p``
    and the score cotangent ``ds = p * (dp - delta)`` for one part of a
    (q-block, k-block) tile (the rows of ``q`` against the columns of
    ``k``, masked to ``keep``), plus the softmax ``scale``. ``q`` and
    ``do`` arrive with the other heads' lanes zeroed (_only_head), so
    the dots over all the lanes of the k and v rows are this head's.
    The ONE copy of the score/mask/prob math used by all three backward
    kernels (two-pass dq, two-pass dk/dv, merged) — they are selected
    at runtime, so their tile math must never diverge."""
    scale = 1.0 / math.sqrt(dh)
    s = _masked(_dot(q, k, (1, 1)) * (scale * _LOG2E), keep)
    # normalised probs
    p = jnp.exp2(s - lse * _LOG2E)
    dp = _dot(do, v, (1, 1))                        # [rows, cols]
    ds = p * (dp - delta)
    return p, ds, scale


def _bwd_tile(in_refs, g, qi, kb, block_q, block_k, T, causal, dh,
              put_dq=None, dk_scr=None, dv_scr=None, window=None):
    """One (q-block, k-block) tile of the backward for the ``hp`` heads
    of head group ``g``, one head after the other, over the tile's live
    parts (_tile_parts): dk and dv of a part are added to its columns'
    rows of ``dk_scr``/``dv_scr`` where given, and its dq contribution
    [rows, hp*dh] goes to ``put_dq(rows, dq)`` where given. dv and dk
    contract the zeroed q and dO, so each head's product is 0 in the
    other heads' lanes and the heads add; dq's product with the k rows
    is lane-selected. delta = rowsum(dO * O) - g_lse is made here from
    the dO and O rows the part holds anyway: a [rows, hp*dh] product
    where a score tile is [rows, cols]."""
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, glse_ref = in_refs
    hp = lse_ref.shape[1]

    def _part(rows, kcols, edges):
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]
        k, v = k_ref[0, kcols, :], v_ref[0, kcols, :]
        do_o = do.astype(jnp.float32) * o_ref[0, rows, :].astype(jnp.float32)
        g_lse = glse_ref[0, rows, :]                  # [rows, H]
        head = jax.lax.broadcasted_iota(jnp.int32, g_lse.shape, 1)
        heads = _head_lanes(q.shape, dh)
        keep = _causal_keep((rows.size, kcols.size), *edges)
        dqs = []
        for i in range(hp):
            q_i = _only_head(q, heads, i)
            do_i = _only_head(do, heads, i)
            # this head's column of g_lse by a select and a lane sum: a
            # lane cannot be sliced at an offset the grid decides
            delta = jnp.sum(_only_head(do_o, heads, i), axis=-1,
                            keepdims=True) \
                - jnp.sum(jnp.where(head == g * hp + i, g_lse, 0.0),
                          axis=-1, keepdims=True)
            p, ds, scale = _bwd_p_ds(q_i, k, v, do_i, lse_ref[0, i, rows, :],
                                     delta, keep, dh)
            ds_lp = ds.astype(q.dtype)
            if dv_scr is not None:
                # p^T @ do and ds^T @ q via dim-0 contractions (no
                # transposes)
                dv_scr[kcols, :] += _dot(p.astype(do.dtype), do_i, (0, 0))
                dk_scr[kcols, :] += _dot(ds_lp, q_i, (0, 0)) * scale
            if put_dq is not None:
                dqs.append(_dot(ds_lp, k, (1, 0)) * scale)
        if put_dq is not None:
            put_dq(rows, _by_head(dqs, heads, q.shape))

    _tile_parts(qi, kb, block_q, block_k, T, causal, _part, window)


def _flash_dq_kernel(*refs, block_q, block_k, T, causal, dh, window=None):
    """dq pass of the two-pass fallback: one (batch, head group,
    q-block, k-block) step; dq accumulates in VMEM. ``refs``: the seven
    inputs of _bwd_tile, dq_ref, dq_scr."""
    in_refs, (dq_ref, dq_scr) = refs[:7], refs[7:]
    g = pl.program_id(1)
    qi = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _add_dq(rows, dq):
        dq_scr[rows, :] += dq

    _bwd_tile(in_refs, g, qi, kb, block_q, block_k, T, causal, dh,
              put_dq=_add_dq, window=window)

    @pl.when(kb == T // block_k - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_dkvdq_kernel(*refs, block_q, block_k, T, causal, dh, window=None):
    """One (batch, head group, k-block, q-block) step of a kv-major
    sweep: q blocks stream innermost, dk/dv accumulate in VMEM. All
    math stays q-major so no in-kernel transposes are needed
    (dot_general contracts dim 0). ``refs``: the seven inputs of
    _bwd_tile, dk_ref, dv_ref, dqp_ref, dk_scr, dv_scr.

    Merged backward: the ONE sweep also writes the dq contribution of
    this k block to a per-(kb) partial slab [n_kb, B, T, H*dh] that XLA
    sums afterwards. Saves the dq pass's full score/prob recomputation
    — one of the two exp sweeps and two of the seven backward T^2 dots
    — at the cost of the slab (bf16 for bf16 inputs, f32 otherwise —
    see _slab_dtype), so the caller only routes here while the slab is
    affordable. Race-free by construction: every grid step owns its dqp
    block exclusively (no output revisiting, which Pallas leaves
    undefined across non-consecutive steps). With ``dqp_ref`` None it
    is the dk/dv pass of the two-pass fallback (_flash_dkv_kernel)."""
    in_refs, (dk_ref, dv_ref, dqp_ref, dk_scr, dv_scr) = refs[:7], refs[7:]
    g = pl.program_id(1)
    kb = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    put_dq = None
    if dqp_ref is not None:
        if causal:
            # dead tiles still own a dqp slab slot — zero it so the XLA
            # sum sees defined content
            dead = (qi + 1) * block_q - 1 < kb * block_k
            if window is not None:
                dead = jnp.logical_or(
                    dead, (kb + 1) * block_k - 1 + window <= qi * block_q)

            @pl.when(dead)
            def _dead():
                dqp_ref[0, 0] = jnp.zeros_like(dqp_ref[0, 0])

        def put_dq(rows, dq):
            # this k block's dq contribution (the dq pass's third dot,
            # without re-deriving s/p), each part to its own rows
            dqp_ref[0, 0, rows, :] = dq.astype(dqp_ref.dtype)

    _bwd_tile(in_refs, g, qi, kb, block_q, block_k, T, causal, dh,
              put_dq, dk_scr, dv_scr, window)

    @pl.when(qi == T // block_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_dkv_kernel(*refs, **kw):
    """dk/dv pass of the two-pass fallback: the kv-major sweep without
    the dq slab."""
    _flash_dkvdq_kernel(*refs[:-2], None, *refs[-2:], **kw)


# merged-backward routing (_flash_lse_bwd): only while the dq-partials
# slab (dtype per _slab_dtype) stays affordable (it scales with n_kb;
# the two-pass path has no such cost). Measured on v5e: 1.11x at n_kb=2
# (flagship), 1.07x at n_kb=8; the win shrinks as partial traffic
# grows, and very long T would need gigabytes of slab — cap the slab
# bytes, not n_kb.
_MERGED_BWD_MAX_SLAB_BYTES = 512 * 1024 * 1024


def _slab_dtype(q_dtype):
    """dq-partial slab dtype — THE one policy site (allocation and the
    routing byte-cap both derive from it): bf16 inputs write bf16
    partials (half the traffic; the n_kb-way sum upcasts to f32 and dq
    is cast to q.dtype at the end regardless, measured rel grad diff
    ~5e-4); anything else keeps exact f32."""
    return jnp.bfloat16 if q_dtype == jnp.bfloat16 else jnp.float32


@functools.partial(jax.jit, static_argnames=_RAW_STATICS + ('merged',))
def _flash_bwd_pallas(q, k, v, o, lse, do, g_lse, *, H, causal, block_q,
                      block_k, interpret, merged, window=None):
    """Blockwise backward on [B, T, H*dh] operands (lse [B, H, T, 1] as
    the forward wrote it): O(T) memory, never materialises the [T, T]
    score matrix (ADVICE r1: the old backward recomputed full attention
    through XLA). Returns (dq, dk, dv) in the operands' layout.

    g_lse [B, H, T]: cotangent of the logsumexp output. The chain rule
    folds it straight into the delta term — ds = p*(dp - delta + g_lse)
    — because dlse/ds_ij = p_ij; dv is unaffected. It goes in as
    [B, T, H], B*H*T float32 being the one array here that changes its
    order: a program takes all H columns of its rows and picks its
    own. ``merged``: the one-sweep backward with its dq slab, else the
    two passes (_flash_lse_bwd decides)."""
    B, T, HD = q.shape
    dh = HD // H
    hp = _lane_heads(H, dh)
    lanes = hp * dh
    n_qb = T // block_q
    n_kb = T // block_k
    operands = (q, k, v, do, o, lse,
                g_lse.astype(jnp.float32).transpose(0, 2, 1))
    kernel_args = dict(block_q=block_q, block_k=block_k, T=T,
                       causal=causal, dh=dh, window=window)

    def in_specs(q_at, k_at):
        heads = pl.BlockSpec((1, block_q, H),
                             lambda b, g, x, y: (b, q_at(x, y), 0))
        return [_wide(block_q, lanes, q_at), _wide(block_k, lanes, k_at),
                _wide(block_k, lanes, k_at), _wide(block_q, lanes, q_at),
                _wide(block_q, lanes, q_at), _cols(hp, block_q, q_at),
                heads]

    kv_major = dict(
        grid=(B, H // hp, n_kb, n_qb),
        in_specs=in_specs(_live_qi(causal, block_q, block_k, window, n_qb),
                          _outer),
        scratch_shapes=[pltpu.VMEM((block_k, lanes), jnp.float32),
                        pltpu.VMEM((block_k, lanes), jnp.float32)],
        compiler_params=_FLASH_COMPILER_PARAMS,
        interpret=interpret)
    dkv_specs = [_wide(block_k, lanes, _outer),
                 _wide(block_k, lanes, _outer)]
    dkv_shapes = [jax.ShapeDtypeStruct((B, T, HD), k.dtype),
                  jax.ShapeDtypeStruct((B, T, HD), v.dtype)]
    if merged:
        dk, dv, dqp = pl.pallas_call(
            functools.partial(_flash_dkvdq_kernel, **kernel_args),
            out_specs=dkv_specs + [pl.BlockSpec(
                (1, 1, block_q, lanes),
                lambda b, g, j, i: (j, b, i, g))],
            out_shape=dkv_shapes + [
                jax.ShapeDtypeStruct((n_kb, B, T, HD),
                                     _slab_dtype(q.dtype))],
            name='_flash_dkvdq_kernel', **kv_major,
        )(*operands)
        dq = jnp.sum(dqp.astype(jnp.float32), axis=0).astype(q.dtype)
        return dq, dk, dv
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, **kernel_args),
        grid=(B, H // hp, n_qb, n_kb),
        in_specs=in_specs(_outer, _live_kb(causal, block_q, block_k, n_kb,
                                           window)),
        out_specs=_wide(block_q, lanes, _outer),
        out_shape=jax.ShapeDtypeStruct((B, T, HD), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, lanes), jnp.float32)],
        name='_flash_dq_kernel',
        compiler_params=_FLASH_COMPILER_PARAMS,
        interpret=interpret,
    )(*operands)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, **kernel_args),
        out_specs=dkv_specs, out_shape=dkv_shapes,
        name='_flash_dkv_kernel', **kv_major,
    )(*operands)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, causal, block_q, block_k, interpret, window=None):
    """The engaged path on q, k, v [B, T, H, D] -> (out [B, T, H, D],
    lse [B, H, T]). The kernels read and write [B, T, H*D] — what a
    reshape of the projections' output is, and of the output
    projection's input — so nothing is transposed or copied on the way
    in or out, and the residuals are q, k, v and out themselves."""
    (out, lse), _ = _flash_lse_fwd(q, k, v, causal, block_q, block_k,
                                   interpret, window)
    return out, lse


def _flash_lse_fwd(q, k, v, causal, block_q, block_k, interpret,
                   window=None):
    B, T, H, D = q.shape
    flat = (B, T, H * D)
    out, lse = _flash_pallas_call(
        q.reshape(flat), k.reshape(flat), v.reshape(flat), H=H,
        causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, window=window)
    out = out.reshape(q.shape)
    return (out, lse[..., 0]), (q, k, v, out, lse)


def _flash_lse_bwd(causal, block_q, block_k, interpret, window, res, g):
    # Blockwise Pallas backward: O(T) memory, recomputes p from the saved
    # logsumexp rather than materialising [T, T] (ADVICE r1). The lse
    # cotangent (nonzero when ring attention merges partial blocks)
    # folds into the delta term.
    g_out, g_lse = g
    q, k, v, out, lse = res
    B, T, H, D = q.shape
    flat = (B, T, H * D)
    slab_bytes = (T // block_k) * B * T * H * D \
        * jnp.dtype(_slab_dtype(q.dtype)).itemsize
    grads = _flash_bwd_pallas(
        q.reshape(flat), k.reshape(flat), v.reshape(flat),
        out.reshape(flat), lse, g_out.reshape(flat), g_lse, H=H,
        causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
        merged=slab_bytes <= _MERGED_BWD_MAX_SLAB_BYTES, window=window)
    return tuple(x.reshape(q.shape) for x in grads)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _pick_block(T, target):
    """Largest multiple of 128 that is <= target and divides T."""
    b = min(target, T)
    b -= b % 128
    while b >= 128:
        if T % b == 0:
            return b
        b -= 128
    return None


# Engagement is never-worse and thresholds on TOTAL grid work B*H*T,
# not T alone (VERDICT r4 weak #4: B=8/T=512 measured 1.10x but the old
# T>=768 rule skipped it, while engaging thin B=1 long-T shapes the
# sweep never covered). r4/r5 sweep on v5e (fwd+bwd, D=64, forced
# engagement, the one-head-a-program kernels of that round): B*H*T =
# 32Ki -> 1.00x (B4 H16 T512, dead even); 64Ki -> 1.10x (B8 T512) /
# 1.19x (B4 T1024); 128Ki -> 1.62x; 256Ki -> 2.49x. Engage strictly
# above the measured break-even: B*H*T >= 64Ki, with T >= 512 so blocks
# stay MXU-sized. Those shapes were T <= 1024; the XLA route alone pays
# the [T, T] scores, which grow with T at equal rows, so past T = 1024 a
# row counts T / 1024 times. One point of that is measured: B1 H4 T4096
# dh128 (16Ki rows; 4.7x, PERF.md section 6, PR 31). The shapes between
# (32-64Ki rows at T 2048, 16-64Ki at T 4096) engage unmeasured.
_FLASH_MIN_T = 512
_FLASH_MIN_ROWS = 64 * 1024  # B*H*T break-even (measured, v5e)


def flash_attention(q, k, v, causal=True, block_q=None, block_k=None,
                    interpret=None, window=None):
    """Blockwise attention. q,k,v: [B, T, H, D] -> [B, T, H, D].
    ``window`` (causal only): a query attends to the ``window`` keys up
    to its own position; the kernels skip the tiles below that band as
    they skip the ones above the diagonal.

    Forward and backward both run as Pallas kernels on TPU (or under
    ``interpret=True``) on the arrays as they are, read as [B, T, H*D]:
    a program takes the 128 lanes of two heads at D = 64 (one head at a
    multiple of 128), so no head is split off by a transpose. The
    forward saves per-row logsumexp and the backward streams q blocks
    past each k/v block in one merged sweep (dk, dv and the dq
    partials), so memory stays O(T) end to end. Off-TPU, for short
    sequences where XLA wins, for non-128-aligned T or a head shape the
    lane addressing cannot take (odd H at D = 64, any other D), the
    identical-math XLA reference runs instead.
    """
    return flash_attention_with_lse(q, k, v, causal, block_q, block_k,
                                    interpret, window)[0]


def effective_window(window, T, causal=True):
    """The window the kernels are given: None for none, and for one
    that reaches past the first key of every query (window >= T), which
    masks nothing: such a layer runs the causal kernels themselves."""
    if not window or window >= T:
        return None
    if not causal:
        raise ValueError('a window needs causal attention: the kernels '
                         'keep the keys up to a query, not around it')
    return int(window)


def flash_plan(q, block_q=None, block_k=None, interpret=None, causal=True,
               window=None):
    """THE engagement decision for q [B, T, H, D]: the (block_q, block_k)
    the Pallas kernels run with, or None where the XLA reference runs
    instead. flash_attention_with_lse routes by it; the flash_attention
    op (ops/misc_ops.py) labels its lowering counter with it and with
    the body its blocks give the diagonal tiles (flash_diag)."""
    B, T, H, D = q.shape
    # dtype-aware default blocks. bf16: 1024x1024 as swept in PR 27,
    # and one tile a program where a causal sequence of at most 2048
    # positions is one: it has no tile below the diagonal, and a
    # diagonal tile goes in row chunks (_row_chunks) whose score values
    # fit VMEM where a whole 2048x2048 tile would not. Measured on v5e,
    # causal, forward + merged backward, ms a layer (PERF.md section 6,
    # PR 28): at B2 H32 T2048 dh64 one 2048x2048 tile reads 1.78
    # against 2.15 at 1024x1024 in the same 512-row chunks and 2.91 at
    # 512x512 (2.40 / 2.96 whole, PR 27); 3.52 against 4.80 at B16 H8
    # dh128. What a k step costs beside its scores is a sweep of
    # per-row state work (two lane reductions a row group a head, m and
    # l, the rescale of acc) that scales with block_q and not with the
    # tile, so fewer, larger tiles win for as long as their dead half
    # is not computed. f32 keeps 512/1024 as swept in r4 (no cell runs
    # f32, nobody has timed larger).
    bf16 = q.dtype == jnp.bfloat16
    # ... and no window: the tiles a window's edge cuts may have to run
    # whole (_tile_parts), which a 2048x2048 tile cannot
    one_tile = causal and T <= 2048 \
        and effective_window(window, T, causal) is None
    if block_q is None:
        block_q = (2048 if one_tile else 1024) if bf16 else 512
    if block_k is None:
        block_k = (2048 if one_tile else 1024) if bf16 else 1024
    work = B * H * T * max(T, 1024) // 1024
    use_pallas = interpret or (
        _on_tpu() and T >= _FLASH_MIN_T and work >= _FLASH_MIN_ROWS)
    bq = _pick_block(T, block_q)
    bk = _pick_block(T, block_k)
    if not use_pallas or bq is None or bk is None \
            or _lane_heads(H, D) is None:
        return None
    return bq, bk


def flash_diag(plan, causal=True, window=None):
    """How the kernels of a flash_plan compute the tiles that straddle
    the diagonal: 'chunked<r>' (r row chunks, each against its live
    columns), 'whole' (the whole tile under the mask), 'none' where no
    tile is masked (not causal, or no plan). Follows from the blocks
    alone, as the kernels decide it (_row_chunks), and under a window
    (an effective one) from where its lower edge falls
    (_window_chunked): the tiles that edge cuts go as the diagonal's
    do."""
    if plan is None or not causal:
        return 'none'
    r = _row_chunks(*plan)
    if window is not None and not _window_chunked(*plan, window):
        r = 1
    return 'whole' if r == 1 else 'chunked%d' % r


def flash_attention_with_lse(q, k, v, causal=True, block_q=None,
                             block_k=None, interpret=None, window=None):
    """flash_attention that also returns per-row logsumexp [B, H, T].

    This is the ring-attention building block: each device computes its
    local (out, lse) partials per KV block and merges them exactly via
    logsumexp weighting — gradients flow through BOTH outputs (the lse
    cotangent folds into the Pallas backward's delta term). Engagement
    policy identical to flash_attention (flash_plan); falls back to the
    XLA reference (with lse) elsewhere."""
    if interpret is None:
        interpret = False
    window = effective_window(window, q.shape[1], causal)
    plan = flash_plan(q, block_q, block_k, interpret, causal, window)
    if plan is None:
        return attention_reference_with_lse(q, k, v, causal, window=window)
    bq, bk = plan
    return _flash_lse(q, k, v, causal, bq, bk, interpret, window)


# ---- fused LSTM cell ------------------------------------------------------------
def _lstm_cell_reference(xg, r_prev, c_prev, w):
    """xg: [B, 4H] pre-projected input+bias; w: [H, 4H]; gate order
    (candidate, input, forget, output) per ops/rnn_ops.py."""
    g = xg + r_prev @ w
    gc, gi, gf, go = jnp.split(g, 4, axis=-1)
    i = jax.nn.sigmoid(gi)
    f = jax.nn.sigmoid(gf)
    c = jnp.tanh(gc) * i + c_prev * f
    o = jax.nn.sigmoid(go)
    return o * jnp.tanh(c), c


def _lstm_cell_kernel(xg_ref, r_ref, c_ref, w_ref, h_out, c_out):
    xg = xg_ref[:].astype(jnp.float32)
    c_prev = c_ref[:].astype(jnp.float32)
    # recurrent dot at INPUT precision (bf16 operands under AMP hit the
    # MXU at full rate, f32 accumulation — same contract as the flash
    # kernel's dots and every AMP matmul); gate math stays f32. The MXU
    # has no fp16 path, so Float16Transpiler-fp16 operands upcast.
    r = r_ref[:]
    w = w_ref[:]
    if r.dtype == jnp.float16:
        r = r.astype(jnp.float32)
    if w.dtype == jnp.float16:
        w = w.astype(jnp.float32)
    g = xg + jax.lax.dot_general(r, w, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    hdim = c_prev.shape[-1]
    # static slices (Mosaic has no dynamic_slice lowering)
    gc = g[:, 0:hdim]
    gi = g[:, hdim:2 * hdim]
    gf = g[:, 2 * hdim:3 * hdim]
    go = g[:, 3 * hdim:4 * hdim]
    i = jax.nn.sigmoid(gi)
    f = jax.nn.sigmoid(gf)
    c = jnp.tanh(gc) * i + c_prev * f
    h = jax.nn.sigmoid(go) * jnp.tanh(c)
    h_out[:] = h.astype(h_out.dtype)
    c_out[:] = c.astype(c_out.dtype)


def _lstm_cell_pallas(xg, r_prev, c_prev, w, interpret):
    B, H = c_prev.shape
    return pl.pallas_call(
        _lstm_cell_kernel,
        out_shape=(jax.ShapeDtypeStruct((B, H), r_prev.dtype),
                   jax.ShapeDtypeStruct((B, H), c_prev.dtype)),
        name='_lstm_cell_kernel',
        interpret=interpret,
    )(xg, r_prev, c_prev, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _lstm_cell(xg, r_prev, c_prev, w, interpret):
    return _lstm_cell_pallas(xg, r_prev, c_prev, w, interpret)


def _lstm_cell_fwd(xg, r_prev, c_prev, w, interpret):
    return (_lstm_cell_pallas(xg, r_prev, c_prev, w, interpret),
            (xg, r_prev, c_prev, w))


def _lstm_cell_bwd(interpret, res, g):
    xg, r_prev, c_prev, w = res
    _, vjp = jax.vjp(_lstm_cell_reference, xg, r_prev, c_prev, w)
    return vjp(g)


_lstm_cell.defvjp(_lstm_cell_fwd, _lstm_cell_bwd)


# ---- fused conv + epilogue ------------------------------------------------------
#
# One kernel for conv (or depthwise conv) plus its trailing elementwise
# epilogue — folded-BN affine, activation, residual add, SE channel
# scale — applied in-register on the conv output tile before the single
# HBM store. The unfused lowering writes the conv output, re-reads it
# for BN, re-reads again for the activation/residual: on a
# bandwidth-bound program (resnet50's ledger: 54.8 ms bandwidth-bound
# vs 14.6 ms compute-bound) those extra round trips are the bill.
#
# Layout: NHWC internally (channels on the TPU lanes); the fused_conv
# op kernel (compiler/passes.py) transposes at the boundary. Block
# sizes are the _FCONV_BLOCK_* constants below.
#
# Grid: (N, H-blocks, outchannel-blocks). 1x1 convs tile H cleanly
# (input rows partition as bh-row blocks); KxK convs take the whole
# padded image per step — overlapping input windows cannot be
# expressed by a BlockSpec partition — with a static python loop over
# the (kh, kw) taps. Strides are taken OUTSIDE the kernel: the padded
# input is split into its sh*sw stride phases (x[:, p::sh, q::sw]), so
# every tap is a unit-stride window of one phase. Mosaic refuses
# strided loads of bf16 and of blocks whose last dim is not 128, and
# its reshape-and-take relayouts overflowed scoped VMEM (PERF.md,
# "Bring-up on the chip").

# Epilogue stage vocabulary. Math mirrors ops/math_ops.py kernels
# one-for-one (the replay fallback runs those exact kernels; the fused
# path must agree within the 1e-5 policy).
_EPI_ACTS = {
    'sigmoid': jax.nn.sigmoid,
    'logsigmoid': jax.nn.log_sigmoid,
    'exp': jnp.exp,
    'relu': jax.nn.relu,
    'tanh': jnp.tanh,
    'tanh_shrink': lambda x: x - jnp.tanh(x),
    'sqrt': jnp.sqrt,
    'abs': jnp.abs,
    'square': jnp.square,
    'ceil': jnp.ceil,
    'floor': jnp.floor,
    'round': jnp.round,
    'reciprocal': lambda x: 1.0 / x,
    'log': jnp.log,
    'softplus': jax.nn.softplus,
    'softsign': jax.nn.soft_sign,
}

_EPI_ACTS_P = {
    'brelu': lambda x, t_min, t_max: jnp.clip(x, t_min, t_max),
    'leaky_relu': lambda x, alpha: jax.nn.leaky_relu(x, alpha),
    'elu': lambda x, alpha: jax.nn.elu(x, alpha),
    'relu6': lambda x, t: jnp.clip(x, 0, t),
    'soft_relu': lambda x, t: jnp.log1p(jnp.exp(jnp.clip(x, -t, t))),
    'hard_shrink': lambda x, t: jnp.where(jnp.abs(x) > t, x, 0.0),
    'softshrink': lambda x, lam: jnp.where(
        x > lam, x - lam, jnp.where(x < -lam, x + lam, 0.0)),
    'pow': lambda x, f: jnp.power(x, f),
    'stanh': lambda x, a, b: b * jnp.tanh(a * x),
    'thresholded_relu': lambda x, t: jnp.where(x > t, x, 0.0),
    'hard_sigmoid': lambda x, s, o: jnp.clip(s * x + o, 0.0, 1.0),
    'swish': lambda x, beta: x * jax.nn.sigmoid(beta * x),
    'clip': lambda x, lo, hi: jnp.clip(x, lo, hi),
}

_EPI_BIN = {
    'elementwise_add': jnp.add,
    'elementwise_sub': jnp.subtract,
    'elementwise_mul': jnp.multiply,
    'elementwise_div': jnp.divide,
    'elementwise_max': jnp.maximum,
    'elementwise_min': jnp.minimum,
    'elementwise_pow': jnp.power,
}


def _apply_stage(y, st, fetch_aux):
    """One epilogue stage on a f32 value. ``fetch_aux(idx)`` returns the
    idx-th aux operand broadcast-shaped for ``y`` — the ONE copy of the
    stage math shared by the Pallas kernel (3D tiles) and the jnp
    reference (4D arrays), so they cannot diverge."""
    kind = st[0]
    if kind == 'affine':
        return y * fetch_aux(st[1]) + fetch_aux(st[2])
    if kind == 'act':
        return _EPI_ACTS[st[1]](y)
    if kind == 'act_p':
        return _EPI_ACTS_P[st[1]](y, *st[2])
    if kind == 'scale':
        s0, b0, after = st[1], st[2], st[3]
        return y * s0 + b0 if after else (y + b0) * s0
    if kind == 'postmul':     # elementwise kernels' trailing scale attr
        return y * st[1]
    if kind == 'bin':
        opname, idx, swap = st[1], st[2], st[3]
        b = fetch_aux(idx)
        fn = _EPI_BIN[opname]
        return fn(b, y) if swap else fn(y, b)
    raise ValueError('unknown epilogue stage %r' % (st,))


def _fconv_kernel(*refs, kh, kw, sh, sw, phases, bh, wo, depthwise,
                  stages, aux_kinds, emit_stats):
    """One (n, h-block, outchannel-block) grid step: conv taps
    accumulate f32, stats partials (train BN) and epilogue stages apply
    in-register, one store."""
    n_aux = len(aux_kinds)
    x_ref, w_ref = refs[0], refs[1]
    aux_refs = refs[2:2 + n_aux]
    out_ref = refs[2 + n_aux]
    acc = None
    for i in range(kh):
        for j in range(kw):
            # tap (i, j) is a unit-stride [bh, wo, C] window of stride
            # phase (i % sh, j % sw), loaded straight from the ref
            t = x_ref[0, phases.index((i % sh, j % sw)),
                      pl.ds(i // sh, bh), pl.ds(j // sw, wo), :]
            if depthwise:
                tap = t.astype(jnp.float32) * \
                    w_ref[i, j].astype(jnp.float32)[None, None, :]
            else:
                # dot at INPUT precision (bf16 -> full-rate MXU), f32
                # accumulation — same contract as the flash kernels
                tap = jax.lax.dot_general(
                    t.reshape(bh * wo, t.shape[-1]), w_ref[i, j],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            acc = tap if acc is None else acc + tap
    y = acc if depthwise else acc.reshape(bh, wo, -1)   # [bh, wo, bc]
    if emit_stats:
        # per-(n, h-block, c-block) first/second-moment partials of the
        # CONV output (train-mode BN statistics), each grid step owning
        # its slab slot exclusively (no output revisiting)
        psum_ref = refs[2 + n_aux + 1]
        psumsq_ref = refs[2 + n_aux + 2]
        psum_ref[0, 0] = jnp.sum(y, axis=(0, 1))[None, :]
        psumsq_ref[0, 0] = jnp.sum(y * y, axis=(0, 1))[None, :]

    def fetch_aux(idx):
        o = aux_refs[idx]
        if aux_kinds[idx] == 't':
            return o[0].astype(jnp.float32)          # [bh, wo, bc]
        # 's' arrives as a [1, 1] block, 'c' / 'nc' as [1, 1, bc]: all
        # broadcast against the tile as vectors (no scalar VMEM loads)
        return o[...].astype(jnp.float32).reshape((1, 1, -1))

    for st in stages:
        y = _apply_stage(y, st, fetch_aux)
    out_ref[0] = y.astype(out_ref.dtype)


def _conv_phases(kh, kw, sh, sw):
    """The stride phases (row % sh, col % sw) the conv's taps read."""
    return tuple(sorted({(i % sh, j % sw)
                         for i in range(kh) for j in range(kw)}))


def _fconv_pallas(x, w, aux, meta):
    """Raw fused-conv pallas_call on padded NHWC operands (x: [N,
    sh*hq, sw*wq, C], see fused_conv_epilogue)."""
    (kh, kw, sh, sw, bh, nh, wo, bc, noc, depthwise, stages, aux_kinds,
     emit_stats, interpret, out_dtype) = meta
    N = x.shape[0]
    ho = nh * bh
    cout = noc * bc
    phases = _conv_phases(kh, kw, sh, sw)
    x = jnp.stack([x[:, p::sh, q::sw, :] for p, q in phases], axis=1)
    npz, hq, wq = x.shape[1], x.shape[2], x.shape[3]
    row_span = bh if kh == 1 else hq
    if depthwise:
        in_specs = [
            pl.BlockSpec((1, npz, row_span, wq, bc),
                         lambda n, h, oc: (n, 0, h, 0, oc)),
            pl.BlockSpec((kh, kw, bc), lambda n, h, oc: (0, 0, oc)),
        ]
    else:
        cin = x.shape[4]
        in_specs = [
            pl.BlockSpec((1, npz, row_span, wq, cin),
                         lambda n, h, oc: (n, 0, h, 0, 0)),
            pl.BlockSpec((kh, kw, cin, bc),
                         lambda n, h, oc: (0, 0, 0, oc)),
        ]
    for kind in aux_kinds:
        if kind == 't':
            in_specs.append(pl.BlockSpec(
                (1, bh, wo, bc), lambda n, h, oc: (n, h, 0, oc)))
        elif kind == 'nc':
            in_specs.append(pl.BlockSpec(
                (1, 1, bc), lambda n, h, oc: (n, 0, oc)))
        elif kind == 's':
            in_specs.append(pl.BlockSpec(
                (1, 1), lambda n, h, oc: (0, 0)))
        else:   # 'c'
            in_specs.append(pl.BlockSpec(
                (1, 1, bc), lambda n, h, oc: (0, 0, oc)))
    out_specs = [pl.BlockSpec((1, bh, wo, bc),
                              lambda n, h, oc: (n, h, 0, oc))]
    out_shape = [jax.ShapeDtypeStruct((N, ho, wo, cout), out_dtype)]
    if emit_stats:
        out_specs += [pl.BlockSpec((1, 1, 1, bc),
                                   lambda n, h, oc: (n, h, 0, oc))] * 2
        out_shape += [jax.ShapeDtypeStruct((N, nh, 1, cout),
                                           jnp.float32)] * 2
    got = pl.pallas_call(
        functools.partial(_fconv_kernel, kh=kh, kw=kw, sh=sh, sw=sw,
                          phases=phases, bh=bh, wo=wo,
                          depthwise=depthwise,
                          stages=stages, aux_kinds=aux_kinds,
                          emit_stats=emit_stats),
        grid=(N, nh, noc),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        name='_fconv_kernel',
        interpret=bool(interpret),
    )(x, w, *[a if k in ('t', 's') else a[:, None, :]
              for k, a in zip(aux_kinds, aux)])
    if not emit_stats:
        return got[0]
    return got[0], got[1][:, :, 0, :], got[2][:, :, 0, :]


def _fconv_reference(x, w, aux, meta):
    """Identical-math XLA composition on the same padded NHWC operands
    — the custom_vjp backward differentiates THIS, so gradients flow
    through conv, stats and every epilogue stage."""
    (kh, kw, sh, sw, bh, nh, wo, _bc, _noc, depthwise, stages,
     aux_kinds, emit_stats, _interpret, out_dtype) = meta
    ho = nh * bh
    if depthwise:
        wr = w[:, :, None, :]
        conv = jax.lax.conv_general_dilated(
            x, wr, (sh, sw), 'VALID',
            feature_group_count=x.shape[-1],
            dimension_numbers=('NHWC', 'HWIO', 'NHWC'),
            preferred_element_type=jnp.float32)
    else:
        conv = jax.lax.conv_general_dilated(
            x, w, (sh, sw), 'VALID',
            dimension_numbers=('NHWC', 'HWIO', 'NHWC'),
            preferred_element_type=jnp.float32)
    # the padded input is rounded up to whole stride phases; VALID
    # over it can yield extra positions — slice to the true output
    y = conv[:, :ho, :wo, :]
    outs = []
    if emit_stats:
        N, c = y.shape[0], y.shape[-1]
        grouped = y.reshape(N, nh, bh, wo, c)
        outs = [jnp.sum(grouped, axis=(2, 3)),
                jnp.sum(grouped * grouped, axis=(2, 3))]

    def fetch_aux(idx):
        kind2 = aux_kinds[idx]
        o = aux[idx].astype(jnp.float32)
        if kind2 == 't':
            return o                                # [N, Ho, Wo, C]
        if kind2 == 'nc':
            return o[:, None, None, :]              # [N, C]
        if kind2 == 's':
            return o.reshape(())                    # scalar
        return o.reshape(1, 1, 1, -1)               # 'c': [1, C]

    for st in stages:
        y = _apply_stage(y, st, fetch_aux)
    y = y.astype(out_dtype)
    return (y,) + tuple(outs) if emit_stats else y


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fconv(x, w, aux, meta):
    return _fconv_pallas(x, w, aux, meta)


def _fconv_fwd(x, w, aux, meta):
    return _fconv_pallas(x, w, aux, meta), (x, w, aux)


def _fconv_bwd(meta, res, g):
    x, w, aux = res
    _, vjp = jax.vjp(
        lambda x_, w_, a_: _fconv_reference(x_, w_, a_, meta),
        x, w, aux)
    return vjp(g)


_fconv.defvjp(_fconv_fwd, _fconv_bwd)


# Engagement override for tests/benchmarks: None -> policy (Pallas on
# TPU, replay elsewhere); 'interpret' -> Pallas interpreter (CPU
# parity tests); True/'tpu' -> force-engage; False -> force-replay.
_FCONV_FORCE = [None]


@contextlib.contextmanager
def force_conv_epilogue(mode='interpret'):
    prev = _FCONV_FORCE[0]
    _FCONV_FORCE[0] = mode
    try:
        yield
    finally:
        _FCONV_FORCE[0] = prev


def conv_epilogue_mode():
    """The live engagement decision: False (exact replay), 'tpu', or
    'interpret'."""
    f = _FCONV_FORCE[0]
    if f is not None:
        return 'tpu' if f is True else f
    return 'tpu' if _on_tpu() else False


def _pick_div(n, target, quantum=1):
    """Largest divisor of ``n`` that is <= target and a multiple of
    ``quantum``; None when no such divisor exists."""
    best = None
    for d in range(1, n + 1):
        if n % d == 0 and d <= target and d % quantum == 0:
            best = d
    return best


# One grid step's tiled bytes must fit Mosaic's default scoped-VMEM
# limit on v5e (16 MiB; the chip has 128 MiB and `vmem_limit_bytes`
# could raise it — nothing here needs that yet). Checked against the
# compiler itself over 304 ResNet-50/SE-ResNeXt-like shapes: the
# smallest estimate it refused was 19.9 MiB (PERF.md, "Bring-up on the
# chip").
_FCONV_MAX_VMEM = 16 * 1024 * 1024

# Block targets: output rows a grid step of a 1x1 conv, output channels
# a step, and the lane quantum the channel block must be a multiple of
# on the chip. Never swept on the chip: the only conv cell engages this
# kernel 0 times (PERF.md section 3, conv_fuse_engaged).
_FCONV_BLOCK_H = 8
_FCONV_BLOCK_C = 256
_FCONV_VECTOR_WIDTH = 128


def _tiled_bytes(shape, dtype):
    """VMEM bytes of one block as Mosaic lays it out: the last dim
    padded to 128 lanes, the second-to-last to whole sublane tiles (8
    rows of 32 bits; 16-bit types pack 16 rows) — so a cin=3 block
    costs 128 lanes, not 3."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * max(1, 4 // item)
    lead = 1
    for d in shape[:-2]:
        lead *= int(d)
    rows = -(-int(shape[-2]) // sub) * sub
    lanes = -(-int(shape[-1]) // 128) * 128
    return lead * rows * lanes * item


def _fconv_vmem_bytes(x_blk, w_blk, out_blk, x_dtype, w_dtype, aux,
                      emit_stats):
    """Tiled VMEM bytes of one _fconv_kernel grid step, from its input,
    weight and output (``[bh, wo, bc]``) block shapes: the pipelined
    blocks double-buffered, plus the f32 accumulator, the epilogue
    value and one tap window, which are live at once. ``aux`` is
    ``[(kind, dtype)]``."""
    bh, wo, bc = out_blk
    est = 2 * (_tiled_bytes(x_blk, x_dtype) + _tiled_bytes(w_blk, w_dtype)
               + _tiled_bytes(out_blk, x_dtype))
    for kind, dtype in aux:
        est += 2 * _tiled_bytes(out_blk if kind == 't' else (1, bc), dtype)
    if emit_stats:
        est += 4 * _tiled_bytes((1, bc), jnp.float32)
    return est + 2 * _tiled_bytes((bh * wo, bc), jnp.float32) \
        + _tiled_bytes((bh, wo, x_blk[-1]), jnp.float32)


def fused_conv_epilogue(x, w, aux, aux_kinds, strides, paddings,
                        depthwise, stages, emit_stats=False,
                        interpret=False):
    """Fused conv + epilogue on NHWC operands. Returns ``(result,
    None)`` when the Pallas path engages, or ``(None, reason)`` when
    this shape/dtype/schedule is unsupported (the caller counts the
    fallback and replays the exact unfused lowering instead — never
    silently, never wrong).

    x: [N, H, W, Cin]; w: [KH, KW, Cin, Cout] (depthwise: [KH, KW,
    C]); aux: per-stage operands already shaped 'c' [1, C] / 'nc'
    [N, C] / 't' [N, Ho, Wo, Cout] / 's' [1, 1]. With ``emit_stats``
    the result is ``(y, psum [N, NH, Cout], psumsq)`` — f32 partial
    moments of the conv output for train-mode BN.
    """
    if x.ndim != 4:
        return None, 'rank'
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        return None, 'dtype'
    sh, sw = strides
    ph, pw = paddings
    kh, kw = (int(w.shape[0]), int(w.shape[1]))
    cout = int(w.shape[-1])
    N, H, W = int(x.shape[0]), int(x.shape[1]), int(x.shape[2])
    ho = (H + 2 * ph - kh) // sh + 1
    wo = (W + 2 * pw - kw) // sw + 1
    if ho <= 0 or wo <= 0:
        return None, 'degenerate'
    bc = _pick_div(cout, _FCONV_BLOCK_C,
                   1 if interpret else _FCONV_VECTOR_WIDTH)
    if bc is None:
        return None, 'channel-align'
    bh = _pick_div(ho, _FCONV_BLOCK_H) if kh == 1 else ho
    nh = ho // bh
    noc = cout // bc
    if not depthwise and not interpret and x.dtype == jnp.bfloat16 \
            and wo % 2 and int(x.shape[3]) % 128:
        # the dot's [bh, wo, cin] -> [bh*wo, cin] row merge: bf16 packs
        # two rows per sublane, and Mosaic has no shape cast for an odd
        # row count unless the lanes are whole tiles ("infer-vector-
        # layout: unsupported shape cast")
        return None, 'packed-row-merge'
    # each stride phase of the padded input is [hq, wq]: deep enough
    # for the farthest tap of that phase
    hq = (kh - 1) // sh + ho
    wq = (kw - 1) // sw + wo
    cin_blk = bc if depthwise else int(x.shape[3])
    est = _fconv_vmem_bytes(
        (len(_conv_phases(kh, kw, sh, sw)), bh if kh == 1 else hq, wq,
         cin_blk),
        (kh, kw, bc) if depthwise else (kh, kw, cin_blk, bc),
        (bh, wo, bc), x.dtype, w.dtype,
        [(k, a.dtype) for k, a in zip(aux_kinds, aux)], emit_stats)
    if est > _FCONV_MAX_VMEM:
        return None, 'vmem'
    xp = jnp.pad(x, ((0, 0), (ph, max(sh * hq - H - ph, 0)),
                     (pw, max(sw * wq - W - pw, 0)),
                     (0, 0)))[:, :sh * hq, :sw * wq]
    meta = (kh, kw, sh, sw, bh, nh, wo, bc, noc, bool(depthwise),
            tuple(stages), tuple(aux_kinds), bool(emit_stats),
            bool(interpret), str(x.dtype))
    return _fconv(xp, w, tuple(aux), meta), None


def fused_lstm_cell(xg, r_prev, c_prev, w, interpret=None):
    """One LSTM step: recurrent matmul + gates + state update in a single
    kernel (differentiable: backward recomputes via the XLA reference).
    xg: [B, 4H], r_prev/c_prev: [B, H], w: [H, 4H]. Called from
    ops/rnn_ops.py::_lstm_scan for the default-activation non-peephole
    path."""
    if interpret is None:
        interpret = False
    use_pallas = interpret or _on_tpu()
    # Whole-array kernel: everything must fit VMEM (~16MB). The weight
    # dominates; past ~10MB of f32 operands Mosaic compilation fails.
    B, H = c_prev.shape
    vmem_bytes = 4 * (w.size + xg.size + 3 * B * H + 2 * B * H)
    if vmem_bytes > 10 * 1024 * 1024:
        use_pallas = False
    if not use_pallas:
        return _lstm_cell_reference(xg, r_prev, c_prev, w)
    return _lstm_cell(xg, r_prev, c_prev, w, interpret)


# ---- grouped matmul (routed experts) --------------------------------------------
# rows [M, K] lie expert by expert; ``sizes`` [held] says how many rows
# each expert owns. They sum to the live rows, at most M: the rows past
# them belong to no expert. The rows go in tiles of ``tm``; a tile that
# straddles a group boundary is visited once for every expert with rows
# in it, under that expert's row mask, so no row is padded and a
# product's grid is M / tm + held - 1 visits whatever the split. A visit
# whose rows all lie past the live ones is a grid step that fetches no
# block and runs no MXU pass: it writes its tile of zeros (a product)
# or nothing at all (a weight gradient).

def grouped_visits(sizes, tiles, tm):
    """The visit table of a grouped product: int32 [tiles + held - 1]
    arrays (tile, expert, lo, hi) and ``live`` [1], the live rows
    (sum(sizes)). Visit v holds rows lo..hi of row tile ``tile`` for
    expert ``expert``: it multiplies those below ``live`` and zeroes
    the others. The visits partition the rows at every tile start and
    every group start, in row order; where the two coincide (or a group
    is empty) a visit has no row (lo == hi), which is how an expert
    with no row still gets its visit. Made by counting, as the layer's
    layout is: a sort of 31 numbers is a kernel of its own on the
    TPU."""
    held = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    point = jnp.concatenate(
        [jnp.arange(tiles, dtype=jnp.int32) * tm, starts[1:]])
    ident = jnp.concatenate(
        [jnp.zeros((tiles,), jnp.int32), jnp.arange(1, held, dtype=jnp.int32)])
    key = point * held + ident                   # a tile's start first
    rank = jnp.sum(key[None, :] < key[:, None], axis=1)
    v = jnp.arange(point.shape[0])[:, None]      # [v, point]
    lo = jnp.sum(jnp.where(rank[None, :] == v, point[None, :], 0), axis=1)
    hi = jnp.concatenate([lo[1:], jnp.full((1,), tiles * tm, jnp.int32)])
    expert = jnp.max(jnp.where(rank[None, :] <= v, ident[None, :], 0),
                     axis=1)
    return (jnp.minimum(lo // tm, tiles - 1), expert, lo, hi,
            jnp.minimum(ends[-1:], tiles * tm))


def live_row_tiles(counts, rows, tm=None):
    """(live, total) row tiles of the grouped products of one expert
    layer in one step: ``counts`` the pairs each held expert was routed
    (the op's TokensPerExpert), ``rows`` a chunk's rows
    (hybrid_ops.expert_chunk_rows), ``tm`` the row tile. The pairs fill
    as many chunks as they need, the first always run; a tile with a
    routed row in it is live, the others cost a grid step each. Plain
    host arithmetic, for tests and for reading a run."""
    tm = tm or _GROUPED_ROW_TILE
    pairs = sum(int(c) for c in counts)
    chunks = max(1, -(-pairs // rows))
    live = sum(-(-min(rows, pairs - c * rows) // tm) for c in range(chunks))
    return live, chunks * (rows // tm)


def _src_tile(tile, live, tm):
    """The row tile a visit reads: its own, or, past the live rows, the
    last live row's, so that no block moves for a visit that reads
    none."""
    return jnp.minimum(tile, jnp.maximum(live - 1, 0) // tm)


def _rows_between(tile, lo, hi, shape, tm):
    """Rows lo..hi of row tile ``tile`` as a mask of ``shape`` ([tm,
    lanes])."""
    row = tile * tm + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return jnp.logical_and(row >= lo, row < hi)


def _gmm_kernel(tile_ref, expert_ref, lo_ref, hi_ref, live_ref, x_ref, w_ref,
                o_ref, *, tm, dims):
    # a tile's block stays in VMEM over its consecutive visits; each
    # writes its own rows and the last leaves every row written: the
    # live ones (lo..mid) with their product, those past them (mid..hi)
    # with zeros, whatever x holds there
    v = pl.program_id(1)
    lo, hi = lo_ref[v], hi_ref[v]
    mid = jnp.clip(live_ref[0], lo, hi)

    @pl.when(lo < mid)
    def _():
        y = _dot(x_ref[...], w_ref[...], dims).astype(o_ref.dtype)
        keep = _rows_between(tile_ref[v], lo, mid, y.shape, tm)
        o_ref[...] = jnp.where(keep, y, o_ref[...])

    @pl.when(mid < hi)
    def _():
        dead = _rows_between(tile_ref[v], mid, hi, o_ref.shape, tm)
        o_ref[...] = jnp.where(dead, 0.0, o_ref[...])


def _tgmm_kernel(tile_ref, expert_ref, lo_ref, hi_ref, live_ref, a_ref, b_ref,
                 o_ref, acc_ref, *, tm):
    # an expert's visits are consecutive: zero at its first, write at
    # its last, whether or not they hold a live row. The rows of other
    # experts in the tile, and those past the live rows, are zeroed in a
    # (through float32: v5e's vector unit has no bf16), so what b holds
    # there is multiplied by 0 (_grouped hands b over with zeros past
    # the live rows, where it may hold anything).
    v, last = pl.program_id(1), pl.num_programs(1) - 1
    e = expert_ref[v]
    lo = lo_ref[v]
    mid = jnp.clip(live_ref[0], lo, hi_ref[v])

    @pl.when(jnp.logical_or(v == 0, expert_ref[jnp.maximum(v - 1, 0)] != e))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(lo < mid)
    def _():
        a = a_ref[...]
        keep = _rows_between(tile_ref[v], lo, mid, a.shape, tm)
        a = jnp.where(keep, a.astype(jnp.float32), 0.0).astype(a.dtype)
        acc_ref[...] += _dot(a, b_ref[...], (0, 0))

    @pl.when(jnp.logical_or(
        v == last, expert_ref[jnp.minimum(v + 1, last)] != e))
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


# both grids are (blocks of the dimension that is not contracted,
# visits); whole weight blocks at the cell's widths need 14 MB (product)
# and 23 MB (weight gradient) of VMEM
_GROUPED_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=('parallel', 'arbitrary'),
    vmem_limit_bytes=32 * 1024 * 1024)


@functools.partial(jax.jit, static_argnames=(
    'tm', 'tn', 'transpose', 'out_dtype', 'interpret'))
def _gmm_pallas_call(visits, x, w, *, tm, tn, transpose, out_dtype,
                     interpret):
    """x [M, K] against w [held, K, N] (``transpose``: [held, N, K], read
    as it lies and contracted on its last dimension) -> [M, N]."""
    M, K = x.shape
    N = w.shape[1] if transpose else w.shape[2]
    if transpose:
        w_spec = pl.BlockSpec((None, tn, K), lambda n, v, t, e, lo, hi, live:
                              (e[v], n, 0))
    else:
        w_spec = pl.BlockSpec((None, K, tn), lambda n, v, t, e, lo, hi, live:
                              (e[v], 0, n))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm,
                          dims=(1, 1) if transpose else (1, 0)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(N // tn, visits[0].shape[0]),
            in_specs=[
                pl.BlockSpec((tm, K), lambda n, v, t, e, lo, hi, live:
                             (_src_tile(t[v], live[0], tm), 0)),
                w_spec,
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n, v, t, e, lo, hi, live:
                                   (t[v], n)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        name='_gmm_kernel',
        compiler_params=_GROUPED_COMPILER_PARAMS,
        interpret=interpret,
    )(*visits, x, w)


@functools.partial(jax.jit, static_argnames=(
    'held', 'tm', 'tn', 'out_dtype', 'interpret'))
def _tgmm_pallas_call(visits, a, b, *, held, tm, tn, out_dtype, interpret):
    """out[e] = a[rows of e].T @ b[rows of e]: a [M, K], b [M, N] ->
    [held, K, N]; an expert without a row gets zeros."""
    (M, K), N = a.shape, b.shape[1]
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(N // tn, visits[0].shape[0]),
            in_specs=[
                pl.BlockSpec((tm, K), lambda n, v, t, e, lo, hi, live:
                             (_src_tile(t[v], live[0], tm), 0)),
                pl.BlockSpec((tm, tn), lambda n, v, t, e, lo, hi, live:
                             (_src_tile(t[v], live[0], tm), n)),
            ],
            out_specs=pl.BlockSpec((None, K, tn),
                                   lambda n, v, t, e, lo, hi, live:
                                   (e[v], 0, n)),
            scratch_shapes=[pltpu.VMEM((K, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((held, K, N), out_dtype),
        name='_tgmm_kernel',
        compiler_params=_GROUPED_COMPILER_PARAMS,
        interpret=interpret,
    )(*visits, a, b)


# The tiles, from a sweep on one v5e chip at the cell's shape (3072 rows
# in 8 groups, 1024 <-> 2688, bf16; device ms of the kernel alone,
# PERF.md section 6, PR 32; lax.ragged_dot on the same operands: 0.595 /
# 0.381 forward, 0.575 / 0.658 data gradients, 0.323 / 0.407 weight
# gradients). Row tile 128 and 256 read alike (0.171 / 0.178 forward,
# 0.184 / 0.187 weight gradient): 256-row tiles fill the MXU better and
# recompute more rows of a straddled tile; 128 keeps the visits' waste
# least. The other dimensions whole wherever a weight block stays
# under _GROUPED_BLOCK_BYTES (doubled by the pipeline; a weight
# gradient's float32 accumulator is twice a bf16 block): N in 896-wide
# blocks read 0.194 forward against 0.171 whole, 384-wide 0.247; a
# weight gradient 0.205 at [1024, 896] against 0.184 whole, 0.197-0.249
# with K in 512-wide blocks too (it stays whole). The time is
# the MXU's (one expert taking every row, an eighth of the weight
# bytes, reads the same 0.171), at about two thirds of its rate.
_GROUPED_ROW_TILE = 128
_GROUPED_BLOCK_BYTES = 6 * 1024 * 1024


def _rows_below(m, live):
    """[m, 1] mask of the rows below ``live``."""
    return jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0) < live


def _grouped_block(n, other, dtype):
    """The widest block of an ``n``-wide dimension, in whole 128-lane
    tiles that divide ``n``, whose [other, block] weights stay under
    _GROUPED_BLOCK_BYTES."""
    room = _GROUPED_BLOCK_BYTES // (other * jnp.dtype(dtype).itemsize)
    return _pick_div(n, max(room, 128), 128)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped(rows, w, visits, tm, interpret):
    K, N = w.shape[1:]
    return _gmm_pallas_call(
        visits, rows, w, tm=tm, tn=_grouped_block(N, K, w.dtype),
        transpose=False, out_dtype=jnp.float32, interpret=interpret)


def _grouped_fwd(rows, w, visits, tm, interpret):
    return _grouped(rows, w, visits, tm, interpret), (rows, w, visits)


def _grouped_bwd(tm, interpret, res, g):
    # both gradients take the cotangent in the rows' dtype and come out
    # in their operand's, which is where jax's transposition of a
    # bf16 x bf16 -> f32 product rounds them too. The cotangent's rows
    # past the live ones go in as zeros (a select beside the cast: they
    # may hold anything, and the weight gradient multiplies them by 0)
    rows, w, visits = res
    K, N = w.shape[1:]
    g = jnp.where(_rows_below(g.shape[0], visits[4]), g, 0.0) \
        .astype(rows.dtype)
    d_rows = _gmm_pallas_call(
        visits, g, w, tm=tm, tn=_grouped_block(K, N, w.dtype),
        transpose=True, out_dtype=rows.dtype, interpret=interpret)
    d_w = _tgmm_pallas_call(
        visits, rows, g, held=w.shape[0], tm=tm,
        tn=_grouped_block(N, K, w.dtype), out_dtype=w.dtype,
        interpret=interpret)
    return d_rows, d_w, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_plan(rows, w, interpret=None):
    """THE engagement decision for rows [M, K] against w [held, K, N]:
    ``(row tile, interpret)`` for the Pallas grouped matmul, or None
    where ``lax.ragged_dot`` runs instead. The kernels take bf16
    operands (AMP's ``mxu_operand`` on the chip) whose K and N are whole
    128-lane tiles and whose rows are whole row tiles; the interpreter
    (tests) takes float32 too. grouped_matmul routes by it and the
    routed_experts op labels its lowering counter with it."""
    M, K = rows.shape
    N = w.shape[2]
    bf16 = rows.dtype == w.dtype == jnp.bfloat16
    if not (interpret or (_on_tpu() and bf16)):
        return None
    if K % 128 or N % 128 or M % _GROUPED_ROW_TILE:
        return None
    return _GROUPED_ROW_TILE, interpret or False


def grouped_matmul(rows, w, sizes, interpret=None):
    """rows[rows of e] @ w[e] for every expert e -> [M, N] float32, as
    ``lax.ragged_dot(rows, w, sizes, preferred_element_type=float32)``
    where rows lie in a group. rows [M, K] lie expert by expert,
    ``sizes`` [held] int32 sums to at most M: the rows past the groups
    belong to no expert. Their product and their data gradient are
    exactly zero and they add to no weight gradient, whatever they or
    their cotangent hold. Differentiable in rows and w on either route.

    The Pallas kernels spend a grid step on a row tile past the groups,
    with no MXU pass and no block fetched (a product writes its zeros),
    so a product costs what its live rows cost.
    ``lax.ragged_dot`` leaves rows outside its groups uninitialised on
    the TPU (in the transposed products too), so on that route the last
    expert is handed them as zero rows and the outcome is masked."""
    plan = grouped_plan(rows, w, interpret)
    if plan is None:
        M, pairs = rows.shape[0], jnp.sum(sizes)
        live = _rows_below(M, pairs)
        y = jax.lax.ragged_dot(
            jnp.where(live, rows, jnp.zeros_like(rows)), w,
            sizes.at[-1].add(M - pairs), preferred_element_type=jnp.float32)
        return jnp.where(live, y, 0.0)
    tm, interpret = plan
    visits = grouped_visits(sizes, rows.shape[0] // tm, tm)
    return _grouped(rows, w, visits, tm, interpret)


# ---- rows summed back into their tokens (routed experts) ------------------------
# A chunk of the routed experts' rows [chunk, L] float32 lies expert by
# expert, one (token, expert) pair a row; its first ``live`` rows are
# routed pairs, the rows past them belong to nobody. The sum of the live
# rows into their tokens [N, L] is the forward's combine and the
# transpose of the row pick. XLA's scatter-add does it at about a
# seventh of HBM's bandwidth (PERF.md section 6) and sweeps the
# dead rows too; the kernel below keeps a column block of the result in
# VMEM, adds the live rows into it one by one and fetches no row past
# them.

def _row_sum_kernel(tok_ref, live_ref, y_ref, o_ref, *, tr):
    # the [N, tl] column block stays in VMEM over the row blocks: zeroed
    # at the first, written back after the last. A row block adds its
    # rows below ``live`` in row order (a token's rows in expert order);
    # a block past them runs no row, and its index map holds the last
    # live block, so nothing moves for it. Four rows an iteration: 8 %
    # faster than two and 22 % faster than one on the chip (PERF.md
    # section 6); eight gain under 2 % more
    r = pl.program_id(1)
    base = r * tr
    rows = jnp.clip(live_ref[0] - base, 0, tr)

    @pl.when(r == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    def add(i, carry):
        t = tok_ref[base + i]
        o_ref[pl.ds(t, 1), :] += y_ref[pl.ds(i, 1), :]
        return carry

    def four(k, carry):
        for q in range(4):
            add(4 * k + q, carry)
        return carry

    jax.lax.fori_loop(0, rows // 4, four, 0)
    jax.lax.fori_loop(rows // 4 * 4, rows, add, 0)


@functools.partial(jax.jit, static_argnames=('n', 'tr', 'tl', 'interpret'))
def _row_sum_call(tok, live, y, *, n, tr, tl, interpret):
    """y [chunk, L] float32 summed by ``tok`` [chunk] into [n, L] over
    the rows below ``live`` [1]."""
    chunk, L = y.shape
    return pl.pallas_call(
        functools.partial(_row_sum_kernel, tr=tr),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(L // tl, chunk // tr),
            in_specs=[pl.BlockSpec((tr, tl), lambda j, r, tok, live:
                                   (_src_tile(r, live[0], tr), j))],
            out_specs=pl.BlockSpec((n, tl), lambda j, r, tok, live: (0, j),
                                   pipeline_mode=pl.Buffered(1)),
        ),
        out_shape=jax.ShapeDtypeStruct((n, L), jnp.float32),
        name='_row_sum_kernel',
        compiler_params=_GROUPED_COMPILER_PARAMS,
        interpret=interpret,
    )(tok, live, y)


# From a sweep on one v5e chip (PERF.md section 6; 8448 rows of
# 2048 into 8192 tokens, 4096 live: XLA's scatter-add 0.955 ms): rows in
# blocks of 128, 256 or 768 read alike (256 divides both cells'
# chunks); the time is one loop iteration a live row and column block,
# so the widest column block wins (one row an iteration: 256 lanes 0.46
# ms, 512 lanes 0.29, 1024 lanes 0.23 but at 64 MB of VMEM); four rows
# an iteration read 0.227 at 512 lanes. The [N, block] result, kept in
# one buffer, stays under 24 MB of the grouped products' 32 MB limit.
_ROW_SUM_BLOCK_ROWS = 256
_ROW_SUM_BLOCK_BYTES = 24 * 1024 * 1024


def row_sum_plan(y, n, interpret=None):
    """THE engagement decision for summing rows y [chunk, L] into ``n``
    tokens: ``(row block, column block, interpret)`` for the Pallas
    kernel, or None where XLA's scatter-add runs. The kernel takes
    float32 rows on a TPU backend (the interpreter, in tests, too),
    ``L`` in whole 128-lane tiles and a chunk of whole row blocks; the
    column block is the widest whose [n, block] result stays under
    _ROW_SUM_BLOCK_BYTES of VMEM."""
    chunk, L = y.shape
    if not (interpret or _on_tpu()) or y.dtype != jnp.float32:
        return None
    room = _ROW_SUM_BLOCK_BYTES // (n * 4)
    if L % 128 or chunk % _ROW_SUM_BLOCK_ROWS or room < 128:
        return None
    return _ROW_SUM_BLOCK_ROWS, _pick_div(L, room, 128), interpret or False


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def row_sum(y, tok, live, n, plan):
    """out[t] = sum of y[r] over the rows r < ``live`` whose token
    ``tok[r]`` is t, in row order, zero for a token no row names: y
    [chunk, L] float32, tok [chunk] int32, live a scalar int32, ``plan``
    row_sum_plan's. Its gradient in y is the row gather of the
    cotangent, zero past ``live``."""
    tr, tl, interpret = plan
    return _row_sum_call(tok, jnp.reshape(live, (1,)).astype(jnp.int32), y,
                         n=n, tr=tr, tl=tl, interpret=interpret)


def _row_sum_fwd(y, tok, live, n, plan):
    return row_sum(y, tok, live, n, plan), (tok, live)


def _row_sum_bwd(n, plan, res, g):
    tok, live = res
    rows = jnp.take(g, jnp.clip(tok, 0, n - 1), axis=0)
    return jnp.where(_rows_below(tok.shape[0], live), rows, 0.0), None, None


row_sum.defvjp(_row_sum_fwd, _row_sum_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def row_pick(u, tok, live, n, plan):
    """u [n, L] float32 picked by ``tok`` [chunk]: row r is u[tok[r]]
    below ``live``, a zero row past it. Its gradient in u is row_sum of
    the cotangent, which reads no row past ``live``."""
    rows = jnp.take(u, jnp.clip(tok, 0, n - 1), axis=0)
    return jnp.where(_rows_below(tok.shape[0], live), rows, 0.0)


def _row_pick_fwd(u, tok, live, n, plan):
    return row_pick(u, tok, live, n, plan), (tok, live)


def _row_pick_bwd(n, plan, res, g):
    tok, live = res
    return row_sum(g, tok, live, n, plan), None, None


row_pick.defvjp(_row_pick_fwd, _row_pick_bwd)
