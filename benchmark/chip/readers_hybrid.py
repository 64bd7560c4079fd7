"""Readers of the hybrid blocks' ops (``paddle_tpu/ops/hybrid_ops.py``):
device time under one Fluid op type's scopes, forward and backward
together, that time against the least the op's required work could
take, and the program's own lowering counters. They read what
``readers_program.by_scope`` joined; a program without the scope map,
the op or the counter gives them nothing to read: each returns None and
the metric is left out of the line.
"""
import readers_program


def _op_seconds(ctx, spec):
    """Device seconds a step of every operation lowered under a Fluid op
    of ``spec['op_type']`` (a fusion that holds several ops counts where
    any of them is it), all phases; None where nothing ran under the
    op."""
    res = readers_program.by_scope(ctx)
    if res is None:
        return None
    secs = [s for (_, kind), s in res['type'].items()
            if kind and spec['op_type'] in kind.split('+')]
    return sum(secs) if secs else None


def scope_ms(ctx, spec):
    secs = _op_seconds(ctx, spec)
    return None if secs is None else 1e3 * secs


def scope_roofline(ctx, spec):
    """The least time the chip could take for the op's required work
    (``work`` of the model module: operations and bytes of one step,
    forward and backward) over the device time under its scopes."""
    secs = _op_seconds(ctx, spec)
    work = getattr(ctx['model'], spec['work'], None)
    if not secs or work is None:
        return None
    flops, nbytes = work(ctx['cfg'], ctx['traffic'], ctx['chips'])
    peaks = ctx['man'].peaks(ctx['device_kind'])
    least = max(flops / peaks[spec.get('peak', 'bf16_flops')],
                nbytes / peaks['hbm_bytes_per_s'])
    return 100.0 * least / secs


def program_count(ctx, spec):
    """The sum of one of the program's lowering counters
    (``compiler.passes.<counts>()``), as the process stands."""
    try:
        from paddle_tpu.compiler import passes
        counts = getattr(passes, spec['counts'])
    except (ImportError, AttributeError):
        return None
    total = sum(counts().values())
    return total if total else None
