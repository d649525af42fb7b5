"""The fused device program: filter -> time-bin -> bincount, one launch.

One jitted function per static signature evaluates Q same-shape query
lanes over U stacked row-group units. Everything literal- or
time-dependent is a RUNTIME argument (per-unit code sets, range
bounds, [start_s, step_s], n_bins), so a literal swap or a shifted
dashboard window re-enters the same traced executable — the retrace
tax the interpreter pays per stage per row group collapses to zero.

Exactness: the per-codec decode bodies are the ops/scan.py resident
kernels' formulas (rle repeat-expansion, dct dictionary gather, dbp
two-limb delta decode via the SAME dbp_decode_limbs the shipped path
uses), and the time binning uses the epoch-seconds identity

    (t_ns - start_s*1e9) // (step_s*1e9)  ==  (t_s - start_s) // step_s
    with t_s = t_ns // 1e9,

exact for integer-second start/step by the nested-floor identity, so
device u32 arithmetic reproduces the interpreter's int64 formula
bit-for-bit (the executor declines any unit whose seconds overflow
u32). Pad rows/runs/dictionary entries are neutralized by the valid
mask, never by sentinel value tricks that could collide with data.

Signature layout (all leading dims static):
  colsig entry ("rle"|"dct"|"dbp", "set"|"range", invert, pad...)
  runtime:  t_s (U,N) u32 · valid (U,N) bool
            per col payload  rle (values,lengths) (U,RP)
                             dct (dvals (U,VP), idx (U,N))
                             dbp (words (U,WP), first_hi/lo (U,), width (U,))
            per col query    set codes (Q,U,K) — per-unit because each
                             BLOCK dictionary maps the literal to its
                             own codes; range bounds (Q,4) u32 limbs
            tb (Q,2) u32 [start_s, step_s] · nb (Q,) u32
  returns counts (Q, slot_pad) int32
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _limb_ge(vh, vl, bh, bl):
    return (vh > bh) | ((vh == bh) & (vl >= bl))


def _limb_le(vh, vl, bh, bl):
    return (vh < bh) | ((vh == bh) & (vl <= bl))


def _u32_range_hit(v, b):
    """Inclusive two-limb range verdict for a u32 column (high limb 0):
    b = [lo_hi, lo_lo, hi_hi, hi_lo]."""
    zero = jnp.zeros_like(v)
    return _limb_ge(zero, v, b[0], b[1]) & _limb_le(zero, v, b[2], b[3])


def build_metrics_program(sig):
    """sig = (colsig, n_pad, slot_pad, q) -> jitted fused program."""
    colsig, n_pad, slot_pad, _q = sig

    def col_hit(cs, payload, qarg):
        codec, kind, invert = cs[0], cs[1], cs[2]
        if codec == "rle":
            values, lengths = payload

            def one_rle(v, l, qa):
                if kind == "set":
                    run = jnp.any(v[:, None] == qa[None, :], axis=1)
                    if invert:
                        run = ~run
                else:
                    run = _u32_range_hit(v, qa)
                return jnp.repeat(run, l, total_repeat_length=n_pad)

            if kind == "set":
                return jax.vmap(one_rle)(values, lengths, qarg)
            return jax.vmap(lambda v, l: one_rle(v, l, qarg))(values, lengths)
        if codec == "dct":
            dvals, idx = payload

            def one_dct(dv, ix, qa):
                if kind == "set":
                    hit = jnp.any(dv[:, None] == qa[None, :], axis=1)
                    if invert:
                        hit = ~hit
                else:
                    hit = _u32_range_hit(dv, qa)
                return hit[ix]

            if kind == "set":
                return jax.vmap(one_dct)(dvals, idx, qarg)
            return jax.vmap(lambda dv, ix: one_dct(dv, ix, qarg))(dvals, idx)
        # dbp: range only (two-limb u64 values)
        from tempo_tpu.ops.pallas_kernels import dbp_decode_limbs

        words, first_hi, first_lo, width = payload

        def one_dbp(w, fh, fl, wd):
            h, l = dbp_decode_limbs(w, fh, fl, wd, n_pad)
            return _limb_ge(h, l, qarg[0], qarg[1]) \
                & _limb_le(h, l, qarg[2], qarg[3])

        return jax.vmap(one_dbp)(words, first_hi, first_lo, width)

    # the name is the program's in a profiler trace: layer, then work
    def compiled_query_range_counts(t_s, valid, payloads, qargs, tb, nb):
        def per_query(qa, tb_q, nb_q):
            hit = valid
            for i, cs in enumerate(colsig):
                hit = hit & col_hit(cs, payloads[i], qa[i])
            # window + binning: u32 throughout; the t_s >= start guard
            # neutralizes the subtraction's wrap exactly like the
            # interpreter's signed comparison does
            ok = hit & (t_s >= tb_q[0])
            bins = (t_s - tb_q[0]) // tb_q[1]
            ok = ok & (bins < nb_q)
            idx = jnp.where(ok, bins, jnp.uint32(slot_pad)).astype(jnp.int32)
            return jnp.zeros(slot_pad + 1, jnp.int32) \
                .at[idx.reshape(-1)].add(1)[:slot_pad]

        return jax.vmap(per_query, in_axes=(0, 0, 0))(qargs, tb, nb)

    return jax.jit(compiled_query_range_counts)
