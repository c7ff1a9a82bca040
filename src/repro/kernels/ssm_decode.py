"""Pallas TPU kernel: the Mamba-2 decode state update over a slot pool.

One decode token per sequence moves each live sequence's whole SSM state,
``(H, P, N)`` float32, once in and once out: at granite-4.0-h widths that
is 4.2 MB per layer per sequence, against a few KB of inputs. The kernel
streams it. Per sequence b and head h, with the state in row ``rows[b]``
of the pool::

    state[h] <- state[h] * exp(dt[h] * A[h]) + (dt[h] * x[h]) (x) B[g(h)]
    y[h]     <- state[h] . C[g(h)] + D[h] * x[h]

(``(x)`` an outer product over (P, N), g(h) the head's B/C group). The
pool is updated in place (``input_output_aliases``); rows that point at
one scratch slot (idle batch rows) write only that slot.

Grid ``(B, H // hb)``: one step is one sequence and ``hb`` heads, a
``(hb, P, N)`` state block in and out (double-buffered, 4 MiB at hb = 32).
The state keeps N on lanes, as the pool stores it, so the per-head x is
wanted as a (P, 1) column: x and y travel transposed, ``(B, P, H)``, and a
head's column is selected from the ``(P, H)`` block by a lane mask and a
lane sum. y accumulates over the head blocks of a sequence and is written
when the sequence's last block is done.

The pure-jnp twin is ``kernels/ref.py::ssm_decode_ref``; tests compare the
two in interpret mode and compile the kernel for a described v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
HEADS_PER_STEP = 32


def _kernel(rows_ref, st_ref, xt_ref, dt_ref, a_ref, d_ref, b_ref, c_ref,
            y_ref, st_out, *, hb, heads_per_group):
    # st_ref/st_out: (hb, P, N); xt_ref/y_ref: (P, H); dt_ref: (1, H);
    # a_ref/d_ref: (1, H); b_ref/c_ref: (G, N)
    j = pl.program_id(1)
    xt = xt_ref[...]
    dt = dt_ref[...]

    @pl.when(j == 0)
    def _skip():
        y_ref[...] = d_ref[...] * xt

    u = xt * dt                                   # (P, H)  dt * x
    decay = jnp.exp(dt * a_ref[...])              # (1, H)
    lane = jax.lax.broadcasted_iota(jnp.int32, decay.shape, 1)

    def head(i, y):
        h = j * hb + i
        sel = lane == h                           # (1, H)
        u_col = jnp.sum(jnp.where(sel, u, 0.0), axis=1, keepdims=True)
        dec = jnp.sum(jnp.where(sel, decay, 0.0), axis=1, keepdims=True)
        g = h // heads_per_group
        b = b_ref[pl.ds(g, 1), :]                 # (1, N)
        c = c_ref[pl.ds(g, 1), :]
        new = st_ref[i] * dec + u_col * b         # (P, N)
        st_out[i] = new
        y_col = jnp.sum(new * c, axis=1, keepdims=True)          # (P, 1)
        return y + jnp.where(sel, y_col, 0.0)

    y_ref[...] += jax.lax.fori_loop(0, hb, head, jnp.zeros(xt.shape, F32))


def ssm_decode_fwd(state, rows, x, dt, a, Bm, Cm, d_skip, *,
                   interpret: bool = False):
    """state (R, H, P, N) f32, rows (B,) int32, x (B, H, P) f32, dt (B, H)
    f32 (after softplus), a (H,) = -exp(a_log), Bm/Cm (B, G, N) f32,
    d_skip (H,). Returns (y (B, H, P) f32 with the D skip, state)."""
    R, H, P, N = state.shape
    B = x.shape[0]
    G = Bm.shape[1]
    hb = HEADS_PER_STEP if H % HEADS_PER_STEP == 0 else H
    kernel = functools.partial(_kernel, hb=hb, heads_per_group=H // G)
    st_spec = pl.BlockSpec((None, hb, P, N),
                           lambda b, j, rows: (rows[b], j, 0, 0))
    per_seq = lambda shape: pl.BlockSpec((None,) + shape,
                                         lambda b, j, rows: (b, 0, 0))
    whole = pl.BlockSpec((1, H), lambda b, j, rows: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H // hb),
        in_specs=[st_spec, per_seq((P, H)), per_seq((1, H)), whole, whole,
                  per_seq((G, N)), per_seq((G, N))],
        out_specs=[per_seq((P, H)), st_spec],
    )
    yt, state = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, P, H), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        input_output_aliases={1: 1},       # state (after rows) -> output 1
        # y accumulates over a sequence's head blocks: keep them in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssm_decode_fwd",
    )(rows, state, jnp.swapaxes(x, 1, 2), dt[:, None, :], a[None, :],
      d_skip[None, :].astype(F32), Bm, Cm)
    return jnp.swapaxes(yt, 1, 2), state
