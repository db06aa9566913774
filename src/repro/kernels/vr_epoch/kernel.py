"""One CentralVR local epoch as one Pallas kernel (TPU target).

The local epoch is a chain of ns dependent steps over a d-vector, and as
an XLA ``while`` loop each step costs ~10 small ops and 2-5 us of loop
latency. Here the step loop runs inside the kernel, every worker of the
caller's stack stepping at once:

    z   = sum(a * x)                      (float32, in XLA's order)
    s   = l'(z; b)                        (convex._pointwise_residual)
    v   = (s - t_old) * a + gbar + 2 lam x
    x   = prox(x - eta * v)
    acc = acc + s * a / ns,   s is written out

the arithmetic of ``distributed._local_centralvr_epoch`` in its order.
With ``t_old = 0`` and ``gbar = 0`` it is the init epoch's SGD step
exactly, since ``(s - 0) * a + 0 == s * a``.

Layout: the rows arrive in visit order (``ops.py`` gathers them), as
``(steps, p, d_pad)`` with the p workers in sublanes and the features in
lanes 0..d-1; lane d holds the row's label ``b`` and lane d+1 its old
table entry, so one row carries everything a step reads. The grid runs
over blocks of ``block`` steps (``"arbitrary"``: one after another), each
block streamed from HBM by a double-buffered BlockSpec, so the shard's
size is bounded by HBM, not VMEM. ``x`` and ``acc`` live in their output
blocks, which stay in VMEM across the grid, and in registers inside a
block; a block's new residuals are written lane-dense, ``(p, block)``.
Steps at or past ``ns`` (the padding of the last block) change nothing.
``eta`` and ``lam`` are runtime scalars in SMEM (scalar prefetch), so a
new step size reuses the compiled kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import convex
from repro.kernels.vr_update.kernel import _prox_epilogue

LANES = 128
# steps a grid step streams: a (block, 8, 128) float32 block of rows is
# 512 KiB, 1 MiB double-buffered
BLOCK = 128
# steps written out in one iteration of the in-kernel loop (Mosaic
# unrolls a loop only fully, so the body repeats the step); divides BLOCK
UNROLL = 2


def _row_sums(prod, d: int):
    """Each row's sum of ``prod`` (p, d_pad), in the order XLA's TPU
    reduce sums the same ``a . x`` in the scan it replaces, so the kernel
    rounds as the scan does: one row across lanes at p = 1; for stacked
    rows, lanes j, j+8, j+16, ... in turn into 8 partial sums, then
    halved 8 -> 4 -> 2 -> 1."""
    p, dp = prod.shape
    if p == 1:
        return jnp.sum(prod, axis=1, keepdims=True)
    part = prod[:, 0:8]
    for k in range(1, -(-d // 8)):
        part = part + prod[:, 8 * k:8 * k + 8]
    if 8 * -(-d // 8) < dp:
        part = part + 0.0     # XLA adds the all-zero groups too
    for h in (4, 2, 1):
        part = part[:, 0:h] + part[:, h:2 * h]
    return part


def _epoch_kernel(sc_ref, a_ref, x_ref, g_ref, xo_ref, s_ref, acc_ref, *,
                  kind: str, prox, ns: int, d: int, block: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        xo_ref[...] = x_ref[...]
        acc_ref[...] = jnp.zeros_like(acc_ref)

    eta, lam2 = sc_ref[0], 2.0 * sc_ref[1]
    gbar = g_ref[...]
    p, dp = gbar.shape
    base = i * block
    feature = jax.lax.broadcasted_iota(jnp.int32, (p, dp), 1) < d
    slot = jax.lax.broadcasted_iota(jnp.int32, (p, block), 1)

    def step(t, x, acc, s_blk):
        row = a_ref[t]
        a = jnp.where(feature, row, 0.0)
        z = _row_sums(a * x, d)
        s = convex._pointwise_residual(z, row[:, d:d + 1], kind)
        v = (s - row[:, d + 1:d + 2]) * a + gbar + lam2 * x
        xn = x - eta * v
        if prox is not None:
            xn = _prox_epilogue(xn, eta, prox)
        live = base + t < ns
        return (jnp.where(live, xn, x),
                jnp.where(live, acc + s * a / ns, acc),
                jnp.where(slot == t, s, s_blk))

    def steps(k, carry):
        for u in range(UNROLL):
            carry = step(k * UNROLL + u, *carry)
        return carry

    x, acc, s_blk = jax.lax.fori_loop(
        0, block // UNROLL, steps,
        (xo_ref[...], acc_ref[...], jnp.zeros((p, block), xo_ref.dtype)))
    xo_ref[...] = x
    acc_ref[...] = acc
    s_ref[0] = s_blk


def vr_epoch(scalars, rows, x, gbar, *, kind: str, prox, ns: int, d: int,
             block: int, interpret: bool = False):
    """``scalars`` (2,) = (eta, lam); ``rows`` (steps, p, d_pad) in visit
    order, features in lanes 0..d-1, ``b`` in lane d and the old table
    entry in lane d+1, ``steps`` a multiple of ``block`` and at least
    ``ns``; ``x``/``gbar`` (p, d_pad). Returns ``(x, s, acc)``: the final
    iterates, the new residuals in visit order as (steps // block, p,
    block), and the running means of ``s * a`` over the ns live steps."""
    steps, p, dp = rows.shape
    assert steps % block == 0 and steps >= ns and dp >= d + 2, (
        steps, block, ns, dp, d)
    nblk = steps // block
    vec = pl.BlockSpec((p, dp), lambda i, sc: (0, 0))
    dt = rows.dtype
    return pl.pallas_call(
        functools.partial(_epoch_kernel, kind=kind, prox=prox, ns=ns, d=d,
                          block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nblk,),
            in_specs=[pl.BlockSpec((block, p, dp), lambda i, sc: (i, 0, 0)),
                      vec, vec],
            out_specs=[vec,
                       pl.BlockSpec((1, p, block), lambda i, sc: (i, 0, 0)),
                       vec]),
        out_shape=[jax.ShapeDtypeStruct((p, dp), dt),
                   jax.ShapeDtypeStruct((nblk, p, block), dt),
                   jax.ShapeDtypeStruct((p, dp), dt)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="vr_epoch",
    )(scalars, rows, x, gbar)
