"""Mamba-2's state-space duality (SSD): a scalar decay a head, B and C
shared by a group of heads, computed in chunks.

Per head h, with state S (P x N), S_0 = 0, and a_t = dt_t A_h <= 0:

    S_t = exp(a_t) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t                    x_t (P,), B_t and C_t (N,)

Inside a chunk that starts from S, with G_t = sum_{i<=t} a_i from the
chunk's start:

    y_t = sum_{i<=t} (C_t . B_i) exp(G_t - G_i) dt_i x_i     the chunk's own
        + exp(G_t) S C_t                                    what came before
    S'  = exp(G_C) S + sum_i exp(G_C - G_i) dt_i x_i B_i^T

KDA's chunked form (ops/kda.py) without the delta rule and its
triangular solve. Every decay is exp of a difference G_t - G_i with
i <= t, or of G_C - G_i, so at most 1: the pair matrix is built from
the differences and never from exp(-G), which overflows float32 once a
chunk's decay passes e^88.

What lies between chunks is a state of P x N a head and no product: a
chunk's contribution to the state and its output from the state before
it are products over the whole sequence at once, and the `lax.scan`
over chunks only decays and adds (KDA's carry runs its products inside
the scan, because there the update reads the state).

The decays, their sums and every exp are float32; the matrix products
take their operands in the activations' dtype (bf16 in a bf16 step) and
accumulate in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _mm(eq, *ops, dt):
    return jnp.einsum(eq, *(a.astype(dt) for a in ops),
                      preferred_element_type=jnp.float32)


def ssd_chunked(x, dt, a, b, c, chunk: int = 128):
    """x (batch, T, H, P); dt (batch, T, H) float32, the step after its
    softplus; a (H,) float32, A = -exp(A_log); b, c (batch, T, G, N),
    head h reading group h // (H / G) -> y (batch, T, H, P) in x's dtype,
    without the D term. T need not divide into chunks: the tail is padded
    with positions of dt = 0, which change nothing before them."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g
    dtype = x.dtype
    pad = (-t) % chunk
    if pad:
        x, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for v in (x, b, c))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    nc = (t + pad) // chunk
    # (batch, chunks, C, G, R, ...): a head is (group, head in group)
    x = x.reshape(bsz, nc, chunk, g, r, p)
    dt = dt.astype(jnp.float32).reshape(bsz, nc, chunk, g, r)
    b = b.reshape(bsz, nc, chunk, g, n)
    c = c.reshape(bsz, nc, chunk, g, n)
    la = dt * a.astype(jnp.float32).reshape(g, r)
    # the running sum inside a chunk as a product with a lower triangle
    # of ones at full float32 precision (`jnp.cumsum` is a
    # `reduce_window` on the chip, ops/kda.py)
    cum = jnp.einsum("ti,bnigr->bntgr",
                     jnp.tril(jnp.ones((chunk, chunk), jnp.float32)), la,
                     precision=lax.Precision.HIGHEST)
    xdt = x.astype(jnp.float32) * dt[..., None]
    with jax.named_scope("intra"):
        # exp(G_t - G_i) for i <= t, pair by pair; 0 above the diagonal
        low = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None, None]
        diff = cum[:, :, :, None] - cum[:, :, None, :]    # (b,n,t,i,g,r)
        decay = jnp.exp(jnp.where(low, diff, -jnp.inf))
        cb = _mm("bntgs,bnigs->bntig", c, b, dt=dtype)
        y = _mm("bntigr,bnigrp->bntgrp", cb[..., None] * decay, xdt,
                dt=dtype)
    with jax.named_scope("state"):
        end = cum[:, :, -1]                                # (b, n, g, r)
        into = _mm("bnigs,bnigrp->bngrps", b,
                   xdt * jnp.exp(end[:, :, None] - cum)[..., None], dt=dtype)

        def carry(s, xs):
            d, add = xs
            return jnp.exp(d)[..., None, None] * s + add, s

        s0 = jnp.zeros((bsz, g, r, p, n), jnp.float32)
        _, before = lax.scan(carry, s0, (jnp.moveaxis(end, 1, 0),
                                         jnp.moveaxis(into, 1, 0)))
        before = jnp.moveaxis(before, 0, 1)                # (b,n,g,r,p,s)
        y = y + jnp.exp(cum)[..., None] * _mm(
            "bntgs,bngrps->bntgrp", c, before, dt=dtype)
    y = y.reshape(bsz, nc * chunk, h, p)
    return y[:, :t].astype(dtype)


def ssd_recurrent(x, dt, a, b, c):
    """The same recurrence a position at a time, float32: what the
    chunked form is tested against inside the program (the benchmark's
    reference computes the quadratic dual form)."""
    bsz, t, h, p = x.shape
    r = h // b.shape[2]
    bh = jnp.repeat(b.astype(jnp.float32), r, axis=2)     # (b, T, H, N)
    ch = jnp.repeat(c.astype(jnp.float32), r, axis=2)

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs
        s = (jnp.exp(dt_t * a)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t)

    xs = tuple(jnp.moveaxis(v.astype(jnp.float32), 1, 0)
               for v in (x, dt, bh, ch))
    s0 = jnp.zeros((bsz, h, p, b.shape[3]), jnp.float32)
    _, y = lax.scan(step, s0, xs)
    return jnp.moveaxis(y, 0, 1)
