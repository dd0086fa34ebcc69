"""Kimi Delta Attention: the gated delta rule with a per-channel decay,
computed in chunks (the WY form).

Per head, with state S (d_k x d_v), S_0 = 0:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                       alpha_t = exp(g_t) in (0, 1)^d_k

Writing u_t = beta_t (v_t - S_{t-1}^T (alpha_t * k_t)) gives
S_t = Diag(alpha_t) S_{t-1} + k_t u_t^T, and inside a chunk that starts
from S with G_t = sum_{i<=t} g_i (per channel, from the chunk's start):

    (I + A) U = beta * (V - (exp(G) * K) S)
        A[t, i] = beta_t sum_c k_tc k_ic exp(G_tc - G_ic),  i < t
    O = (exp(G) * Q) S + tril(P) U
        P[t, i] = sum_c q_tc k_ic exp(G_tc - G_ic),         i <= t
    S' = Diag(exp(G_C)) S + (exp(G_C - G) * K)^T U

so a chunk solves one unit-lower-triangular system (T = (I + A)^-1,
W = T (beta exp(G) K), U0 = T (beta V), U = U0 - W S) and the state is
carried between chunks by a `lax.scan`. This is the recurrence itself,
regrouped: nothing is approximated.

Every decay that appears is exp of a difference G_t - G_i with i <= t,
so at most 1. A[t, i] and P[t, i] are NOT computed as (k_t exp(G_t)) .
(k_i exp(-G_i)): exp(-G_i) overflows float32 once a chunk's decay
passes e^88. Instead a chunk is cut into sub-blocks of `SUB` positions.
For a row block a and the columns before it, R_a = G just before the
block, and exp(G_t - G_i) = exp(G_t - R_a) exp(R_a - G_i), both factors
at most 1: a product of two matrices. Inside a diagonal block the
differences are taken pair by pair (SUB x SUB x d_k terms).

The decay g, its sums, every exp and the triangular solve are float32;
the matrix products take their operands in the activations' dtype
(bf16 in a bf16 step) and accumulate in float32.

What runs where. The chunk-local part - G, A and P, all a chunk
computes from its own q, k, g and beta - has two routes, chosen by
what `kda_chunked` can see and by no key (`_kernel_route`): the two
Pallas kernels of ops/pallas_kda.py on a TPU, where the traced step
spans one device (no mesh axis over heads or sequence), the chunk is
64 and d_k and d_v are multiples of 128; `local_xla` below everywhere
else - the CPU, a mesh, other shapes - which is also what the kernels
are compared against. The same sub-blocks and precisions on both; on
the kernel route the SUB x SUB x d_k pairwise tensors live in VMEM and
never in HBM. `route.pallas` / `route.xla` in the step's text says
which ran. The solve, W, U0 and the scan over chunks are XLA on either
route.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

SUB = 16        # positions a sub-block; a chunk is a multiple of it


def _mm(eq, a, b, dt):
    return jnp.einsum(eq, a.astype(dt), b.astype(dt),
                      preferred_element_type=jnp.float32)


def _pair_matrices(q, k, g_cum, dt):
    """A' (k against k, before beta) and P (q against k), each (..., C, C)
    float32 with the decays exp(G_t - G_i) inside; entries with i > t are
    whatever (the caller masks). q, k, g_cum: (..., C, d)."""
    c, d = k.shape[-2:]
    sub = SUB if c % SUB == 0 else c
    nsb = c // sub
    lead = k.shape[:-2]
    gb = g_cum.reshape(lead + (nsb, sub, d))
    kb = k.astype(jnp.float32).reshape(lead + (nsb, sub, d))
    qb = q.astype(jnp.float32).reshape(lead + (nsb, sub, d))
    # diagonal blocks, pair by pair: exp(G_t - G_i) for i <= t, else 0
    diff = gb[..., :, None, :] - gb[..., None, :, :]     # (.., nsb,t,i,d)
    low = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    dec = jnp.exp(jnp.where(low, diff, -jnp.inf))
    kk_d = jnp.sum(kb[..., :, None, :] * kb[..., None, :, :] * dec, -1)
    qk_d = jnp.sum(qb[..., :, None, :] * kb[..., None, :, :] * dec, -1)
    eye = jnp.eye(nsb, dtype=jnp.float32)
    kk = jnp.einsum("...atj,ab->...atbj", kk_d, eye)
    qk = jnp.einsum("...atj,ab->...atbj", qk_d, eye)
    if nsb > 1:
        # row block a against every column before it, through R_a
        r = jnp.concatenate(
            [jnp.zeros_like(gb[..., :1, 0, :]), gb[..., :-1, -1, :]], -2)
        rows = jnp.exp(gb - r[..., :, None, :])           # <= 1
        cols = jnp.exp(jnp.minimum(
            r[..., :, None, None, :] - gb[..., None, :, :, :], 0.0))
        kcol = kb[..., None, :, :, :] * cols              # (.., a, b, j, d)
        before = (jnp.arange(nsb)[:, None] > jnp.arange(nsb)[None, :])
        before = before[:, None, :, None]
        kk = kk + jnp.where(before, _mm(
            "...atd,...abjd->...atbj", kb * rows, kcol, dt), 0.0)
        qk = qk + jnp.where(before, _mm(
            "...atd,...abjd->...atbj", qb * rows, kcol, dt), 0.0)
    return kk.reshape(lead + (c, c)), qk.reshape(lead + (c, c))


def local_xla(q, k, g, beta):
    """The chunk-local part in plain XLA ops: the CPU route, and what
    the Pallas kernels (ops/pallas_kda.py) are compared against (tests,
    chip_smoke.py). q, k, g (..., C, d), beta (..., C, 1), g and beta
    float32 -> A (beta applied, zero from the diagonal up), P (zero
    above the diagonal), both (..., C, C), and G (..., C, d), float32."""
    chunk = k.shape[-2]
    # the running sum of g inside a chunk, as a product with a lower
    # triangle of ones at full float32 precision (`jnp.cumsum` is a
    # `reduce_window` on the chip: 8 ms a layer and direction at 8,192
    # positions, against 0.3 for this)
    g_cum = jnp.einsum(
        "ti,...id->...td", jnp.tril(jnp.ones((chunk, chunk), jnp.float32)),
        g, precision=lax.Precision.HIGHEST)
    kk, qk = _pair_matrices(q, k, g_cum, k.dtype)
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    a = jnp.where(strict, beta * kk, 0.0)
    p = jnp.where(strict | jnp.eye(chunk, dtype=bool), qk, 0.0)
    return a, p, g_cum


# test hook: take the kernel route off the TPU, the kernels in interpret
# mode
_FORCE_INTERPRET = False

_KERNEL_CHUNK = 64      # the chunk ops/pallas_kda.py's blocks are sized for
_LANES = 128


def _backend_ok() -> bool:
    return jax.default_backend() == "tpu" or _FORCE_INTERPRET


def _kernel_route(d_k: int, d_v: int, chunk: int):
    """ops/pallas_kda.py where its kernels run, else None: the chunk
    they are sized for and whole lane tiles of channels, a TPU, and a
    traced step that spans one device (parallel/mesh.py
    active_device_span: pallas_call has no GSPMD partitioning rule).
    The module, and Pallas with it (1.3-2 s of every process that
    imports it), is imported only once all of that holds: a net
    without a `kda` layer, or one off the TPU, never pays for it."""
    if chunk != _KERNEL_CHUNK or d_k % _LANES or d_v % _LANES:
        return None
    if not _backend_ok():
        return None
    from cxxnet_tpu.parallel.mesh import active_device_span
    if active_device_span() != 1:
        return None
    from cxxnet_tpu.ops import pallas_kda
    return pallas_kda


def kda_chunked(q, k, v, g, beta, chunk: int = 64):
    """q, k, g (b, T, H, d_k); v (b, T, H, d_v); beta (b, T, H);
    g = log alpha <= 0 in float32 -> o (b, T, H, d_v) in q's dtype.
    T need not divide into chunks: the tail is padded with positions
    that change nothing (k = 0, beta = 0, g = 0)."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    dt = q.dtype
    pad = (-t) % chunk
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n = (t + pad) // chunk

    def chunks(a):       # (b, T, H, d) -> (b, H, n, C, d)
        return jnp.moveaxis(a.reshape(b, n, chunk, h, a.shape[-1]), 3, 1)

    q, k, v = chunks(q), chunks(k), chunks(v)
    g = chunks(g.astype(jnp.float32))
    beta = chunks(beta.astype(jnp.float32)[..., None])      # (b,H,n,C,1)
    # the scope says, in the step's text and in any trace, which route
    # the chunk-local part took
    pk = _kernel_route(dk, dv, chunk)
    with jax.named_scope("route.pallas" if pk else "route.xla"):
        a, p, g_cum = (pk.local_pallas(q, k, g, beta, _FORCE_INTERPRET)
                       if pk else local_xla(q, k, g, beta))
    eye = jnp.broadcast_to(jnp.eye(chunk, dtype=jnp.float32), a.shape)
    t_inv = lax.linalg.triangular_solve(
        a + eye, eye, left_side=True, lower=True, unit_diagonal=True)
    decay = jnp.exp(g_cum)                                  # <= 1
    kf = k.astype(jnp.float32)
    w = _mm("...ti,...id->...td", t_inv, beta * decay * kf, dt)
    u0 = _mm("...ti,...id->...td", t_inv, beta * v.astype(jnp.float32), dt)
    q_in = q.astype(jnp.float32) * decay
    g_end = g_cum[..., -1:, :]                              # (b,H,n,1,dk)
    k_out = kf * jnp.exp(g_end - g_cum)                     # <= 1

    def step(s, xs):
        w_n, u0_n, q_n, p_n, k_n, ge_n = xs
        u = u0_n - _mm("...td,...dv->...tv", w_n, s, dt)
        o = (_mm("...td,...dv->...tv", q_n, s, dt)
             + _mm("...ti,...iv->...tv", p_n, u, dt))
        s = (jnp.exp(ge_n)[..., 0, :, None] * s
             + _mm("...td,...tv->...dv", k_n, u, dt))
        return s, o

    xs = tuple(jnp.moveaxis(x, 2, 0)
               for x in (w, u0, q_in, p, k_out, g_end))
    s0 = jnp.zeros((b, h, dk, dv), jnp.float32)
    _, o = lax.scan(step, s0, xs)                           # (n,b,H,C,dv)
    o = jnp.moveaxis(o, 0, 2)                               # (b,H,n,C,dv)
    o = jnp.moveaxis(o, 1, 3).reshape(b, n * chunk, h, dv)
    return o[:, :t].astype(dt)


def kda_recurrent(q, k, v, g, beta):
    """The same recurrence a position at a time, float32: what the
    chunked form is tested against inside the program (the benchmark's
    reference has its own)."""
    b, t, h, dk = q.shape

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None] * s
        ks = jnp.einsum("bhk,bhkv->bhv", k_t, s)
        s = s + (b_t[..., None] * k_t)[..., None] * (v_t - ks)[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s)

    xs = tuple(jnp.moveaxis(a.astype(jnp.float32), 1, 0)
               for a in (q, k, v, g, beta))
    s0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, o = lax.scan(step, s0, xs)
    return jnp.moveaxis(o, 0, 1)
