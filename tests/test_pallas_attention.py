"""Flash-attention Pallas kernel vs the XLA ground truth.

Interpret mode on CPU (same convention as test_pallas_lrn.py): the
kernel math - online-softmax tiling, causal tile skipping, lse/delta
backward recompute - is validated off-chip; on-TPU execution uses the
identical program with interpret=False.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cxxnet_tpu.ops import attention as A
from cxxnet_tpu.ops import pallas_attention as PA


def _qkv(b=2, h=3, s=32, d=16, dtype=np.float32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: rng.randn(b, h, s, d).astype(dtype)  # noqa: E731
    return jnp.asarray(mk()), jnp.asarray(mk()), jnp.asarray(mk())


@pytest.fixture
def small_blocks(monkeypatch):
    """Force multi-tile grids at test sizes."""
    monkeypatch.setattr(PA, "BLOCK_Q", 8)
    monkeypatch.setattr(PA, "BLOCK_K", 8)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_naive(causal, small_blocks):
    q, k, v = _qkv()
    ref = A.naive_attention(q, k, v, causal=causal)
    out = PA.flash_attention(q, k, v, causal, None, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_single_tile_and_uneven_blocks(small_blocks):
    # s not divisible by 8 -> _blocks falls back to a divisor
    q, k, v = _qkv(s=12)
    ref = A.naive_attention(q, k, v, causal=True)
    out = PA.flash_attention(q, k, v, True, None, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_naive(causal, small_blocks):
    q, k, v = _qkv(b=1, h=2, s=16, d=8)

    def loss_ref(q, k, v):
        return jnp.sum(jnp.cos(A.naive_attention(q, k, v, causal=causal)))

    def loss_pal(q, k, v):
        return jnp.sum(jnp.cos(
            PA.flash_attention(q, k, v, causal, None, True)))

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gp = jax.grad(loss_pal, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gr, gp):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=1e-4, atol=1e-5,
            err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("h,hkv,s,window", [
    (2, 2, 32, 0),        # full causal attention
    (4, 4, 64, 24),       # a window: whole tiles lie left of it
    (14, 2, 32, 0),       # 7:1 grouped heads, as the `gqa` layers run
    (7, 1, 40, 16),       # ... under a window
])
def test_kernels_with_statistics_on_lanes_match_naive(h, hkv, s, window,
                                                      small_blocks):
    """Forward, dq, dk and dv against `naive_attention`, and what the
    custom vjp keeps for the backward: the row statistic is (b, h, 1,
    s), the positions last (on the chip: on lanes), never a trailing
    dim of 8 that the chip's tiling would pad to 128."""
    r = np.random.RandomState(1)
    q = jnp.asarray(r.randn(2, h, s, 8), jnp.float32)
    k = jnp.asarray(r.randn(2, hkv, s, 8), jnp.float32)
    v = jnp.asarray(r.randn(2, hkv, s, 8), jnp.float32)

    def kern(q, k, v):
        return PA.flash_attention(q, k, v, True, None, True, window)

    def naive(q, k, v):
        return A.naive_attention(q, k, v, causal=True, window=window)

    out, res = PA._vjp_fwd(q, k, v, True, None, True, window)
    assert [x.shape for x in res] == [q.shape, k.shape, v.shape, q.shape,
                                      (2, h, 1, s)]
    np.testing.assert_allclose(out, naive(q, k, v), rtol=1e-5, atol=1e-5)
    # lse is the log of the row's sum of exponentials, row by row
    scores = jnp.einsum("bhqd,bhkd->bhqk", q,
                        jnp.repeat(k, h // hkv, axis=1)) / np.sqrt(8.0)
    qi, ki = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = (ki <= qi) & ((qi - ki < window) if window else True)
    np.testing.assert_allclose(
        res[4][:, :, 0], jax.nn.logsumexp(
            jnp.where(seen, scores, -jnp.inf), axis=-1),
        rtol=1e-5, atol=1e-5)
    gk = jax.grad(lambda *a: jnp.sum(jnp.cos(kern(*a))), (0, 1, 2))(q, k, v)
    gn = jax.grad(lambda *a: jnp.sum(jnp.cos(naive(*a))), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gk, gn):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5,
                                   err_msg=f"d{name}")


def test_query_tile_is_the_statistics_lane_dim():
    """A row statistic's block is (1, 1, 1, bq): Mosaic takes a last
    block dim that is a multiple of 128 or the whole array's, so the
    query tile is chosen so, and a length that has no such divisor
    goes the XLA route."""
    def tiles(s, d=128, dtype=jnp.bfloat16):
        q = jax.ShapeDtypeStruct((1, 4, s, d), dtype)
        return PA._tiles_of(q, s), PA._tile_ok(q, s)

    assert tiles(16384) == ((1024, 1024), True)      # the `gqa` cell
    assert tiles(8192, 192) == ((512, 512), True)    # the `mla` cell
    assert tiles(784, dtype=jnp.float32) == ((784, 784), True)   # whole
    # 1,536 = 12 x 128: 768 both ways; 1,200 has no divisor that is a
    # multiple of 128 and is too long for one tile
    assert tiles(1536) == ((768, 768), True)
    assert tiles(1200) == ((600, 400), False)


def test_bf16_forward(small_blocks):
    q, k, v = _qkv(s=16)
    ref = A.naive_attention(q, k, v, causal=True)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = PA.flash_attention(qb, kb, vb, True, None, True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=0.05, atol=0.05)


def test_custom_scale(small_blocks):
    q, k, v = _qkv(s=16)
    ref = A.naive_attention(q, k, v, scale=0.5)
    out = PA.flash_attention(q, k, v, False, 0.5, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_routing_gate():
    """The single-device route follows the MESH the step runs over,
    not the host's device count (8 here): a one-device mesh takes the
    kernel, a multi-device mesh the shard_map route (pallas_call has
    no GSPMD rule), the zero_stage>=2 region (None bound) declines."""
    from jax.sharding import Mesh
    from cxxnet_tpu.parallel.mesh import active_mesh
    assert jax.device_count() == 8
    q, _, _ = _qkv(b=8, s=32, d=16)
    assert not PA.use_flash(q)          # cpu backend, no hook
    assert not PA.use_flash_sharded(q, None)
    PA._FORCE_INTERPRET = True
    try:
        mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))
        one = Mesh(np.asarray(jax.devices()[:1]), ("data",))
        assert PA.use_flash(q)          # direct call: one device
        with active_mesh(one):
            assert PA.use_flash(q)
        with active_mesh(mesh):
            assert not PA.use_flash(q)
        with active_mesh(None):
            assert not PA.use_flash(q)
        assert PA.use_flash_sharded(q, mesh)
        # untileable sublane (seq 12 -> best divisor 12 or 4, not 8-mult)
        q2, _, _ = _qkv(s=12)
        assert not PA._tile_ok(q2, 12)
        # prime seq would degrade to 1-wide tiles: gated out
        q3, _, _ = _qkv(s=31)
        assert not PA._tile_ok(q3, 31)
    finally:
        PA._FORCE_INTERPRET = False


def test_sharded_matches_naive():
    from jax.sharding import Mesh
    q, k, v = _qkv(b=8, h=2, s=16, d=8)
    ref = A.naive_attention(q, k, v, causal=True)
    mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2),
                ("data", "model"))
    PA._FORCE_INTERPRET = True
    try:
        out = PA.flash_attention_sharded(q, k, v, mesh, causal=True)
    finally:
        PA._FORCE_INTERPRET = False
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
