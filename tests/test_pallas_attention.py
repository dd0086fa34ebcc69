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


def _backward_both_ways(q, k, v, causal, window, monkeypatch):
    """dq, dk, dv from the fused kernel and from the two kernels (forced
    by a budget of 0), as the custom vjp's backward calls them."""
    _, res = PA._vjp_fwd(q, k, v, causal, None, True, window)
    do = jnp.asarray(np.random.RandomState(2).randn(*q.shape), q.dtype)

    def backward():
        return PA._vjp_bwd(causal, None, True, window, res, do)

    assert PA.fused_backward(q, k)
    fused = backward()
    budget = PA._FUSED_VMEM
    monkeypatch.setattr(PA, "_FUSED_VMEM", 0)
    assert not PA.fused_backward(q, k)
    two = backward()
    monkeypatch.setattr(PA, "_FUSED_VMEM", budget)
    return fused, two


def _check_against_two_kernels(fused, two):
    """dk and dv bit for bit (the same products summed in the same
    order), dq to float32 rounding (its scores are computed transposed)."""
    np.testing.assert_array_equal(fused[1], two[1], err_msg="dk")
    np.testing.assert_array_equal(fused[2], two[2], err_msg="dv")
    np.testing.assert_allclose(fused[0], two[0], rtol=1e-5, atol=1e-6,
                               err_msg="dq")


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


@pytest.mark.parametrize("causal,h,hkv,s,d,bq,bk", [
    pytest.param(False, 2, 2, 16, 8, 8, 8, id="False"),
    pytest.param(True, 2, 2, 16, 8, 8, 8, id="True"),
    # 4:1 groups, 16-wide heads, query tiles wider than key tiles
    pytest.param(False, 8, 2, 32, 16, 16, 8, id="noncausal-g4-d16"),
    pytest.param(True, 4, 1, 32, 16, 8, 16, id="causal-g4-d16"),
    # one query tile: every dq tile completes at the last key tile
    pytest.param(True, 7, 1, 16, 8, 16, 8, id="causal-g7-one-query-tile"),
    pytest.param(False, 3, 3, 16, 16, 16, 8, id="noncausal-one-query-tile"),
])
def test_gradients_match_naive(causal, h, hkv, s, d, bq, bk, monkeypatch):
    """The gradients through the custom vjp against `naive_attention`,
    and the fused backward's against the two kernels'."""
    monkeypatch.setattr(PA, "BLOCK_Q", bq)
    monkeypatch.setattr(PA, "BLOCK_K", bk)
    q, _, _ = _qkv(b=1, h=h, s=s, d=d)
    _, k, v = _qkv(b=1, h=hkv, s=s, d=d, seed=3)
    assert PA._tiles_of(q, s) == (bq, bk)

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
    _check_against_two_kernels(
        *_backward_both_ways(q, k, v, causal, 0, monkeypatch))


@pytest.mark.parametrize("h,hkv,s,window,bq,bk", [
    (2, 2, 32, 0, 8, 8),        # full causal attention
    (4, 4, 64, 24, 8, 8),       # a window: whole tiles lie left of it
    (14, 2, 32, 0, 8, 8),       # 7:1 grouped heads, as the `gqa` layers run
    (7, 1, 40, 16, 8, 8),       # ... under a window
    # interior tiles beside edge tiles and dead steps, 7:1 groups
    (7, 1, 64, 0, 8, 8),        # 28 interior tiles, 28 wholly future
    (7, 1, 64, 20, 8, 8),       # a window off the tiling: left edges
    (7, 1, 64, 0, 16, 8),       # query tiles wider than key tiles
    (14, 2, 64, 32, 16, 8),     # ... under a window
    (8, 2, 64, 28, 8, 16),      # 4:1 groups, key tiles wider, a window
    (4, 1, 32, 0, 8, 8),        # 4:1 groups, full causal
])
def test_kernels_with_statistics_on_lanes_match_naive(
        h, hkv, s, window, bq, bk, small_blocks, monkeypatch):
    """Forward, dq, dk and dv against `naive_attention`, and what the
    custom vjp keeps for the backward: the row statistic is (b, h, 1,
    s), the positions last (on the chip: on lanes), never a trailing
    dim of 8 that the chip's tiling would pad to 128. Every case runs
    interior tiles, unmasked, beside masked edge tiles. The fused
    backward gives the two kernels' dk and dv bit for bit."""
    monkeypatch.setattr(PA, "BLOCK_Q", bq)
    monkeypatch.setattr(PA, "BLOCK_K", bk)
    r = np.random.RandomState(1)
    q = jnp.asarray(r.randn(2, h, s, 8), jnp.float32)
    k = jnp.asarray(r.randn(2, hkv, s, 8), jnp.float32)
    v = jnp.asarray(r.randn(2, hkv, s, 8), jnp.float32)

    def kern(q, k, v):
        return PA.flash_attention(q, k, v, True, None, True, window)

    def naive(q, k, v):
        return A.naive_attention(q, k, v, causal=True, window=window)

    assert PA._tiles_of(q, s) == (bq, bk)
    assert 0 < PA.masked_share(q, window) < 1
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
    _check_against_two_kernels(
        *_backward_both_ways(q, k, v, True, window, monkeypatch))


@pytest.mark.parametrize("s,bq,bk,window", [
    (64, 8, 8, 0),        # full causal
    (64, 8, 8, 16),       # a window that is a multiple of the tile
    (64, 8, 8, 20),       # ... and one that is not
    (64, 16, 8, 0),       # query tiles wider than key tiles
    (64, 16, 8, 28),
    (64, 8, 16, 28),      # key tiles wider
])
def test_a_tile_is_dead_edge_or_interior_as_its_pairs_say(
        s, bq, bk, window, monkeypatch):
    """The kernels' classes against brute force over positions: a dead
    tile holds no pair a query sees, an edge tile some, an interior tile
    all; and the `masked` and `tiles` counters count them so."""
    qpos, kpos = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = (kpos <= qpos) & ((qpos - kpos < window) if window else True)
    live = edge = 0
    for qo in range(0, s, bq):
        for ko in range(0, s, bk):
            n = seen[qo:qo + bq, ko:ko + bk].sum()
            assert bool(PA._tile_live(qo, ko, bq, bk, window)) == (n > 0)
            if n:
                masked = bool(PA._tile_edge(qo, ko, bq, bk, window))
                assert masked == (n < bq * bk)
                live, edge = live + 1, edge + masked
    assert 0 < edge < live
    monkeypatch.setattr(PA, "BLOCK_Q", bq)
    monkeypatch.setattr(PA, "BLOCK_K", bk)
    q = jax.ShapeDtypeStruct((1, 2, s, 8), jnp.float32)
    assert PA._tiles_of(q, s) == (bq, bk)
    assert PA.masked_share(q, window) == edge / live
    assert PA.tile_share(q, window) == live / len(PA._live_tiles(q, 0)[2])


def _blocks_asked(spec, sweep):
    return [tuple(int(x) for x in spec.index_map(*at)) for at in sweep]


def _copies(blocks):
    return [x for i, x in enumerate(blocks) if i == 0 or x != blocks[i - 1]]


@pytest.mark.parametrize("h,hkv,bq,bk,window", [
    (2, 2, 8, 8, 0), (8, 2, 8, 8, 0), (7, 1, 16, 8, 0), (4, 1, 8, 16, 0),
    (7, 1, 8, 8, 16), (8, 2, 8, 8, 20),
])
def test_a_dead_step_asks_for_its_live_neighbours_block(h, hkv, bq, bk,
                                                        window):
    """In the index maps of all four kernels a step that runs no tile
    asks for the block of the live step beside it, so along a row of
    `flash_fwd` / `flash_dq` (key blocks) and a head's column of
    `flash_dkv` and `flash_dqkv` (query blocks and their statistics) the
    block changes only at a live step: the pipeline copies nothing for a
    dead one. `flash_dqkv`'s outputs are written back when their index
    changes: each output block is asked for in one run of steps, in
    order, and the kernel writes it once, inside that run, at the step
    that completes it."""
    s = 64
    q = jax.ShapeDtypeStruct((1, h, s, 8), jnp.float32)
    k = jax.ShapeDtypeStruct((1, hkv, s, 8), jnp.float32)
    _, _, nq, nkv, group, steps, _, kspec, _ = PA._specs(
        q, k, True, window, (bq, bk))
    for hq in range(h):
        for qi in range(nq):
            sweep = [(0, hq, qi, j) for j in range(steps)]
            kis = [PA._kv_tile(qi, j, bq, bk, steps, window)
                   for j in range(steps)]
            live = [(0, hq // group, ki, 0) for ki in kis if ki >= 0 and
                    PA._tile_live(qi * bq, ki * bk, bq, bk, window)]
            assert _copies(_blocks_asked(kspec, sweep)) == live
    qsteps, qspec, _, rspec = PA._key_specs(q, k, True, window, (bq, bk))
    for hk in range(hkv):
        for ki in range(nkv):
            for g in range(group):
                sweep = [(0, hk, ki, g * qsteps + j) for j in range(qsteps)]
                qis = [PA._q_tile(ki, j, bq, bk, window)
                       for j in range(qsteps)]
                live = [qi for qi in qis if qi < nq and
                        PA._tile_live(qi * bq, ki * bk, bq, bk, window)]
                hq = hk * group + g
                assert _copies(_blocks_asked(qspec, sweep)) == [
                    (0, hq, qi, 0) for qi in live]
                assert _copies(_blocks_asked(rspec, sweep)) == [
                    (0, hq, 0, qi) for qi in live]
    (_, _, _, qsteps), (qspec, _, rspec, dqspec, dkvspec) = (
        PA._fused_specs(q, k, True, window, (bq, bk)))
    for hk in range(hkv):
        dkv_asked, dkv_written = [], []
        for g in range(group):
            hq = hk * group + g
            sweep = [(0, hk, g, ki, j) for ki in range(nkv)
                     for j in range(qsteps)]
            live, dq_asked, dq_written = [], [], []
            for at in sweep:
                ki, qi = at[3], PA._q_tile(at[3], at[4], bq, bk, window)
                if qi < nq and PA._tile_live(qi * bq, ki * bk, bq, bk,
                                             window):
                    live.append(qi)
                first, after = PA._dq_done(ki, True, bq, bk, nq, nkv)
                asked = tuple(int(x) for x in dqspec.index_map(*at))
                dq_asked.append(asked)
                if first <= qi < int(after):     # the kernel writes dq
                    assert asked == (0, hq, qi, 0)
                    # ... after the query tile's last live key tile
                    assert ki == min((qi * bq + bq - 1) // bk, nkv - 1)
                    dq_written.append(qi)
                dkv_asked.append(
                    tuple(int(x) for x in dkvspec.index_map(*at)))
                if g == group - 1 and at[4] == qsteps - 1:
                    dkv_written.append(len(dkv_asked) - 1)
            # query blocks as the dk/dv pass asks for them
            assert _copies(_blocks_asked(qspec, sweep)) == [
                (0, hq, qi, 0) for qi in _copies(live)]
            assert _copies(_blocks_asked(rspec, sweep)) == [
                (0, hq, 0, qi) for qi in _copies(live)]
            # each dq block once, in order, written once
            assert _copies(dq_asked) == [(0, hq, qi, 0) for qi in range(nq)]
            assert dq_written == list(range(nq))
        # a key tile's dk/dv block is asked for from the group's first
        # head on, and written at the last head's last step
        assert _copies(dkv_asked) == [(0, hk, ki, 0) for ki in range(nkv)]
        for ki, at in enumerate(dkv_written):
            assert dkv_asked[at] == (0, hk, ki, 0)
            assert at == len(dkv_asked) - 1 or dkv_asked[at + 1] != (
                0, hk, ki, 0)


@pytest.mark.parametrize("h,hkv,s,d,fused", [
    (32, 8, 32768, 64, True),       # the LFM2 cell's `gqa` layer: 40 MiB
    (28, 4, 16384, 128, True),      # SmallThinker's, full and window: 24
    (32, 32, 8192, 192, True),      # Kimi-Linear's `mla`: 22
    (32, 8, 65536, 64, False),      # twice LFM2's row: 80 MiB
    (28, 4, 131072, 128, False),
])
def test_the_backward_is_one_kernel_where_its_accumulators_fit(
        h, hkv, s, d, fused):
    """`fused_backward` by shape: the three cells' attention takes the
    one kernel, whose float32 dq, dk and dv of the whole sequence fit
    the VMEM budget; a longer row takes the two kernels."""
    q = jax.ShapeDtypeStruct((1, h, s, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, hkv, s, d), jnp.bfloat16)
    assert PA._tile_ok(q, s)
    assert PA.fused_backward(q, k) == fused
    assert (PA._resident_bytes(q, k, PA._tiles_of(q, s))
            <= PA._FUSED_VMEM) == fused


def test_a_gqa_layer_counts_the_fused_backward(monkeypatch):
    """`<layer>.fused_bwd` reads 1 where the kernels' backward is one
    kernel, 0 where it is two and on the XLA route."""
    from cxxnet_tpu.layers import lm
    layer = lm.GQALayer("l0")
    for key, val in (("nhead", "4"), ("nkvhead", "1"), ("head_dim", "8")):
        layer.set_param(key, val)
    x = jnp.asarray(np.random.RandomState(0).randn(1, 1, 16, 8),
                    jnp.float32)
    params = {"wq": jnp.ones((8, 32)) * 0.1, "wk": jnp.ones((8, 8)) * 0.1,
              "wv": jnp.ones((8, 8)) * 0.1, "wo": jnp.ones((32, 8)) * 0.1}

    def counted():
        return float(layer.apply_with_stats(params, [x], train=True)[2][
            "fused_bwd"])

    assert counted() == 0.0                  # the XLA route
    monkeypatch.setattr(PA, "_FORCE_INTERPRET", True)
    assert counted() == 1.0
    monkeypatch.setattr(PA, "_FUSED_VMEM", 0)
    assert counted() == 0.0


def test_the_roofline_readers_count_the_fused_kernel_once():
    """`flash_d64_roofline` and `flash_gqa_roofline` find the kernels'
    device events by substring (`trace_reduce.named`): `flash_dq` is in
    `flash_dqkv` and `flash_win_dq` in `flash_win_dqkv`, `flash_dkv` in
    neither, so the fused kernel's time is counted once, and a window
    kernel's only by the reader of the window layers."""
    from benchmark import trace_reduce
    from benchmark.layer_metrics import flash_d64_roofline as d64
    from benchmark.layer_metrics import flash_gqa_roofline as gqa
    for name, in_d64 in (("flash_dqkv.3", True), ("jvp_flash_dqkv_", True),
                         ("flash_win_dqkv.1", False)):
        event = [(name, 0.0, 1.0)]
        assert trace_reduce.kernel_ns(event, d64.KERNELS) == float(in_d64)
        assert trace_reduce.kernel_ns(event, gqa.KERNELS) == 1.0


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
