"""Kernels of the main path compiled for a v5e chip that is described,
not attached: what interpret mode cannot show (a slice off the tiling,
too much VMEM, an op Mosaic has no rule for) fails here, at no chip
time. Nothing runs, so no result and no time comes out of this file.

The topology is described inside a fixture: only the worker that is
given this file loads the TPU's library, and it compiles in its own
process. Keep such tests in this one file."""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from cxxnet_tpu.ops import pallas_lrn

_HYPER = (5, 0.001, 0.75, 1.0)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs in /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # noqa: BLE001 - any cause skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shape, dtype, sharding) -> str:
    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)
    return jax.jit(fn).lower(x).compile().as_text()


# the benchmark's two maps (alexnet.train_resident) and AlexNet.conf's,
# a four-chip shard, GoogLeNet's, Server buckets of the 256-channel map
@pytest.mark.parametrize("shape,dtype", [
    ((2048, 96, 27, 27), "bfloat16"),
    ((2048, 256, 13, 13), "bfloat16"),
    ((256, 96, 27, 27), "float32"),
    ((512, 256, 13, 13), "float32"),
    ((128, 64, 56, 56), "bfloat16"),
    ((128, 192, 56, 56), "bfloat16"),
    ((1, 256, 13, 13), "bfloat16"),
    ((8, 256, 13, 13), "float32"),
    ((128, 1024, 7, 7), "float32"),         # the most channels taken
])
def test_lrn_kernels_compile_for_the_chip(one_chip, shape, dtype):
    assert pallas_lrn._tile_ok(jax.ShapeDtypeStruct(shape, dtype))
    fwd = _compile(lambda x: pallas_lrn.lrn_pallas(x, *_HYPER, False),
                   shape, dtype, one_chip)
    assert fwd.count("tpu_custom_call") == 1 and "lrn_fwd" in fwd
    grad = _compile(jax.grad(lambda x: jnp.sum(pallas_lrn.lrn_pallas(
        x, *_HYPER, False).astype(jnp.float32))), shape, dtype, one_chip)
    assert "lrn_bwd" in grad


def test_lrn_kernel_with_power_compiles_for_the_chip(one_chip):
    """`knorm = 0` keeps `jnp.power` in both kernels."""
    grad = _compile(jax.grad(lambda x: jnp.sum(pallas_lrn.lrn_pallas(
        x, 5, 1.0, 0.75, 0.0, False).astype(jnp.float32))),
        (256, 96, 27, 27), "bfloat16", one_chip)
    assert "lrn_bwd" in grad


def test_flash_kernels_compile_for_the_chip_at_mla_head_size(one_chip):
    """The `mla` layer's core: 32 heads, 8,192 positions, queries and
    keys 192 wide (values padded to it). At 1024 x 1024 tiles the dk/dv
    kernel asked for 17 MB of VMEM; `_block_limits` gives such heads
    512."""
    from cxxnet_tpu.ops import pallas_attention as pa
    assert pa._block_limits(192) == (512, 512)
    assert pa._block_limits(128) == (pa.BLOCK_Q, pa.BLOCK_K)
    shape = (1, 32, 8192, 192)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(pa.flash_attention(q, k, v, True).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert name in text


@pytest.mark.parametrize("window", [0, 4096])
def test_gqa_flash_kernels_compile_for_the_chip_at_the_cells_size(
        one_chip, window):
    """The `gqa` layer's core in the SmallThinker cell: 28 query heads
    on 4 key/value heads, 128 wide, 16,384 positions, 1,024 x 1,024
    tiles; with the window the three kernels carry their own names and
    a band row is 5 tiles."""
    from cxxnet_tpu.ops import pallas_attention as pa
    q = jax.ShapeDtypeStruct((1, 28, 16384, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4, 16384, 128), jnp.bfloat16,
                              sharding=one_chip)
    assert pa._tiles_of(q, 16384) == (1024, 1024)

    def loss(q, k, v):
        return jnp.sum(pa.flash_attention(q, k, v, True, None, False,
                                          window).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    for kernel in ("fwd", "dq", "dkv"):
        assert ("flash_win_" + kernel in text) == bool(window)
        assert ("flash_" + kernel in text) == (not window)


@pytest.mark.parametrize("checkpointed", [8, 0])
def test_smallthinker_step_fits_the_chip(one_chip, monkeypatch,
                                         checkpointed):
    """The whole train step of `smallthinker_21b_a3b.train_seq16k` as
    the cell states it (`remat = 1`; 643,852,800 parameters under Adam,
    one row of 16,384 positions), compiled from abstract state: the
    chip's compiler takes it, and the kernels' row statistics in it are
    (1, 28, 1, 16384), 1.75 MiB a layer. As the (1, 28, 16384, 8) they
    were, each padded to 224 MiB, the eight `gqa` layers had to be
    checkpointed: without (`checkpointed = 0`: `GQALayer.remat_worthy`
    turned off here) the compiler refused the step, `Used 16.12G of
    15.75G hbm`. Now it fits either way; why the layers are checkpointed
    all the same is in PERF.md section 6, PR 37."""
    from benchmark import run as bench
    from cxxnet_tpu.layers import lm
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.ops import pallas_attention as pa
    from cxxnet_tpu.utils.config import parse_config_string
    monkeypatch.setattr(pa, "_backend_ok", lambda: True)
    monkeypatch.setattr(lm.GQALayer, "remat_worthy", bool(checkpointed))
    cell = bench.load_cell("smallthinker_21b_a3b.train_seq16k")
    overrides = dict(cell.cfg["overrides"], dev="cpu", silent="1")
    t = NetTrainer()
    for k, v in parse_config_string(cell.cfg["conf_text"]):
        if k not in overrides:
            t.set_param(k, v)
    for k, v in overrides.items():
        t.set_param(k, v)
    t.net_cfg.configure(t.cfg_pairs)
    t._build_net()
    assert t.net.remat and len(t.net.checkpointed) == checkpointed

    def state():
        t._init_state(t.net.init_params(jax.random.PRNGKey(0)))
        return t.state

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    seq = int(cell.traffic["seq_len"])
    args = jax.tree.map(on_chip, (
        jax.eval_shape(state),
        jax.ShapeDtypeStruct((1, 1, seq, 1), jnp.int32), (),
        {f: jax.ShapeDtypeStruct((1, seq), jnp.float32)
         for f in t.net_cfg.label_name_map},
        jax.ShapeDtypeStruct((1,), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32)))
    t.state = None                       # (tracers, left by eval_shape)
    compiled = jax.jit(t._train_step.__wrapped__,
                       donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.75 * 2 ** 30)
    assert "f32[1,28,16384,8]" not in text
    assert "f32[1,28,1,16384]" in text
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv", "flash_win_fwd",
                   "flash_win_dq", "flash_win_dkv"):
        assert f"/{kernel}/" in text
    assert ("rematted_computation/scores" in text) == bool(checkpointed)


@pytest.mark.parametrize("route,dtype", [
    ("xla", "bfloat16"), ("pallas", "bfloat16"), ("pallas", "float32")])
def test_kda_chunk_scan_compiles_for_the_chip_at_the_cells_size(
        one_chip, monkeypatch, route, dtype):
    """`ops/kda.py` at the Kimi cell's sizes (8,192 positions, 32 heads
    of 128, chunks of 64), forward and gradients of all five inputs.
    `route.xla`: what could be refused is its memory (the pairwise
    decay tensors are 2.1 GB each when written out) and the triangular
    solve. `route.pallas` (what the chip takes; `ops.kda._backend_ok`
    is patched, since the process sees the CPU): the two kernels of
    ops/pallas_kda.py lower for the chip, and the temporaries shrink
    to a fraction."""
    from cxxnet_tpu.ops import kda
    monkeypatch.setattr(kda, "_backend_ok", lambda: route == "pallas")

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    x = sds((1, 8192, 32, 128), jnp.dtype(dtype))
    g = sds((1, 8192, 32, 128), jnp.float32)
    beta = sds((1, 8192, 32), jnp.float32)

    def loss(q, k, v, g, beta):
        return jnp.sum(kda.kda_chunked(q, k, v, g, beta, 64)
                       .astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        x, x, x, g, beta).compile()
    text = compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert "triangular" in text.lower() or "custom-call" in text
    if route == "pallas":
        assert "route.pallas" in text and "route.xla" not in text
        assert "kda_local_fwd" in text and "kda_local_bwd" in text
        assert temp < 3 << 30
    else:
        assert "route.xla" in text and "kda_local" not in text
        assert temp < 6 << 30
