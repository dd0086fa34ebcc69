"""Multi-controller distributed training (parallel/distributed.py).

The invariant (the reason the sync-SPMD design can replace the async
parameter server): N worker processes over the same global batch train
to weights identical to a single process - the AllReduce makes gradient
math placement-invariant. Exercised with 2 real OS processes on the CPU
backend via the gloo cross-process collectives (the "local PS stands in
for dist PS" proxy of SURVEY.md par.4.6, upgraded to real processes).
"""

import os
import socket
import subprocess
import sys

import numpy as np

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import os, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.environ["CXN_TEST_REPO"])
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.utils.config import parse_config_string

NET = os.environ.get("CXN_TEST_NET") or '''
netconfig=start
layer[0->1] = fullc:fc1
  nhidden = 16
layer[1->2] = relu
layer[2->3] = fullc:fc2
  nhidden = 4
layer[3->3] = softmax
netconfig=end
input_shape = 1,1,8
random_type = xavier
eta = 0.1
momentum = 0.9
batch_size = 8
silent = 1
eval_train = 0
param_server = dist
'''
SHAPE = tuple(int(x) for x in
              os.environ.get("CXN_TEST_SHAPE", "1,1,8").split(","))
WKEY = os.environ.get("CXN_TEST_WKEY", "fc1")

t = NetTrainer()
for k, v in parse_config_string(NET):
    t.set_param(k, v)
for k, v in parse_config_string(os.environ.get("CXN_TEST_EXTRA", "")):
    t.set_param(k, v)
t.init_model()

nproc = jax.process_count()
rank = jax.process_index()
assert nproc == int(os.environ["CXN_NUM_WORKER"]), nproc
# rows this process must feed: batch/nproc on a data mesh, the FULL
# batch when the batch dim is replicated across processes (seq mesh)
local_b = t._local_batch
nclass = 4

rng = np.random.RandomState(42)
for step in range(5):
    data = rng.randn(8, *SHAPE).astype(np.float32)    # global batch
    label = rng.randint(0, nclass, size=(8, 1)).astype(np.float32)
    lo = (rank * local_b) % 8
    t.update(DataBatch(data=data[lo:lo + local_b],
                       label=label[lo:lo + local_b]))

bad = t.check_weights()
assert bad == [], bad
w, _ = t.get_weight(WKEY, "wmat")
out = os.environ["CXN_TEST_OUT"]
np.save(f"{out}.{rank}.npy", w)
print("worker", rank, "done", flush=True)
"""

SEQ_NET = """
netconfig=start
layer[0->1] = pos_embed:pe
layer[1->2] = layernorm:ln1
layer[2->3] = attention:att1
  nhead = 2
  causal = 1
layer[3->4] = flatten
layer[4->5] = fullc:head
  nhidden = 4
layer[5->5] = softmax
netconfig=end
input_shape = 1,4,8
random_type = xavier
eta = 0.05
momentum = 0.9
batch_size = 8
silent = 1
eval_train = 0
param_server = dist
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _single_process_reference(tmp_path, net=None, shape=(1, 1, 8),
                              wkey="fc1", mesh="data:1"):
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config_string
    cfg = net or WORKER.split("or '''")[1].split("'''")[0]
    cfg = cfg.replace("param_server = dist", "")
    t = NetTrainer()
    for k, v in parse_config_string(cfg):
        t.set_param(k, v)
    t.set_param("mesh", mesh)
    t.init_model()
    rng = np.random.RandomState(42)
    for step in range(5):
        data = rng.randn(8, *shape).astype(np.float32)
        label = rng.randint(0, 4, size=(8, 1)).astype(np.float32)
        t.update(DataBatch(data=data, label=label))
    w, _ = t.get_weight(wkey, "wmat")
    return w


def _spawn_workers(argv, extra_env=None, nproc=2):
    """Launch nproc coordinator-connected worker processes and return
    their outputs; kills survivors if one times out (a dead peer leaves
    the rest blocked inside collectives)."""
    port = _free_port()
    procs = []
    for rank in range(nproc):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        env["JAX_PLATFORMS"] = "cpu"
        # one CPU device per worker process (a 2-host x 1-chip slice;
        # the pytest parent's 8-virtual-device XLA_FLAGS must not leak)
        env["XLA_FLAGS"] = ""
        env["CXN_COORDINATOR"] = f"127.0.0.1:{port}"
        env["CXN_NUM_WORKER"] = str(nproc)
        env["CXN_WORKER_RANK"] = str(rank)
        env.update(extra_env or {})
        procs.append(subprocess.Popen(
            argv, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    outs = []
    try:
        for p in procs:
            outs.append(p.communicate(timeout=300)[0])
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out
    return outs


def _run_two_process(tmp_path, extra_cfg="", net="", shape="1,1,8",
                     wkey="fc1"):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    out_prefix = str(tmp_path / "w")
    _spawn_workers(
        [sys.executable, str(script)],
        {"CXN_TEST_REPO": REPO, "CXN_TEST_OUT": out_prefix,
         "CXN_TEST_EXTRA": extra_cfg, "CXN_TEST_NET": net,
         "CXN_TEST_SHAPE": shape, "CXN_TEST_WKEY": wkey})
    w0 = np.load(f"{out_prefix}.0.npy")
    w1 = np.load(f"{out_prefix}.1.npy")
    return w0, w1


def test_two_process_training_matches_single(tmp_path):
    w0, w1 = _run_two_process(tmp_path)
    np.testing.assert_array_equal(w0, w1)  # cross-process identical
    ref = _single_process_reference(tmp_path)
    np.testing.assert_allclose(w0, ref, rtol=1e-5, atol=1e-6)


def test_two_process_zero1_matches_single(tmp_path):
    """shard_optimizer=1 across 2 real processes: updater state shards
    over devices owned by DIFFERENT processes (put_global_full path +
    GSPMD-partitioned update); training math is unchanged."""
    w0, w1 = _run_two_process(tmp_path,
                              extra_cfg="shard_optimizer = 1\n")
    np.testing.assert_array_equal(w0, w1)
    ref = _single_process_reference(tmp_path)
    np.testing.assert_allclose(w0, ref, rtol=1e-5, atol=1e-6)


def test_two_process_seq_parallel_matches_single(tmp_path):
    """Ring attention with the 'seq' axis spanning 2 REAL processes:
    the batch dim is replicated across hosts (each feeds the full
    batch - trainer._local_batch is mesh-aware) while the sequence dim
    and its ppermute K/V rotation cross the process boundary. Weights
    must match the single-process blockwise run exactly."""
    w0, w1 = _run_two_process(
        tmp_path, extra_cfg="mesh = data:1,seq:2\n", net=SEQ_NET,
        shape="1,4,8", wkey="att1")
    np.testing.assert_array_equal(w0, w1)
    ref = _single_process_reference(tmp_path, net=SEQ_NET,
                                    shape=(1, 4, 8), wkey="att1")
    np.testing.assert_allclose(w0, ref, rtol=1e-5, atol=1e-6)


def test_cli_two_process_seq_parallel(tmp_path):
    """The FULL CLI path (main.py round loop + iterator auto-wiring)
    across 2 real processes on a seq mesh: main must NOT data-shard the
    iterators when the batch dim is replicated across hosts (each
    worker feeds the same full batch), and the per-round
    test_on_server consistency check must pass. Regression for the
    mesh-unaware batch/nproc auto-sharding that silently fed each
    worker different data."""
    import gzip
    import struct
    rng = np.random.RandomState(3)
    n = 64
    labels = rng.randint(0, 10, size=n).astype(np.uint8)
    images = rng.randint(0, 255, size=(n, 28, 28)).astype(np.uint8)
    with gzip.open(tmp_path / "img.gz", "wb") as f:
        f.write(struct.pack(">iiii", 2051, n, 28, 28))
        f.write(images.tobytes())
    with gzip.open(tmp_path / "lbl.gz", "wb") as f:
        f.write(struct.pack(">ii", 2049, n))
        f.write(labels.tobytes())
    conf = tmp_path / "seq.conf"
    conf.write_text(f"""
data = train
iter = mnist
    path_img = "{tmp_path}/img.gz"
    path_label = "{tmp_path}/lbl.gz"
    input_flat = 0
iter = end
netconfig=start
layer[0->1] = layernorm:ln1
layer[1->2] = attention:att1
  nhead = 4
  causal = 1
layer[2->3] = flatten
layer[3->4] = fullc:head
  nhidden = 10
layer[4->4] = softmax
netconfig=end
input_shape = 1,28,28
random_type = xavier
batch_size = 32
eta = 0.05
momentum = 0.9
num_round = 1
max_round = 1
metric = error
save_model = 0
test_on_server = 1
param_server = dist
mesh = data:1,seq:2
silent = 1
""")
    outs = _spawn_workers(
        [sys.executable, "-m", "cxxnet_tpu.main", str(conf)])
    for out in outs:
        assert "diverge" not in out, out
    # both workers saw the same data: identical train-error lines
    lines = [next(l for l in out.splitlines() if "train-error" in l)
             for out in outs]
    assert lines[0] == lines[1], lines


def test_check_replicated_clean():
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config_string
    cfg = WORKER.split("or '''")[1].split("'''")[0]
    cfg = cfg.replace("param_server = dist", "")
    t = NetTrainer()
    for k, v in parse_config_string(cfg):
        t.set_param(k, v)
    t.set_param("mesh", f"data:{min(8, len(jax.devices()))}")
    t.init_model()
    assert t.check_weights() == []
