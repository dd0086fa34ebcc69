"""Production serving-front smoke: overload + hot-swap end to end.

    python -m cxxnet_tpu.tools.serve_http_smoke [--out DIR] [--keep]

Trains the tiny synthetic-MNIST MLP through the real CLI (two rounds,
two checkpoints with genuinely different weights), then drives a live
HTTP server (`Server(http_port=..., queue_limit=..., swap_watch=...)`)
through the overload matrix of docs/SERVING.md "Serving over HTTP":

- the `serve_dispatch_delay` fault injector pins every dispatch to a
  fixed service time first: the tiny MLP is otherwise so fast that a
  GIL-bound python client can never exceed capacity, and "2x the
  sustainable rate" would depend on the CI machine. With service time
  pinned, sustainable capacity is deterministic everywhere;
- an uncontended leg measures the baseline p99 (sequential) and the
  sustainable rate (concurrent closed-loop burst - a single blocked
  client measures latency, not capacity), and every /metrics scrape
  must be exposition-valid;
- an OPEN-LOOP storm at ~2x sustainable past `queue_limit` must shed
  (429 + Retry-After observed) while the ACCEPTED requests keep p99
  within 3x uncontended - bounded latency is what shedding buys;
- a fresh checkpoint atomically published MID-STORM must be picked up
  live (swap event, zero errored requests - every response a 200 or a
  429, never a 5xx) and the post-swap answers must match a cold
  Server restarted on the new checkpoint bit for bit;
- a torn publish (CXXNET_FAULT `swap_torn_checkpoint:corrupt` writes
  half the bytes, trailer missing) must be REJECTED (`swap.rejected`)
  with serving uninterrupted on the last good weights.

Exit 0 iff all checks pass; CI uploads the response-code tallies and
latency summaries as artifacts.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

import numpy as np

from cxxnet_tpu.tools.telemetry_smoke import write_synth_mnist

CONF = """
data = train
iter = mnist
    path_img = "{d}/train-img.gz"
    path_label = "{d}/train-lbl.gz"
    shuffle = 1
iter = end

netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 16
  init_sigma = 0.1
layer[+1:sg1] = tanh
layer[sg1->fc2] = fullc:fc2
  nhidden = 3
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end

input_shape = 1,1,36
batch_size = 32
dev = cpu
save_model = 1
num_round = 2
max_round = 2
eta = 0.3
metric = error
silent = 1
"""

# the same net, sans data/training keys: the in-process servers load
# the CLI-trained checkpoints into this config
NET_CFG = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 16
  init_sigma = 0.1
layer[+1:sg1] = tanh
layer[sg1->fc2] = fullc:fc2
  nhidden = 3
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,36
batch_size = 32
dev = cpu
silent = 1
"""


def _run_cli(out_dir: str, *overrides: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "cxxnet_tpu.main",
         os.path.join(out_dir, "serve_http_smoke.conf"), *overrides],
        env=env, capture_output=True, text=True, timeout=540)


def _post(port: int, payload: dict, timeout: float = 120.0):
    """POST /predict; returns (status, headers, parsed body)."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/predict",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, dict(r.headers), json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read())


def _scrape(port: int) -> str:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30) as r:
        return r.read().decode()


def _p99(lat_ms: list) -> float:
    if not lat_ms:
        return 0.0
    s = sorted(lat_ms)
    return s[min(len(s) - 1, int(0.99 * len(s)))]


def run_smoke(out_dir: str) -> int:
    from cxxnet_tpu import telemetry
    from cxxnet_tpu.nnet import checkpoint
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.serve import Server
    from cxxnet_tpu.telemetry.http import validate_exposition
    from cxxnet_tpu.utils import fault

    write_synth_mnist(out_dir, 192, 0, "train")
    conf = os.path.join(out_dir, "serve_http_smoke.conf")
    with open(conf, "w") as f:
        f.write(CONF.format(d=out_dir))
    mdir = os.path.join(out_dir, "models")
    ck_old = os.path.join(mdir, "0001.model")
    ck_new = os.path.join(mdir, "0002.model")
    publish = os.path.join(out_dir, "publish.model")

    train = _run_cli(out_dir, f"model_dir={mdir}")
    trained = (train.returncode == 0 and os.path.exists(ck_old)
               and os.path.exists(ck_new))

    checks = [("train run produced two checkpoints", trained)]
    tally = {"200": 0, "429": 0, "other": 0}
    storm_p99 = uncont_p99 = 0.0
    bad_scrapes = []
    stats = {}
    swap_before_storm_end = post_matches_cold = served_through_torn = \
        saw_retry_after = False

    if trained:
        tr = NetTrainer(dev="cpu", cfg=NET_CFG)
        with open(ck_old, "rb") as f:
            tr.load_model(f)
        srv = Server(tr, max_batch=8, max_wait_ms=2.0, replicas=2,
                     http_port=0, queue_limit=8,
                     swap_watch=publish, swap_poll_ms=25.0)
        srv.warmup()
        # pin the service time: 30ms per dispatch, armed for far more
        # hits than the whole smoke dispatches
        fault.clear()
        for k in range(2000):
            fault.inject("serve_dispatch_delay", "delay", "0.03",
                         at=k + 1)
        srv.start()
        port = srv.metrics_server.port
        rng = np.random.RandomState(29)
        probe = rng.randn(4, 36).astype(np.float32).tolist()
        payload = {"data": probe, "raw": True}
        lock = threading.Lock()

        def timed_post(sink):
            ts = time.perf_counter()
            code, headers, _ = _post(port, payload)
            dt = (time.perf_counter() - ts) * 1e3
            with lock:
                tally[str(code) if str(code) in tally
                      else "other"] += 1
                if sink is not None and code == 200:
                    sink.append(dt)
            return code, headers

        # --- leg 1: uncontended p99, sequential ----------------------
        lat = []
        for _ in range(40):
            timed_post(lat)
        uncont_p99 = _p99(lat)
        # with service time pinned at 30ms/dispatch, sustainable
        # capacity is known analytically: replicas * max_batch rows
        # per dispatch window, in 4-row requests
        sustainable_rps = (2 * 8 / 0.03) / 4.0
        pre_swap = _post(port, payload)[2].get("outputs")
        bad_scrapes.extend(validate_exposition(_scrape(port)))

        # --- leg 2: open-loop storm at ~2x + mid-storm publish ------
        n_req = 160
        gaps = rng.exponential(1.0 / (2.0 * sustainable_rps), n_req)
        arrivals = np.cumsum(gaps)
        acc_lat = []
        storm_shed = 0

        def fire(i):
            nonlocal saw_retry_after, storm_shed
            ts = time.perf_counter()
            code, headers, _ = _post(port, payload)
            dt = (time.perf_counter() - ts) * 1e3
            with lock:
                tally[str(code) if str(code) in tally else
                      "other"] += 1
                if code == 200:
                    acc_lat.append(dt)
                elif code == 429:
                    storm_shed += 1
                    if "Retry-After" in headers:
                        saw_retry_after = True

        threads = []
        t_start = time.perf_counter()
        for i in range(n_req):
            pause = t_start + float(arrivals[i]) - time.perf_counter()
            if pause > 0:
                time.sleep(pause)
            if i == n_req // 3:
                # mid-storm: atomically publish the round-2 weights
                # to the watched path - the poller must pick it up
                # while the storm is still running
                checkpoint.publish_model(ck_new, publish)
            t = threading.Thread(target=fire, args=(i,),
                                 daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=300)
        swap_before_storm_end = srv.stats()["swaps"] >= 1
        storm_p99 = _p99(acc_lat)
        bad_scrapes.extend(validate_exposition(_scrape(port)))

        # --- leg 3: post-swap answers == cold restart on ck_new -----
        post_swap = _post(port, payload)[2].get("outputs")

        # --- leg 4: torn publish rejected, serving uninterrupted ----
        # clear first: hit counters only tick while faults are armed,
        # and the delay entries armed above mean the mid-storm publish
        # already consumed this point's hit 1
        fault.clear()
        fault.inject("swap_torn_checkpoint", "corrupt")
        try:
            checkpoint.publish_model(ck_new, publish)
        finally:
            fault.clear()
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            if srv.stats()["swap_rejected"] >= 1:
                break
            time.sleep(0.05)
        code, _, body = _post(port, payload)
        served_through_torn = (
            srv.stats()["swap_rejected"] >= 1 and code == 200
            and body.get("outputs") == post_swap)
        bad_scrapes.extend(validate_exposition(_scrape(port)))
        stats = srv.stop()

        tr_new = NetTrainer(dev="cpu", cfg=NET_CFG)
        with open(ck_new, "rb") as f:
            tr_new.load_model(f)
        srv2 = Server(tr_new, max_batch=8, max_wait_ms=2.0,
                      replicas=1, http_port=0)
        srv2.warmup()
        srv2.start()
        cold = _post(srv2.metrics_server.port, payload)[2].get(
            "outputs")
        srv2.stop()
        post_matches_cold = (post_swap == cold
                             and post_swap != pre_swap)
        telemetry.reset_for_tests()

        checks += [
            ("storm shed: 429s observed with Retry-After",
             storm_shed > 0 and saw_retry_after),
            ("storm accepted requests resolved (200s on both sides "
             "of the swap)", tally["200"] >= 41 and bool(acc_lat)),
            ("no 5xx / dropped requests across the storm + swap",
             tally["other"] == 0 and stats.get("errors") == 0),
            ("accepted p99 bounded: storm within 3x uncontended",
             0 < storm_p99 <= 3.0 * uncont_p99),
            ("mid-storm publish swapped live (swap event, no drain)",
             swap_before_storm_end and stats.get("swaps") == 1),
            ("post-swap answers == cold restart on the new "
             "checkpoint", post_matches_cold),
            ("torn publish rejected; serving uninterrupted",
             served_through_torn
             and stats.get("swap_rejected") == 1),
            ("every /metrics scrape exposition-valid",
             not bad_scrapes),
        ]

    ok = True
    for label, passed in checks:
        print(f"  [{'ok' if passed else 'FAIL'}] {label}")
        ok = ok and bool(passed)
    if not trained:
        print("--- train stderr tail ---")
        print(train.stderr[-2000:])
    for line in bad_scrapes[:5]:
        print(f"  bad exposition line: {line}")
    with open(os.path.join(out_dir, "storm_summary.json"), "w") as f:
        json.dump({"codes": tally, "uncontended_p99_ms": uncont_p99,
                   "storm_p99_ms": storm_p99,
                   "server_stats": stats}, f, indent=1, default=str)
    print(f"serve_http_smoke: {'PASS' if ok else 'FAIL'} "
          f"(codes {tally}, p99 uncontended {uncont_p99:.1f}ms "
          f"storm {storm_p99:.1f}ms)")
    return 0 if ok else 1


def main() -> int:
    args = sys.argv[1:]
    if "--out" in args:
        i = args.index("--out")
        if i + 1 >= len(args):
            print("usage: serve_http_smoke [--out DIR] [--keep]")
            return 2
        out = args[i + 1]
        os.makedirs(out, exist_ok=True)
        return run_smoke(out)
    if "--keep" in args:
        d = tempfile.mkdtemp(prefix="serve_http_smoke_")
        rc = run_smoke(d)
        print(f"serve_http_smoke: artifacts kept in {d}")
        return rc
    with tempfile.TemporaryDirectory() as d:
        return run_smoke(d)


if __name__ == "__main__":
    sys.exit(main())
