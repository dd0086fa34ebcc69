"""bench.py harness smoke test - ALWAYS in the default suite.

bench.py once called the jitted train step with a stale 5-arg
signature; nothing in the (green) suite imported the measurement
functions, so the regression reached the on-chip run and zeroed its
headline artifact. These tests run the REAL harness end-to-end on the
CPU backend at a tiny batch (`bench.run()`; nothing it returns here
is a device number) so any drift in the train-step signature,
sharding specs, or the extras plumbing fails the suite - and pin the
CLI's contract: `python bench.py` is a DEVICE benchmark that exits
non-zero without a TPU and on every error.
"""

import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_run_end_to_end(monkeypatch, tmp_path):
    """bench.run() produces a complete artifact with nonzero numbers
    and no *_error fields from any CPU-reachable path - and the
    watchdog's emergency artifact (_PARTIAL, snapshotted after every
    measurement) carries the headline: a hang in a later stage may
    only truncate extras, never zero the value."""
    import bench
    monkeypatch.setattr(bench, "_PARTIAL", {})
    out = bench.run(steps_override=1, batch_override=4)
    snap = bench._PARTIAL
    assert snap["value"] > 0 and snap["value_is"] == "e2e"
    assert snap["compute_ips"] > 0

    assert out["platform"] == "cpu"
    assert out["value"] > 0 and out["compute_ips"] > 0
    assert out["value_is"] == "e2e"
    assert out["unit"] == "images/sec"
    # the eval_train variant exercises the metric-compiled step
    assert out["e2e_eval_train_ips"] > 0
    # the continuous-batching serving family (docs/SERVING.md):
    # qps + latency percentiles + the vs-batch-predict ratio
    assert out["serve_qps"] > 0
    assert out["serve_rows_per_s"] > 0
    assert out["serve_p99_ms"] is not None
    assert out["serve_over_predict"] > 0
    assert out["serve_buckets"] >= 1
    # the input-split extra runs on CPU too
    assert out["host_prep_ms_p50"] > 0
    assert out["device_step_ms_p50"] > 0
    assert out["augment_ips"] > 0
    errors = {k: v for k, v in out.items() if k.endswith("_error")}
    assert not errors, errors
    # the artifact is the driver contract: one JSON-serializable dict
    json.dumps(out)


def test_bench_crash_after_measurement_emits_snapshot(monkeypatch, capsys):
    """A CRASH (not just a hang) after a completed measurement must
    emit the snapshotted headline (labeled truncated), never the
    value=0.0 error artifact - and exit non-zero: an error is never a
    clean run."""
    import bench
    monkeypatch.setattr(bench, "_PARTIAL", {})

    def boom(profile_dir="", steps_override=0, batch_override=0):
        bench._snapshot({"metric": "m", "value": 123.0, "unit":
                         "images/sec", "compute_ips": 123.0})
        raise RuntimeError("late explosion")

    monkeypatch.setattr(bench, "run", boom)
    monkeypatch.setattr(bench.jax, "devices", lambda *a: [_FakeTpu()])
    monkeypatch.setenv("CXN_BENCH_TIMEOUT", "0")
    assert bench.main([]) == 1
    out = json.loads(capsys.readouterr().out.strip())
    assert out["value"] == 123.0
    assert "late explosion" in out["truncated"]


class _FakeTpu:
    """What main()'s platform gate looks at (the crash-path tests
    replace run(), so nothing else ever touches the 'device')."""
    platform = "tpu"
    device_kind = "TPU v5 lite"


def test_bench_crash_before_measurement_emits_error(monkeypatch, capsys):
    import bench
    monkeypatch.setattr(bench, "_PARTIAL", {})
    monkeypatch.setattr(bench, "run", lambda *a, **k: (_ for _ in ()
                                                      ).throw(
        ValueError("early explosion")))
    monkeypatch.setattr(bench.jax, "devices", lambda *a: [_FakeTpu()])
    monkeypatch.setenv("CXN_BENCH_TIMEOUT", "0")
    assert bench.main([]) == 1
    out = json.loads(capsys.readouterr().out.strip())
    assert out["value"] == 0.0 and "early explosion" in out["error"]


def test_bench_cli_refuses_without_a_tpu(capsys):
    """`python bench.py` on a CPU-only machine: non-zero exit, the
    platform it found named on stderr, NO result line - and no second
    process (the refusal happens in main(), before anything runs)."""
    import bench
    assert bench.main([]) == 1
    cap = capsys.readouterr()
    assert cap.out == ""
    assert "'cpu'" in cap.err and "TPU" in cap.err


def test_bench_all_failed_run_exits_nonzero(monkeypatch, capsys):
    """A run in which every measurement degraded to an *_error field
    (value_is == none) prints its artifact and still fails."""
    import bench
    monkeypatch.setattr(bench, "_PARTIAL", {})
    monkeypatch.setattr(bench.jax, "devices", lambda *a: [_FakeTpu()])
    monkeypatch.setattr(bench, "run", lambda *a, **k: {
        "metric": "m", "value": 0.0, "value_is": "none",
        "e2e_error": "x"})
    monkeypatch.setenv("CXN_BENCH_TIMEOUT", "0")
    assert bench.main([]) == 1
    assert json.loads(capsys.readouterr().out)["value_is"] == "none"


def test_bench_one_failed_extra_exits_nonzero(monkeypatch, capsys):
    """A headline with ONE extra that failed (say Mosaic refused the
    attention kernel): the artifact is printed whole, the failed
    measurement is named on stderr, and the exit code is 1 - a
    `*_error` field is never a clean run."""
    import bench
    monkeypatch.setattr(bench, "_PARTIAL", {})
    monkeypatch.setattr(bench.jax, "devices", lambda *a: [_FakeTpu()])
    art = {"metric": "m", "value": 123.0, "value_is": "e2e",
           "attn_error": "MosaicError: refused"}
    monkeypatch.setattr(bench, "run", lambda *a, **k: dict(art))
    monkeypatch.setenv("CXN_BENCH_TIMEOUT", "0")
    assert bench.main([]) == 1
    cap = capsys.readouterr()
    assert json.loads(cap.out) == art
    assert "attn_error" in cap.err
    # the same artifact without the error field is a clean run
    del art["attn_error"]
    monkeypatch.setattr(bench, "_PARTIAL", {})
    assert bench.main([]) == 0


def test_bench_bad_arguments_exit_nonzero(capsys):
    import bench
    assert bench.main(["--steps"]) == 1
    assert "bad arguments" in json.loads(capsys.readouterr().out)["error"]


def test_peak_table_rejects_unknown_device():
    """A device that is not in the peaks table is an error, not 0.0:
    an mfu_pct against a default peak would be a made-up number."""
    import bench
    assert bench._peak_for("TPU v5 lite") == 197.0
    with pytest.raises(ValueError, match="no bf16 peak known"):
        bench._peak_for("TPU v99")
    with pytest.raises(ValueError):
        bench._peak_for("cpu")


def test_bench_device_augment_extra_runs(monkeypatch, tmp_path):
    """The device_augment bench extra builds its own AlexNet trainer
    with override keys that must track the trainer's config surface -
    run it for real (tiny batch; the platform gate is bypassed, the
    CPU backend executes) so drift degrades a test, not the artifact."""
    import bench
    out = bench._bench_device_augment(4, 1, "tpu")
    assert out.get("device_augment_ips", 0) > 0, out


def test_all_failed_artifact_is_self_describing():
    """When every measurement fails the artifact keeps an e2e-flavored
    metric name; value_is must say 'none' so a zeroed artifact cannot
    read as a measured e2e of 0. A good artifact is left alone."""
    import bench
    out = {"metric": "alexnet_b256_tpu_train_e2e"}
    bench._finalize(out)
    assert out["value"] == 0.0 and out["value_is"] == "none"
    good = {"platform": "tpu", "value": 50.0, "value_is": "e2e",
            "e2e_ips": 50.0}
    bench._finalize(good)
    assert good == {"platform": "tpu", "value": 50.0, "value_is": "e2e",
                    "e2e_ips": 50.0}


def test_derive_relabels_headline():
    """_derive labels the artifact by its best available number:
    compute-only until an e2e number lands, then e2e with the ratio
    and the chip-relative fields."""
    import bench
    out = {"compute_ips": 7402.0}
    bench._derive(out, 256, "tpu", 1, 197.0)
    assert out["value"] == 7402.0 and out["value_is"] == "compute_only"
    assert out["metric"] == "alexnet_b256_tpu_train_compute"
    out["e2e_ips"] = 1140.0
    bench._derive(out, 256, "tpu", 1, 197.0)
    assert out["value"] == 1140.0 and out["value_is"] == "e2e"
    assert out["e2e_over_compute"] == pytest.approx(1140.0 / 7402.0,
                                                    rel=1e-3)
    assert out["peak_tflops"] == 197.0 and out["mfu_pct"] > 0


def test_bench_is_one_process():
    """One process holds the chip: bench.py starts no child (the
    isolation, probe and re-exec machinery is gone) and ends timed
    work in block_until_ready."""
    with open(os.path.join(REPO, "bench.py")) as f:
        src = f.read()
    for gone in ("subprocess", "execve", "--only", "_run_isolated",
                 "_readback_sync", "last_good"):
        assert gone not in src, gone
    assert "block_until_ready" in src


@pytest.mark.slow
def test_bench_googlenet_extra_runs(monkeypatch, tmp_path):
    """The googlenet bench extra (streamed + device-resident variants)
    builds its own trainer with override keys that must track the
    config surface - run it for real at a tiny batch (platform gate
    bypassed, CPU executes; slow: a GoogLeNet compile)."""
    import bench
    out = bench._bench_googlenet(2, 1, "tpu")
    assert out.get("googlenet_ips", 0) > 0, out
    assert out.get("googlenet_devicedata_ips", 0) > 0, out


@pytest.mark.slow
def test_bench_resnet_extra_runs(monkeypatch, tmp_path):
    """Same protocol for the third family (shared _bench_model_family
    body, distinct conf/field prefix). Slow: full ResNet-18 compile."""
    import bench
    out = bench._bench_resnet(2, 1, "tpu")
    assert out.get("resnet18_ips", 0) > 0, out
    assert out.get("resnet18_devicedata_ips", 0) > 0, out


def test_bench_error_artifact_is_json():
    """A crash before any measurement still prints the one-line JSON
    artifact (value 0.0 + error) - beside the non-zero exit."""
    import bench
    line = bench._error_json("boom")
    d = json.loads(line)
    assert d["value"] == 0.0 and "boom" in d["error"]
