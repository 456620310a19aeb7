"""bench.py contract tests: ONE JSON line on every path, no silent CPU.

These pin what the harness promises: it asks jax for the backend in its
own process, refuses to report CPU as a TPU number (and carries no number
over from an earlier run), and has an explicit CPU smoke mode that still
emits the full line shape."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(env_extra, timeout=420):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    env.update(env_extra)
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=REPO)
    lines = [ln for ln in r.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert len(lines) == 1, f"expected ONE JSON line, got: {r.stdout!r}"
    return r.returncode, json.loads(lines[0])


def test_refuses_silent_cpu_fallback():
    """Default mode on a CPU-only machine must FAIL with the structured
    line (never report CPU throughput as the TPU headline) and carry no
    number from any earlier run."""
    rc, payload = _run_bench({})
    assert rc != 0
    assert payload["value"] == 0.0
    assert "refusing" in payload["error"]
    assert payload["metric"] == \
        "alexnet_ilsvrc12_train_images_per_sec_per_chip"
    assert "last_good" not in payload


def test_probe_backend_reports_platform():
    sys.path.insert(0, REPO)
    import bench
    info = bench.probe_backend()
    assert info["platform"] == "cpu" and info["n"] >= 1


def test_unknown_device_kind_has_no_peak():
    sys.path.insert(0, REPO)
    import bench
    assert bench.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError, match="no peak FLOP/s on record"):
        bench.peak_flops("TPU v99")


@pytest.mark.slow
def test_cpu_smoke_emits_full_line():
    """POSEIDON_BENCH_CPU=1 with tiny knobs: rc 0, labeled cpu, value > 0,
    and the cost-analysis extras present (the ADVICE fix)."""
    rc, payload = _run_bench({
        "POSEIDON_BENCH_CPU": "1", "POSEIDON_BENCH_BATCH": "1",
        "POSEIDON_BENCH_IMAGE": "67", "POSEIDON_BENCH_CLASSES": "8",
        "POSEIDON_BENCH_ITERS": "1", "POSEIDON_BENCH_AB": "0",
        "POSEIDON_BENCH_LAYOUT_AB": "0", "POSEIDON_BENCH_TOPK": "0",
        "POSEIDON_BENCH_GOOGLENET": "0", "POSEIDON_BENCH_LM": "0"})
    assert rc == 0
    assert payload["backend"] == "cpu"
    assert payload["value"] > 0
    assert payload["alexnet_step_flops_per_device"] > 0
    # per-section checkpointing: the completed headline section must have
    # landed on disk even before the final line (a mid-run SIGKILL loses
    # nothing — round-3's 1200 s rc -9 whole-window loss, made impossible)
    with open(os.path.join(REPO, "evidence", "bench_partial.json")) as f:
        partial = json.load(f)
    assert "alexnet" in partial["sections_done"]
    assert partial["alexnet_step_ms"] > 0


def _run_bench_serving(env_extra, timeout=420):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    env.update(env_extra)
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py"),
                        "serving"],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=REPO)
    lines = [ln for ln in r.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert len(lines) == 1, f"expected ONE JSON line, got: {r.stdout!r}"
    return r.returncode, json.loads(lines[0])


def test_serving_mode_refuses_silent_cpu():
    """`bench.py serving` keeps the no-silent-CPU contract: without the
    explicit smoke flag on a CPU-only machine it fails with the structured
    serving line."""
    rc, payload = _run_bench_serving({})
    assert rc != 0
    assert payload["metric"] == "serving_p99_ms"
    assert payload["value"] == 0.0
    assert "refusing" in payload["error"]


@pytest.mark.slow
def test_serving_mode_cpu_smoke_emits_full_line():
    """Explicit CPU smoke: rc 0, the BENCH line shape, and the serving
    extras (p50/p99/throughput/batch_fill) all present."""
    rc, payload = _run_bench_serving({
        "POSEIDON_BENCH_CPU": "1",
        "POSEIDON_BENCH_SERVE_REQUESTS": "40",
        "POSEIDON_BENCH_SERVE_CONCURRENCY": "2",
        "POSEIDON_BENCH_SERVE_BUCKETS": "1,2,4"})
    assert rc == 0
    assert payload["metric"] == "serving_p99_ms"
    assert payload["unit"] == "ms"
    assert payload["value"] > 0 and payload["vs_baseline"] > 0
    assert payload["p50_ms"] is not None
    assert payload["throughput_rps"] > 0
    assert payload["cpu_smoke"] is True and payload["platform"] == "cpu"
