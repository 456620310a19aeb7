"""chip_smoke.py on CPU: it must refuse to report, and its whole flow must
run at a tiny size — so chip time is never spent finding a Python error."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd=REPO, script=SMOKE, **env):
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, capture_output=True,
        text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def _reports_nothing(r):
    assert r.returncode != 0
    assert '"ok"' not in r.stdout and "ms_per_step" not in r.stdout, r.stdout


def test_refuses_without_an_accelerator():
    r = _run([])
    _reports_nothing(r)
    assert "REFUSING" in r.stderr and "platform=cpu" in r.stdout


def test_fails_outside_a_checkout(tmp_path):
    alone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    _reports_nothing(_run([], cwd=str(tmp_path), script=str(alone)))


def test_cpu_tiny_rehearsal_runs_every_phase(tmp_path):
    """The same flow as on the chip — f32 and bf16 trains through cli.main,
    TEST pass, snapshot, restore, --sfb-auto, kernel parity, then a second
    fresh process that must add nothing to the cache — with the cache
    placed from outside: everything cached lands under that directory."""
    cache = tmp_path / "cache"
    r = _run(["--cpu-tiny"], JAX_COMPILATION_CACHE_DIR=str(cache),
             XLA_FLAGS="--xla_force_host_platform_device_count=2")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 2}}
    with open(os.path.join(REPO, "chiprun_out", "chip_smoke.json")) as f:
        result = json.load(f)
    cold, warm = result["cold"], result["warm"]
    assert cold["cache_dir"] == str(cache)
    assert sorted(cold["phases"]) == ["flash", "held_chunks", "kernels",
                                      "resume", "scan", "train_bf16",
                                      "train_f32", "train_sfb_auto"]
    assert cold["phases"]["train_bf16"]["steps"] == 24
    assert cold["phases"]["resume"]["compiled_step"]["source"] == "loaded"
    assert warm["phases"]["warm_resume"]["xla_entries_added"] == 0
    assert warm["phases"]["warm_resume"]["compiled_step"]["source"] == \
        "loaded"
    assert len(cold["phases"]["kernels"]) == 11   # 35 at full size
    held = cold["phases"]["held_chunks"]["tiny"]   # one trip and two
    assert (held["rows"], held["chunk"], held["loop"]) == (512, 512, False)
    assert sum("relative l2" in k for k in held) == 12
    assert sorted(k for k in held if k.startswith("ms at chunk")) == [
        "ms at chunk 128", "ms at chunk 256", "ms at chunk 512"]
    assert sorted(held["ms at chunk 256"]) == ["0.03", "0.06", "0.125",
                                               "0.25", "0.5", "1.0"]
    # the flash kernels on both operand forms where the route rule allows
    flash = cold["phases"]["flash"]
    assert flash["tiny"]["operand form"] == "operands token-major (B,S,HxD)"
    assert flash["tiny narrow"]["operand form"] == \
        "operands head-major (Dh 24, not lane-aligned)"
    assert sum("relative l2" in k for k in flash["tiny"]) == 8
    assert sum("relative l2" in k for k in flash["tiny narrow"]) == 4
    # the delta-rule scans, each through the arm kda_route chose
    scan = cold["phases"]["scan"]
    assert scan["tiny per-head"]["arm"] == scan["tiny per-channel"]["arm"] \
        == "chunked"
    assert "one decay a head" in scan["tiny per-head"]["route"]
    assert all(sum("relative l2" in k for k in v) == 6
               for name, v in scan.items() if name != "tiny ssd")
    # Mamba-2's scan through the arm ssd_route chose, six gradients
    assert scan["tiny ssd"]["arm"] == "chunked" \
        and "not pallas" in scan["tiny ssd"]["route"]
    assert sum("relative l2" in k for k in scan["tiny ssd"]) == 7
    assert sorted(os.listdir(cache / "aot"))   # the step store rode along


def test_restart_rule_follows_what_the_aot_store_holds():
    """A restart must not compile: it loads the step where aot/ holds it,
    and may be answered by the XLA cache only where aot/ does not (the
    driver's machine refused PR 21 over a store that was not written)."""
    import pytest
    sys.path.insert(0, REPO)
    import chip_smoke

    def facts(source):
        return {"compiled_step": {"source": source}}

    chip_smoke.check_restart("r", facts("loaded"), must_load=True)
    chip_smoke.check_restart("r", facts("loaded"), must_load=False)
    chip_smoke.check_restart("r", facts("xla_cache"), must_load=False)
    for source, must_load in (("xla_cache", True), ("compiled", False),
                              ("jit", False)):
        with pytest.raises(AssertionError):
            chip_smoke.check_restart("r", facts(source), must_load=must_load)


def test_stats_yaml_reads_back(tmp_path):
    """The smoke reads what `train` wrote: read_stats_yaml is the inverse
    of StatsRegistry's own renderer."""
    from poseidon_tpu.runtime.metrics import StatsRegistry, read_stats_yaml
    reg = StatsRegistry()
    reg.add("train_iters", 24)
    reg.set_gauge("peak_bytes_in_use", 1932490240)
    reg.set_section("compiled_step", {"source": "loaded", "seconds": 0.1,
                                      "pallas_custom_calls": 4})
    reg.set_section("comm", {"per_layer": {"fc6": {"strategy": "sfb"}}})
    path = str(tmp_path / "stats.yaml")
    reg.dump_yaml(path)
    doc = read_stats_yaml(path)
    assert doc["counters"]["train_iters"] == "24.0"
    assert doc["gauges"]["peak_bytes_in_use"] == "1932490240"
    assert doc["compiled_step"] == {"source": "loaded", "seconds": "0.1",
                                    "pallas_custom_calls": "4"}
    assert doc["comm"]["per_layer"]["fc6"]["strategy"] == "sfb"
