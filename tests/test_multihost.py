"""Multi-process distributed training over jax.distributed on localhost.

The reference tests its distributed story with multi-process binaries on
127.0.0.1 (ps/tests/petuum_ps/comm_handler/, SURVEY §4.3). Same idea: spawn 2
real processes x 4 virtual CPU devices each through scripts/launch.py --local,
train LeNet on the shared synthetic MNIST LMDB, and check both processes agree
on the final parameters (replicated state implies identical snapshots).
"""

import os
import socket
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_train_touches_no_backend_before_init_distributed():
    """jax.distributed.initialize refuses to run once any backend exists,
    so `train` must call init_distributed before ANYTHING that touches
    one. A fresh process — backends already exist in this one."""
    import subprocess
    code = f"""
import sys
sys.path.insert(0, {REPO!r})
from jax._src import xla_bridge
from poseidon_tpu.runtime import cli, cluster

def init_distributed(**kw):
    assert not xla_bridge.backends_are_initialized(), \\
        "a backend was initialized before init_distributed"
    print("INIT_FIRST_OK", flush=True)
    sys.exit(0)

cluster.init_distributed = init_distributed
cli.main(["train", "--solver={REPO}/examples/mnist/lenet_solver.prototxt"])
"""
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0 and "INIT_FIRST_OK" in r.stdout, \
        r.stdout[-1000:] + r.stderr[-2000:]


def _run_local_train(tmp_path, prefix: str, max_iter: int, extra_args=()):
    """Drive the REAL launcher (scripts/launch.py --local path): 2 processes
    x 4 virtual devices training lenet; returns (logs, per-process snapshot
    npz handles at max_iter)."""
    solver = tmp_path / "solver.prototxt"
    solver.write_text(f"""
net: "{REPO}/examples/mnist/lenet_train_test.prototxt"
base_lr: 0.01
lr_policy: "fixed"
momentum: 0.9
display: 5
max_iter: {max_iter}
test_interval: 0
snapshot_after_train: true
snapshot_prefix: "{prefix}"
random_seed: 5
""")
    outs = [tmp_path / "p0", tmp_path / "p1"]
    for o in outs:
        o.mkdir()
    scripts = os.path.join(REPO, "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    import launch
    rc, raw_logs = launch.launch_local(
        2, 4, _free_port(),
        ["train", "--solver", str(solver), *extra_args,
         "--output_dir", str(tmp_path / "p{proc_id}")],
        capture=True)
    logs = [b.decode() for b in raw_logs]
    assert rc == 0, f"launch failed:\n{logs[0][-2000:]}\n{logs[1][-2000:]}"
    snaps = [np.load(str(o / f"{prefix}_iter_{max_iter}.solverstate.npz"))
             for o in outs]
    # replicated state: every process writes identical snapshot bytes
    assert set(snaps[0].files) == set(snaps[1].files)
    for k in snaps[0].files:
        np.testing.assert_array_equal(snaps[0][k], snaps[1][k], err_msg=k)
    return logs, snaps


@pytest.mark.skipif(not os.path.isdir(
    os.path.join(REPO, "examples/mnist/mnist_train_lmdb")),
    reason="synthetic MNIST LMDB not generated")
def test_two_process_training(tmp_path):
    logs, _ = _run_local_train(tmp_path, "lenet_mp", 12)
    # training actually progressed (loss decreased in the rank-0 log)
    assert "Iteration 10" in logs[0]


@pytest.mark.skipif(not os.path.isdir(
    os.path.join(REPO, "examples/mnist/mnist_train_lmdb")),
    reason="synthetic MNIST LMDB not generated")
def test_two_process_two_tier_training(tmp_path):
    """--dcn_slices 2 across TWO REAL PROCESSES: the dcn axis lands on the
    inter-process boundary (each process's 4 local devices form one slice) —
    exactly the topology the managed-comm tier exists for."""
    _, snaps = _run_local_train(
        tmp_path, "lenet_tier", 10,
        ["--dcn_slices", "2", "--strategy", "topk"])
    # PER-SLICE residuals (leading dim = 2 slices, not 8 devices): pins the
    # hierarchical grouping, not just that TOPK ran
    err_keys = [k for k in snaps[0].files if k.startswith("comm_error/")]
    assert err_keys
    for k in err_keys:
        assert snaps[0][k].shape[0] == 2, (k, snaps[0][k].shape)


@pytest.mark.skipif(not os.path.isdir(
    os.path.join(REPO, "examples/mnist/mnist_test_lmdb")),
    reason="synthetic MNIST LMDB not generated")
def test_two_process_cli_test_command(tmp_path):
    """`test` under 2 processes: each host scores a disjoint shard against a
    sharded eval step (the round-1 gap: pipelines built without a Shard)."""
    model = tmp_path / "net.prototxt"
    # TEST-phase-only view of the lenet train/test net
    src = open(os.path.join(REPO,
                            "examples/mnist/lenet_train_test.prototxt")).read()
    model.write_text(src)
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import launch
    rc, raw_logs = launch.launch_local(
        2, 4, _free_port(),
        ["test", "--model", str(model), "--iterations", "4"],
        capture=True)
    logs = [b.decode() for b in raw_logs]
    assert rc == 0, f"cli test failed:\n{logs[0][-2000:]}\n{logs[1][-2000:]}"
    # rank 0 prints averaged metrics; rank 1 stays quiet
    assert "loss:" in logs[0]
    assert "accuracy:" in logs[0]
    assert "loss:" not in logs[1]


@pytest.mark.skipif(not os.path.isdir(
    os.path.join(REPO, "examples/mnist/mnist_train_lmdb")),
    reason="synthetic MNIST LMDB not generated")
def test_two_process_ssp_two_tier_wire(tmp_path):
    """The full round-3 composition across TWO REAL PROCESSES: staleness on
    the inter-process (DCN) tier, dense intra-process tier, bf16 wire,
    blocked TOPK. Each process's 4 local devices form one slice; the slices
    diverge for one step and reconcile compressed bf16 deltas over the
    process boundary — the SSPAggr deployment on a real process topology."""
    logs, snaps = _run_local_train(
        tmp_path, "lenet_sspaggr", 10,
        ["--staleness", "1", "--dcn_slices", "2", "--strategy", "topk",
         "--wire_dtype", "bf16", "--topk_block", "256"])
    assert "Iteration 10" in logs[0] or "Iteration 5" in logs[0]
    # SSP state with per-slice groups: local replicas stacked (2, ...)
    local_keys = [k for k in snaps[0].files if k.startswith("local_params/")]
    assert local_keys, sorted(snaps[0].files)[:8]
    for k in local_keys:
        assert snaps[0][k].shape[0] == 2, (k, snaps[0][k].shape)


def test_two_process_lm_tensor_parallel():
    """The LM family over the REAL distributed control plane: 2 processes
    x 4 devices run dp x tp with mesh data=1 x model=8, so the Megatron
    f/g psums themselves cross the process boundary (a data=2 x model=4
    mesh would keep every model group inside one process). Loss must fall
    and both ranks must exit clean. Launched through launch_local — the
    one owner of the multi-process env contract."""
    import re
    scripts = os.path.join(REPO, "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    import launch
    rc, raw_logs = launch.launch_local(
        2, 4, _free_port(),
        ["--mode", "tp", "--data_axis", "1", "--par_axis", "8",
         "--steps", "20", "--seq", "32", "--d_model", "32",
         "--n_heads", "8", "--display", "19", "--batch", "8"],
        capture=True,
        program=[sys.executable,
                 os.path.join(REPO, "examples/lm/train_lm.py")])
    logs = [b.decode() for b in raw_logs]
    assert rc == 0, logs[0][-2000:] + logs[1][-2000:]
    losses = [float(m) for m in re.findall(r"loss (\d+\.\d+)", logs[0])]
    assert len(losses) >= 2 and losses[-1] < losses[0], losses
