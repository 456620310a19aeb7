#!/usr/bin/env python
"""Cluster launcher: run `python -m poseidon_tpu train` on every hostfile node.

The analog of the reference's examples/*/train_*.py SSH launchers
(examples/cifar10/train_cifar10.py:26-35): reads the hostfile, SSHes to each
host (or spawns local processes for 127.0.0.1 testing), and starts one
training process per node with its node id. Kills strays first, like the
reference's run_local.py killall preamble.

    python scripts/launch.py --hostfile machinefiles/cluster4 \
        -- train --solver=examples/mnist/lenet_solver.prototxt

Local multi-process CPU SIMULATION ONLY (no SSH; N processes x M virtual
devices — every child is pinned to the CPU backend; on a TPU host one
process drives every local chip, so there is nothing to launch):

    python scripts/launch.py --local 2 --devices-per-proc 4 \
        -- train --solver=...
"""

from __future__ import annotations

import argparse
import os
import shlex
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def launch_local(n_proc: int, devices: int, port: int, train_args,
                 capture: bool = False, program=None) -> int:
    """Spawn n_proc local training processes. Any '{proc_id}' in
    train_args is replaced per process (e.g. per-rank output dirs).
    With capture=True, returns (rc, [stdout bytes]) for tests.
    ``program`` overrides the argv prefix (default: the poseidon_tpu CLI)
    so other entry points — e.g. examples/lm/train_lm.py — run under the
    same multi-process env contract without copying it."""
    procs = []
    for pid in range(n_proc):
        env = dict(os.environ)
        # CPU SIMULATION ONLY: every child is pinned to the CPU backend (a
        # chip belongs to one process; N local processes cannot share it)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count={devices}"
                            ).strip()
        env["POSEIDON_COORDINATOR"] = f"127.0.0.1:{port}"
        env["POSEIDON_NUM_PROCS"] = str(n_proc)
        env["POSEIDON_PROC_ID"] = str(pid)
        sub = [a.replace("{proc_id}", str(pid)) for a in train_args]
        cmd = (program or [sys.executable, "-m", "poseidon_tpu"]) + sub
        kw = dict(stdout=subprocess.PIPE, stderr=subprocess.STDOUT) \
            if capture else {}
        procs.append(subprocess.Popen(cmd, env=env, cwd=REPO, **kw))
    rc = 0
    logs = []
    try:
        for p in procs:
            if capture:
                out, _ = p.communicate(timeout=600)
                logs.append(out)
            else:
                p.wait()
            rc |= p.returncode
    finally:
        # a dead rank leaves the others blocked in rendezvous/collectives;
        # never leak them past the launcher
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    return (rc, logs) if capture else rc


def launch_ssh(hostfile: str, train_args) -> int:
    from poseidon_tpu.runtime.cluster import parse_hostfile
    hosts = parse_hostfile(hostfile)
    ssh_opts = ("-o StrictHostKeyChecking=no "
                "-o UserKnownHostsFile=/dev/null")
    # Stray cleanup first, in its OWN ssh session: the [p] trick keeps the
    # pattern from matching that shell, and the training command must not
    # share a shell with the pkill (its cmdline would contain the real
    # module name and self-kill).
    for h in hosts:
        subprocess.run(["ssh"] + ssh_opts.split()
                       + [h.ip, "pkill -f '[p]oseidon_tpu' || true"])
    procs = []
    for h in hosts:
        remote = (f"cd {shlex.quote(REPO)} && "
                  f"python -m poseidon_tpu "
                  + " ".join(shlex.quote(a) for a in train_args)
                  + f" --hostfile {shlex.quote(hostfile)} --node_id {h.id}")
        procs.append(subprocess.Popen(["ssh"] + ssh_opts.split()
                                      + [h.ip, remote]))
    rc = 0
    for p in procs:
        rc |= p.wait()
    return rc


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--hostfile")
    ap.add_argument("--local", type=int, default=0,
                    help="spawn N local CPU-pinned processes instead of "
                         "SSH (simulation only; never touches a chip)")
    ap.add_argument("--devices-per-proc", type=int, default=4)
    ap.add_argument("--port", type=int, default=12355)
    ap.add_argument("rest", nargs=argparse.REMAINDER,
                    help="-- followed by poseidon_tpu CLI args")
    args = ap.parse_args()
    rest = args.rest
    if rest and rest[0] == "--":
        rest = rest[1:]
    if args.local:
        return launch_local(args.local, args.devices_per_proc, args.port, rest)
    if not args.hostfile:
        ap.error("--hostfile or --local required")
    return launch_ssh(args.hostfile, rest)


if __name__ == "__main__":
    sys.exit(main())
