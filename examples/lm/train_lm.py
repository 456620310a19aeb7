"""Long-context character LM — runnable demo of every LM parallelism mode.

The transformer family is this framework's beyond-the-reference flagship.
``--mode`` picks the second mesh axis next to data parallelism:

  sp  (default) ring attention over a ("data","seq") mesh — sequence chunks
      rotate K/V over ICI; on TPU each chunk runs the Pallas flash kernels
  tp  Megatron-style tensor parallelism over ("data","model") — heads/FFN
      columns split, f/g conjugate collectives inside each block
  pp  GPipe-style pipeline over ("data","stage") — layers split, microbatch
      ticks on a ppermute ring, backward pipeline from autodiff
  ep  switch MoE over ("data","expert") — top-1 routing, one all_to_all
      pair per MoE layer

    # 8 virtual devices, 2 data x 4 sequence shards:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/lm/train_lm.py --steps 200 --seq 256

    # same devices, tensor parallelism / pipeline / MoE:
    ... train_lm.py --mode tp --steps 100
    ... train_lm.py --mode pp --n_layers 4 --microbatches 2 --steps 100
    ... train_lm.py --mode ep --experts 8 --steps 100

    # one real TPU chip (mesh collapses to 1x1):
    python examples/lm/train_lm.py --steps 500 --seq 2048 --bf16 --remat

Which family trains where: the modern block (RMSNorm, RoPE, QK-norm, top-k
dropless MoE, AdamW) — OLMoE-1B-7B — trains through the CLI as layers of a
prototxt Net (`python -m poseidon_tpu train --solver
examples/lm/olmoe_1b_7b_solver.prototxt --bf16`). This script is still the
only way to train the GPT-2-style block (LayerNorm, learned positions, GELU,
switch MoE) and the only user of the sp/tp/pp/ep step builders (ROADMAP D2).

Data: the script's own source file, byte-level — no downloads. Loss should
fall from ~5.5 (ln 256) toward ~2 as it memorizes the file.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("sp", "tp", "pp", "ep"), default="sp")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8, help="global batch")
    ap.add_argument("--d_model", type=int, default=128)
    ap.add_argument("--n_layers", type=int, default=2)
    ap.add_argument("--n_heads", type=int, default=4)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--data_axis", type=int, default=0,
                    help="data-axis size; 0 = auto (devices/par_axis)")
    ap.add_argument("--par_axis", type=int, default=0,
                    help="size of the mode's axis (seq/model/stage/expert "
                         "ranks); 0 = auto (up to 4)")
    ap.add_argument("--microbatches", type=int, default=2, help="pp only")
    ap.add_argument("--experts", type=int, default=8, help="ep only")
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--display", type=int, default=20)
    ap.add_argument("--generate", type=int, default=0, metavar="N",
                    help="after training, greedy-decode N bytes from a "
                         "corpus prompt (all modes; MoE decodes dropless)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from poseidon_tpu import config
    from poseidon_tpu.models import moe as moe_mod
    from poseidon_tpu.models import transformer as tfm
    from poseidon_tpu.parallel.mesh import make_mesh
    from poseidon_tpu.proto.messages import SolverParameter
    from poseidon_tpu.runtime.cluster import init_distributed
    from poseidon_tpu.solvers.updates import init_state

    if args.bf16:
        config.set_policy(compute_dtype=jnp.bfloat16)

    # joins the jax.distributed cluster when launched multi-process (the
    # scripts/launch.py env contract); no-op standalone. The mesh below
    # then spans every process's devices and the step's collectives ride
    # the real transport.
    rank = init_distributed()
    n_dev = jax.device_count()
    if args.par_axis:
        par_ax = args.par_axis
    else:  # largest divisor of the device count, at most 4
        par_ax = next(d for d in (4, 3, 2, 1) if n_dev % d == 0)
    data_ax = args.data_axis or max(1, n_dev // par_ax)
    if data_ax * par_ax != n_dev:
        raise SystemExit(f"mesh {data_ax}x{par_ax} != {n_dev} devices "
                         f"(pick --data_axis/--par_axis that multiply to "
                         f"{n_dev})")
    axis_name = {"sp": "seq", "tp": "model", "pp": "stage",
                 "ep": "expert"}[args.mode]
    batch_div = data_ax * (par_ax if args.mode == "ep" else 1)
    if args.batch % batch_div or (args.mode == "sp"
                                  and args.seq % par_ax):
        raise SystemExit(
            f"--batch {args.batch} must divide by {batch_div}"
            + (f" and --seq {args.seq} by {par_ax}"
               if args.mode == "sp" else ""))
    mesh = make_mesh(axes=("data", axis_name), shape=(data_ax, par_ax))
    if rank == 0:
        print(f"mesh: data={data_ax} x {axis_name}={par_ax} "
              f"({n_dev} devices)")

    cfg = tfm.TransformerConfig(
        vocab_size=256, d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers, d_ff=4 * args.d_model,
        max_seq=args.seq, remat=args.remat)
    sp = SolverParameter(base_lr=args.lr, lr_policy="fixed", momentum=0.9)
    rng = jax.random.PRNGKey(0)
    if args.mode == "sp":
        params = tfm.init_params(cfg, rng)
        step = tfm.build_dp_sp_train_step(cfg, sp, mesh, donate=False)
    elif args.mode == "tp":
        if args.n_heads % par_ax or (4 * args.d_model) % par_ax:
            raise SystemExit(f"--n_heads {args.n_heads} and d_ff "
                             f"{4 * args.d_model} must divide by the "
                             f"model axis {par_ax}")
        params = tfm.to_tp_layout(tfm.init_params(cfg, rng), cfg)
        step = tfm.build_dp_tp_train_step(cfg, sp, mesh, params,
                                          donate=False)
    elif args.mode == "pp":
        if args.n_layers % par_ax:
            raise SystemExit(f"--n_layers {args.n_layers} must divide by "
                             f"the stage axis {par_ax} (try --n_layers "
                             f"{par_ax})")
        if (args.batch // data_ax) % args.microbatches:
            raise SystemExit(f"local batch {args.batch // data_ax} must "
                             f"divide by --microbatches "
                             f"{args.microbatches}")
        params = tfm.to_pp_layout(tfm.init_params(cfg, rng), cfg)
        step = tfm.build_dp_pp_train_step(
            cfg, sp, mesh, params, microbatches=args.microbatches,
            donate=False)
    else:  # ep
        if args.experts % par_ax:
            raise SystemExit(f"--experts {args.experts} must divide by the "
                             f"expert axis {par_ax}")
        mcfg = moe_mod.MoEConfig(base=cfg, n_experts=args.experts)
        params = moe_mod.init_moe_params(mcfg, rng)
        step = moe_mod.build_dp_ep_train_step(mcfg, sp, mesh, params,
                                              donate=False)

    # byte-level corpus: this very file, tiled so any --seq fits
    corpus = np.frombuffer(open(__file__, "rb").read(), np.uint8)
    if len(corpus) <= args.seq + 1:
        corpus = np.tile(corpus, args.seq // len(corpus) + 2)
    rs = np.random.RandomState(0)

    def sample_batch():
        starts = rs.randint(0, len(corpus) - args.seq - 1, size=args.batch)
        toks = np.stack([corpus[s:s + args.seq + 1] for s in starts])
        return (jnp.asarray(toks[:, :-1].astype(np.int32)),
                jnp.asarray(toks[:, 1:].astype(np.int32)))

    state = init_state(params)
    if jax.process_count() > 1:
        # host-numpy leaves are the multi-process placement contract:
        # identical on every process, pjit shards/replicates them per the
        # step's in_specs (sharded jnp singles would be process-local)
        params = jax.tree_util.tree_map(np.asarray, params)
        state = jax.tree_util.tree_map(np.asarray, state)
    t0 = steps_timed = 0
    for it in range(1, args.steps + 1):
        tokens, targets = sample_batch()
        params, state, metrics = step(params, state, tokens, targets,
                                      jax.random.PRNGKey(it))
        if it == 1:
            # first step is compile-dominated: report it, then restart the
            # throughput clock so tok/s reflects steady state
            if rank == 0:
                print(f"step {it:5d}  loss {float(metrics['loss']):.4f}  "
                      f"(compiling)", flush=True)
            t0, steps_timed = time.perf_counter(), 0
            continue
        steps_timed += 1
        if it % args.display == 0 and rank == 0:
            dt = time.perf_counter() - t0
            tps = steps_timed * args.batch * args.seq / dt
            print(f"step {it:5d}  loss {float(metrics['loss']):.4f}  "
                  f"{tps:,.0f} tok/s", flush=True)

    if args.generate and jax.process_count() > 1:
        if rank == 0:
            print("--generate: single-process only; skipping")
    elif args.generate:
        if args.generate > cfg.max_seq - 8:
            raise SystemExit(f"--generate {args.generate} must be < "
                             f"max_seq - 8 = {cfg.max_seq - 8} (learned "
                             f"positions cover prompt + generation)")
        from poseidon_tpu.models.generate import generate as gen
        # decoding runs on canonical (single-device) params; MoE decode
        # routes all experts locally (dropless)
        plain, gen_cfg = params, cfg
        if args.mode == "tp":
            plain = tfm.from_tp_layout(params, cfg)
        elif args.mode == "pp":
            plain = tfm.from_pp_layout(params, cfg)
        elif args.mode == "ep":
            gen_cfg = mcfg
        p_len = max(1, min(32, cfg.max_seq - args.generate))
        prompt = jnp.asarray(
            corpus[None, :p_len].astype(np.int32))
        toks, _ = gen(plain, gen_cfg, prompt, args.generate)
        text = bytes(np.asarray(toks)[0].astype(np.uint8)).decode(
            "utf-8", errors="replace")
        print(f"prompt: "
              f"{bytes(corpus[:p_len]).decode('utf-8', errors='replace')!r}")
        print(f"generated: {text!r}")
    print("done")


if __name__ == "__main__":
    main()
