"""Production training launcher.

Wires every subsystem together: config registry -> mesh + sharding rules
-> pjit train step -> synthetic sharded data -> PMT PowerMonitor (per-step
energy, CSV log, cumulative accounting) -> atomic async checkpoints with
energy metadata -> restart-exact resume (params, optimizer, data cursor,
joules) -> power-based straggler detection hooks.

On this CPU container it runs real (small) configs on the 1-device smoke
mesh; on a pod it is the same code with ``--mesh prod``.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --reduced --steps 100 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.core as pmt
from repro import configs
from repro.checkpoint.manager import (CheckpointManager, CheckpointMeta,
                                      latest_step, restore)
from repro.data.pipeline import DataConfig, SyntheticLMDataset
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import base_rules, make_production_mesh, \
    make_smoke_mesh
from repro.optim.optimizers import OptimizerConfig
from repro.sharding.specs import axis_rules
from repro.train.steps import (init_train_state, make_measured_train_step,
                               make_train_step)


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m",
                    choices=list(configs.ARCH_NAMES))
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", choices=["smoke", "prod", "prod2"],
                    default="smoke")
    ap.add_argument("--energy-log", default="")
    ap.add_argument("--energy-jsonl", default="",
                    help="structured per-region JSONL export path")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    cfg = configs.get_config(args.arch, reduced=args.reduced)
    ocfg = OptimizerConfig(name=cfg.optimizer, lr=args.lr,
                           warmup_steps=min(20, args.steps // 5 + 1),
                           decay_steps=args.steps)

    if args.mesh == "smoke":
        mesh = make_smoke_mesh()
    else:
        mesh = make_production_mesh(multi_pod=(args.mesh == "prod2"))
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    rules = base_rules(multi_pod=(args.mesh == "prod2"))

    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch, seed=args.seed)
    ds = SyntheticLMDataset(dcfg)

    # One shared measurement session for the whole run: the monitor, any
    # serve engine, and ad-hoc regions all resolve off the same background
    # sampler per backend (drawn from the process-wide pool).
    session = pmt.Session(["cpuutil", "tpu"])
    if args.energy_jsonl:
        session.add_exporter(pmt.JsonlExporter(args.energy_jsonl))
    monitor = pmt.PowerMonitor(log_path=args.energy_log or None,
                               session=session)
    mgr = (CheckpointManager(args.ckpt_dir, every=args.ckpt_every)
           if args.ckpt_dir else None)

    with mesh, axis_rules(rules, sizes):
        state, _ = init_train_state(jax.random.PRNGKey(args.seed), cfg,
                                    ocfg)
        start_step = 0
        if mgr and latest_step(args.ckpt_dir) is not None:
            state, meta = restore(args.ckpt_dir, state)
            start_step = meta.data_step
            monitor = pmt.PowerMonitor(
                log_path=args.energy_log or None,
                initial_joules=meta.cumulative_joules, session=session)
            print(f"resumed step={meta.step} "
                  f"joules={meta.cumulative_joules:.1f}")

        step_fn = jax.jit(make_train_step(cfg, ocfg,
                                          microbatches=args.microbatches))
        tokens_per_step = args.batch * args.seq
        measured_step = make_measured_train_step(
            step_fn, monitor, tokens_per_step=tokens_per_step)
        t_start = time.time()
        for s in range(start_step + 1, args.steps + 1):
            batch = {k: jnp.asarray(v) for k, v in ds.batch(s).items()}
            state, metrics, box = measured_step(state, batch, s)
            if mgr:
                sd = monitor.state_dict()
                mgr.maybe_save(s, state, CheckpointMeta(
                    step=s, data_step=s,
                    cumulative_joules=sd["cumulative_joules"],
                    joules_per_step_ema=sd["joules_per_step_ema"]))
            if s % args.log_every == 0 or s == args.steps:
                r = box.records[0]
                print(f"step {s:5d} loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"lr={float(metrics['lr']):.2e} "
                      f"J/step={r.joules:.3f} "
                      f"tok/s={tokens_per_step / max(r.seconds, 1e-9):.0f}",
                      flush=True)
        if mgr:
            mgr.finalize()
    dt = time.time() - t_start
    print(f"done: {args.steps - start_step} steps in {dt:.1f}s, "
          f"total energy {monitor.cumulative_joules:.1f} J "
          f"(cpuutil measured + tpu modeled)")
    monitor.close()
    session.close()
    return state


if __name__ == "__main__":
    main()
