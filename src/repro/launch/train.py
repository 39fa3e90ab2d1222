"""Production training driver.

Composes: model registry + sharding rules + optimizer + data pipeline +
checkpoint manager + fault tolerance. Runs on 1 CPU device (smoke/examples)
or any mesh; on TPU fleets launch one process per host (jax.distributed) —
the code is identical, only `--mesh` changes.

XLA flags we set on real TPU fleets for compute/comm overlap (recorded here;
they are no-ops on CPU):
    --xla_enable_async_collective_permute=true
    --xla_tpu_enable_async_collective_fusion=true
    --xla_tpu_overlap_compute_collective_tc=true
    --xla_tpu_enable_data_parallel_all_reduce_opt=true

Usage (CPU example, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.attention import AttentionSpec
from repro.ckpt import CheckpointManager
from repro.configs import get_config, get_smoke_config
from repro.data import SyntheticLM, make_batch_iterator
from repro.ft import PreemptionHandler, StragglerMonitor
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.steps import make_train_step, pick_optimizer
from repro.models import init_model
from repro.models.param import count_params
from repro.sharding import batch_spec, param_shardings


class TrainResult(NamedTuple):
    params: dict
    losses: list                  # per-step loss, in step order
    mesh: Optional[jax.sharding.Mesh]   # the (data, seq) mesh under --cp


def build(args):
    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    over = {}
    if args.attn:
        over["attn"] = AttentionSpec.parse(args.attn)
    if args.layers:
        over["n_layers"] = args.layers
    if over:
        cfg = dataclasses.replace(cfg, **over)
    return cfg


def _cp_mesh(args):
    """The (data, seq) mesh for --cp > 1, else None.

    Under the active mesh, `attention()` plans seq mode
    (`repro.kernels.sharded`): each device scans its sequence shard with
    the Pallas kernels and exchanges one constant-size moment carry per
    boundary (forward prefix / backward suffix).
    """
    if args.cp <= 1:
        return None
    from repro.launch.mesh import make_test_mesh

    n_dev = len(jax.devices())
    if args.cp > n_dev or n_dev % args.cp:
        raise SystemExit(
            f"--cp {args.cp} must divide the device count ({n_dev})")
    if args.seq % args.cp:
        raise SystemExit(
            f"--seq {args.seq} must be divisible by --cp {args.cp}")
    mesh = make_test_mesh(shape=(n_dev // args.cp, args.cp),
                          axes=("data", "seq"))
    print(f"context parallelism: cp={args.cp} "
          f"mesh=(data={n_dev // args.cp}, seq={args.cp}) devices="
          f"{[d.id for d in mesh.devices.flat]}", flush=True)
    return mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--attn", default=None,
                    help="attention operator (AttentionSpec.parse name, "
                         "e.g. softmax, fastmax2, fastmax2-kernel)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the model to this many layers (widths stay "
                         "at the config's)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--cp", type=int, default=1,
                    help="context-parallel degree: train under a "
                         "(data=n_dev/cp, seq=cp) mesh — fastmax attention "
                         "shards the sequence over 'seq' with one constant-"
                         "size moment exchange per shard boundary "
                         "(docs/context_parallel.md)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args(argv)
    setup_compile_cache()

    mesh = _cp_mesh(args)
    with (jax.set_mesh(mesh) if mesh is not None
          else contextlib.nullcontext()):
        cfg = build(args)
        key = jax.random.PRNGKey(0)
        params, axes = init_model(key, cfg)
        n_params = count_params(params)
        print(f"arch={cfg.name} params={n_params/1e6:.2f}M "
              f"attn={cfg.attn}", flush=True)

        opt_name, optimizer = pick_optimizer(cfg, n_params, lr=args.lr,
                                             total_steps=args.steps)
        opt_init, _ = optimizer
        opt_state = opt_init(params)
        train_step = jax.jit(make_train_step(cfg, optimizer),
                             donate_argnums=(0, 1))

        data = SyntheticLM(cfg.vocab_size, args.seq, seed=0)
        start_step = 0

        mgr = None
        if args.ckpt_dir:
            mgr = CheckpointManager(args.ckpt_dir)
            if args.resume and mgr.latest_step() is not None:
                (params, opt_state), start_step, _ = mgr.restore(
                    (params, opt_state))
                print(f"resumed from step {start_step}", flush=True)

        pre = PreemptionHandler()
        mon = StragglerMonitor()
        it = make_batch_iterator(data, args.batch, start_step=start_step)
        losses = []
        try:
            for step, batch in it:
                if step >= args.steps or pre.requested:
                    break
                mon.start_step()
                batch = jax.tree.map(jnp.asarray, batch)
                params, opt_state, metrics = train_step(params, opt_state,
                                                        batch)
                dt = mon.end_step()
                losses.append(float(metrics["loss"]))
                if step % args.log_every == 0:
                    print(f"step {step:5d} loss "
                          f"{float(metrics['loss']):.4f} "
                          f"gnorm {float(metrics['gnorm']):.3f} "
                          f"{dt*1e3:.0f}ms"
                          + (" [STRAGGLER]" if mon.straggling else ""),
                          flush=True)
                if mgr and step > 0 and step % args.ckpt_every == 0:
                    mgr.save(step, (params, opt_state), block=False)
        finally:
            it.close()
        if mgr:
            mgr.save(min(step, args.steps), (params, opt_state), block=True)
        print(f"final loss {np.mean(losses[-10:]):.4f} "
              f"(first10 {np.mean(losses[:10]):.4f}) "
              f"step_stats={mon.stats()}", flush=True)
        return TrainResult(params, losses, mesh)


if __name__ == "__main__":
    main()
