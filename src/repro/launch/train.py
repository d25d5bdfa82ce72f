"""Training driver: end-to-end, fault-tolerant, resumable.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --steps 200 --batch 8 --seq 128 --smoke --ckpt-dir /tmp/ckpt

Production behaviors wired in (all exercised by tests / examples on CPU):
  * jit'd train step with donated params/opt-state (no double-buffering of
    the 12-bytes/param optimizer + master state);
  * async checkpointing every ``--ckpt-every`` steps (params, opt state,
    data-iterator state), atomic commit, crc-verified restore;
  * automatic resume from the latest complete checkpoint;
  * simulated failure injection (``--fail-at-step``) to exercise the
    crash->restart->resume path end to end;
  * grad accumulation (``--grad-accum``) — the elastic re-mesh lever that
    keeps the global batch constant when the data axis shrinks (ft/monitor).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import AsyncCheckpointer, latest_step, load_checkpoint
from repro.configs import get_config
from repro.configs.base import ShapeCfg
from repro.data import make_dataset
from repro.distributed import sharding as shd
from repro.core.compile_cache import enable_compile_cache
from repro.launch.mesh import make_smoke_mesh
from repro.models import init_model, steps as ST
from repro.optim import adamw_init


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-sized)")
    ap.add_argument("--quantize", default="off")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at-step", type=int, default=-1,
                    help="simulate a crash at this step (fault-tolerance drill)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--model-parallel", type=int, default=1)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if args.quantize != "off":
        cfg = cfg.replace(quantize=args.quantize)

    enable_compile_cache()
    mesh = make_smoke_mesh(args.model_parallel)
    shape = ShapeCfg("train_cli", args.seq, args.batch, "train")

    with shd.use_mesh(mesh):
        params = init_model(cfg, jax.random.PRNGKey(0))
        opt_state = adamw_init(params)
        ds = make_dataset(cfg, shape)
        start = 0

        ckpt = AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
        if ckpt is not None:
            s = latest_step(args.ckpt_dir)
            if s is not None:
                state = load_checkpoint(args.ckpt_dir, s,
                                        {"params": params, "opt": opt_state,
                                         "data": ds.state()})
                params, opt_state = state["params"], state["opt"]
                ds.restore(jax.tree.map(lambda x: np.asarray(x), state["data"]))
                start = s
                print(f"[train] resumed from checkpoint step {s}", flush=True)
                if start >= args.steps:
                    print(f"[train] checkpoint already at/past --steps "
                          f"{args.steps}; nothing to do", flush=True)
                    return 0

        train_step = jax.jit(ST.make_train_step(cfg), donate_argnums=(0, 1))

        t0 = time.time()
        tokens_done = 0
        for step in range(start, args.steps):
            if step == args.fail_at_step:
                print(f"[train] SIMULATED FAILURE at step {step}", flush=True)
                if ckpt:
                    ckpt.wait()
                return 42  # crash exit code — restart resumes from checkpoint

            loss_acc = 0.0
            for _ in range(args.grad_accum):
                batch = next(ds)
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
                params, opt_state, metrics = train_step(
                    params, opt_state, batch, jnp.asarray(step, jnp.int32))
                loss_acc += float(metrics["loss"])
            tokens_done += args.batch * args.seq * args.grad_accum

            if step % args.log_every == 0 or step == args.steps - 1:
                dt = time.time() - t0
                print(f"[train] step {step:5d} loss {loss_acc / args.grad_accum:.4f} "
                      f"gnorm {float(metrics['gnorm']):.3f} lr {float(metrics['lr']):.2e} "
                      f"tok/s {tokens_done / max(dt, 1e-9):.0f}", flush=True)

            if ckpt is not None and step > start and step % args.ckpt_every == 0:
                ckpt.save(step, {"params": params, "opt": opt_state, "data": ds.state()})

        if ckpt is not None:
            ckpt.save(args.steps, {"params": params, "opt": opt_state, "data": ds.state()})
            ckpt.wait()
        print(f"[train] done: {args.steps} steps, final loss "
              f"{loss_acc / args.grad_accum:.4f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
