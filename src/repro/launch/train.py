"""End-to-end training driver.

``make_train_step`` is the single train-step factory used by BOTH the
real driver (this file's CLI, host mesh) and the multi-pod dry-run
(launch/dryrun.py, 512 placeholder devices): forward + CE, grad
accumulation over microbatches, optional gradient compression, LR
schedule, AdamW, all under pjit with the cell's sharding rules.

CLI (see examples/train_lm.py for the library-level version):

    PYTHONPATH=src python -m repro.launch.train \
        --arch granite-3-2b-smoke --steps 200 --batch 16 --seq 128
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.config import ParallelConfig, TrainConfig
from repro.configs import get_config
from repro.data.lm_data import bigram_ce_floor, lm_batch
from repro.data.pipeline import ShardedFeed, batch_sharding
from repro.launch.mesh import make_host_mesh
from repro.distributed.sharding import default_rules
from repro.models.model import Model, build_model
from repro.optim.adamw import AdamWState, adamw_init, adamw_update
from repro.optim.compression import compress_decompress
from repro.optim.schedule import cosine_schedule


def make_train_step(model: Model, tcfg: TrainConfig):
    pcfg = model.parallel
    ct = model.cfg.compute_dtype

    def loss_fn(params, batch):
        # cast-before-gather: matrix params drop to compute dtype ONCE at
        # step start, while still sharded — every FSDP all-gather then
        # moves bf16 instead of fp32 (the model's per-use .astype becomes
        # a no-op).  Grads flow through the cast, so the optimizer still
        # accumulates into fp32 master params.  1-D params (norm scales,
        # biases) stay fp32.
        cast = jax.tree_util.tree_map(
            lambda p: p.astype(ct) if p.ndim >= 2 else p, params)
        return model.loss_fn(cast, batch)

    # PartitionSpecs for the grad accumulator: a bare jnp.zeros is
    # data-independent, so GSPMD REPLICATES it — every microbatch's
    # weight grads were then fp32-all-reduced to full size (measured:
    # 2 x 315 GiB/chip/step on arctic train_4k).  Constraining the
    # accumulator to the param sharding turns those into reduce-scatters
    # onto the FSDP shards.
    pspecs = None
    if model.rules is not None:
        from repro.distributed.sharding import param_specs
        pspecs = param_specs(model.schema(), model.rules)

    def train_step(params, opt: AdamWState, batch):
        if pcfg.microbatch > 1:
            m = pcfg.microbatch

            def resh(x):
                return x.reshape((m, x.shape[0] // m) + x.shape[1:])

            mbs = jax.tree_util.tree_map(resh, batch)
            acc_dt = pcfg.grad_accum_dtype
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, acc_dt), params)
            if pspecs is not None:
                zeros = jax.tree_util.tree_map(
                    lambda z, s: jax.lax.with_sharding_constraint(z, s),
                    zeros, pspecs)

            def acc(carry, mb):
                gsum, lsum = carry
                (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, mb)
                gsum = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(a.dtype), gsum, g)
                if pspecs is not None:
                    # re-assert inside the loop body: while-carry
                    # shardings do not propagate reliably (same issue as
                    # the layer-scan residual carry)
                    gsum = jax.tree_util.tree_map(
                        lambda z, sp: jax.lax.with_sharding_constraint(z, sp),
                        gsum, pspecs)
                return (gsum, lsum + l), None

            (gsum, lsum), _ = jax.lax.scan(acc, (zeros, jnp.float32(0.0)),
                                           mbs)
            grads = jax.tree_util.tree_map(lambda g: g / m, gsum)
            loss = lsum / m
            metrics: Dict[str, jax.Array] = {"ce": loss}
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)

        if pcfg.gradient_compression != "none":
            grads = jax.tree_util.tree_map(
                lambda g: compress_decompress(g, pcfg.gradient_compression),
                grads)

        lr = cosine_schedule(opt.step, peak=tcfg.learning_rate,
                             warmup=tcfg.warmup_steps, total=tcfg.total_steps)
        params, opt, om = adamw_update(grads, opt, params, lr, tcfg,
                                       pcfg.adam_moment_dtype)
        return params, opt, {"loss": loss, **metrics, **om}

    return train_step


@dataclasses.dataclass
class TrainState:
    params: Any
    opt: AdamWState
    step: int = 0


def train_loop(model: Model, tcfg: TrainConfig, feed, *,
               manager: Optional[CheckpointManager] = None,
               ckpt_every: int = 0, log_every: int = 10,
               state: Optional[TrainState] = None,
               log=print) -> TrainState:
    if state is None:
        params = model.init(jax.random.PRNGKey(tcfg.seed))
        state = TrainState(params=params,
                           opt=adamw_init(params,
                                          model.parallel.adam_moment_dtype))
    step_fn = jax.jit(make_train_step(model, tcfg), donate_argnums=(0, 1))
    t0 = time.time()
    for batch in feed:
        state.params, state.opt, metrics = step_fn(state.params, state.opt,
                                                   batch)
        state.step += 1
        if log_every and state.step % log_every == 0:
            loss = float(metrics["loss"])
            log(f"step {state.step:5d}  loss {loss:.4f}  "
                f"gnorm {float(metrics['grad_norm']):.3f}  "
                f"lr {float(metrics['lr']):.2e}  "
                f"{(time.time() - t0) / log_every:.3f}s/step")
            t0 = time.time()
        if manager is not None and ckpt_every and state.step % ckpt_every == 0:
            manager.save_async(state.step,
                               {"params": state.params, "opt": state.opt},
                               metric=float(metrics["loss"]))
        if state.step >= tcfg.total_steps:
            break
    if manager is not None:
        manager.wait()
    return state


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b-smoke")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    mesh = make_host_mesh()
    rules = default_rules(fsdp=False)
    pcfg = ParallelConfig(fsdp=False, microbatch=args.microbatch)
    model = build_model(cfg, pcfg, rules)
    tcfg = TrainConfig(learning_rate=args.lr, warmup_steps=args.steps // 10,
                       total_steps=args.steps)

    key = jax.random.PRNGKey(0)
    feed = ShardedFeed(
        lambda s: lm_batch(jax.random.fold_in(key, s), args.batch, args.seq,
                           cfg.vocab_size),
        sharding=batch_sharding(mesh))
    manager = (CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None)
    print(f"training {args.arch}: vocab {cfg.vocab_size}, "
          f"CE floor ≈ {bigram_ce_floor(cfg.vocab_size):.3f} nats")
    with jax.set_mesh(mesh):
        train_loop(model, tcfg, feed, manager=manager,
                   ckpt_every=args.ckpt_every)
    feed.close()


if __name__ == "__main__":
    main()
