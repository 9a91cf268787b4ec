"""Training launcher.

Two modes:

  standard       pjit/GSPMD data+tensor parallel training — gradients are
                 synchronized exactly (the baseline all-reduce semantics).

  decentralized  the paper's contribution generalized to LM training: each
                 data shard ("node") holds ITS OWN parameter copy (leading
                 node axis sharded over "data"); every step does H local
                 optimizer steps then a gossip synchronization of the
                 parameters (sync = allreduce | gossip-hypercube[k] |
                 gossip-ring[k]). With sync=allreduce, H=1 this is exactly
                 standard data-parallel SGD; with partial gossip the nodes
                 drift and re-converge at the lambda2 rate — the DELEDA
                 trade-off, applied to transformers.

CPU-friendly: defaults to the smoke variant of the arch on the host mesh.

  PYTHONPATH=src python -m repro.launch.train --arch granite_3_8b \
      --steps 20 --batch 8 --seq 64
  PYTHONPATH=src python -m repro.launch.train --arch xlstm_125m \
      --mode decentralized --sync gossip-ring[1] --local-steps 2
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import save_checkpoint
from repro.configs import get_config, list_archs, smoke_variant
from repro.core import decentralized as dec
from repro.data.lm_pipeline import TokenPipeline
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as tf
from repro.optim import make_optimizer, make_lr_schedule


def _init_state(cfg, key, opt):
    params = (tf.init_decoder_lm(cfg, key))
    return steps_mod.TrainState(params=params, opt=opt.init(params),
                                step=jnp.zeros((), jnp.int32))


def train_standard(cfg, args, mesh):
    train_step, opt = steps_mod.make_train_step(cfg, args.lr)
    state = _init_state(cfg, jax.random.key(args.seed), opt)
    jitted = jax.jit(train_step, donate_argnums=(0,))
    pipe = TokenPipeline(cfg.vocab_size, args.seq, args.batch,
                         seed=args.seed)
    losses = []
    t0 = time.time()
    for step, batch in zip(range(args.steps), pipe.batches()):
        state, metrics = jitted(state, {"tokens": batch.tokens,
                                        "targets": batch.targets,
                                        "mask": batch.mask})
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0 or step == args.steps - 1:
            # async dispatch: drain in-flight steps before reading the
            # per-step wall clock
            jax.block_until_ready(state)
            print(f"step {step:4d} loss {losses[-1]:.4f} "
                  f"({(time.time()-t0)/(step+1):.2f}s/step)")
    if args.ckpt:
        path = save_checkpoint(args.ckpt, state.params, args.steps)
        print("checkpoint:", path)
    return losses


def train_decentralized(cfg, args, mesh):
    """Node-stacked params [n, ...] sharded over "data"; gossip sync."""
    n = mesh.devices.size
    spec = dec.parse_sync(args.sync)
    opt = make_optimizer(cfg.optimizer, make_lr_schedule("constant",
                                                         args.lr))

    keys = jax.random.split(jax.random.key(args.seed), n)
    params0 = jax.vmap(lambda k: tf.init_decoder_lm(cfg, k))(keys)
    # start from CONSENSUS (same init): average the stacked copies
    params0 = jax.tree.map(
        lambda x: jnp.broadcast_to(x.mean(0, keepdims=True), x.shape),
        params0)
    state = steps_mod.TrainState(params=params0,
                                 opt=jax.vmap(opt.init)(params0),
                                 step=jnp.zeros((), jnp.int32))

    node_sharding = jax.tree.map(
        lambda x: NamedSharding(mesh, P("data") if jnp.ndim(x) else P()),
        state)
    state = jax.device_put(state, node_sharding)

    def local_steps(params, opt_state, step, tokens, targets, mask):
        """H local optimizer steps on ONE node (unbatched leading axis)."""
        def one(i, carry):
            params, opt_state = carry
            b = {"tokens": tokens[i], "targets": targets[i], "mask": mask[i]}
            loss, grads = jax.value_and_grad(
                lambda p: tf.lm_loss(cfg, p, b))(params)
            params, opt_state = opt.update(grads, opt_state, params,
                                           step + i)
            return params, opt_state

        params, opt_state = jax.lax.fori_loop(0, args.local_steps, one,
                                              (params, opt_state))
        # loss after updates, on the last microbatch (for logging)
        b = {"tokens": tokens[-1], "targets": targets[-1], "mask": mask[-1]}
        return params, opt_state, tf.lm_loss(cfg, params, b)

    def step_fn(state: steps_mod.TrainState, tokens, targets, mask):
        # inside shard_map: leaves have leading node axis of size 1
        sq = lambda t: jax.tree.map(lambda x: x[0], t)
        params, opt_state, loss = local_steps(
            sq(state.params), sq(state.opt), state.step,
            tokens[0], targets[0], mask[0])
        params = jax.tree.map(lambda x: x[None], params)
        opt_state = jax.tree.map(lambda x: x[None], opt_state)
        # gossip-synchronize the PARAMETERS across nodes
        params = dec.sync_tree_mesh(params, spec, ("data",), (n,))
        loss = jax.lax.pmean(loss, "data")
        return steps_mod.TrainState(params, opt_state,
                                    state.step + args.local_steps), loss

    node = P("data")
    state_spec = jax.tree.map(lambda x: node if jnp.ndim(x) else P(), state)
    shmap = jax.shard_map(
        step_fn, mesh=mesh,
        in_specs=(state_spec, node, node, node),
        out_specs=(state_spec, P()))
    jitted = jax.jit(shmap, donate_argnums=(0,))

    pipe = TokenPipeline(cfg.vocab_size, args.seq,
                         n * args.local_steps * args.batch, seed=args.seed)
    losses = []
    t0 = time.time()
    for step, batch in zip(range(args.steps), pipe.batches()):
        shp = (n, args.local_steps, args.batch, args.seq)
        tokens = batch.tokens.reshape(shp)
        targets = batch.targets.reshape(shp)
        mask = batch.mask.reshape(shp)
        state, loss = jitted(state, tokens, targets, mask)
        losses.append(float(loss))
        if step % args.log_every == 0 or step == args.steps - 1:
            # consensus diagnostic: max param spread across nodes
            spread = max(float(jnp.abs(x - x.mean(0, keepdims=True)).max())
                         for x in jax.tree.leaves(state.params))
            # async dispatch: drain in-flight steps before reading the
            # per-step wall clock
            jax.block_until_ready(state)
            print(f"step {step:4d} loss {losses[-1]:.4f} "
                  f"param_spread {spread:.2e} "
                  f"({(time.time()-t0)/(step+1):.2f}s/step)")
    return losses


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm_125m", choices=list_archs())
    ap.add_argument("--mode", default="standard",
                    choices=["standard", "decentralized"])
    ap.add_argument("--sync", default="gossip-hypercube",
                    help="allreduce | gossip-hypercube[k] | gossip-ring[k]")
    ap.add_argument("--local-steps", type=int, default=1)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full", action="store_true",
                    help="use the full config (default: smoke variant)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = smoke_variant(cfg)
    if cfg.family == "encdec":
        raise SystemExit("use examples/whisper_train.py for the enc-dec arch")
    mesh = make_host_mesh()
    print(f"arch={cfg.name} family={cfg.family} params~{cfg.n_params():,} "
          f"mode={args.mode} devices={mesh.devices.size}")
    if args.mode == "standard":
        losses = train_standard(cfg, args, mesh)
    else:
        losses = train_decentralized(cfg, args, mesh)
    print(f"first loss {losses[0]:.4f} -> last loss {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
