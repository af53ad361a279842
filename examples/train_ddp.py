"""Fault-tolerant DDP training example (reference: train_ddp.py:104-213).

One process = one replica group (TPU slice or CPU worker). Point every
replica at the same Lighthouse and they form an elastic quorum: kill any
replica mid-run and the rest keep training; restart it and it live-heals
its weights from a healthy peer — no full-job restart.

Single-machine demo (threads-as-replicas + in-process Lighthouse):

    python examples/train_ddp.py --local-replicas 2 --steps 50

Demo mode is a CPU demo: every thread-replica creates its state on the
default device, so on an accelerator host all of them would share chip 0.
Pass ``--cpu`` (or set ``JAX_PLATFORMS=cpu``) for it; on a TPU the path is
one process per slice (below), and ``python chip_smoke.py`` is the check
that this loop runs there at flagship size.

Note: kill-based chaos testing (dashboard kill button, punisher.py) needs
the one-process-per-replica deployment below — a kill RPC exits the whole
process, so in demo mode it would take down every thread-replica at once.

Real deployment (one process per slice):

    TORCHFT_LIGHTHOUSE=host:port REPLICA_GROUP_ID=0 python examples/train_ddp.py
    TORCHFT_LIGHTHOUSE=host:port REPLICA_GROUP_ID=1 python examples/train_ddp.py

The model is the reference's CIFAR-shaped CNN on synthetic data (this
image has no dataset egress); swap in a real dataloader + the
DistributedSampler shard for production.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=100, help="committed steps to train")
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--min-replicas", type=int, default=1)
    p.add_argument("--sync-quorum", action="store_true",
                   help="synchronous quorum (default overlaps with forward)")
    p.add_argument("--local-replicas", type=int, default=0,
                   help="demo mode: run N replica-group threads + a local Lighthouse")
    p.add_argument("--cpu", action="store_true", help="force the CPU backend")
    p.add_argument("--profile-dir", default=None,
                   help="write a jax profiler trace here (Perfetto-compatible)")
    p.add_argument("--save-dir", default=None,
                   help="write durable checkpoints here (cold-start resume)")
    p.add_argument("--save-every", type=int, default=10,
                   help="checkpoint every N committed steps")
    p.add_argument("--resume", action="store_true",
                   help="resume from the latest checkpoint in --save-dir")
    return p.parse_args(argv)


def train(replica_id: str, lighthouse_addr: str, args, log=print) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import torchft_tpu as ft
    from torchft_tpu.models import cnn

    params = cnn.init_params(jax.random.PRNGKey(0))
    state = {"params": params, "opt_state": None}

    manager = ft.Manager(
        pg=ft.ProcessGroupTCP(timeout=30.0),
        min_replica_size=args.min_replicas,
        # a live heal delivers host numpy: put it back on the device here,
        # before the next jitted step touches it
        load_state_dict=lambda sd: state.update(jax.device_put(sd)),
        state_dict=lambda: {"params": state["params"],
                            "opt_state": state["opt_state"]},
        replica_id=replica_id,
        lighthouse_addr=lighthouse_addr,
        group_rank=0,
        group_world_size=1,
        use_async_quorum=not args.sync_quorum,
        timeout=30.0,
    )
    ddp = ft.DistributedDataParallel(manager)
    optimizer = ft.Optimizer(manager, optax.adamw(args.lr))
    state["opt_state"] = optimizer.init(params)

    # Durable resume (total-failure case: no live peer to heal from).
    # Restores user state AND the torchft step so the quorum resumes from
    # the checkpointed step (reference: train_ddp.py:201-208).
    if args.resume and args.save_dir:
        from torchft_tpu.checkpointing import latest_checkpoint, load_checkpoint

        path = latest_checkpoint(args.save_dir)
        if path is not None:
            ckpt = load_checkpoint(path)
            state.update(ckpt["user"])
            manager.load_state_dict(ckpt["torchft"])
            log(f"[{replica_id}] resumed from {path} "
                f"at step {manager.current_step()}")

    def loss_fn(params, images, labels):
        logits = cnn.forward(params, images)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    rng = np.random.default_rng(hash(replica_id) % 2**31)

    try:
        while manager.current_step() < args.steps:
            # synthetic CIFAR-shaped batch; each replica sees its own data
            images = jnp.asarray(
                rng.standard_normal((args.batch_size, 32, 32, 3), dtype=np.float32)
            )
            labels = jnp.asarray(rng.integers(0, 10, args.batch_size))

            # must be called at the start of each step: triggers the quorum
            # (overlapped with forward unless --sync-quorum)
            optimizer.begin_step()

            loss, grads = grad_fn(state["params"], images, labels)
            # gradient averaging over the live quorum (zero-contribution
            # participation: membership changes never change compiled shapes)
            avg_grads = ddp.allreduce_gradients(grads).wait(timeout=30)

            # The vote is where an async-quorum heal lands in `state`, so
            # the state is read only AFTER it (optimizer.step(...) would
            # evaluate its arguments before voting and update the
            # pre-heal params).  The update is one donated jit.
            committed = manager.should_commit()
            if committed:
                state["params"], state["opt_state"] = optimizer.update(
                    state["params"], avg_grads, state["opt_state"]
                )
            if committed and manager.current_step() % 10 == 0:
                log(f"[{replica_id} step {manager.current_step()}] "
                    f"loss={float(loss):.4f} "
                    f"participants={manager.num_participants()}")
            if (
                committed
                and args.save_dir
                and manager.current_step() % args.save_every == 0
                and manager.participating_rank() == 0
            ):
                # single-writer: the participating-rank-0 replica saves the
                # composite {user, torchft} dict (others would write the
                # same bytes)
                from torchft_tpu.checkpointing import save_checkpoint

                path = save_checkpoint(
                    args.save_dir,
                    manager.current_step(),
                    {
                        "user": {"params": state["params"],
                                 "opt_state": state["opt_state"]},
                        "torchft": manager.state_dict(),
                    },
                )
                log(f"[{replica_id}] saved checkpoint {path}")
        return {"params": state["params"], "step": manager.current_step()}
    finally:
        manager.shutdown()


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax

    from torchft_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)

    try:
        if args.local_replicas:
            from _demo import run_demo

            rc = run_demo(
                train, args.local_replicas, min_replicas=args.min_replicas,
                replica_prefix="train_ddp", extra_args=(args,),
            )
        else:
            from _demo import resolve_lighthouse

            replica_id = f"train_ddp_{os.environ.get('REPLICA_GROUP_ID', 0)}"
            result = train(replica_id, resolve_lighthouse(), args)
            print(f"done: {result['step']} committed steps")
            rc = 0
    finally:
        if args.profile_dir:
            jax.profiler.stop_trace()
            print(f"profiler trace written to {args.profile_dir}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
