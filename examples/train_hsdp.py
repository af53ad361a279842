"""Fault-tolerant HSDP: inner fsdp/tp sharding x elastic replica groups.

The HSDP composition (reference: torchft README "HSDP" + fsdp_test.py):
each replica group owns a TPU slice and shards the model over its ICI mesh
(fsdp/tp via pjit); the replica dimension across slices is elastic — grads
are averaged through the Manager on host buffers, so slices can die and
rejoin at step granularity while inner sharding stays compiled-once.

Single-machine demo (2 replica-group threads x 4 virtual CPU devices each):

    python examples/train_hsdp.py --local-replicas 2 --steps 20

``--local-replicas`` is a CPU-only demo: it always forces the CPU backend
with enough virtual devices for every group's mesh, at a toy model size.

Real deployment: one process per slice, TORCHFT_LIGHTHOUSE set, and the
inner mesh built over the slice's own devices (jax.local_devices()).  The
same composition at flagship width on real chips — two groups on disjoint
2-chip fsdp meshes with a kill and a live heal back onto the mesh — is
``python chip_smoke.py --chips 4``.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--min-replicas", type=int, default=1)
    p.add_argument("--fsdp", type=int, default=2)
    p.add_argument("--tp", type=int, default=2)
    p.add_argument("--local-replicas", type=int, default=0,
                   help="demo mode: N replica-group threads + local lighthouse "
                        "(forces the virtual CPU backend)")
    return p.parse_args(argv)


def train(replica_id: str, lighthouse_addr: str, devices, args, log=print) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import torchft_tpu as ft
    from torchft_tpu.models import transformer as tfm
    from torchft_tpu.parallel.device_mesh import ft_init_device_mesh

    cfg = tfm.TransformerConfig(
        vocab_size=256, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
        n_layers=2, max_seq_len=32, dtype=jnp.float32,
    )
    state = {}

    manager = ft.Manager(
        pg=ft.ProcessGroupTCP(timeout=30.0),
        min_replica_size=args.min_replicas,
        lighthouse_addr=lighthouse_addr,
        replica_id=replica_id,
        group_rank=0,
        group_world_size=1,
        use_async_quorum=False,
        timeout=30.0,
        load_state_dict=lambda sd: state.update(sd),
        state_dict=lambda: {
            "params": jax.tree_util.tree_map(np.asarray, state["params"]),
            "opt_state": jax.tree_util.tree_map(np.asarray, state["opt_state"]),
        },
    )
    try:
        fmesh = ft_init_device_mesh(
            manager, {"fsdp": args.fsdp, "tp": args.tp}, devices=devices
        )
        mesh = fmesh.mesh
        params = tfm.shard_params(
            tfm.init_params(jax.random.PRNGKey(0), cfg), mesh, cfg
        )
        optimizer = ft.Optimizer(manager, optax.adamw(args.lr))
        state["params"] = params
        state["opt_state"] = optimizer.init(params)
        pspecs = tfm.param_specs(cfg, mesh)

        grad_fn = jax.jit(
            lambda p, t: jax.value_and_grad(tfm.loss_fn)(p, t, cfg, mesh=mesh)
        )
        rng = np.random.default_rng(hash(replica_id) % 2**31)

        def reshard_if_healed():
            # a heal delivers host numpy arrays via load_state_dict; they
            # must go back onto the inner mesh BEFORE the jitted grad_fn
            # touches them (else: recompile + fully-replicated weights).
            # Steady-state steps skip the device_put entirely.
            leaves = jax.tree_util.tree_leaves(state["params"])
            if leaves and not isinstance(leaves[0], jax.Array):
                state["params"] = jax.tree_util.tree_map(
                    lambda x, s: jax.device_put(
                        jnp.asarray(x), jax.sharding.NamedSharding(mesh, s)
                    ),
                    state["params"], pspecs,
                )
                state["opt_state"] = jax.tree_util.tree_map(
                    jnp.asarray, state["opt_state"]
                )

        while manager.current_step() < args.steps:
            optimizer.begin_step()  # starts the quorum (sync: heal lands here)
            reshard_if_healed()
            # per-replica batch shape stays FIXED under elastic membership
            # (WorldSizeMode.DYNAMIC semantics): zero-fill + divide-by-live
            # -count absorbs joins/failures without any re-jit
            tokens = jnp.asarray(
                rng.integers(
                    0, cfg.vocab_size, (args.batch_size, cfg.max_seq_len)
                ),
                jnp.int32,
            )
            loss, grads = grad_fn(state["params"], tokens)
            # the leaves go as they are, sharded inside the group: with
            # peers the collective gathers them to the host for the ring; a
            # group alone gets them back as they are, sharding intact
            avg = manager.allreduce(grads).wait(timeout=30)
            new_params, new_opt, committed = optimizer.step(
                state["params"],
                jax.tree_util.tree_map(jnp.asarray, avg),
                state["opt_state"],
            )
            if committed:
                state["params"] = new_params
                state["opt_state"] = new_opt
                step = manager.current_step()
                if step % 5 == 0:
                    log(f"[{replica_id} step {step}] loss={float(loss):.4f} "
                        f"participants={manager.num_participants()}")
        log(f"done: {manager.current_step()} committed steps")
        return {"step": manager.current_step()}
    finally:
        manager.shutdown()


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax

    from torchft_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.local_replicas:
        per = args.fsdp * args.tp
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", per * args.local_replicas)
        from _demo import run_demo

        return run_demo(
            train, args.local_replicas, min_replicas=args.min_replicas,
            replica_prefix="hsdp", devices_per_replica=per,
            extra_args=(args,),
        )
    from _demo import resolve_lighthouse

    replica_id = f"hsdp_{os.environ.get('REPLICA_GROUP_ID', 0)}"
    train(replica_id, resolve_lighthouse(), jax.local_devices(), args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
