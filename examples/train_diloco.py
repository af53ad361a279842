"""Streaming DiLoCo training example (reference: train_diloco.py:76-238).

Communication-reducing semi-sync data parallelism: each replica group
trains locally for ``--sync-every`` inner steps; parameter fragments are
synchronized round-robin with pseudogradient allreduces overlapped with
compute (``--fragment-sync-delay``), an outer Nesterov-SGD step applied on
commit.  Ideal when replica groups are connected by slow DCN (multi-slice,
multi-region).

Single-machine demo (kill-based chaos testing needs the one-process-per-
replica deployment below; a kill RPC exits the whole process):

    python examples/train_diloco.py --local-replicas 2 --steps 40

Demo mode is a CPU demo (``--cpu``): its thread-replicas all create their
state on the default device, i.e. on an accelerator host they would share
chip 0.  On a TPU the path is one process per slice.

Real deployment (one process per slice):

    TORCHFT_LIGHTHOUSE=host:port REPLICA_GROUP_ID=0 python examples/train_diloco.py

Model: MLP fragments (the reference splits an MLP with torch pipelining
SplitPoints; here fragments are pytree key partitions — see
torchft_tpu/local_sgd.py).
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=80, help="inner steps to run")
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--inner-lr", type=float, default=4e-4)
    p.add_argument("--outer-lr", type=float, default=0.7)
    p.add_argument("--sync-every", type=int, default=20,
                   help="inner steps per full sync round (reference default)")
    p.add_argument("--fragment-sync-delay", type=int, default=1,
                   help="steps between kicking off a fragment allreduce and "
                        "blocking on it")
    p.add_argument("--n-fragments", type=int, default=2)
    p.add_argument("--min-replicas", type=int, default=1)
    p.add_argument("--local-replicas", type=int, default=0)
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--wire-gbps", type=float, default=None,
                   help="shape the DCN egress to this rate (decimal GB/s, "
                        "token bucket) — demo/validate DiLoCo under a real "
                        "bandwidth constraint; also settable via "
                        "TORCHFT_WIRE_GBPS")
    p.add_argument("--quantize", action="store_true",
                   help="int8-quantize the outer pseudogradient sync "
                        "(TORCHFT_QUANT_WIRE selects int8/fp8_e4m3)")
    return p.parse_args(argv)


def train(replica_id: str, lighthouse_addr: str, args, log=print) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import torchft_tpu as ft
    from torchft_tpu.models import mlp

    params = mlp.init_params(jax.random.PRNGKey(0), sizes=(64, 128, 128, 128, 10))
    state = {"params": params}

    manager = ft.Manager(
        # --wire-gbps: token-bucket egress shaping (None = unshaped or the
        # TORCHFT_WIRE_GBPS env default) — lets this demo show DiLoCo's
        # sync-every-N advantage under a real DCN bandwidth constraint
        pg=ft.ProcessGroupTCP(timeout=30.0, bandwidth_gbps=args.wire_gbps),
        min_replica_size=args.min_replicas,
        replica_id=replica_id,
        lighthouse_addr=lighthouse_addr,
        group_rank=0,
        group_world_size=1,
        use_async_quorum=False,  # DiLoCo requires a synchronous quorum
        timeout=30.0,
    )

    # fragments = contiguous layer partitions (the reference's
    # pipeline-split analog, mlp.fragment_keys)
    fragments = mlp.fragment_keys(params, args.n_fragments)

    def get_params():
        return dict(state["params"])

    def set_params(flat):
        state["params"] = {**state["params"], **flat}

    inner_opt = optax.adamw(args.inner_lr)
    opt_state = inner_opt.init(params)
    outer_opt = optax.sgd(args.outer_lr, momentum=0.9, nesterov=True)

    def loss_fn(params, x, y):
        return optax.softmax_cross_entropy_with_integer_labels(
            mlp.forward(params, x), y
        ).mean()

    @jax.jit
    def inner_step(params, opt_state, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        updates, opt_state = inner_opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    rng = np.random.default_rng(hash(replica_id) % 2**31)
    try:
        with ft.DiLoCo(
            manager,
            fragments,
            get_params,
            set_params,
            outer_opt,
            sync_every=args.sync_every,
            fragment_sync_delay=args.fragment_sync_delay,
            should_quantize=args.quantize,
        ) as diloco:
            for i in range(args.steps):
                x = jnp.asarray(
                    rng.standard_normal((args.batch_size, 64), dtype=np.float32)
                )
                y = jnp.asarray(rng.integers(0, 10, args.batch_size))
                state["params"], opt_state, loss = inner_step(
                    state["params"], opt_state, x, y
                )
                diloco.step()  # counts inner steps; syncs on its schedule
                if i % 10 == 0:
                    log(f"[{replica_id} inner {i} outer "
                        f"{manager.current_step()}] loss={float(loss):.4f}")
        return {"params": state["params"], "outer_steps": manager.current_step()}
    finally:
        manager.shutdown()


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax

    from torchft_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    if args.local_replicas:
        from _demo import run_demo

        return run_demo(
            train, args.local_replicas, min_replicas=args.min_replicas,
            replica_prefix="train_diloco", extra_args=(args,),
        )
    from _demo import resolve_lighthouse

    replica_id = f"train_diloco_{os.environ.get('REPLICA_GROUP_ID', 0)}"
    result = train(replica_id, resolve_lighthouse(), args)
    print(f"done: {result['outer_steps']} outer steps committed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
