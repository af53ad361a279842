"""From a configuration file to what every family's loop is handed: the sizes
as run, the seed's key, the token batches, the optimizer.  What depends on
the architecture (weights, the program's grad step, the FLOP count, the
reference's loss) is the configuration's family module
(``benchmarks/families/<family>.py``)."""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np


def sizes_of(config: Dict[str, Any], overrides: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The configuration's sizes with a test's tiny preset laid over them."""
    sizes = {k: v for k, v in config.items() if k != "assumed"}
    sizes.update(config["assumed"])
    sizes.update(overrides or {})
    return sizes


def seed_key(seed: int) -> Any:
    """A PRNG key from any non-negative seed, also one past 32 bits."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def tokens_for(vocab: int, batch: int, seq: int, seed: int, group: int, step: int) -> np.ndarray:
    """The rows group ``group`` trains on at ``step``: all differ."""
    rng = np.random.default_rng([seed, group, step])
    return rng.integers(0, vocab, (batch, seq), dtype=np.int32)


def vocab_rows(family: Any, sizes: Dict[str, Any]) -> int:
    """The rows of the vocabulary this chip holds: the traffic draws its ids
    from them (a sliced vocabulary is a smaller vocabulary)."""
    return sizes[family.CUT_KEYS["vocab"]]


def setup_batches(vocab: int, traffic: Dict[str, Any], seed: int) -> "list[list[np.ndarray]]":
    """``[step][group]``: the rows of the set-up steps the reference follows."""
    return [[tokens_for(vocab, traffic["batch_per_group"], traffic["seq_len"], seed, g, step)
             for g in range(traffic["groups"])]
            for step in range(traffic["warmup_steps"])]


def reference_devices(devices: "list[Any]", traffic: Dict[str, Any]) -> "list[Any]":
    """All the cell's chips where a group's rows split over them, else one."""
    return devices if traffic["batch_per_group"] % len(devices) == 0 else devices[:1]


HYPER_KEYS = ("learning_rate", "adam_b1", "adam_b2", "adam_eps", "weight_decay")


def hyper(sizes: Dict[str, Any]) -> Dict[str, float]:
    return {k: sizes[k] for k in HYPER_KEYS}


def optimizer(sizes: Dict[str, Any]) -> Any:
    import optax

    if sizes["optimizer"] != "adamw":
        raise ValueError(f"optimizer {sizes['optimizer']!r} is not wired")
    return optax.adamw(
        sizes["learning_rate"], b1=sizes["adam_b1"], b2=sizes["adam_b2"],
        eps=sizes["adam_eps"], weight_decay=sizes["weight_decay"])
