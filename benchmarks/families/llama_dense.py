"""The dense Llama-architecture family (RMSNorm, RoPE, GQA, SwiGLU, no biases,
tied output head): SmolLM2 as ``torchft_tpu/models/transformer.py`` trains it.

A configuration file names its family (``"family": "llama_dense"``); the
harness finds this module by that name and takes from it everything that
depends on the architecture:

``check(sizes)``                      refuse sizes the program cannot express
``make_weights_fn(sizes)``            ``key -> weights``, the benchmark's own, in the program's layout
``program_init_shapes(sizes)``        the abstract tree of the program's own initialiser: the layout test
                                      holds ``make_weights_fn`` to it (structure, shapes, dtypes, count)
``n_params(sizes)``                   from the configuration's shapes
``make_grad_step(sizes, seq_len)``    the program under test: jitted ``(params, tokens) -> (loss, grads)``
``flops_per_step(sizes, batch, seq)`` model FLOPs of forward + backward
``reference_loss``                    the plain reference's ``(params, tokens, sizes, operand_dtype) -> loss``
``STACKED``                           top-level groups whose leaves are stacked by layer ([L, ...]): the
                                      comparison reads their norms layer by layer (``reference/train.py``)
``CUT_KEYS``                          which key counts ``layers``, ``experts`` held, ``vocab`` rows (or None):
                                      the only keys a configuration may cut, down to the guide's floors
``layer_pattern(sizes)``              ``{"leading_dense": n, "period": n}`` of the layer pattern
``WIDTH_KEYS``                        this family's widths beyond what any family's key names give away
``ASSUMED_KEYS``                      what a configuration file of this family sets itself, under ``assumed``
``aot_prepare()``                     optional: what ``aot_check.py`` must steer to compile for a described chip

and, for its kernels' per-layer metrics, the operations and bytes a kernel needs
(``flash_attn_work``).  Another family is another module here with its plain
reference under ``reference/``, and edits nothing (``benchmarks/README.md``)."""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from benchmarks.reference.smollm2 import loss_fn as reference_loss  # noqa: F401


STACKED = ("blocks",)
CUT_KEYS = {"layers": "num_hidden_layers", "experts": None, "vocab": "vocab_size"}
# heads are widths here: no configuration of this family holds a share of them
WIDTH_KEYS = ("hidden_size", "intermediate_size", "head_dim", "num_attention_heads",
              "num_key_value_heads")
ASSUMED_KEYS = ("remat", "remat_policy", "attn_impl")


def layer_pattern(sizes: Dict[str, Any]) -> Dict[str, int]:
    return {"leading_dense": 0, "period": 1}


def check(sizes: Dict[str, Any]) -> None:
    if sizes["head_dim"] * sizes["num_attention_heads"] != sizes["hidden_size"]:
        raise ValueError("the program derives head_dim as hidden_size // heads")
    if not sizes["tie_word_embeddings"]:
        raise ValueError("models/transformer.py has a tied output head only")


def _program_config(sizes: Dict[str, Any], seq_len: int) -> Any:
    import jax.numpy as jnp

    from torchft_tpu.models import transformer as tfm

    return tfm.TransformerConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        n_heads=sizes["num_attention_heads"], n_kv_heads=sizes["num_key_value_heads"],
        d_ff=sizes["intermediate_size"], n_layers=sizes["num_hidden_layers"],
        max_seq_len=seq_len, rope_theta=float(sizes["rope_theta"]),
        dtype=jnp.dtype(sizes["compute_dtype"]), param_dtype=jnp.dtype(sizes["param_dtype"]),
        attn_impl=sizes["attn_impl"], remat=sizes["remat"],
        remat_policy=sizes["remat_policy"],
    )


def make_grad_step(sizes: Dict[str, Any], seq_len: int) -> Any:
    from torchft_tpu.models import transformer as tfm

    return tfm.make_grad_step(_program_config(sizes, seq_len))


def program_init_shapes(sizes: Dict[str, Any]) -> Any:
    import jax

    from torchft_tpu.models import transformer as tfm

    cfg = _program_config(sizes, sizes["seq_len"])
    return jax.eval_shape(lambda k: tfm.init_params(k, cfg), jax.random.PRNGKey(0))


def aot_prepare() -> None:
    """The program asks the backend whether to interpret its kernels; a
    compile for a described chip runs on the CPU backend and must not."""
    from torchft_tpu.ops import flash_attention

    flash_attention._interpret = lambda: False


def weight_shapes(sizes: Dict[str, Any]) -> Dict[str, Any]:
    e, f, l = sizes["hidden_size"], sizes["intermediate_size"], sizes["num_hidden_layers"]
    q = sizes["num_attention_heads"] * sizes["head_dim"]
    kv = sizes["num_key_value_heads"] * sizes["head_dim"]
    return {
        "embed": (sizes["vocab_size"], e),
        "blocks": {
            "attn_norm": (l, e), "wq": (l, e, q), "wk": (l, e, kv), "wv": (l, e, kv),
            "wo": (l, q, e), "mlp_norm": (l, e),
            "w_gate": (l, e, f), "w_up": (l, e, f), "w_down": (l, f, e),
        },
        "final_norm": (e,),
    }


def n_params(sizes: Dict[str, Any]) -> int:
    import jax

    return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        weight_shapes(sizes), is_leaf=lambda x: isinstance(x, tuple)))


def make_weights_fn(sizes: Dict[str, Any]) -> Any:
    """``key -> weights``: float32 master weights, to be jitted by the caller
    onto the device that will hold them."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(sizes)
    pd = jnp.dtype(sizes["param_dtype"])

    def make(key):
        names = sorted(shapes["blocks"])
        keys = dict(zip(names, jax.random.split(key, len(names))))
        blocks = {}
        for name in names:
            shape = shapes["blocks"][name]
            if name.endswith("_norm"):
                blocks[name] = jnp.ones(shape, pd)
            else:
                blocks[name] = jax.random.normal(keys[name], shape, pd) / np.sqrt(shape[-2])
        return {
            "embed": jax.random.normal(jax.random.fold_in(key, 1), shapes["embed"], pd) * 0.02,
            "blocks": blocks,
            "final_norm": jnp.ones(shapes["final_norm"], pd),
        }

    return make


def flops_per_step(sizes: Dict[str, Any], batch: int, seq: int) -> float:
    """Model FLOPs of forward + backward (= 3x forward) for ``batch`` rows
    (copied from ``bench.py`` ``_model_flops_per_step``).

    Matmul parameters: block weights + the tied head (the embedding gather is
    not a matmul; the tied head is one).  Attention: QK^T and AV are each
    2*B*T^2*E forward over the full causal square, x3 with the backward.
    Recomputation under remat is deliberately NOT counted: the utilisation is
    over model FLOPs."""
    e, f, l = sizes["hidden_size"], sizes["intermediate_size"], sizes["num_hidden_layers"]
    hd = sizes["head_dim"]
    nh, nkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    n_block = l * (e * nh * hd + 2 * e * nkv * hd + nh * hd * e + 3 * e * f)
    n_head = sizes["vocab_size"] * e
    mm = 6 * (n_block + n_head) * batch * seq
    attn = 3 * (2 * 2 * batch * seq * seq * nh * hd) * l
    return float(mm + attn)


# what `models/transformer.py` names its attention kernels (the Mosaic
# module's name of each `pallas_call` in `ops/flash_attention.py`)
FLASH_KERNELS = ("_fwd_kernel", "_bwd_kv_kernel", "_bwd_q_kernel")


def flash_attn_work(sizes: Dict[str, Any], batch: int, seq: int) -> Dict[str, Dict[str, float]]:
    """Operations and bytes of one call of each flash-attention kernel: one
    layer, ``batch`` rows.  Of the causal half only: ``T (T + 1) / 2``
    query-key pairs a head, which is what the kernels compute but for the
    masked part of their diagonal tiles (not counted).  Matrix products only,
    2 operations a multiply-add: forward ``S = Q K^T`` and ``P V``; the
    key-value backward recomputes ``S`` and forms ``dV = P^T dO``, ``dP = dO
    V^T``, ``dK = dS^T Q``; the query backward recomputes ``S`` and forms
    ``dP`` and ``dQ = dS K``.  Bytes: every operand read once and every
    result written once, in the compute type (the kernels see K and V
    repeated up to the query heads), row statistics in float32."""
    import jax.numpy as jnp

    d = sizes["head_dim"]
    heads = batch * sizes["num_attention_heads"]
    pairs = heads * seq * (seq + 1) // 2
    tile = heads * seq * d * jnp.dtype(sizes["compute_dtype"]).itemsize  # one [T, d] operand of every head
    stat = heads * seq * 4
    return {
        "_fwd_kernel": {"flops": 2.0 * 2 * pairs * d, "bytes": 4.0 * tile + stat},
        "_bwd_kv_kernel": {"flops": 2.0 * 4 * pairs * d, "bytes": 6.0 * tile + 2 * stat},
        "_bwd_q_kernel": {"flops": 2.0 * 3 * pairs * d, "bytes": 5.0 * tile + 2 * stat},
    }
