"""The Kimi Linear family (``model_type`` ``kimi_linear``): Kimi Delta Attention
layers beside NoPE latent attention in a published pattern, a leading dense
SwiGLU FFN then sigmoid-routed experts of which the chip holds a share, a
shared expert, an untied head of which the chip holds a slice of rows;
``torchft_tpu/models/kimi_linear.py`` trains it.  The members are those
``families/llama_dense.py`` lists; the plain reference is
``reference/kimi_linear.py``, whose text holds the layers' equations.

A configuration keeps ``linear_attn_config`` whole as published: of its two
lists the layers up to ``num_hidden_layers`` are run.  ``num_experts`` counts
the experts held here (their published ids are ``held_expert_ids``) and
``router_outputs`` the experts the router scores, which is never cut.
``head_dim`` (hidden over heads) is published and used by nothing: attention
has the latent layer's widths.

For the per-layer metrics: ``scope_ms`` / ``scope_rows`` (device time of the grad step's
operations under ``jax.named_scope``s of the program), ``kda_work`` and
``flash_attn_work`` (operations and bytes)."""

from __future__ import annotations

import importlib.util
import threading
from typing import Any, Dict, Iterable, Optional

import numpy as np

from benchmarks.reference import kimi_linear as _reference

STACKED = ("kda", "mla", "dense", "moe")
CUT_KEYS = {"layers": "num_hidden_layers", "experts": "num_experts", "vocab": "vocab_size"}
# heads, experts per token and the router's outputs are widths here: the
# router scores every published expert whichever of them live on this chip
WIDTH_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim", "num_experts_per_token",
              "router_outputs", "num_shared_experts")
ASSUMED_KEYS = ("remat", "remat_policy", "attn_impl", "held_expert_ids", "kda_gate_rank",
                "kda_chunk", "expert_slack")


def _lists(sizes: Dict[str, Any]) -> "tuple[tuple[int, ...], tuple[int, ...]]":
    """The layers run, of the two published lists."""
    lin, depth = sizes["linear_attn_config"], sizes["num_hidden_layers"]
    return (tuple(n for n in lin["kda_layers"] if n <= depth),
            tuple(n for n in lin["full_attn_layers"] if n <= depth))


def layer_pattern(sizes: Dict[str, Any]) -> Dict[str, int]:
    """The leading dense layers, then the published ratio: a latent-attention
    layer every ``period`` layers (the spacing of ``full_attn_layers``)."""
    full = sorted(sizes["linear_attn_config"]["full_attn_layers"])
    gaps = [b - a for a, b in zip(full, full[1:])]
    return {"leading_dense": sizes["first_k_dense_replace"], "period": max(gaps) if gaps else 1}


def check(sizes: Dict[str, Any]) -> None:
    if importlib.util.find_spec("torchft_tpu.models.kimi_linear") is None:
        raise ValueError("this checkout's program has no models/kimi_linear.py")
    kda, full = _lists(sizes)
    if sorted(kda + full) != list(range(1, sizes["num_hidden_layers"] + 1)):
        raise ValueError("every layer is in exactly one of kda_layers and full_attn_layers")
    fixed = {"tie_word_embeddings": False, "mla_use_nope": True, "q_lora_rank": None,
             "num_shared_experts": 1, "num_expert_group": 1, "topk_group": 1,
             "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
             "moe_layer_freq": 1, "num_nextn_predict_layers": 0, "hidden_act": "silu"}
    wrong = {k: sizes[k] for k, v in fixed.items() if sizes[k] != v}
    if wrong:
        raise ValueError(f"models/kimi_linear.py expresses {fixed} only; the sizes have {wrong}")
    held = sizes["held_expert_ids"]
    if len(held) != sizes["num_experts"] or len(set(held)) != len(held) or not all(
            0 <= e < sizes["router_outputs"] for e in held):
        raise ValueError("held_expert_ids names num_experts distinct experts of the router's outputs")
    if sizes["num_experts_per_token"] > sizes["router_outputs"]:
        raise ValueError("more experts a token than the router scores")


def _program_config(sizes: Dict[str, Any]) -> Any:
    import jax.numpy as jnp

    from torchft_tpu.models import kimi_linear as kl

    lin = sizes["linear_attn_config"]
    kda, full = _lists(sizes)
    return kl.KimiLinearConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"], kda_layers=kda, full_attn_layers=full,
        first_k_dense=sizes["first_k_dense_replace"], n_heads=sizes["num_attention_heads"],
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        conv_kernel=lin["short_conv_kernel_size"], kda_gate_rank=sizes["kda_gate_rank"],
        kda_chunk=sizes["kda_chunk"], kv_lora_rank=sizes["kv_lora_rank"],
        qk_nope_head_dim=sizes["qk_nope_head_dim"], qk_rope_head_dim=sizes["qk_rope_head_dim"],
        v_head_dim=sizes["v_head_dim"], d_ff=sizes["intermediate_size"],
        d_expert=sizes["moe_intermediate_size"], n_routed_experts=sizes["router_outputs"],
        experts_per_token=sizes["num_experts_per_token"],
        held_experts=tuple(sizes["held_expert_ids"]),
        routed_scaling_factor=sizes["routed_scaling_factor"], expert_slack=sizes["expert_slack"],
        rms_norm_eps=sizes["rms_norm_eps"], dtype=jnp.dtype(sizes["compute_dtype"]),
        param_dtype=jnp.dtype(sizes["param_dtype"]), remat=sizes["remat"],
        remat_policy=sizes["remat_policy"], attn_impl=sizes["attn_impl"])


# ---- the program's compiled step, and when it lets go of the chip's memory ----
#
# A TPU keeps the temporaries of a loaded executable reserved for as long as
# the executable lives (4.3 GB for this step; `memory_stats()` `bytes_reserved`),
# and the harness keeps the compiled step through the reference's run, to read
# its HLO afterwards.  The float32 reference holds 20 bytes a parameter beside a
# block's gradient and one row's activations (15.8 of the chip's 16.9 GB at
# 602 M parameters), most of it before it first traces the loss: there is no
# room for the reservation beside it.  So what `make_grad_step` returns hands
# out compiled steps that can be released, keeping their HLO text and memory
# analysis, which is all the harness reads of them afterwards.  The reference's
# first trace releases them all (the harness runs it after the loop only).
# Asking for weights (`make_weights_fn`: every group's loop does as it starts,
# the harness once more before the reference) releases only the steps whose
# compiling thread has ended: a group is a thread that compiles its own step,
# so no group can take a step from under a group that is still running.

_COMPILED: "list[_Compiled]" = []
_COMPILED_LOCK = threading.Lock()


class _Compiled:
    """A compiled grad step: call it, ask it what the harness asks, release it."""

    def __init__(self, executable: Any) -> None:
        self._executable = executable
        self._analysis = executable.memory_analysis()
        self._text: Optional[str] = None
        self._owner = threading.current_thread()

    def __call__(self, params: Any, tokens: Any) -> Any:
        return self._executable(params, tokens)

    def memory_analysis(self) -> Any:
        return self._analysis

    def as_text(self) -> str:
        return self._text if self._executable is None else self._executable.as_text()

    def release(self) -> None:
        if self._executable is not None:
            self._text = self._executable.as_text()
            self._executable = None


class _Lowered:
    def __init__(self, lowered: Any) -> None:
        self._lowered = lowered

    def as_text(self) -> str:
        return self._lowered.as_text()

    def compile(self) -> _Compiled:
        compiled = _Compiled(self._lowered.compile())
        with _COMPILED_LOCK:
            _COMPILED.append(compiled)
        return compiled


class _GradStep:
    """The program's jitted ``(params, tokens) -> (loss, grads)``, whose
    ``lower(...).compile()`` gives a ``_Compiled``."""

    def __init__(self, jitted: Any) -> None:
        self._jitted = jitted
        self.__name__ = jitted.__name__

    def __call__(self, params: Any, tokens: Any) -> Any:
        return self._jitted(params, tokens)

    def lower(self, *args: Any) -> _Lowered:
        return _Lowered(self._jitted.lower(*args))


def make_grad_step(sizes: Dict[str, Any], seq_len: int) -> Any:
    from torchft_tpu.models import kimi_linear as kl

    return _GradStep(kl.make_grad_step(_program_config(sizes)))


def _release_compiled(of_ended_threads_only: bool) -> None:
    with _COMPILED_LOCK:
        for compiled in list(_COMPILED):
            if not (of_ended_threads_only and compiled._owner.is_alive()):
                compiled.release()
                _COMPILED.remove(compiled)


def reference_loss(params: Any, tokens: Any, sizes: Dict[str, Any],
                   operand_dtype: Optional[str] = None) -> Any:
    """The plain reference's loss (``reference/kimi_linear.py``).  Tracing it
    releases the program's compiled steps: the window is over by then."""
    _release_compiled(of_ended_threads_only=False)
    return _reference.loss_fn(params, tokens, sizes, operand_dtype)


def make_routing_stats(sizes: Dict[str, Any]) -> Any:
    """The program's jitted ``routing_stats(params, tokens)``: how far a batch
    is from the uniform routing ``flops_per_step`` counts on."""
    from torchft_tpu.models import kimi_linear as kl

    return kl.make_routing_stats(_program_config(sizes))


def program_init_shapes(sizes: Dict[str, Any]) -> Any:
    import jax

    from torchft_tpu.models import kimi_linear as kl

    cfg = _program_config(sizes)
    return jax.eval_shape(lambda k: kl.init_params(k, cfg), jax.random.PRNGKey(0))


def aot_prepare() -> None:
    """The flash kernels ask the backend whether to interpret themselves; a
    compile for a described chip runs on the CPU backend and must not."""
    from torchft_tpu.ops import flash_attention

    flash_attention._interpret = lambda: False


def weight_shapes(sizes: Dict[str, Any]) -> Dict[str, Any]:
    e, v = sizes["hidden_size"], sizes["vocab_size"]
    lin = sizes["linear_attn_config"]
    kh, kd, taps = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    d, r = kh * kd, sizes["kda_gate_rank"]
    nh, rank = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, rope, dv = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    f, fx = sizes["intermediate_size"], sizes["moe_intermediate_size"]
    held, outs = sizes["num_experts"], sizes["router_outputs"]
    kda, full = _lists(sizes)
    lk, lm = len(kda), len(full)
    ld = min(sizes["first_k_dense_replace"], sizes["num_hidden_layers"])
    lx = sizes["num_hidden_layers"] - ld
    return {
        "embed": (v, e), "head": (e, v), "final_norm": (e,),
        "kda": {
            "attn_norm": (lk, e), "wq": (lk, e, d), "wk": (lk, e, d), "wv": (lk, e, d),
            "conv_q": (lk, d, taps), "conv_k": (lk, d, taps), "conv_v": (lk, d, taps),
            "f_a": (lk, e, r), "f_b": (lk, r, d), "a_log": (lk, kh), "dt_bias": (lk, d),
            "b_proj": (lk, e, kh), "g_a": (lk, e, r), "g_b": (lk, r, d), "o_norm": (lk, kd),
            "wo": (lk, d, e)},
        "mla": {
            "attn_norm": (lm, e), "wq": (lm, e, nh * (nope + rope)), "kv_a": (lm, e, rank + rope),
            "kv_norm": (lm, rank), "kv_b": (lm, rank, nh * (nope + dv)), "wo": (lm, nh * dv, e)},
        "dense": {"mlp_norm": (ld, e), "w_gate": (ld, e, f), "w_up": (ld, e, f), "w_down": (ld, f, e)},
        "moe": {
            "mlp_norm": (lx, e), "router": (lx, e, outs),
            "w_gate": (lx, held, e, fx), "w_up": (lx, held, e, fx), "w_down": (lx, held, fx, e),
            "shared_gate": (lx, e, fx), "shared_up": (lx, e, fx), "shared_down": (lx, fx, e)},
    }


def _leaves(shapes: Any) -> "list[tuple[int, ...]]":
    import jax

    return jax.tree_util.tree_leaves(shapes, is_leaf=lambda s: isinstance(s, tuple))


def n_params(sizes: Dict[str, Any]) -> int:
    """Trained parameters by the shapes.  The router's correction bias
    (``router_outputs`` a layer) is a buffer and not counted."""
    return sum(int(np.prod(s)) for s in _leaves(weight_shapes(sizes)))


def make_weights_fn(sizes: Dict[str, Any]) -> Any:
    """``key -> weights``, the benchmark's own: matrices normal over the
    square root of the fan-in (a convolution's is its taps), norms ones, the
    embedding 0.02 normal, and the decay started as the published layer
    starts it: ``A_log = log(uniform(1, 16))`` per head, ``dt_bias`` the
    inverse softplus of a step drawn log-uniformly from [0.001, 0.1]."""
    import jax
    import jax.numpy as jnp

    _release_compiled(of_ended_threads_only=True)
    shapes = weight_shapes(sizes)
    pd = jnp.dtype(sizes["param_dtype"])

    def make(key):
        flat, tree = jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=lambda s: isinstance(s, tuple))
        out = []
        for i, (path, shape) in enumerate(flat):
            name = str(getattr(path[-1], "key", path[-1]))
            k = jax.random.fold_in(key, i)
            if name.endswith("norm"):
                leaf = jnp.ones(shape, pd)
            elif name == "embed":
                leaf = jax.random.normal(k, shape, pd) * 0.02
            elif name == "a_log":
                leaf = jnp.log(jax.random.uniform(k, shape, pd, 1.0, 16.0))
            elif name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(k, shape, pd, np.log(1e-3), np.log(1e-1)))
                leaf = dt + jnp.log(-jnp.expm1(-dt))
            else:
                fan_in = shape[-1] if name.startswith("conv_") else shape[-2]
                leaf = jax.random.normal(k, shape, pd) / np.sqrt(fan_in)
            out.append(leaf)
        return jax.tree_util.tree_unflatten(tree, out)

    return make


# ---- operations and bytes ---------------------------------------------------

def _pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def kda_work(sizes: Dict[str, Any], batch: int, seq: int) -> Dict[str, Dict[str, float]]:
    """Operations and bytes of the chunked delta rule of one KDA layer on
    ``batch`` rows: ``{"forward": ..., "backward": ...}``.

    Operations: the chunkwise algorithm's matrix products, 2 a multiply-add,
    a chunk of ``C`` steps and a head of ``d`` (keys and values alike): keys
    on keys and queries on keys (``2 C^2 d`` each), the inverse applied to
    keys and values (``2 C^2 d`` each), what was written applied to the
    queries' scores (``2 C^2 d``), and three products with the ``d x d`` state
    (read by the keys, read by the queries, the update: ``2 C d^2`` each):
    ``10 C^2 d + 6 C d^2`` a chunk and head.  Forming the triangular inverse
    is not counted (the published kernels substitute, this program squares),
    nor is any elementwise work.  The backward is twice the forward.

    Bytes, each array once: forward reads ``q, k, v`` in the compute type and
    ``g`` (float32, a channel) and ``beta`` (float32, a head), writes ``o``
    and writes then reads the chunk states (``T / C`` of ``d x d`` a head, in
    the compute type); the backward reads all of those and ``do``, writes the
    five gradients and writes then reads the states' gradients."""
    import jax.numpy as jnp

    lin = sizes["linear_attn_config"]
    heads, d, c = batch * lin["num_heads"], lin["head_dim"], sizes["kda_chunk"]
    chunks = -(-seq // c)
    item = jnp.dtype(sizes["compute_dtype"]).itemsize
    flops = float(heads * chunks * (10 * c * c * d + 6 * c * d * d))
    wide = heads * seq * d            # one of q, k, v, o, g
    states = heads * chunks * d * d
    beta = heads * seq
    forward = 3 * wide * item + wide * 4 + beta * 4 + wide * item + 2 * states * item
    backward = (forward - wide * item) + 2 * wide * item + (3 * wide * item + wide * 4 + beta * 4) \
        + 2 * states * item
    return {"forward": {"flops": flops, "bytes": float(forward)},
            "backward": {"flops": 2 * flops, "bytes": float(backward)}}


# what `ops/flash_attention.py` names its kernels (as `families/llama_dense.py`)
FLASH_KERNELS = ("_fwd_kernel", "_bwd_kv_kernel", "_bwd_q_kernel")


def flash_attn_work(sizes: Dict[str, Any], batch: int, seq: int) -> Dict[str, Dict[str, float]]:
    """Operations and bytes of one call of each flash-attention kernel: one
    latent-attention layer, ``batch`` rows, the causal half only, matrix
    products only (as ``families/llama_dense.py`` counts them), with queries
    and keys of ``nope + rope`` and values of ``v_head_dim``: a product with
    ``K`` or ``Q`` costs the first width, one with ``V`` or ``dO`` the second.
    Forward ``S = Q K^T`` and ``P V``; the key-value backward recomputes ``S``
    and forms ``dV``, ``dP``, ``dK``; the query backward recomputes ``S`` and
    forms ``dP`` and ``dQ``.  Bytes: every operand read once and every result
    written once in the compute type, row statistics in float32."""
    import jax.numpy as jnp

    dq = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    dv = sizes["v_head_dim"]
    heads = batch * sizes["num_attention_heads"]
    pairs = heads * _pairs(seq)
    item = jnp.dtype(sizes["compute_dtype"]).itemsize
    wide, narrow = heads * seq * dq * item, heads * seq * dv * item
    stat = heads * seq * 4
    return {
        "_fwd_kernel": {"flops": 2.0 * pairs * (dq + dv), "bytes": 2.0 * wide + 2 * narrow + stat},
        "_bwd_kv_kernel": {"flops": 2.0 * pairs * (2 * dq + 2 * dv),
                           "bytes": 3.0 * wide + 3 * narrow + 2 * stat},
        "_bwd_q_kernel": {"flops": 2.0 * pairs * (2 * dq + dv),
                          "bytes": 3.0 * wide + 2 * narrow + 2 * stat},
    }


def flops_per_step(sizes: Dict[str, Any], batch: int, seq: int) -> float:
    """Model FLOPs of forward + backward (= 3x forward) for ``batch`` rows;
    recomputation under remat is not counted.

    Six a token for every matmul parameter the token meets: the KDA layers'
    projections (and their convolutions' taps), the latent layers', the dense
    FFN, the router, the shared expert, the head; of the routed experts held
    here a token meets, **under uniform routing**, ``experts per token x held
    / router outputs`` (a quarter of one at 8 x 8 / 256): the program's
    ``routing_stats`` says how far a batch is from that.  Beside them the
    chunked delta rule (``kda_work``) and causal attention over the causal
    half (``flash_attn_work``'s products, forward x 3)."""
    e = sizes["hidden_size"]
    lin = sizes["linear_attn_config"]
    d, r = lin["num_heads"] * lin["head_dim"], sizes["kda_gate_rank"]
    nh, rank = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, rope, dv = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    fx = sizes["moe_intermediate_size"]
    kda, full = _lists(sizes)
    ld = min(sizes["first_k_dense_replace"], sizes["num_hidden_layers"])
    lx = sizes["num_hidden_layers"] - ld
    met = sizes["num_experts_per_token"] * sizes["num_experts"] / sizes["router_outputs"]
    per_kda = (3 * e * d + 3 * d * lin["short_conv_kernel_size"] + 2 * (e * r + r * d)
               + e * lin["num_heads"] + d * e)
    per_mla = e * nh * (nope + rope) + e * (rank + rope) + rank * nh * (nope + dv) + nh * dv * e
    per_token = (len(kda) * per_kda + len(full) * per_mla + ld * 3 * e * sizes["intermediate_size"]
                 + lx * (e * sizes["router_outputs"] + (1 + met) * 3 * e * fx)
                 + e * sizes["vocab_size"])
    core = 3 * kda_work(sizes, batch, seq)["forward"]["flops"] * len(kda)
    attn = 3 * flash_attn_work(sizes, batch, seq)["_fwd_kernel"]["flops"] * len(full)
    return float(6 * per_token * batch * seq + core + attn)


# ---- the device trace by the program's scopes --------------------------------

def scope_rows(run: Dict[str, Any], scopes: Iterable[str]) -> "Optional[list[Dict[str, Any]]]":
    """The grad step's device operations whose ``op_name`` passes through one
    of the program's ``jax.named_scope``s ``scopes`` (a whole path component:
    ``kda`` is not ``kda.proj``; a scope right under a transform shows as
    ``jvp(kda)``); ``None`` where the run has no operations."""
    ops = run.get("trace", {}).get("ops")
    if ops is None:
        return None
    scopes = set(scopes)
    return [op for op in ops if op["module"] == run["grad_module"] and op["op_name"]
            and scopes & {part.split("(")[-1].rstrip(")") for part in op["op_name"].split("/")}]


def scope_ms(run: Dict[str, Any], scopes: Iterable[str]) -> Optional[float]:
    """Device milliseconds a grad step spends under ``scopes``.  A run whose
    trace holds no run of the grad step on a device (a rehearsal on the CPU)
    reads 0: nothing ran there."""
    rows = scope_rows(run, scopes)
    if rows is None:
        return None
    runs = run["trace"]["module_seconds"].get(run["grad_module"])
    return 1e3 * sum(op["seconds"] for op in rows) / len(runs) if runs else 0.0
