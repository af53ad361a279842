"""The chunked delta rule (``ops/kda.py`` ``kda_chunked``) against its recurrence
(``kda_recurrent``): forward and every gradient, over several chunks, at
lengths that are no multiple of the chunk, under gentle and violent decay, and
with keys that resemble each other.  Then the same rule as Pallas kernels
(``kda_kernels``, in the interpreter here) against both."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.ops import flash_attention
from torchft_tpu.ops import kda as kda_ops
from torchft_tpu.ops.kda import kda, kda_chunked, kda_kernels, kda_recurrent
from torchft_tpu.utils import metrics


def _kda_inputs(t, strength, seed=0, b=2, h=2, dk=32, dv=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, t, h, dk))
    k = jax.random.normal(ks[1], (b, t, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -strength * jax.nn.softplus(jax.random.normal(ks[3], (b, t, h, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta


# several chunks; lengths that are no multiple of 64; decays from gentle to a
# mean of exp(-2.4) a step; a chunk of 32
@pytest.mark.parametrize("t,strength,chunk", [
    (256, 0.05, 64), (192, 0.5, 64), (200, 0.5, 64), (70, 0.2, 64), (130, 3.0, 64), (96, 0.5, 32)])
def test_chunked_delta_rule_is_the_recurrence(t, strength, chunk):
    x = _kda_inputs(t, strength, seed=t)
    want = kda_recurrent(*x)
    got = jax.jit(lambda *a: kda_chunked(*a, chunk=chunk))(*x)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5 * float(jnp.abs(want).max()))

    def loss(fn):
        weight = jnp.cos(jnp.arange(want.size, dtype=jnp.float32)).reshape(want.shape)
        return lambda *a: (fn(*a) * weight).sum()

    g_want = jax.grad(loss(kda_recurrent), argnums=(0, 1, 2, 3, 4))(*x)
    g_got = jax.jit(jax.grad(loss(lambda *a: kda_chunked(*a, chunk=chunk)), argnums=(0, 1, 2, 3, 4)))(*x)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5 * float(jnp.abs(b).max()),
                                   err_msg=f"d{name}")


def test_chunked_delta_rule_with_keys_that_resemble_each_other():
    """Keys within a tenth of one direction and ``beta`` at 0.99: the rows of
    the triangular system are nearly equal, where a series in its powers
    (which an earlier form of this code summed) loses every digit and, on
    the chip, turned a trained layer's output into NaN."""
    q, k, v, g, beta = _kda_inputs(256, 0.02, seed=3)
    k = jax.random.normal(jax.random.PRNGKey(9), (1, 1, 1, 32)) + 0.1 * k
    x = (q, k / jnp.linalg.norm(k, axis=-1, keepdims=True), v, g, 0.99 * jnp.ones_like(beta))
    want = kda_recurrent(*x)
    np.testing.assert_allclose(np.asarray(jax.jit(kda_chunked)(*x)), np.asarray(want),
                               atol=2e-5 * float(jnp.abs(want).max()))
    g_want = jax.grad(lambda *a: (kda_recurrent(*a) ** 2).sum(), argnums=(0, 1, 2, 3, 4))(*x)
    g_got = jax.jit(jax.grad(lambda *a: (kda_chunked(*a) ** 2).sum(), argnums=(0, 1, 2, 3, 4)))(*x)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5 * float(jnp.abs(b).max()))


# a few channels wiped at a sustained rate (a step's log decay; half a block of
# 8 steps at 10.9 and more leaves what float32 can carry as one factor), and
# single steps of exp(-36) on every channel now and then: what the later KDA
# layers see at published widths (PERF.md, PR 27)
@pytest.mark.parametrize("rate", [9.0, 10.5, 11.8, 12.5, 17.0, 25.0, "spikes"])
def test_chunked_delta_rule_under_channels_that_are_wiped(rate):
    q, k, v, g, beta = _kda_inputs(256, 0.05, seed=11, dk=64)
    if rate == "spikes":
        hit = jax.random.uniform(jax.random.PRNGKey(5), g.shape) < 0.05
        g = jnp.where(hit, -36.0, g)
    else:
        wobble = 1 + 0.05 * jnp.sin(jnp.arange(256.0))
        for head, channel in ((0, 5), (1, 40), (1, 41)):
            g = g.at[:, :, head, channel].set(-rate * wobble)
    # where a twentieth of all decays are held at exp(-10) for exp(-36), the
    # floor's own 4.5e-5 shows; the fault this guards against read 1e-3 to 1e-2
    loose = 5.0 if rate == "spikes" else 1.0
    x = (q, k, v, g, beta)
    want = kda_recurrent(*x)
    got = jax.jit(kda_chunked)(*x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=loose * 2e-5 * float(jnp.abs(want).max()))
    weight = jnp.cos(jnp.arange(want.size, dtype=jnp.float32)).reshape(want.shape)
    g_want = jax.grad(lambda *a: (kda_recurrent(*a) * weight).sum(), argnums=(0, 1, 2, 3, 4))(*x)
    g_got = jax.jit(jax.grad(lambda *a: (kda_chunked(*a) * weight).sum(), argnums=(0, 1, 2, 3, 4)))(*x)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=loose * 5e-5 * float(jnp.abs(b).max()),
                                   err_msg=f"d{name}")


def test_chunked_delta_rule_stays_finite_under_a_decay_that_wipes_the_state():
    x = _kda_inputs(128, 30.0)
    out, grads = jax.value_and_grad(lambda *a: kda_chunked(*a).sum(), argnums=(0, 1, 2, 3, 4))(*x)
    assert np.isfinite(float(out)) and all(bool(jnp.isfinite(g).all()) for g in grads)


def test_chunked_delta_rule_in_bfloat16_is_near_the_recurrence():
    q, k, v, g, beta = _kda_inputs(256, 0.3, dk=64, dv=64)
    want = kda_recurrent(q, k, v, g, beta)
    got = kda_chunked(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), g, beta)
    assert got.dtype == jnp.bfloat16
    err = jnp.abs(got.astype(jnp.float32) - want).max() / jnp.abs(want).max()
    assert float(err) < 0.03


def _alike(x):
    q, k, v, g, beta = x
    k = jax.random.normal(jax.random.PRNGKey(9), (1, 1, 1, k.shape[-1])) + 0.1 * k
    return q, k / jnp.linalg.norm(k, axis=-1, keepdims=True), v, g, 0.99 * jnp.ones_like(beta)


def _wiped(x):
    q, k, v, g, beta = x
    for head, channel in ((0, 5), (1, 40), (1, 41)):
        g = g.at[:, :, head, channel].set(-36.0)
    return q, k, v, g, beta


def _in_bfloat16(x):
    return tuple(a.astype(jnp.bfloat16) for a in x[:3]) + x[3:]


# case -> (inputs, chunk, the output's and the gradients' distance from the
# recurrence as a share of its largest entry)
KERNEL_CASES = {
    "float32": (lambda: _kda_inputs(256, 0.5, seed=21), 64, 2e-5, 5e-5),
    "bfloat16": (lambda: _in_bfloat16(_kda_inputs(256, 0.3, seed=22, h=4, dk=64, dv=64)), 64, 0.03, 0.06),
    "a length that is no multiple of the chunk": (lambda: _kda_inputs(200, 0.5, seed=23), 64, 2e-5, 5e-5),
    "a chunk of 32, one head more than a grid step takes": (
        lambda: _kda_inputs(96, 0.5, seed=24, b=1, h=5), 32, 2e-5, 5e-5),
    "channels that decay by exp(-36) a step": (
        lambda: _wiped(_kda_inputs(256, 0.05, seed=25, dk=64)), 64, 2e-5, 5e-5),
    "beta near one with keys alike": (lambda: _alike(_kda_inputs(256, 0.02, seed=26)), 64, 2e-5, 5e-5),
}


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_the_kernels_are_the_chunked_form_and_the_recurrence(case):
    """Output and all five gradients of the Pallas kernels, in the interpreter,
    against ``kda_recurrent`` in float32 (to the case's tolerance) and against
    ``kda_chunked`` on the same operands (no further from the recurrence than
    the XLA form is, to a factor for the two forms' different roundings)."""
    make, chunk, tol_o, tol_g = KERNEL_CASES[case]
    x = make()
    x32 = tuple(a.astype(jnp.float32) for a in x)
    want = kda_recurrent(*x32)
    weight = jnp.cos(jnp.arange(want.size, dtype=jnp.float32)).reshape(want.shape)

    def run(fn, args):
        o = jax.jit(fn)(*args)
        grads = jax.jit(jax.grad(lambda *a: (fn(*a).astype(jnp.float32) * weight).sum(),
                                 argnums=(0, 1, 2, 3, 4)))(*args)
        return o, grads

    o_want, g_want = run(kda_recurrent, x32)
    o_kernels, g_kernels = run(lambda *a: kda_kernels(*a, chunk, True), x)
    o_chunked, g_chunked = run(lambda *a: kda_chunked(*a, chunk=chunk), x)
    assert o_kernels.shape == want.shape and o_kernels.dtype == x[2].dtype

    def far(a, b):
        return float(jnp.abs(a.astype(jnp.float32) - b).max() / jnp.abs(b).max())

    assert far(o_kernels, o_want) <= max(tol_o, 2 * far(o_chunked, o_want)), "o"
    for name, a, c, b in zip(("q", "k", "v", "g", "beta"), g_kernels, g_chunked, g_want):
        assert a.shape == b.shape and a.dtype == c.dtype, f"d{name}"
        assert bool(jnp.isfinite(a.astype(jnp.float32)).all()), f"d{name}"
        assert far(a, b) <= max(tol_g, 2 * far(c, b)), f"d{name}: {far(a, b)} against the XLA form's {far(c, b)}"


def test_the_op_takes_the_kernels_by_backend_and_head_width_and_counts_its_calls(monkeypatch):
    """``kda`` asks what the flash kernels ask and looks at the head widths,
    nothing else; each traced call lands in ``torchft_kda_calls_total``."""
    def count(path):
        return metrics.KDA_CALLS.labels(path=path).get()

    assert kda_ops.kernels_take((2, 100, 3, 128), (2, 100, 3, 256), 64)
    assert not kda_ops.kernels_take((2, 128, 4, 64), (2, 128, 4, 128), 64)     # keys of half a lane
    assert not kda_ops.kernels_take((2, 128, 4, 128), (2, 128, 4, 128), 48)    # no power of two
    x = _kda_inputs(70, 0.3, seed=31)
    before = count("chunked"), count("kernels")
    got = jax.jit(kda)(*x)                         # off the TPU: the XLA form, a row at a time
    np.testing.assert_array_equal(np.asarray(got), np.asarray(jax.jit(
        lambda *a: jax.lax.map(lambda r: kda_chunked(*(leaf[None] for leaf in r))[0], a))(*x)))
    assert (count("chunked"), count("kernels")) == (before[0] + 1, before[1])
    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    jax.make_jaxpr(lambda *a: kda(*a))(*x)       # a TPU, heads of 32 and 16: still the XLA form
    assert (count("chunked"), count("kernels")) == (before[0] + 2, before[1])
    wide = _kda_inputs(70, 0.3, seed=31, dk=128, dv=128)
    jax.make_jaxpr(lambda *a: kda(*a))(*wide)    # whole lanes: the kernels
    assert (count("chunked"), count("kernels")) == (before[0] + 2, before[1] + 1)
