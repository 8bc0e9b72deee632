"""The mixers' scan as a Pallas kernel pair (ops/ssm_scan.py): in interpret
mode on the CPU against the token-by-token recurrence and the einsum form,
and the rule by which ``nn/nemotron_h.py::ssm_scan`` takes it. What only the
v5e's compiler can say stands with the other compile-only tests, in
tests/test_short_attention.py (one process may hold the TPU's library)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness.manifest import load_module  # noqa: E402
from tests.test_nemotron_h import _recurrence  # noqa: E402
from tpu_dist.nn import nemotron_h as decoder  # noqa: E402
from tpu_dist.obs import counters  # noqa: E402
from tpu_dist.ops import ssm_scan as K  # noqa: E402

CHUNK = 128
_WRT = (0, 1, 2, 3, 4)


def _inputs(bsz, s, heads, p, groups, n, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (bsz, s, heads, p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bsz, s, heads)) - 1.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0, maxval=2.5))
    b = jax.random.normal(ks[3], (bsz, s, groups, n)).astype(dtype)
    c = jax.random.normal(ks[4], (bsz, s, groups, n)).astype(dtype)
    return (x, dt, a, b, c), jax.random.normal(ks[5], x.shape)


def _value_and_grads(scan, args, w):
    f = lambda *a: (scan(*a).astype(jnp.float32) * w).sum()  # noqa: E731
    return scan(*args), jax.grad(f, argnums=_WRT)(*args)


def _rms(x):
    return float(jnp.sqrt(jnp.mean(jnp.square(x.astype(jnp.float32)))))


# (batch, tokens, heads, head width, groups, state)
_SHAPES = {
    "one_chunk_one_group": (1, 128, 2, 64, 1, 128),
    "chunks_batch_groups": (2, 384, 4, 64, 2, 128),
    "four_heads_a_group": (1, 256, 4, 64, 1, 128),
    "whole_lane_heads": (1, 256, 2, 128, 1, 128),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16_operands"])
@pytest.mark.parametrize("shape", list(_SHAPES.values()), ids=list(_SHAPES))
def test_kernel_pair_equals_the_recurrence_and_the_einsum_form(shape, dtype):
    """Values and all five gradients. In float32 the three agree to
    rounding. With bfloat16 operands the truth is the recurrence in float32
    on the same (rounded) inputs, and the kernel may stray from it no
    further than the einsum form does, with a fifth of room (``d a``, a
    number a head: within one bfloat16 step of its size)."""
    args, w = _inputs(*shape, dtype)
    hi = tuple(t.astype(jnp.float32) for t in args)
    y_true, g_true = _value_and_grads(_recurrence, hi, w)
    y_ker, g_ker = _value_and_grads(lambda *a: K.ssm_scan(*a, CHUNK), args, w)
    y_xla, g_xla = _value_and_grads(lambda *a: decoder.ssm_scan(*a, CHUNK), args, w)
    assert y_ker.dtype == args[0].dtype and y_ker.shape == args[0].shape
    pairs = [("y", y_true, y_xla, y_ker)] + [
        ("d" + name, *g) for name, g in zip("x dt a b c".split(), zip(g_true, g_xla, g_ker))]
    for name, true, xla, ker in pairs:
        assert ker.shape == true.shape and ker.dtype == xla.dtype, name
        scale = _rms(true)
        if dtype == jnp.float32:
            # d a sums thousands of terms of both signs into a number a head:
            # the recurrence's own sum is no better than 1e-4 (the einsum
            # form reads 1e-5 to 5e-5 against it)
            tol = 2e-4 if name == "da" else 2e-5
            assert _rms(ker - true) <= tol * scale, name
            assert _rms(ker - xla) <= tol * scale, name
        elif name == "da":  # a few numbers, each a sum of rounded terms: one bfloat16 step
            assert _rms(ker - true) <= 2.0 ** -8 * scale, name
        else:
            assert _rms(ker - true) <= 1.2 * _rms(xla - true) + 1e-6 * scale, name


def test_an_impulse_in_the_first_chunk_is_read_in_the_last():
    """The carried state crosses every chunk boundary: x is zero but for
    token 0, and the last chunk's output is what the recurrence says."""
    (x, dt, a, b, c), _ = _inputs(1, 4 * CHUNK, 2, 64, 1, 128, jnp.float32, seed=3)
    x = jnp.zeros_like(x).at[:, 0].set(x[:, 0])
    dt = 0.02 * dt  # slow decays, so that the impulse is still there 512 tokens on
    got = K.ssm_scan(x, dt, a, b, c, CHUNK)
    want = _recurrence(x, dt, a, b, c)
    tail = float(jnp.abs(want[:, -CHUNK:]).max())
    assert tail > 1e-3
    np.testing.assert_allclose(got[:, -CHUNK:], want[:, -CHUNK:], atol=2e-5 * tail + 1e-7)
    # and its cotangent crosses them back: the last chunk's output moves token 0's x
    w = jnp.zeros_like(x).at[:, -CHUNK:].set(1.0)
    dx = jax.grad(lambda x: (K.ssm_scan(x, dt, a, b, c, CHUNK) * w).sum())(x)
    dx_want = jax.grad(lambda x: (_recurrence(x, dt, a, b, c) * w).sum())(x)
    assert float(jnp.abs(dx_want[:, 0]).max()) > 1e-3
    np.testing.assert_allclose(dx[:, 0], dx_want[:, 0], rtol=2e-4, atol=1e-6)


def test_kernel_refuses_what_fits_refuses():
    (x, dt, a, b, c), _ = _inputs(1, 64, 2, 64, 1, 128, jnp.float32)
    with pytest.raises(ValueError, match="cannot take"):
        K.ssm_scan(x, dt, a, b, c, 16)
    with pytest.raises(ValueError, match="whole chunks"):
        K.ssm_scan(x, dt, a, b, c, CHUNK)


# -- which realisation ssm_scan takes -----------------------------------------------

_FITS = [
    ("the_share_preset", 128, 8, 64, 128, jnp.bfloat16, True),
    ("whole_lane_heads", 256, 2, 128, 128, jnp.float32, True),
    ("short_chunk", 16, 8, 64, 128, jnp.bfloat16, False),
    ("narrow_state", 128, 8, 64, 64, jnp.bfloat16, False),
    ("half_a_lane_group", 128, 3, 64, 128, jnp.bfloat16, False),
    ("heads_straddle_lanes", 128, 4, 96, 128, jnp.bfloat16, False),
    ("past_the_vmem_budget", 1024, 8, 64, 128, jnp.bfloat16, False),
]


@pytest.mark.parametrize("chunk,heads,p,n,dtype,ok", [c[1:] for c in _FITS], ids=[c[0] for c in _FITS])
def test_fits(chunk, heads, p, n, dtype, ok):
    assert K.fits(chunk, heads, p, n, dtype) is ok


def _sites(fn):
    """(ssm.sites_kernel, ssm.sites_xla) that tracing ``fn`` adds."""
    before = counters.get("ssm.sites_kernel"), counters.get("ssm.sites_xla")
    fn()
    return (counters.get("ssm.sites_kernel") - before[0], counters.get("ssm.sites_xla") - before[1])


def _trace_scan(chunk=CHUNK, p=64, dtype=jnp.bfloat16, **kw):
    (x, dt, a, b, c), _ = _inputs(1, 256, 4, p, 2, 128, dtype)
    return lambda: jax.eval_shape(lambda *r: decoder.ssm_scan(*r, chunk, **kw), x, dt, a, b, c)


_RULE = [
    ("off_the_tpu", False, {}, (0, 1)),
    ("on_the_tpu", True, {}, (1, 0)),
    ("bfloat16_state", True, {"state_dtype": jnp.bfloat16}, (0, 1)),
    ("chunk_of_16", True, {"chunk": 16}, (0, 1)),
    ("shape_fits_refuses", True, {"p": 96}, (0, 1)),
]


@pytest.mark.parametrize("on_tpu,kw,want", [c[1:] for c in _RULE], ids=[c[0] for c in _RULE])
def test_selection_and_counters(monkeypatch, on_tpu, kw, want):
    monkeypatch.setattr(decoder, "_on_tpu", lambda: on_tpu)
    assert _sites(_trace_scan(**kw)) == want


def test_the_controls_partial_still_patches_what_the_mixer_calls(monkeypatch):
    """``benchmarks/controls/nemotron_h.py`` plants its bfloat16 state by
    replacing the module attribute: the mixer must look ``ssm_scan`` up there
    at every call, and a bfloat16 state must take the einsum form on a TPU
    too. At the share preset's widths, traced and not run."""
    controls = load_module(REPO, "controls", "nemotron_h")
    monkeypatch.setattr(decoder, "_on_tpu", lambda: True)
    m = decoder.nemotron3_nano_share()
    p = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0))[0]["layers"][0])
    h = jax.ShapeDtypeStruct((1, 2 * m.chunk_size, m.hidden), jnp.bfloat16)
    mixer = lambda: jax.eval_shape(lambda p, h: m._mixer(p, h, jnp.bfloat16), p, h)  # noqa: E731
    assert _sites(mixer) == (1, 0)
    with controls.bf16_scan_state():
        assert _sites(mixer) == (0, 1)
    assert _sites(mixer) == (1, 0)
