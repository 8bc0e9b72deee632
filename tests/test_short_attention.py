"""The whole-sequence attention kernel (``ops/short_attention.py``), the
packed entry that selects it (``nn/attention.py::projected_attention``), and
one compile-only look at what it takes out of the v5e program.

The kernel runs in Pallas interpret mode here; the compile-only test builds
the ViT-B/16 block for a described (not attached) ``v5e:2x2`` and is skipped
where that topology cannot be described. It is the only test file that loads
the TPU compiler: keep it that way (one process holds the library's lock),
which is why the scan kernel's compile-only test (``ops/ssm_scan.py``; its
other tests are ``tests/test_ssm_scan_kernel.py``) stands at the end of it, and
after it the experts' grouped product's (``ops/grouped_matmul.py``; its other
tests are ``tests/test_grouped_matmul_kernel.py``).
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.nn import attention as A
from tpu_dist.obs import counters
from tpu_dist.ops import short_attention as K


def _reference(q, k, v):
    """Plain float32 softmax attention on [B, S, H, D]."""
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("heads", [12, 6], ids=["h12", "tp_h6"])
@pytest.mark.parametrize("seq", [196, 65, 197])
def test_forward_and_gradients_match_float32(seq, heads, dtype):
    """Forward, dq, dk and dv against float32 ``jax.numpy`` attention on the
    same (rounded) inputs: S=196 and lengths that are no multiple of 8 or
    128, all 12 heads and a tensor-parallel shard's 6, D=64 (two heads to a
    lane group)."""
    b, d = 2, 64
    kq, kw = jax.random.split(jax.random.PRNGKey(seq * heads))
    q, k, v = (
        t.astype(dtype) for t in jax.random.normal(kq, (3, b, seq, heads, d), jnp.float32)
    )
    w = jax.random.normal(kw, (b, seq, heads, d), jnp.float32)
    packed = jnp.concatenate([t.reshape(b, seq, heads * d) for t in (q, k, v)], axis=-1)

    def kernel_loss(packed):
        o = K.short_attention(packed, heads, interpret=True)
        return jnp.sum(o.astype(jnp.float32).reshape(w.shape) * w), o

    def reference_loss(q, k, v):
        o = _reference(q, k, v)
        return jnp.sum(o * w), o

    (_, o), d_packed = jax.value_and_grad(kernel_loss, has_aux=True)(packed)
    (_, o_ref), d_ref = jax.value_and_grad(reference_loss, argnums=(0, 1, 2), has_aux=True)(
        q, k, v
    )
    assert o.dtype == dtype and o.shape == (b, seq, heads * d)
    assert d_packed.dtype == dtype and d_packed.shape == packed.shape
    tol = 1e-2 if dtype == jnp.bfloat16 else 1e-5
    assert _rel(o.reshape(o_ref.shape), o_ref) < tol
    for name, got, want in zip(
        ("dq", "dk", "dv"), jnp.split(d_packed, 3, axis=-1), d_ref
    ):
        assert _rel(got.reshape(want.shape), want) < tol, name


@pytest.mark.parametrize(
    "heads,d", [(4, 32), (2, 128), (1, 256)], ids=["four_to_a_group", "one_group_a_head", "wide_head"]
)
def test_other_head_widths(heads, d):
    """Head widths that divide 128 share a lane group; multiples of 128 are
    a group each."""
    b, seq = 1, 40
    q, k, v = jax.random.normal(jax.random.PRNGKey(d), (3, b, seq, heads, d), jnp.float32)
    packed = jnp.concatenate([t.reshape(b, seq, heads * d) for t in (q, k, v)], axis=-1)
    o = K.short_attention(packed, heads, interpret=True)
    assert _rel(o.reshape(b, seq, heads, d), _reference(q, k, v)) < 1e-5


@pytest.mark.parametrize(
    "seq,heads,d,dtype,ok",
    [
        (196, 12, 64, jnp.bfloat16, True),    # ViT-B/16 at 224 px
        (196, 6, 64, jnp.bfloat16, True),     # its two-way tensor-parallel shard
        (196, 3, 64, jnp.bfloat16, False),    # odd local heads: half a lane group
        (196, 12, 48, jnp.bfloat16, False),   # heads straddle 128-lane groups
        (4096, 12, 64, jnp.bfloat16, False),  # ViT-B/16 at 1024 px: 64 MB a score tile
        (1024, 12, 64, jnp.bfloat16, False),
        (196, 12, 64, jnp.float32, True),
        (256, 12, 64, jnp.float32, False),    # f32 operands double the blocks
    ],
)
def test_fits_is_computed_from_the_shape(seq, heads, d, dtype, ok):
    assert K.fits(seq, heads, d, dtype) is ok
    if not ok:
        with pytest.raises(ValueError, match="short_attention cannot take"):
            K.short_attention(
                jax.ShapeDtypeStruct((1, seq, 3 * heads * d), dtype), heads, interpret=True
            )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("heads,d", [(12, 64), (6, 64), (4, 16)])
def test_qkv_major_projection_is_the_head_major_one_bit_for_bit(heads, d, dtype):
    """Permuting the projection's columns at trace time gives the very q, k
    and v the head-major slices give, and its transpose carries the same
    gradient back to the parameters' own layout. Small whole numbers, so every
    sum is exact in either dtype whatever order a matmul adds in: a
    difference would be a wrong column, not rounding."""
    b, s, din = 2, 9, 48
    ky, kw, kb, kc = jax.random.split(jax.random.PRNGKey(heads + d), 4)
    whole = lambda key, shape: jax.random.randint(key, shape, -2, 3).astype(dtype)
    y = whole(ky, (b, s, din))
    w = whole(kw, (din, heads * 3 * d))
    bias = whole(kb, (heads * 3 * d,))
    cot = whole(kc, (3, b, s, heads, d))

    def head_major(w, bias):
        qkv = (y @ w + bias).reshape(b, s, heads, 3, d)
        return jnp.stack([qkv[:, :, :, i, :] for i in range(3)])

    def qkv_major(w, bias):
        qkv = y @ A.qkv_major(w, d) + A.qkv_major(bias, d)
        return jnp.moveaxis(qkv.reshape(b, s, 3, heads, d), 2, 0)

    want, vjp_want = jax.vjp(head_major, w, bias)
    got, vjp_got = jax.vjp(qkv_major, w, bias)
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    for g, wnt in zip(vjp_got(cot), vjp_want(cot)):
        np.testing.assert_array_equal(np.asarray(g, np.float32), np.asarray(wnt, np.float32))


_RULE = [
    # name, on a TPU, impl, seq_axis, causal, S, local heads, D -> takes the kernel
    ("tpu_and_fits", True, None, None, False, 196, 12, 64, True),
    ("tpu_tp_shard", True, "auto", None, False, 196, 6, 64, True),
    ("cpu", False, None, None, False, 196, 12, 64, False),
    ("sequence_axis", True, None, "seq", False, 196, 12, 64, False),
    ("too_long", True, None, None, False, 4096, 12, 64, False),
    ("odd_local_heads", True, None, None, False, 196, 3, 64, False),
    ("causal", True, None, None, True, 196, 12, 64, False),
    ("forced_xla", True, "xla", None, False, 196, 12, 64, False),
    ("forced_flash", True, "flash", None, False, 196, 12, 64, False),
]


@pytest.mark.parametrize(
    "on_tpu,impl,seq_axis,causal,seq,heads,d,fused", [c[1:] for c in _RULE],
    ids=[c[0] for c in _RULE],
)
def test_selection_rule(monkeypatch, on_tpu, impl, seq_axis, causal, seq, heads, d, fused):
    monkeypatch.setattr(A, "_on_tpu", lambda: on_tpu)
    assert A.takes_short_kernel(impl, seq_axis, causal, seq, heads, d, jnp.bfloat16) is fused


def _count(fn):
    """(attn.sites_fused, attn.sites_xla) that tracing ``fn`` adds."""
    before = counters.get("attn.sites_fused"), counters.get("attn.sites_xla")
    out = fn()
    return (
        counters.get("attn.sites_fused") - before[0],
        counters.get("attn.sites_xla") - before[1],
        out,
    )


def _shapes_outside_kernels(jaxpr):
    """Shapes of every value a jaxpr computes, sub-jaxprs included, but not
    what lives inside a Pallas kernel (VMEM values, no arrays of the program)."""
    for eqn in jaxpr.eqns:
        yield from (v.aval.shape for v in eqn.outvars)
        if eqn.primitive.name == "pallas_call":
            continue
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                yield from _shapes_outside_kernels(inner)


@pytest.mark.parametrize("on_tpu", [True, False], ids=["tpu", "cpu"])
def test_projected_attention_lowers_each_way_and_counts(monkeypatch, on_tpu):
    """On a TPU the call site becomes the two-kernel pair over the packed
    projection; off it, the head-major slices and the XLA chain, op for op
    what the block did before. Each site is counted once, at trace time."""
    monkeypatch.setattr(A, "_on_tpu", lambda: on_tpu)
    heads, d, din = 12, 64, 768
    y = jax.ShapeDtypeStruct((2, 196, din), jnp.bfloat16)
    proj = {
        "w": jax.ShapeDtypeStruct((din, 3 * heads * d), jnp.bfloat16),
        "b": jax.ShapeDtypeStruct((3 * heads * d,), jnp.bfloat16),
    }

    def loss(y, proj):
        return A.projected_attention(y, proj, d).astype(jnp.float32).sum()

    fused, xla, jaxpr = _count(lambda: jax.make_jaxpr(jax.grad(loss, argnums=1))(y, proj))
    assert (fused, xla) == ((1, 0) if on_tpu else (0, 1))
    text = str(jaxpr)
    assert ("short_attn_fwd" in text) is on_tpu
    assert ("short_attn_bwd" in text) is on_tpu
    score_tiles = [s for s in _shapes_outside_kernels(jaxpr.jaxpr) if s[-2:] == (196, 196)]
    assert bool(score_tiles) is (not on_tpu)  # the [S, S] tile as an array of the program


@pytest.mark.parametrize("on_tpu", [True, False], ids=["tpu", "cpu"])
def test_every_vit_b16_layer_takes_the_same_path(monkeypatch, on_tpu):
    from tpu_dist.nn.vit import vit_b16

    monkeypatch.setattr(A, "_on_tpu", lambda: on_tpu)
    model = vit_b16()
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.bfloat16)[0])
    x = jax.ShapeDtypeStruct((2, 224, 224, 3), jnp.bfloat16)
    fused, xla, (logits, _) = _count(
        lambda: jax.eval_shape(lambda p, x: model.apply(p, {}, x, train=True), params, x)
    )
    assert logits.shape == (2, 1000)
    assert (fused, xla) == ((12, 0) if on_tpu else (0, 12))


def test_default_impl_is_chosen_by_shape_and_flash_is_not_counted_as_xla(monkeypatch):
    assert A.get_default_attention_impl() == "auto"
    with pytest.raises(ValueError, match="attention impl"):
        A.set_default_attention_impl("short")
    y = jnp.zeros((1, 16, 32), jnp.float32)
    proj = {"w": jnp.zeros((32, 3 * 2 * 16), jnp.float32), "b": jnp.zeros((96,), jnp.float32)}
    fused, xla, _ = _count(lambda: jax.eval_shape(
        lambda y, p: A.projected_attention(y, p, 16, impl="flash"), y, proj))
    assert (fused, xla) == (0, 0)


# -- compile only: the mechanism in the v5e program ---------------------------


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the compiler from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


_COMPILED = [
    (196, 12, 64, jnp.bfloat16),  # ViT-B/16 at 224 px
    (196, 6, 64, jnp.bfloat16),   # its two-way tensor-parallel shard
    (296, 12, 64, jnp.bfloat16),  # the longest that fits the VMEM budget at these widths
    (196, 12, 64, jnp.float32),
    (197, 6, 128, jnp.bfloat16),  # a head a lane group, a length that is no multiple of 8
    (64, 8, 32, jnp.bfloat16),    # four heads a lane group
]


@pytest.mark.parametrize(
    "seq,heads,d,dtype", _COMPILED, ids=[f"s{s}_h{h}_d{d}_{jnp.dtype(t).name}" for s, h, d, t in _COMPILED]
)
def test_kernel_pair_compiles_for_v5e(one_chip, seq, heads, d, dtype):
    """What interpret mode cannot show: Mosaic takes both kernels (tiling of
    unaligned lengths, the transposed matmuls, VMEM) at every kind of shape
    the selection rule lets through."""
    assert K.fits(seq, heads, d, dtype)
    x = jax.ShapeDtypeStruct((8, seq, 3 * heads * d), dtype, sharding=one_chip)
    loss = lambda x: K.short_attention(x, heads, interpret=False).astype(jnp.float32).sum()
    text = jax.jit(jax.grad(loss)).lower(x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("heads,kv,d", [(32, 8, 64), (32, 2, 128)], ids=["lfm2_64", "nemotron_128"])
def test_tiled_kernels_compile_for_v5e_at_the_token_cells_shapes(one_chip, heads, kv, d):
    """The tiled flash kernels (this file holds the suite's one described
    topology) at the two token cells' heads, causal, 8,192 tokens, bf16,
    1,024-wide tiles: forward, dK/dV and dQ, with heads of 64 channels as
    whole blocks of a 64-wide array."""
    from tpu_dist.ops.flash_attention import flash_attention

    q = jax.ShapeDtypeStruct((1, 8192, heads, d), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, 8192, kv, d), jnp.bfloat16, sharding=one_chip)
    loss = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, interpret=False).astype(jnp.float32).sum()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, k).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


_BYTES = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}
_ENTRY_OP = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (.*?)\s([\w\-]+)\(")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")


def _entry_ops(hlo_text):
    """(opcode, [(dtype, dims), ...]) of each instruction of the entry
    computation: every array of its result, a tuple's elements in order."""
    entry = hlo_text[hlo_text.index("\nENTRY "):]
    entry = entry[: entry.index("\n}")]
    for line in entry.splitlines():
        m = _ENTRY_OP.match(line)
        if m:
            yield m.group(2), [
                (dt, [int(x) for x in dims.split(",") if x])
                for dt, dims in _SHAPE.findall(m.group(1))
            ]


def _block_program(one_chip, impl):
    """ViT-B/16's block, forward and backward at batch 128 in bf16 compute
    over f32 parameters, as the step runs it, compiled for one v5e chip."""
    from tpu_dist.nn.vit import vit_b16

    model = vit_b16()
    blk = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0]["blocks"][0])
    place = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=one_chip)
    t = jax.ShapeDtypeStruct((128, 196, 768), jnp.bfloat16, sharding=one_chip)

    def loss(blk, t):
        from tpu_dist.nn.vit import tp_block_forward

        blk = jax.tree_util.tree_map(lambda p: p.astype(jnp.bfloat16), blk)
        same = lambda v: v
        out = tp_block_forward(blk, t, 64, same, same, attn_impl=impl)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    grad = jax.jit(jax.grad(loss, argnums=(0, 1)))
    return grad.lower(jax.tree_util.tree_map(place, blk), t).compile().as_text()


def test_v5e_block_program_holds_no_score_array_and_few_copies(monkeypatch, one_chip):
    monkeypatch.setattr(A, "_on_tpu", lambda: True)  # the host's backend is the CPU
    texts = {impl: _block_program(one_chip, impl) for impl in ("auto", "xla")}
    programs = {impl: list(_entry_ops(text)) for impl, text in texts.items()}

    def score_arrays(ops):
        return [(op, a) for op, arrays in ops for a in arrays if a[1][-2:] == [196, 196]]

    def copy_bytes(ops):
        # what a copy writes: its result, a copy-start's first element
        return sum(
            _BYTES[arrays[0][0]] * int(np.prod(arrays[0][1]))
            for op, arrays in ops if op in ("copy", "copy-start")
        )

    assert score_arrays(programs["xla"]), "the XLA path keeps its scores in HBM"
    assert not score_arrays(programs["auto"])
    mosaic = 'custom_call_target="tpu_custom_call"'
    assert (texts["auto"].count(mosaic), texts["xla"].count(mosaic)) == (2, 0)
    assert copy_bytes(programs["auto"]) < 0.4 * copy_bytes(programs["xla"])


# -- compile only: the mixers' scan kernel pair (ops/ssm_scan.py) ----------------


def _scan_program(one_chip, scan):
    """Forward + backward of one mixer's scan at the Nemotron share's shapes
    (2 x 8,192 tokens, 64 heads of 64 in 8 groups, state 128, chunk 128,
    bfloat16 operands), x, B and C flat as the mixer's convolution leaves
    them, compiled for one v5e chip."""
    b, t, h, p, g, n = 2, 8192, 64, 64, 8, 128
    f32, bf16 = jnp.float32, jnp.bfloat16
    place = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    args = (place((b, t, h * p), bf16), place((b, t, h), f32), place((h,), f32),
            place((b, t, g * n), bf16), place((b, t, g * n), bf16), place((b, t, h * p), bf16))

    def loss(x, dt, a, bb, cc, w):
        y = scan(x.reshape(b, t, h, p), dt, a, bb.reshape(b, t, g, n), cc.reshape(b, t, g, n), 128)
        return (y.reshape(b, t, h * p).astype(f32) * w.astype(f32)).sum()

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(*args).compile()


def _state_sized_float32(compiled):
    """(opcode, dims) of the entry computation's float32 arrays as large as
    every chunk's state, [2, 64, 8, 8, 64, 128] elements."""
    return [(op, dims) for op, arrays in _entry_ops(compiled.as_text()) for dt, dims in arrays
            if dt == "f32" and int(np.prod(dims)) >= 2 * 64 * 8 * 8 * 64 * 128]


def test_scan_kernel_pair_compiles_for_v5e_and_keeps_one_state_array(monkeypatch, one_chip):
    """What interpret mode cannot show: Mosaic takes the three kernels at
    the cell's shapes, and of the einsum form's per-chunk states (float32 and
    their cotangents, through HBM) one array is left, the states the chunks
    start from, which the reverse sweep reads."""
    from tpu_dist.nn import nemotron_h as decoder
    from tpu_dist.ops import ssm_scan as S

    assert S.fits(128, 8, 64, 128, jnp.bfloat16)
    kernel = _scan_program(one_chip, lambda *a: S.ssm_scan(*a, interpret=False))
    assert kernel.as_text().count('custom_call_target="tpu_custom_call"') == 3
    assert _state_sized_float32(kernel) == [("custom-call", [2, 64, 8, 512, 128])]
    assert kernel.memory_analysis().temp_size_in_bytes < 0.6e9

    monkeypatch.setattr(decoder, "_on_tpu", lambda: False)  # the einsum form, for the v5e
    einsums = _scan_program(one_chip, decoder.ssm_scan)
    assert len(_state_sized_float32(einsums)) >= 2
    assert einsums.memory_analysis().temp_size_in_bytes > 0.9e9


# -- compile only: the experts' grouped product (ops/grouped_matmul.py) -----------

# (tiles, hidden, expert width): the expert layers of lfm2_24b_a2b_share and
# nemotron3_nano_share, 512-row tiles, 8 experts held
_GMM_CELLS = {"lfm2": (72, 2048, 1536), "nemotron_1856_whole": (32, 2688, 1856)}


@pytest.mark.parametrize("tiles,d,f", list(_GMM_CELLS.values()), ids=list(_GMM_CELLS))
def test_grouped_matmul_kernel_pair_compiles_for_v5e_at_the_token_cells_shapes(one_chip, tiles, d, f):
    """What interpret mode cannot show: Mosaic takes the product, its
    transposed form and the weight gradient at both cells' shapes in bf16,
    up and down (a width of 1856 = 14.5 x 128 as one whole block, the 20 to
    70 MB a step holds under ``vmem_limit_bytes``)."""
    from tpu_dist.ops import grouped_matmul as G

    assert G.fits(512, d, f, jnp.bfloat16)
    place = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    te, n_live = place((tiles,), jnp.int32), place((), jnp.int32)

    def loss(x, w_up, w_down, te, n_live):
        # up and down through the kernels' own custom_vjp-free calls: the
        # product, the transposed product and the weight gradient of each
        h = G.gmm(x, w_up, te, n_live, interpret=False)
        y = G.gmm(h, w_down, te, n_live, interpret=False)
        dh = G.gmm(y, w_down, te, n_live, True, interpret=False)
        dx = G.gmm(dh, w_up, te, n_live, True, interpret=False)
        dw_down = G.tgmm(h, y, te, n_live, 8, jnp.bfloat16, interpret=False)
        dw_up = G.tgmm(x, dh, te, n_live, 8, jnp.bfloat16, interpret=False)
        return dx, dw_up, dw_down

    text = jax.jit(loss).lower(
        place((tiles, 512, d)), place((8, d, f)), place((8, f, d)), te, n_live).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 6


_SLAB_UPDATE_IN_A_LOOP = re.compile(
    r"= f32\[8,(?:2048,1536|1536,2048)\]\S* dynamic-update-slice\(.*op_name=\"[^\"]*while/body")


def test_v5e_expert_layer_gradient_keeps_no_slab_update_in_a_loop(monkeypatch, one_chip):
    """``dropless_experts`` at the LFM2 share's shapes, loss and gradient,
    compiled for one v5e chip: with the kernel pair the program's loops hold
    no ``f32[8,2048,1536]`` ``dynamic-update-slice`` (the XLA loop's weight
    gradient, a slab read and written a tile), with the loop they do."""
    from tpu_dist.parallel import expert as E

    t, d, f, k = 4 * 8192, 2048, 1536, 4
    place = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    args = (place((t, d)), place((t, k), jnp.int32), place((t, k)),
            place((8, d, f)), place((8, f, d)), place((8, d, f)))

    def loss(x, chosen, weights, w_up, w_down, w_gate):
        out, _ = E.dropless_experts(x, chosen, weights, w_up, w_down, held=(0, 8),
                                    capacity=t, activation=jax.nn.silu, w_gate=w_gate)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def slab_updates_in_loops(on_tpu):
        monkeypatch.setattr(E, "_on_tpu", lambda: on_tpu)
        text = jax.jit(jax.grad(loss, argnums=(0, 3, 4, 5))).lower(*args).compile().as_text()
        return (text.count('custom_call_target="tpu_custom_call"'),
                len(_SLAB_UPDATE_IN_A_LOOP.findall(text)))

    assert slab_updates_in_loops(True) == (11, 0)  # three products: gmm, its dx, tgmm; the combine, the dispatch's dx
    calls, in_loops = slab_updates_in_loops(False)
    assert calls == 0 and in_loops >= 3


def test_v5e_expert_layer_reads_an_1856_wide_matrix_as_the_chip_keeps_it(monkeypatch, one_chip):
    """The Nemotron share's ``w_up`` is ``f32[8,2688,1856]``, which the v5e
    keeps with 2688 minor (1856 is no multiple of 128). The kernels take its
    transpose read transposed, the same bytes, so that the layer's gradient
    program copies no array of that shape (row-major operands cost one copy
    of the weights here, and of gradient and moments in the step: 9.2 ms)."""
    from tpu_dist.parallel import expert as E

    t, d, f, k = 2 * 8192, 2688, 1856, 6
    place = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    args = (place((t, d)), place((t, k), jnp.int32), place((t, k)),
            place((8, d, f), jnp.float32), place((8, f, d), jnp.float32))

    def loss(x, chosen, weights, w_up, w_down):
        out, _ = E.dropless_experts(
            x, chosen, weights, w_up.astype(jnp.bfloat16), w_down.astype(jnp.bfloat16),
            held=(0, 8), capacity=12288, activation=lambda v: jnp.square(jax.nn.relu(v)))
        return jnp.sum(out.astype(jnp.float32) ** 2)

    monkeypatch.setattr(E, "_on_tpu", lambda: True)
    text = jax.jit(jax.grad(loss, argnums=(0, 3, 4))).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 8  # and the combine's two
    assert "f32[8,2688,1856]{1,2,0" in text  # w_up as the chip keeps it
    assert not re.findall(r"copy[.\d]* = \w+\[8,(?:2688,1856|1856,2688)\]", text)


# -- compile only: the experts' combine (ops/expert_combine.py) --------------------

# (tokens, hidden, top-k, experts, buffer capacity, gated): the expert layers of
# lfm2_24b_a2b_share and nemotron3_nano_share, 8 experts held
_COMBINE_CELLS = {"lfm2": (32768, 2048, 4, 64, 32768, True), "nemotron": (16384, 2688, 6, 128, 12288, False)}


@pytest.mark.parametrize("t,d,k,n_experts,capacity,gated", list(_COMBINE_CELLS.values()), ids=list(_COMBINE_CELLS))
def test_v5e_expert_layer_keeps_no_token_sized_scatter(monkeypatch, one_chip, t, d, k, n_experts, capacity, gated):
    """``dropless_experts``' gradient at both token cells' shapes, compiled
    for one v5e chip: with the combine kernel (and the dispatch's backward
    through it) the program holds no scatter into a ``[T, d]`` array; with
    XLA's forms it holds two, the combine's float32 and the dispatch
    gather's transpose (bf16: the kernel's float32 sum is not less)."""
    from tpu_dist.parallel import expert as E

    del n_experts
    place = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    args = (place((t, d)), place((t, k), jnp.int32), place((t, k)), place((8, d, 1536)),
            place((8, 1536, d))) + ((place((8, d, 1536)),) if gated else ())

    def loss(x, chosen, weights, w_up, w_down, *w_gate):
        out, _ = E.dropless_experts(x, chosen, weights, w_up, w_down, held=(0, 8), capacity=capacity,
                                    activation=jax.nn.silu, **({"w_gate": w_gate[0]} if gated else {}))
        return jnp.sum(out.astype(jnp.float32) ** 2)

    token_sized = re.compile(rf"= (\w+)\[{t},{d}\]\S* scatter\(")

    def scatters(kernel):
        monkeypatch.setattr(E, "_on_tpu", lambda: True)
        monkeypatch.setattr(E, "takes_combine_kernel", lambda *a: kernel)
        text = jax.jit(jax.grad(loss)).lower(*args).compile().as_text()
        return sorted(token_sized.findall(text))

    assert scatters(True) == []
    assert scatters(False) == ["bf16", "f32"]


@pytest.mark.parametrize("t,d,rows", [(32768, 2048, 36864), (16384, 2688, 16384)], ids=["lfm2", "nemotron"])
@pytest.mark.parametrize("scaled", [True, False], ids=["combine", "dispatch_backward"])
def test_combine_kernel_compiles_for_v5e_at_the_token_cells_shapes(one_chip, t, d, rows, scaled):
    """What interpret mode cannot show: Mosaic takes the kernel (its DMAs
    from HBM at a chunk's dynamic row, the token row read at a dynamic
    sublane) at both cells' buffers, in bf16."""
    from tpu_dist.ops import expert_combine as C

    assert C.fits(t, d, rows, jnp.bfloat16)
    place = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731

    def build(src, token, scale, runs, over):
        return C.tokens_from_runs(src, token, scale if scaled else None, runs, over, t, jnp.bfloat16,
                                  interpret=False)

    text = jax.jit(build).lower(place((rows, d)), place((rows,), jnp.int32), place((rows,), jnp.float32),
                                place((t // C.TOKEN_BLOCK, 8, 2), jnp.int32), place((), jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


# -- compile only: the mixers' depthwise convolution (ops/causal_conv1d.py) --------


def _mixer_program(monkeypatch, one_chip, conv_kernel):
    """One mixer layer of the Nemotron share (norm, mixer, residual: 2 x
    8,192 tokens, ``proj`` 10,304 columns = gate 4,096 | x 4,096 | B 1,024 |
    C 1,024 | dt 64, 4 taps, bfloat16) with its gradients, the scan through
    its kernel pair, compiled for one v5e chip."""
    from tpu_dist.nn import nemotron_h as decoder

    monkeypatch.setattr(decoder, "_on_tpu", lambda: True)
    if not conv_kernel:
        monkeypatch.setattr(decoder, "takes_conv_kernel", lambda *a: False)
    m = decoder.nemotron3_nano_share()
    place = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=one_chip)  # noqa: E731
    p = jax.tree_util.tree_map(place, jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0))[0]["layers"][0]))
    x = jax.ShapeDtypeStruct((2, 8192, m.hidden), jnp.bfloat16, sharding=one_chip)

    def layer(p, x):
        return x + m._mixer(p, decoder.rms_norm(p["norm"], x, m.eps), jnp.bfloat16)

    def step(p, x, ct):
        y, vjp = jax.vjp(layer, p, x)
        return y, vjp(ct)

    return jax.jit(step).lower(p, x, x).compile().as_text()


_CHAIN_FLOAT32 = re.compile(r"f32\[2,819[25],6144\]")  # the padded input, the pre-activation


def test_conv_kernel_pair_compiles_for_v5e_and_reads_the_projection_in_place(monkeypatch, one_chip):
    """What interpret mode cannot show: Mosaic takes both kernels at the
    cell's shape (blocks of ``proj [2, 8192, 10304]`` read where x, B and C
    lie, 4 taps over 6,144 channels); the layer's program holds three calls
    forward and three backward beside the scan's three, none of the chain's
    float32 ``[2, 8195, 6144]`` / ``[2, 8192, 6144]`` arrays, and no copy of
    ``proj``, of a slice of it or of the scan's operands between the input
    product, these kernels and the scan's."""
    from tpu_dist.ops import causal_conv1d as C

    assert C.fits(8192, (4096, 8192, 9216, 10240), 4, jnp.bfloat16)
    text = _mixer_program(monkeypatch, one_chip, conv_kernel=True)
    assert text.count('custom_call_target="tpu_custom_call"') == 9
    assert text.count("causal_conv1d_fwd/pallas_call") >= 3 and text.count("causal_conv1d_bwd/pallas_call") >= 3
    assert not _CHAIN_FLOAT32.search(text)
    assert not re.findall(r"copy[.\d]* = bf16\[2,8192,(?:10304|6144|4096|1024)\]", text)

    chain = _mixer_program(monkeypatch, one_chip, conv_kernel=False)
    assert chain.count('custom_call_target="tpu_custom_call"') == 3
    assert _CHAIN_FLOAT32.search(chain)
