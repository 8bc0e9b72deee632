"""``mxu_roofline_share`` counts the time of every op that runs a matrix
product, whichever implements it: XLA's convolution and dot fusions and the
Pallas kernels whose body holds a product (told from the compiled program's
text, ``harness/trace.py::mosaic_kernels``); and the float32 reference takes
the program's state as an argument, so one program serves every seed."""

import os

import numpy as np
import pytest

from benchmarks.harness import manifest
from benchmarks.harness import trace as T

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(T.__file__))))
TESTDATA = os.path.join(os.path.dirname(T.__file__), "testdata")
# a described v5e's compile of two tiny kernels, ``tiny_product`` (a
# ``jnp.dot`` of two bf16 [128, 128] blocks) and ``tiny_scale`` (x * 2 + 1 of
# its output): ``jax.jit(step).lower(...).compile().as_text()`` with the
# operands on ``get_topology_desc("tpu", "v5e:2x2").devices[0]``
TWO_KERNELS = os.path.join(TESTDATA, "v5e_two_kernels.hlo.txt")


def _text():
    with open(TWO_KERNELS, encoding="utf-8") as f:
        return f.read()


def _event(op, target="tpu_custom_call"):
    return (f'%{op} = f32[128,128]{{1,0:T(8,128)}} custom-call(f32[128,128]{{1,0:T(8,128)}} %x), '
            f'custom_call_target="{target}"')


# -- telling a kernel that holds a matrix product from one that does not -------------------

def test_a_v5e_program_text_says_which_kernel_holds_a_matrix_product():
    text = _text()
    assert text.count(T.MOSAIC_TARGET) == 2
    assert T.mosaic_kernels(text) == {"tiny_product.1": True, "tiny_scale.1": False}
    assert T.matmul_computations(text) == ["tiny_product.1"]
    matmuls = T.matmul_computations(text)
    assert T.op_kind(_event("tiny_product.1"), matmuls) == "matmul"
    assert T.op_kind(_event("tiny_scale.1"), matmuls) == "other"
    assert T.op_kind(_event("tiny_product.1"), None) == "other"  # not known: not counted
    # XLA's own custom calls are no kernel, and a fusion is never taken by its op's name
    assert T.op_kind(_event("custom-call.9", "AllocateBuffer"), matmuls) == "other"
    fusion = "%tiny_product.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%fc.3"
    assert T.op_kind(fusion, matmuls) == "other"
    assert T.kernel_stem(_event("tiny_product.1")) == "tiny_product"
    assert T.kernel_stem(fusion) is None


@pytest.mark.parametrize("spoil", ["no_body", "not_base64", "not_bytecode"])
def test_a_kernel_whose_body_cannot_be_read_is_counted(spoil):
    text = _text()
    line = next(ln for ln in text.splitlines() if ln.lstrip().startswith("ROOT %tiny_scale.1"))
    body = T.MOSAIC_BODY.search(line).group(0)
    spoiled = {"no_body": '"other":""', "not_base64": '"body":"@@@"',
               "not_bytecode": '"body":"eJzLSM3JyVcozy/KSQEAGgQEXQ=="'}[spoil]
    text = text.replace(line, line.replace(body, spoiled))
    assert T.mosaic_kernels(text) == {"tiny_product.1": True, "tiny_scale.1": None}
    assert T.matmul_computations(text) == ["tiny_product.1", "tiny_scale.1"]


def _kernel_programs():
    """Each kernel module of the tree, forward and backward where it has
    both, at a small size, as a function of its ``place``d operands."""
    import jax
    import jax.numpy as jnp

    bf16, f32 = jnp.bfloat16, jnp.float32

    def short_attention(place):
        from tpu_dist.ops import short_attention as K

        loss = lambda x: K.short_attention(x, 8, interpret=False).astype(f32).sum()  # noqa: E731
        return jax.value_and_grad(loss), (place((2, 64, 3 * 8 * 32)),)

    def flash_attention(place):
        from tpu_dist.ops.flash_attention import flash_attention

        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True, interpret=False).astype(f32).sum()

        kv = place((1, 1024, 1, 128))
        return jax.value_and_grad(loss, argnums=(0, 1, 2)), (place((1, 1024, 2, 128)), kv, kv)

    def ssm_scan(place):
        from tpu_dist.ops import ssm_scan as S

        b, t, h, p, g, n = 1, 256, 2, 64, 1, 128

        def loss(x, dt, a, bb, cc):
            return S.ssm_scan(x, dt, a, bb, cc, 128, interpret=False).astype(f32).sum()

        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)), (
            place((b, t, h, p)), place((b, t, h), f32), place((h,), f32),
            place((b, t, g, n)), place((b, t, g, n)))

    def grouped_matmul(place):
        from tpu_dist.ops import grouped_matmul as G

        def both(x, w, dy, te, n_live):
            return (G.gmm(x, w, te, n_live, interpret=False),
                    G.tgmm(x, dy, te, n_live, 2, bf16, interpret=False))

        return both, (place((2, 512, 256)), place((2, 256, 256)), place((2, 512, 256)),
                      place((2,), jnp.int32), place((), jnp.int32))

    def expert_combine(place):
        from tpu_dist.ops import expert_combine as C

        def build(src, token, scale, runs, over):
            return C.tokens_from_runs(src, token, scale, runs, over, C.TOKEN_BLOCK, bf16,
                                      interpret=False)

        return build, (place((512, 256)), place((512,), jnp.int32), place((512,), f32),
                       place((1, 2, 2), jnp.int32), place((), jnp.int32))

    def causal_conv1d(place):
        from tpu_dist.ops import causal_conv1d as C

        def loss(x, w, b):
            ys = C.causal_conv1d(x, w, b, activation="silu", interpret=False)
            return sum(y.astype(f32).sum() for y in ys)

        return jax.value_and_grad(loss, argnums=(0, 1, 2)), (
            place((1, 256, 256)), place((4, 256)), place((256,)))

    def fused_sgd(place):
        from tpu_dist.ops import fused_sgd as F

        leaf = place((256, 256), f32)
        return (lambda p, g, b: F.fused_sgd_leaf(p, g, b, 0.1, interpret=False)), (leaf, leaf, leaf)

    return {f.__name__: f for f in (short_attention, flash_attention, ssm_scan, grouped_matmul,
                                    expert_combine, causal_conv1d, fused_sgd)}


# kernel module -> (its ops' stem in a described v5e's compile, whether it counts), an
# op each. The flash pair's ops carry the names of the functions around the call
# (here ``_fwd`` and ``_bwd_pallas``, one op for dK/dV and one for dQ; in the token
# cells' steps ``_fwd.1``, ``_bwd_pallas.2``, ``_bwd_pallas.3``)
CLASSIFIED = {
    "short_attention": [("short_attn_bwd", True), ("short_attn_fwd", True)],
    "flash_attention": [("jvp_jit__fwd__", True), ("transpose_jvp_jit__bwd_pallas___", True),
                        ("transpose_jvp_jit__bwd_pallas___", True)],
    "ssm_scan": [("ssm_scan_bwd", True), ("ssm_scan_fwd", True), ("ssm_scan_states", True)],
    "grouped_matmul": [("moe_gmm", True), ("moe_tgmm", True)],
    "expert_combine": [("moe_combine", True)],  # its 0/1 product of a chunk runs on the MXU
    "causal_conv1d": [("causal_conv1d_bwd", False), ("causal_conv1d_fwd", False)],
    "fused_sgd": [("_lambda_", False)],
}


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


@pytest.mark.parametrize("kernel", sorted(CLASSIFIED))
def test_every_kernel_of_the_tree_is_classified_in_a_v5e_compile(one_chip, kernel):
    """Every kernel that holds a matrix product counts, and the depthwise
    convolution's pair and the fused SGD update, which hold none, do not."""
    import jax

    place = lambda shape, dtype=None: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype or jax.numpy.bfloat16, sharding=one_chip)
    fn, args = _kernel_programs()[kernel](place)
    seen = T.mosaic_kernels(jax.jit(fn).lower(*args).compile().as_text())
    assert sorted((name.split(".")[0], holds) for name, holds in seen.items()) == CLASSIFIED[kernel]


# -- the reader on a trace with half of the operations inside a kernel ----------------------

MS = 1e6


def _trace():
    """One chip, 10 ms: a dot fusion (``fc.1``) 4 ms, the product kernel 4 ms,
    the scale kernel 1 ms, an elementwise fusion 0.5 ms, XLA's own custom call
    0.5 ms."""
    ops = [["%fusion.1 = bf16[128,128]{1,0} fusion(bf16[128,128]{1,0} %a), kind=kOutput, calls=%fc.1",
            0.0, 4 * MS],
           [_event("tiny_product.1"), 4 * MS, 4 * MS],
           [_event("tiny_scale.1"), 8 * MS, 1 * MS],
           ["%fusion.2 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%fc.2", 9 * MS, 0.5 * MS],
           [_event("custom-call.9", "AllocateBuffer"), 9.5 * MS, 0.5 * MS]]
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_step(1)", 0.0, 10 * MS]]}]}]}


class _Cell:
    batch_per_chip = 4


def _read(matmuls, said):
    from benchmarks.layer_metrics import mxu_roofline_share as reader

    # 2 steps of 4 samples, 0.75 GFLOP a sample: 6 GFLOP, 6 ms at 1 TFLOP/s;
    # half of it the dot fusion's (3 GFLOP in 4 ms), half the kernel's (the same)
    window = {"trace": T.reduce_trace(_trace(), 1, matmuls), "cell": _Cell(),
              "peaks": {"bf16_flops_per_s": 1e12}, "flops_per_sample": 0.75e9,
              "traced_epoch": {"steps": 2}, "say": said.append}
    return window["trace"], reader.read(window)


def test_the_reader_counts_a_kernels_matrix_products_and_reads_the_hand_count():
    said = []
    matmuls = ["fc.1"] + T.matmul_computations(_text())
    reduced, value = _read(matmuls, said)
    assert reduced["chip0_matmul_s"] == pytest.approx(0.008)
    assert reduced["chip0_matmul_split_s"] == {
        "xla": pytest.approx(0.004), "kernels": {"tiny_product": pytest.approx(0.004)},
        "other_calls": {"tiny_scale": pytest.approx(0.001), "custom-call": pytest.approx(0.0005)}}
    assert value == pytest.approx(75.0) and value <= 100
    assert len(said) == 1
    assert "XLA's convolution and dot ops 0.0040 s (40.0%)" in said[0]
    assert "Pallas kernels 0.0040 s (40.0%): tiny_product 0.0040 s (40.0%)" in said[0]
    assert "not counted: tiny_scale 0.0010 s (10.0%), custom-call 0.0005 s (5.0%)" in said[0]


def test_the_old_denominator_reads_over_100_on_the_same_trace():
    """The fault this reader had: the dot fusions' time alone against all the
    operations, 6 ms at the peak over 4 ms."""
    reduced, value = _read(["fc.1"], [])
    assert reduced["chip0_matmul_split_s"]["kernels"] == {}
    assert value == pytest.approx(150.0)


def test_the_reader_reports_nothing_where_no_op_holds_a_product():
    assert _read([], [])[1] is None
    assert _read(None, [])[1] is None


# -- the reference takes the program's state as an argument -----------------------------------

def _tiny(name):
    """(reference file, arch with a router bias a seed would leave, params, tokens) at the
    tiny preset of a token configuration."""
    import jax

    from tests.helpers import hybrid_arch, lfm2_arch
    from tpu_dist.nn.nemotron_h import lfm2_moe_tiny, nemotron_h_tiny

    m, arch_of = {"nemotron_h": (nemotron_h_tiny(), hybrid_arch),
                  "lfm2_moe": (lfm2_moe_tiny(), lfm2_arch)}[name]
    model = manifest.load_module(REPO, "models", name)
    params, _ = m.init(jax.random.PRNGKey(0))
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, m.seq_len), 0, m.vocab_size))

    def arch(seed):
        bias = np.random.default_rng(seed).normal(0, 0.5, (m.pattern.count("E"), m.n_experts))
        return arch_of(m, bias.astype(np.float32))

    return model, arch, params, tokens


class _Closed:
    """A reference file whose loss closes over the bias, as the harness's
    reference did: the bias a constant of the program."""

    def __init__(self, model, bias):
        self.WHOLE_BATCH, self.INPUT_DTYPE = model.WHOLE_BATCH, model.INPUT_DTYPE
        self.loss_sum = lambda arch, p, x, y: model.loss_sum({**arch, "router_bias": bias}, p, x, y)


@pytest.mark.parametrize("name", ["nemotron_h", "lfm2_moe"])
def test_the_bias_as_an_argument_gives_the_closed_over_reference_bit_for_bit(name):
    import jax

    from benchmarks.harness import reference

    model, arch, params, tokens = _tiny(name)
    a = arch(3)
    dev = jax.devices()[0]
    loss, grads = reference.reference_loss_and_grads(model, a, params, tokens, tokens, 1, dev)
    sizes = {k: v for k, v in a.items() if k != "router_bias"}
    closed_loss, closed_grads = reference.reference_loss_and_grads(
        _Closed(model, a["router_bias"]), sizes, params, tokens, tokens, 1, dev)
    assert loss == closed_loss
    for g, c in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(closed_grads)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(c))
    # the bias is in the computation: another seed's routes other tokens
    assert reference.reference_loss_and_grads(model, arch(4), params, tokens, tokens, 1, dev)[0] != loss


@pytest.mark.parametrize("name", ["nemotron_h", "lfm2_moe"])
def test_one_lowering_serves_two_seeds_biases(name):
    import jax

    from benchmarks.harness import reference

    model, arch, params, tokens = _tiny(name)
    p32 = jax.tree_util.tree_map(lambda t: np.asarray(t, np.float32), params)

    def text(a, m=model):
        jitted, state = reference.reference_program(m, a, 2, 1)
        assert set(state) == ({"router_bias"} if "router_bias" in a else set())
        with jax.default_matmul_precision("highest"):
            return jitted.lower(p32, state, tokens, tokens).as_text()

    assert text(arch(3)) == text(arch(4))
    # closed over, each seed's bias is a constant of its own program
    closed = lambda seed: text(  # noqa: E731
        {k: v for k, v in arch(seed).items() if k != "router_bias"},
        _Closed(model, arch(seed)["router_bias"]))
    assert closed(3) != closed(4)
