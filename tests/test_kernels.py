"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs the
pure-jnp oracles in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.chain_norm import chain_norm
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gconv_matmul import gconv_matmul
from repro.kernels.gconv_spatial import gconv_spatial


def rnd(key, shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# grouped matmul
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("g,m,k,n", [
    (1, 8, 16, 8), (4, 32, 64, 16), (2, 17, 33, 9),   # ragged shapes
    (8, 128, 128, 128),                               # tile-aligned
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gconv_matmul_sweep(g, m, k, n, dtype):
    x, w = rnd(0, (g, m, k), dtype), rnd(1, (g, k, n), dtype)
    got = gconv_matmul(x, w, block_m=32, block_n=32, block_k=32,
                       interpret=True)
    want = ref.gconv_matmul_ref(x, w)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("post,scale", [("relu", 1.0), ("silu", 0.5),
                                        ("exp", 0.1)])
def test_gconv_matmul_epilogue(post, scale):
    x, w = rnd(2, (2, 16, 24), jnp.float32), rnd(3, (2, 24, 8), jnp.float32)
    got = gconv_matmul(x, w, post=post, scale=scale, block_m=8, block_n=8,
                       block_k=8, interpret=True)
    want = ref.gconv_matmul_ref(x, w, post=post, scale=scale)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_gconv_matmul_fused_operand_sequences():
    """§4.3-fused pre/post sequences with tensor operands ride in-register:
    prologue (per-K gamma, per-M stat, const) + epilogue (per-N bias, relu,
    const scale) against the jnp composition."""
    x, w = rnd(30, (2, 17, 33), jnp.float32), rnd(31, (2, 33, 9), jnp.float32)
    gamma = rnd(32, (1, 1, 33), jnp.float32)
    ms = rnd(33, (2, 17, 1), jnp.float32)
    bias = rnd(34, (1, 1, 9), jnp.float32)
    got = gconv_matmul(
        x, w,
        prologue=(("mul", None, 0), ("add", None, 1),
                  ("add_const", 0.3, None)),
        epilogue=(("add", None, 2), ("relu", None, None),
                  ("scale", 2.0, None)),
        operands=(gamma, ms, bias),
        block_m=8, block_n=8, block_k=8, interpret=True)
    want = jnp.einsum("gmk,gkn->gmn", x * gamma + ms + 0.3, w)
    want = jnp.maximum(want + bias, 0) * 2.0
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_gconv_matmul_grouped_epilogue_operand():
    """Per-group epilogue operand (G, 1, N) — the MoE bias layout."""
    x, w = rnd(35, (3, 8, 16), jnp.float32), rnd(36, (3, 16, 8), jnp.float32)
    bias = rnd(37, (3, 1, 8), jnp.float32)
    got = gconv_matmul(x, w, epilogue=(("add", None, 0),), operands=(bias,),
                       block_m=8, block_n=8, block_k=8, interpret=True)
    want = jnp.einsum("gmk,gkn->gmn", x, w) + bias
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# spatial conv
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,h,c,o,kk,stride,pad", [
    (1, 8, 8, 8, 3, 1, 1), (2, 12, 4, 8, 3, 2, 1), (1, 11, 3, 5, 5, 2, 2),
    (2, 9, 16, 32, 1, 1, 0),
    # output widths off the 8-row sublane tile (the block computes them
    # padded to 8 and 16), one O over 128 that is not a multiple of it,
    # and an aligned control
    (2, 7, 16, 208, 3, 1, 1), (1, 14, 8, 32, 3, 1, 1),
    (1, 7, 8, 16, 5, 1, 2), (2, 14, 8, 16, 5, 1, 2),
    (1, 16, 8, 16, 3, 1, 1),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gconv_spatial_sweep(b, h, c, o, kk, stride, pad, dtype):
    x = rnd(4, (b, h, h, c), dtype)
    w = rnd(5, (kk, kk, c, o), dtype)
    got = gconv_spatial(x, w, stride=stride, pad=pad, interpret=True)
    want = ref.gconv_spatial_ref(x, w, stride=stride, pad=pad)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


# (B, H, G, C/G, O/G, K, pad, block_o): two groups of channels that fill
# no lane tile (AlexNet's 48 and 192, scaled down), output widths 7 and
# 13, pads 1 and 2; O/G 12 at block_o 8 takes a whole-group block, O/G 16
# two blocks of 8; and three groups
@pytest.mark.parametrize("b,h,g,cg,og,kk,pad,block_o", [
    (2, 7, 2, 6, 12, 3, 1, 128), (1, 13, 2, 12, 20, 3, 1, 128),
    (2, 9, 2, 6, 16, 5, 2, 8), (1, 13, 2, 6, 12, 5, 2, 8),
    (1, 7, 3, 4, 8, 3, 1, 128),
])
def test_gconv_spatial_grouped_matches_feature_group_count(b, h, g, cg, og,
                                                           kk, pad, block_o):
    x = rnd(40, (b, h, h, g * cg), jnp.float32)
    w = rnd(41, (g, kk, kk, cg, og), jnp.float32)
    got = gconv_spatial(x, w, groups=g, pad=pad, block_o=block_o,
                        interpret=True)
    # (G, KH, KW, C/G, O/G) -> HWIO with group-major O
    w_lax = jnp.transpose(w, (1, 2, 3, 0, 4)).reshape(kk, kk, cg, g * og)
    want = jax.lax.conv_general_dilated(
        x, w_lax, (1, 1), [(pad, pad)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=g,
        precision=jax.lax.Precision.HIGHEST)
    assert got.shape == want.shape == (b, h, h, g * og)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("groups,w_shape", [(2, (3, 3, 4, 8)),
                                            (1, (2, 3, 3, 4, 8)),
                                            (3, (2, 3, 3, 4, 8))])
def test_gconv_spatial_refuses_weights_that_do_not_match_groups(groups,
                                                                w_shape):
    x = rnd(42, (1, 7, 7, 8), jnp.float32)
    with pytest.raises(ValueError, match="groups="):
        gconv_spatial(x, rnd(43, w_shape, jnp.float32), groups=groups,
                      interpret=True)


# ---------------------------------------------------------------------------
# fused norm
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("t,c", [(16, 64), (33, 40), (256, 128)])
@pytest.mark.parametrize("mode", ["rms", "layer"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_chain_norm_sweep(t, c, mode, dtype):
    x = rnd(6, (t, c), dtype)
    g = rnd(7, (c,), dtype) * 0.1 + 1.0
    b = rnd(8, (c,), dtype) * 0.1 if mode == "layer" else None
    got = chain_norm(x, g, b, mode=mode, block_t=32, interpret=True)
    want = ref.chain_norm_ref(x, g, b, mode=mode)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("h,tq,tk,d,causal", [
    (2, 32, 32, 16, True), (2, 32, 32, 16, False),
    (1, 17, 40, 8, False), (1, 40, 40, 8, True),
    (4, 64, 64, 32, True),
])
def test_flash_attention_sweep(h, tq, tk, d, causal):
    q, k, v = (rnd(i, (h, tq if i == 9 else tk, d), jnp.float32)
               for i in (9, 10, 11))
    got = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_attention_decode_offset():
    """Decode: 1 query attending a long KV prefix with q_offset."""
    h, tk, d = 2, 48, 16
    q = rnd(12, (h, 1, d), jnp.float32)
    k = rnd(13, (h, tk, d), jnp.float32)
    v = rnd(14, (h, tk, d), jnp.float32)
    got = flash_attention(q, k, v, causal=True, q_offset=tk - 1,
                          block_q=8, block_k=16, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, q_offset=tk - 1)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    q, k, v = (rnd(i, (2, 32, 32), jnp.bfloat16) for i in (15, 16, 17))
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# kernels vs the GCONV chain oracle (the end-to-end equivalence the paper
# needs: mapped/fused execution == chain semantics)
# ---------------------------------------------------------------------------
def test_gconv_matmul_equals_chain_interpreter():
    from repro.core import layers as L
    from repro.core.chain import Chain
    from repro.core.interpreter import ChainExecutor

    B, Cin, Cout = 8, 24, 16
    chain = Chain("fc")
    xin = chain.add_input("x", (B, Cin))
    y = L.fc(chain, xin, out_f=Cout, bias=False)
    ex = ChainExecutor(chain)
    params = ex.init_params(jax.random.PRNGKey(0))
    xv = rnd(20, (B, Cin), jnp.float32)
    chain_out = ex({"x": xv}, params)[y]
    w = params[f"{y}.w"].reshape(Cout, Cin).T[None]     # (1, K, N)
    kern_out = gconv_matmul(xv[None], w, block_m=8, block_n=8, block_k=8,
                            interpret=True)[0]
    np.testing.assert_allclose(kern_out, chain_out, rtol=2e-5, atol=2e-5)


def test_flash_equals_attention_chain_segment():
    from repro.core import layers as L
    from repro.core.chain import Chain
    from repro.core.interpreter import ChainExecutor

    B, H, T, D = 1, 2, 16, 8
    chain = Chain("attn")
    qi = chain.add_input("q", (B, H, T, 1, D))
    ki = chain.add_input("k", (B, H, 1, T, D))
    vi = chain.add_input("v", (B, H, 1, T, D))
    s = L.attention_scores(chain, qi, ki, scale=D ** -0.5)
    pr = L.softmax(chain, s, axis=3)
    o = L.attention_values(chain, pr, vi)
    ex = ChainExecutor(chain)
    q = rnd(21, (H, T, D), jnp.float32)
    k = rnd(22, (H, T, D), jnp.float32)
    v = rnd(23, (H, T, D), jnp.float32)
    chain_out = ex({"q": q[None, :, :, None, :], "k": k[None, :, None],
                    "v": v[None, :, None]}, {})[o][0, :, :, 0, :]
    kern_out = flash_attention(q, k, v, causal=False, block_q=8, block_k=8,
                               interpret=True)
    np.testing.assert_allclose(kern_out, chain_out, rtol=2e-4, atol=2e-4)
