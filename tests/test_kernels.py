"""Per-kernel allclose vs the pure-jnp oracle: shape/dtype sweeps, all in
Pallas interpret mode (the kernel body executes in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssca_update import ssca_update_pallas


@pytest.mark.parametrize("shape", [(4, 128), (3, 7, 256), (1, 1024), (2, 37, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_ref(shape, dtype):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, shape).astype(dtype)
    sc = (jax.random.normal(jax.random.fold_in(key, 1), (shape[-1],)) * 0.1)
    got = rmsnorm_pallas(x, sc, interpret=True, block_rows=16)
    want = ref.rmsnorm_ref(x, sc)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("n", [17, 1000, 4096, 70000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssca_update_matches_ref(n, dtype):
    key = jax.random.PRNGKey(1)
    w = jax.random.normal(key, (n,)).astype(dtype)
    buf = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    g = jax.random.normal(jax.random.fold_in(key, 2), (n,)).astype(dtype)
    rho, gamma, tau, lam = 0.7, 0.25, 0.2, 1e-4
    gw, gb = ssca_update_pallas(w, buf, g, rho, gamma, tau, lam,
                                block=8192, interpret=True)
    ww, wb = ref.ssca_update_ref(w, buf, g, rho, gamma, tau, lam)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(gw, np.float32),
                               np.asarray(ww, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(wb), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,h,kv,sq,sk,d", [
    (1, 4, 4, 128, 128, 64),       # MHA square
    (2, 8, 2, 128, 128, 64),       # GQA
    (1, 8, 1, 64, 256, 128),       # MQA, right-aligned decode-ish window
    (1, 4, 4, 256, 256, 32),
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 96), (False, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(b, h, kv, sq, sk, d, causal, window, dtype):
    key = jax.random.PRNGKey(2)
    q = jax.random.normal(key, (b, h, sq, d)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, kv, sk, d)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, kv, sk, d)).astype(dtype)
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_flash_attention_blocks_fully_masked_rows():
    """Sliding window that masks whole K tiles must not produce NaNs."""
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, 2, 256, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 256, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 256, 32))
    got = flash_attention_pallas(q, k, v, causal=True, window=32,
                                 block_q=64, block_k=64, interpret=True)
    assert bool(jnp.all(jnp.isfinite(got)))
    want = ref.flash_attention_ref(q, k, v, causal=True, window=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def _attention_inputs(key, b, h, sq, sk, dqk, dv, dtype):
    q = jax.random.normal(key, (b, h, sq, dqk)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, h, sk, dqk)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, h, sk, dv)).astype(dtype)
    do = jax.random.normal(jax.random.fold_in(key, 3), (b, h, sq, dv)).astype(dtype)
    return q, k, v, do


def _scan_attention(q, k, v, q_pos, k_pos):
    """``chunked_attention``'s jnp scan on (B, H, S, D) inputs."""
    from repro.models import layers as L
    t = lambda x: x.transpose(0, 2, 1, 3)
    return t(L.chunked_attention(t(q), t(k), t(v), q_pos, k_pos, causal=True,
                                 block=64))


def _rel_err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("dqk,dv", [(192, 128), (64, 64)])
@pytest.mark.parametrize("block", [64, 128])
@pytest.mark.parametrize("dtype,mxu,tol", [
    (jnp.float32, None, 2e-5),                 # float32 products
    (jnp.bfloat16, None, 3e-2),                # bfloat16 inputs
    (jnp.float32, jnp.bfloat16, 2e-2),         # the TPU path: one bf16 pass
], ids=["f32", "bf16", "f32-bf16-products"])
def test_flash_attention_grads_match_references(dqk, dv, block, dtype, mxu,
                                                tol):
    """Causal forward and dq, dk, dv of the kernel (interpret mode) against
    ``chunked_attention``'s jnp scan and ``ref.flash_attention_ref``."""
    s = 256
    q, k, v, do = _attention_inputs(jax.random.PRNGKey(7), 1, 2, s, s, dqk,
                                    dv, dtype)
    pos = jnp.arange(s, dtype=jnp.int32)[None]
    kernel = lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True, block_q=block, block_k=block, mxu_dtype=mxu,
        interpret=True)
    out, vjp = jax.vjp(kernel, q, k, v)
    grads = vjp(do)
    assert out.shape == (1, 2, s, dv) and out.dtype == dtype
    assert [g.dtype for g in grads] == [dtype] * 3
    for reference in (lambda q, k, v: _scan_attention(q, k, v, pos, pos),
                      lambda q, k, v: ref.flash_attention_ref(q, k, v)):
        want, want_vjp = jax.vjp(reference, q, k, v)
        assert _rel_err(out, want) < tol
        for g, w in zip(grads, want_vjp(do)):
            assert _rel_err(g, w) < tol


def test_flash_attention_fully_masked_rows_have_finite_grads():
    """Queries right-aligned past the keys (Sq > Sk, causal): the first
    Sq - Sk rows see no key. They read 0, and every gradient is finite and
    equal to the jnp scan's."""
    sq, sk = 256, 128
    q, k, v, do = _attention_inputs(jax.random.PRNGKey(8), 1, 2, sq, sk, 192,
                                    128, jnp.float32)
    kernel = lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True, block_q=64, block_k=64, interpret=True)
    out, vjp = jax.vjp(kernel, q, k, v)
    grads = vjp(do)
    assert bool(jnp.all(out[:, :, :sq - sk] == 0.0))
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in (out, *grads))
    q_pos = jnp.arange(sq, dtype=jnp.int32)[None] - (sq - sk)
    k_pos = jnp.arange(sk, dtype=jnp.int32)[None]
    want, want_vjp = jax.vjp(
        lambda q, k, v: _scan_attention(q, k, v, q_pos, k_pos), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    for g, w in zip(grads, want_vjp(do)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_mla_attention_dispatch(platform):
    """The grad of a small latent attention, lowered for the TPU on this
    host, runs the three flash kernels, each under the ``mla-attention``
    scope as the benchmark reads op names (a whole ``/`` component, here
    inside a ``client-compute`` scope as in the round engine); lowered for
    the CPU it runs none and keeps the jnp scan."""
    import re
    from repro.configs.moonlight_16b_a3b import CONFIG
    from repro.models import layers as L
    from repro.obs.trace import phase

    cfg = CONFIG.smoke()
    assert cfg.attention_impl == "chunked"
    params = jax.eval_shape(
        lambda: L.mla_init(jax.random.PRNGKey(0), cfg, jnp.float32))
    x = jax.ShapeDtypeStruct((1, 256, cfg.d_model), jnp.float32)
    pos = jnp.arange(256, dtype=jnp.int32)[None]

    def loss(p, x):
        with phase("client-compute"):
            return jnp.sum(L.mla_attention(p, x, pos, cfg) ** 2)

    hlo = jax.jit(jax.grad(loss)).trace(params, x).lower(
        lowering_platforms=(platform,)).as_text(dialect="hlo", debug_info=True)
    calls = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in hlo.splitlines() if "tpu_custom_call" in line]
    if platform == "cpu":
        assert calls == []
        return
    kernels = sorted(re.search(r"/(flash_\w+)/", c).group(1) for c in calls)
    assert kernels == ["flash_dkv", "flash_dq", "flash_fwd"]
    assert all("mla-attention" in c.split("/") for c in calls), calls


def test_ops_dispatch_ref_on_cpu():
    from repro.kernels import ops
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (4, 64))
    sc = jnp.zeros((64,))
    np.testing.assert_allclose(np.asarray(ops.rmsnorm(x, sc)),
                               np.asarray(ref.rmsnorm_ref(x, sc)), rtol=1e-6)


@pytest.mark.parametrize("n", [17, 1000, 4096, 70000])
@pytest.mark.parametrize("qmax", [127, 7])
def test_quantize_kernel_matches_ref(n, qmax):
    """Fused quantize-dequantize kernel == the comm/codecs.py math exactly
    (same PRNG bits in on the portable path -> same wire values out)."""
    from repro.kernels.quantize import stochastic_quantize_pallas
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (n,)) * 3.0
    chunk = 256
    num_chunks = -(-n // chunk)
    bits = jax.random.bits(jax.random.fold_in(key, 1),
                           (num_chunks * chunk,), jnp.uint32)
    v_r, s_r, xh_r = ref.stochastic_quantize_ref(x, bits, qmax, chunk)
    v_p, s_p, xh_p = stochastic_quantize_pallas(x, qmax, chunk, bits=bits,
                                                block_rows=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(v_p), np.asarray(v_r))
    np.testing.assert_array_equal(np.asarray(s_p), np.asarray(s_r))
    np.testing.assert_array_equal(np.asarray(xh_p), np.asarray(xh_r))
    # per-chunk absmax scales are deterministic and exact
    pad = num_chunks * chunk - n
    xc = np.pad(np.asarray(x), (0, pad)).reshape(num_chunks, chunk)
    np.testing.assert_allclose(np.asarray(s_p),
                               np.abs(xc).max(axis=1) / qmax, rtol=1e-6)


def test_quantize_kernel_through_codec_pallas_impl():
    """The codec's impl="pallas" path (interpret mode) is bit-identical to
    impl="ref" — both consume the same jax.random bits."""
    from repro.comm.codecs import StochasticQuantizer
    key = jax.random.PRNGKey(6)
    x = jax.random.normal(key, (3000,))
    r = StochasticQuantizer(bits=8, chunk=256, impl="ref")
    p = StochasticQuantizer(bits=8, chunk=256, impl="pallas", interpret=True)
    enc_r, xh_r = r.roundtrip(x, jax.random.fold_in(key, 1))
    enc_p, xh_p = p.roundtrip(x, jax.random.fold_in(key, 1))
    np.testing.assert_array_equal(np.asarray(enc_p.values),
                                  np.asarray(enc_r.values))
    np.testing.assert_array_equal(np.asarray(enc_p.scales),
                                  np.asarray(enc_r.scales))
    np.testing.assert_array_equal(np.asarray(xh_p), np.asarray(xh_r))
