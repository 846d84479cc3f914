"""Per-assigned-architecture smoke tests: a REDUCED variant of the same family
(2 layers, d_model <= 512, <= 4 experts) runs one forward/train step on CPU;
output shapes and finiteness asserted. Decode families also run one
serve_step against a fresh cache."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import FLConfig
from repro.configs.registry import ARCHS
from repro.core import optimizer
from repro.models import get_model

B, S = 2, 64


def _batch(c, key):
    if c.family == "mlp":
        return {"features": jax.random.normal(key, (B, c.d_model)),
                "labels_onehot": jax.nn.one_hot(jnp.array([1, 2]), c.vocab_size)}
    if c.family == "vlm":
        st = S - c.num_prefix_tokens
        return {"tokens": jnp.ones((B, st), jnp.int32),
                "targets": jnp.ones((B, st), jnp.int32),
                "prefix_embeddings": jax.random.normal(
                    key, (B, c.num_prefix_tokens, c.d_model)).astype(c.dtype)}
    if c.family == "audio":
        return {"frame_embeddings": jax.random.normal(key, (B, S, c.d_model)).astype(c.dtype),
                "tokens": jnp.ones((B, S // 4), jnp.int32),
                "targets": jnp.ones((B, S // 4), jnp.int32)}
    return {"tokens": jnp.ones((B, S), jnp.int32),
            "targets": jnp.ones((B, S), jnp.int32)}


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_forward_and_train_step(arch):
    c = ARCHS[arch].smoke()
    model = get_model(c)
    key = jax.random.PRNGKey(0)
    params = model.init(key, c)
    batch = _batch(c, key)

    loss = model.loss_fn(params, batch, c)
    assert loss.shape == () and bool(jnp.isfinite(loss)), f"{arch}: bad loss {loss}"

    # one SSCA train step: the loss's gradient through optimizer.ssca_step
    fl = FLConfig(tau=0.2, l2_lambda=1e-5)

    @jax.jit
    def step(state, batch):
        loss, grads = jax.value_and_grad(model.loss_fn)(state.params, batch, c)
        return optimizer.ssca_step(state, grads, fl), loss

    state, loss = step(optimizer.ssca_init(params), batch)
    assert bool(jnp.isfinite(loss))
    for leaf in jax.tree.leaves(state.params):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32)))), f"{arch}: NaN params"
    # params actually moved
    moved = any(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))) > 0
                for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(params)))
    assert moved, f"{arch}: train step was a no-op"


@pytest.mark.parametrize("arch", [a for a in sorted(ARCHS)
                                  if ARCHS[a].family != "mlp"])
def test_smoke_decode_step(arch):
    c = ARCHS[arch].smoke()
    model = get_model(c)
    assert model.has_decode
    key = jax.random.PRNGKey(0)
    params = model.init(key, c)
    cache = model.init_cache(c, B, S)
    tok = jnp.ones((B, 1), jnp.int32)
    logits, cache2 = model.decode_step(params, cache, tok, jnp.int32(0), c)
    assert logits.shape[0] == B and logits.shape[-1] == c.vocab_size
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "zamba2-1.2b", "xlstm-1.3b",
                                  "glm4-9b-swa", "seamless-m4t-medium"])
def test_prefill_then_decode_consistency(arch):
    """decode_step after prefill must reproduce the full-forward logits of
    the extended sequence (KV-cache/SSM-state correctness)."""
    c = ARCHS[arch].smoke()
    model = get_model(c)
    key = jax.random.PRNGKey(1)
    params = model.init(key, c)
    s = 32
    toks = jax.random.randint(key, (B, s + 1), 0, c.vocab_size)
    batch = {"tokens": toks[:, :s]}
    if c.family == "audio":
        batch["frame_embeddings"] = jax.random.normal(
            jax.random.fold_in(key, 2), (B, 4 * s, c.d_model)).astype(c.dtype)

    logits_p, cache = model.prefill(params, batch, c)

    # full forward over s+1 tokens: compare last-position logits
    from repro.launch.serve import grow_cache
    cache = grow_cache(cache, 4)
    pos = jnp.asarray(s, jnp.int32)
    logits_d, _ = model.decode_step(params, cache, toks[:, s:s + 1], pos, c)

    batch2 = dict(batch, tokens=toks[:, :s + 1])
    logits_f, _ = model.prefill(params, batch2, c)

    import numpy as np
    np.testing.assert_allclose(
        np.asarray(logits_d[:, -1, :], np.float32),
        np.asarray(logits_f[:, -1, :], np.float32), rtol=5e-2, atol=5e-2)
