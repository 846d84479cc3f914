"""Checkpointing, data pipeline, HLO cost parser, and roofline helpers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.data.synthetic import (classification_dataset, make_batch_iterator,
                                  token_dataset)
from repro.roofline import hlo_cost
from repro.roofline.analysis import HW, jit_cost_summary, roofline_terms


def test_checkpoint_roundtrip(tmp_path):
    key = jax.random.PRNGKey(0)
    tree = {"a": jax.random.normal(key, (7, 3)),
            "b": {"c": jnp.arange(5, dtype=jnp.int32),
                  "d": jnp.float32(2.5)}}
    path = str(tmp_path / "ckpt.msgpack")
    save_checkpoint(path, tree, step=42)
    like = jax.tree.map(jnp.zeros_like, tree)
    restored, step = load_checkpoint(path, like)
    assert step == 42
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_structure_mismatch(tmp_path):
    path = str(tmp_path / "c.msgpack")
    save_checkpoint(path, {"a": jnp.zeros(3)})
    with pytest.raises(ValueError):
        load_checkpoint(path, {"a": jnp.zeros(3), "b": jnp.zeros(2)})


def test_classification_dataset_deterministic():
    key = jax.random.PRNGKey(0)
    (z1, y1, l1), _ = classification_dataset(key, n=100, num_features=8,
                                             num_classes=3, test_n=10)
    (z2, y2, l2), _ = classification_dataset(key, n=100, num_features=8,
                                             num_classes=3, test_n=10)
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z2))
    assert y1.shape == (100, 3)


def test_token_dataset_and_iterator():
    toks = token_dataset(jax.random.PRNGKey(0), vocab_size=64, n_tokens=2000)
    assert toks.shape == (2000,) and int(toks.max()) < 64
    it = make_batch_iterator(toks, batch=4, seq=16, key=jax.random.PRNGKey(1))
    b = next(it)
    assert b["tokens"].shape == (4, 16) and b["targets"].shape == (4, 16)
    np.testing.assert_array_equal(np.asarray(b["tokens"][:, 1:]),
                                  np.asarray(b["targets"][:, :-1]))


def test_hlo_cost_matches_xla_on_loop_free_module():
    def f(x, w1, w2):
        return jnp.sum(jnp.tanh(x @ w1) @ w2)

    args = [jax.ShapeDtypeStruct(s, jnp.float32)
            for s in [(64, 128), (128, 256), (256, 32)]]
    compiled = jax.jit(f).lower(*args).compile()
    got = hlo_cost.analyze(compiled.as_text())
    want_flops = 2 * 64 * 128 * 256 + 2 * 64 * 256 * 32
    assert abs(got["flops"] - want_flops) / want_flops < 1e-6
    # "bytes accessed" may be absent entirely on some backends
    xla_bytes = hlo_cost.xla_cost_analysis(compiled).get("bytes accessed")
    if xla_bytes:
        assert abs(got["bytes"] - xla_bytes) / xla_bytes < 0.2


def test_hlo_cost_scan_multiplier():
    def body(x, w):
        return jnp.tanh(x @ w), None

    def f(x, ws):
        return jax.lax.scan(body, x, ws)[0].sum()

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((12, 64, 64), jnp.float32)
    compiled = jax.jit(f).lower(x, ws).compile()
    got = hlo_cost.analyze(compiled.as_text())
    want = 12 * 2 * 64**3
    assert abs(got["flops"] - want) / want < 1e-6


def test_jit_cost_summary_reports_and_raises():
    """The run manifest's cost probe: a program that compiles is summarized,
    one that does not raises instead of yielding an empty summary."""
    x = jnp.ones((8, 16), jnp.float32)
    got = jit_cost_summary(lambda a: jnp.tanh(a @ a.T), x)
    assert set(got) == {"xla", "flops", "bytes", "collectives"}
    assert got["flops"] >= 2 * 8 * 8 * 16
    with pytest.raises(TypeError):
        jit_cost_summary(lambda a: a @ a, x)     # (8,16) @ (8,16)


def test_roofline_terms_bottleneck():
    t = roofline_terms({"flops": 197e12, "bytes accessed": 1e9}, 0)
    assert t["bottleneck"] == "compute" and abs(t["compute_s"] - 1.0) < 1e-9
    t = roofline_terms({"flops": 1e9, "bytes accessed": 819e9}, 0)
    assert t["bottleneck"] == "memory"
    t = roofline_terms({"flops": 0, "bytes accessed": 0}, 50e9)
    assert t["bottleneck"] == "collective" and abs(t["collective_s"] - 1.0) < 1e-9
