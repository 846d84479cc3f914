"""Main-path programs compiled for a described TPU v5e chip: no chip is
attached, so nothing runs, but the TPU compiler refuses here what it would
refuse on the chip (a Mosaic lowering it lacks, a block that breaks the
(8, 128) tiling rule, a program that does not fit the chip's memory).

The topology is described only inside the module-scoped fixture below:
only one process at a time may load the TPU library, and the test workers
all import this file, so describing it at import time would make the
workers disagree on which tests exist. Every test here uses the fixture,
and they all stay in this one file, so one worker loads the library.
"""
import dataclasses
import importlib.util
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.comm.codecs import make_codec, tree_flat_dim
from repro.configs import FLConfig
from repro.core import algorithms, optimizer, rounds
from repro.data.synthetic import VirtualTokenData
from repro.models import get_model, mlp, transformer

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
            try:
                desc = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def test_quantizer_compiles_at_lm_flat_dim(one_chip):
    """The fused quantizer over a 4-layer qwen2.5-3b's flat gradient (the
    train loop's ``--codec int8 --codec-impl pallas`` upload)."""
    cfg = dataclasses.replace(chip_smoke.lm_config(), n_layers=4)
    model = get_model(cfg)
    p = tree_flat_dim(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), cfg)))
    codec = make_codec("int8", impl="pallas")
    x, key = _on(one_chip, (jax.ShapeDtypeStruct((p,), jnp.float32),
                            jax.ShapeDtypeStruct((2,), jnp.uint32)))
    compiled = jax.jit(codec.roundtrip).lower(x, key).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_quantizer_compiles_under_vmap_at_cohort_dim(one_chip):
    """One quantizer call per client, vmapped over a 256-client cohort of
    cohort_train_loop's default MLP (32 features, 16 hidden, 4 classes):
    the vmapped scales block must keep to the tiling rule."""
    p = tree_flat_dim(jax.eval_shape(
        lambda: mlp.init(jax.random.PRNGKey(0), 32, 16, 4)))
    codec = make_codec("int8", impl="pallas")
    x, keys = _on(one_chip, (jax.ShapeDtypeStruct((256, p), jnp.float32),
                             jax.ShapeDtypeStruct((256, 2), jnp.uint32)))
    compiled = jax.jit(jax.vmap(codec.roundtrip)).lower(x, keys).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_depth_cut_lm_scan_step_fits_one_chip(one_chip):
    """chip_smoke's LM job as ``train_loop`` runs it: Algorithm 1 through
    the cohort engine with ``per_sequence_loss`` over a ``VirtualTokenData``
    population (qwen2.5-3b at published widths, depth cut, bf16 + remat;
    silos, cohort, rows and rounds as run there). Its state is large enough
    that the chip donates it to the K-round program, and that program
    leaves >= 2 GB of the chip's 16 GB free by memory_analysis()."""
    cfg = chip_smoke.lm_config()
    fl = FLConfig(batch_size=chip_smoke.LM_BATCH)
    data = VirtualTokenData(jax.random.PRNGKey(0), chip_smoke.LM_SILOS,
                            chip_smoke.LM_SEQ, cfg.vocab_size)
    step = algorithms.make_algorithm1_step(
        lambda p, x, y: transformer.per_sequence_loss(p, x, y, cfg), data,
        fl, participation=chip_smoke.LM_S, cohort=True)
    params = jax.eval_shape(lambda: transformer.init(jax.random.PRNGKey(0),
                                                     cfg))
    state = _on(one_chip, jax.eval_shape(optimizer.ssca_init, params))
    inputs = _on(one_chip, jax.eval_shape(
        lambda: rounds.make_inputs(fl, 1, chip_smoke.LM_ROUNDS,
                                   jax.random.PRNGKey(0))))
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    assert nbytes > rounds.DONATE_SHARE * V5E_HBM_BYTES, nbytes
    m = rounds._scan_jit_donated(step).lower(
        state, inputs).compile().memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total + 2e9 <= V5E_HBM_BYTES, total


def test_latent_attention_kernels_compile_at_cell_size(one_chip):
    """The grad of causal latent attention as the LM cell runs it (16
    heads, 4 096 tokens, q.k 192 / v 128, float32 inputs, one bf16 pass)
    takes the three flash kernels and no scan over key blocks."""
    from repro.models import layers as L

    b, s, h = 1, 4096, 16
    q, k, v = _on(one_chip, (jax.ShapeDtypeStruct((b, s, h, 192), jnp.float32),
                             jax.ShapeDtypeStruct((b, s, h, 192), jnp.float32),
                             jax.ShapeDtypeStruct((b, s, h, 128), jnp.float32)))
    pos = jnp.arange(s, dtype=jnp.int32)[None]

    def loss(q, k, v):
        return jnp.sum(L.chunked_attention(q, k, v, pos, pos, causal=True))

    with jax.default_matmul_precision("bfloat16"):
        hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, k, v).compile().as_text()
    assert sorted(re.findall(r"%(flash_\w+)\.?\d* = ", hlo)) == [
        "flash_dkv", "flash_dq", "flash_fwd"]
    assert " while(" not in hlo


EF_CLIENTS = 203


def _cohort_int8_step_hlo(sharding, topology=None):
    """Compiled HLO text of the cohort engine's int8 + EF step (I = 203,
    S = 32, a 32-16-4 MLP), scanned over 2 rounds, with every input on
    ``sharding``; and the store's flat dim P."""
    from repro.comm.error_feedback import CommCarry, ef_store_init
    from repro.core import algorithms
    from repro.data.synthetic import VirtualFedData

    data = VirtualFedData(jax.random.PRNGKey(0), EF_CLIENTS,
                          num_features=32, num_classes=4)
    fl = FLConfig(batch_size=4)
    step = algorithms.make_algorithm1_step(
        mlp.per_sample_loss, data, fl, participation=32,
        codec=make_codec("int8"), cohort=True, topology=topology)
    params = jax.eval_shape(lambda: mlp.init(jax.random.PRNGKey(1), 32, 16, 4))
    dim = tree_flat_dim(params)
    state = _on(sharding, jax.eval_shape(
        lambda p: CommCarry(opt=optimizer.ssca_init(p),
                            ef=ef_store_init(EF_CLIENTS, dim)), params))
    inputs = _on(sharding, jax.eval_shape(
        lambda: rounds.make_inputs(fl, 1, 2, jax.random.PRNGKey(2))))
    return rounds._scan_jit(step).lower(state, inputs).compile().as_text(), dim


def test_cohort_int8_step_copies_ef_rows_in_place(one_chip):
    """Compiled for the chip, the cohort int8 + EF step reads and writes
    the EF store through the row kernels under their scopes (`ef_norm`'s
    gather under `round-metrics`), and the (I, P) store is never copied
    inside the scanned loop: the scatter writes the carry's buffer in
    place."""
    hlo, dim = _cohort_int8_step_hlo(one_chip)
    kernels = re.findall(r"%(ef_rows_\w+)\.\d+ = .*?op_name=\"([^\"]*)\"", hlo)
    assert sorted((k, re.search(r"round/([\w-]+)/", n).group(1))
                  for k, n in kernels) == [
        ("ef_rows_gather", "ef-gather"), ("ef_rows_gather", "round-metrics"),
        ("ef_rows_scatter", "ef-scatter")]
    # no pass over the whole store (a copy, or a fusion that makes one)
    # in any loop body
    store = re.compile(rf"= f32\[{EF_CLIENTS},{dim}\]\S* (copy|copy-start|"
                       r"fusion)\(")
    bodies = set(re.findall(r"\bbody=%?([\w.\-]+)", hlo))
    computation = None
    for line in hlo.splitlines():
        if line and not line[0].isspace():
            computation = line.removeprefix("ENTRY ").split()[0].lstrip("%")
        elif computation in bodies:
            assert not store.search(line), line


def test_sharded_cohort_int8_step_compiles_for_four_chips(topo):
    """Under `ShardedTopology` the EF store stays replicated and XLA cannot
    partition a Mosaic kernel: the row kernels run on each chip's replica
    (inside a shard_map over the topology's mesh), and the step compiles
    for a 2x2 v5e."""
    from repro.core.topology import ShardedTopology

    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    hlo, _ = _cohort_int8_step_hlo(NamedSharding(mesh, PartitionSpec()),
                                   ShardedTopology(mesh))
    assert sorted(re.findall(r"%(ef_rows_\w+)\.\d+ = ", hlo)) == [
        "ef_rows_gather", "ef_rows_gather", "ef_rows_scatter"]
