"""The LM silo cell's correctness check, at a size a CPU holds: the
unbroken run is ``correct``; the program broken underneath, or a planted
fault of the reference put in the program's place, is not; the shape
counts of ``bench/lib/moe_counts.py`` match a hand count.

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_lm_silo.py -q
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.engines import lm_silo
from bench.lib import moe_counts
from bench.lib.common import BENCH, peaks_for

jax.config.update("jax_enable_compilation_cache", False)

CELL = "moonlight-16b-a3b.silo-dense"
FAULTS = ("capacity", "no_shared", "offset", "rope_nope", "half_silos")


def tiny():
    """The cell's files, widths and sizes cut for a CPU."""
    cell, cfg, tr, e2e, _ = run.load_cell(CELL)
    cfg = dict(cfg, hidden_size=64, num_attention_heads=2,
               num_key_value_heads=2, kv_lora_rank=32, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
               moe_intermediate_size=32, router_experts=16, n_routed_experts=4,
               num_experts_per_tok=4, num_hidden_layers=3, vocab_size=256,
               rope_theta=500)        # RoPE turns far within 64 positions
    # limits of the toy size: the cell's are set against bfloat16 products
    # in every layer on the chip, while on the CPU only the grouped expert
    # products are bfloat16, so the program reads 10-100x lower, and the
    # toy's faults are smaller; these sit between the two
    tr = dict(tr, population=4, participation=4, seq_len=64,
              sizes={"n_min": 4, "n_max": 16, "tail": 1.0},
              rounds_per_dispatch=2,
              limits={"loss_gap": 5e-6, "grad_gap": 5e-5, "delta_gap": 2e-3})
    return cfg, tr, e2e, jax.devices()[:cell["chips"]]


def drive(seed=1234):
    cfg, tr, e2e, devices = tiny()
    result, _ = run.run_once(CELL, cfg, tr, e2e, [], seed, 0.2, 0, devices,
                             peaks_for("TPU v5 lite"))
    return result


def test_unbroken_run_is_correct():
    r = drive()
    assert r["correct"], r["checks"]
    assert r["checks"]["moe_dropped"]["value"] == 0


def test_state_unchanged_is_not_correct(monkeypatch):
    from repro.core import optimizer
    monkeypatch.setattr(optimizer, "ssca_step",
                        lambda state, *a, **k: state._replace(t=state.t + 1))
    r = drive()
    assert not r["correct"], r["checks"]


def test_wrong_expert_offset_is_not_correct(monkeypatch):
    """The program computes the next shard's experts with these weights."""
    cut = lm_silo.model_config
    monkeypatch.setattr(lm_silo, "model_config",
                        lambda c: dataclasses.replace(cut(c), expert_shard=1))
    r = drive()
    assert not r["correct"], r["checks"]


def test_missing_shared_expert_is_not_correct(monkeypatch):
    from repro.models import layers
    held = layers.moe_held

    def no_shared(params, x, cfg):
        out, stats = held(params, x, cfg)
        b, s, d = x.shape
        shared = layers.mlp(params["shared"], x.reshape(b * s, d), "swiglu")
        return out - shared.reshape(b, s, d), stats

    monkeypatch.setattr(layers, "moe_held", no_shared)
    r = drive()
    assert not r["correct"], r["checks"]


@pytest.fixture(scope="module")
def engine():
    cfg, tr, _, devices = tiny()
    eng = lm_silo.Engine(cfg, tr, 99, devices, lambda m: None)
    eng.free()
    return eng, eng.reference(), tr["limits"]


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(engine, fault):
    """The reference with a planted fault, put in the program's place,
    fails at least one of the cell's limits."""
    eng, ref, limits = engine
    bad = dict(eng.reference("f32", fault), moe_dropped=0)
    checks = lm_silo.checks(bad, ref, limits)
    assert any(v > lim for _, v, lim, _ in checks), checks


def test_lower_precision_control_is_not_correct(engine):
    """The reference with float8 (e4m3) products fails a limit; with the
    program's bfloat16 products it passes them all."""
    eng, ref, limits = engine
    for prec, fails in (("fp8", True), ("bf16", False)):
        other = dict(eng.reference(prec), moe_dropped=0)
        checks = lm_silo.checks(other, ref, limits)
        assert any(v > lim for _, v, lim, _ in checks) == fails, (prec, checks)


def test_reference_population_makes_the_program_rows():
    from bench.reference.moonlight import Silos
    from repro.data.synthetic import VirtualTokenData
    sizes = {"n_min": 4, "n_max": 16, "tail": 1.0}
    prog = VirtualTokenData(lm_silo.population_key(), 6, 32, 256, **sizes)
    ref = Silos(lm_silo.population_key(), 6, 32, 256, **sizes)
    assert prog.total == ref.total
    ids = jnp.array([0, 3, 5], jnp.int32)
    assert [int(n) for n in prog.counts_for(ids)] == [ref.count(i) for i in (0, 3, 5)]
    toks, tgts = prog.batch_rows(ids, jnp.array([[0], [2], [7]], jnp.int32))
    for j, (i, r) in enumerate(((0, 0), (3, 2), (5, 7))):
        x, y = ref.row(i, r)
        assert (toks[j, 0] == x).all() and (tgts[j, 0] == y).all()


def test_cut_is_the_registry_model():
    """The cell's configuration is the registry's Moonlight at published
    widths with only the listed keys cut."""
    from repro.configs.registry import get_config
    cfg = json.loads((BENCH / "configs" / "moonlight-16b-a3b.json").read_text())
    full = get_config("moonlight-16b-a3b")
    cut = dataclasses.replace(full, n_layers=5, vocab_size=20480,
                              experts_held=8)
    assert lm_silo.model_config(cfg) == cut
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in spec["configs"]}["moonlight-16b-a3b"]
    assert set(entry["reduced"]) == set(cfg["reduced"])


def test_moonlight_counts_match_a_hand_count():
    cfg = json.loads((BENCH / "configs" / "moonlight-16b-a3b.json").read_text())
    p = moe_counts.moonlight_params(cfg)
    # attention: q 2048 x 16 x 192, kv_a 2048 x 576, latent norm 512,
    # kv_b 512 x 16 x 256, o 2048 x 2048
    attn = 2048 * 3072 + 2048 * 576 + 512 + 512 * 4096 + 2048 * 2048
    assert p["attn"] == attn == 13_763_072
    assert p["dense_layer"] == attn + 4096 + 3 * 2048 * 11264 == 82_973_184
    # router 2048 x 64, 8 held + 2 shared experts of 3 x 2048 x 1408
    assert p["moe_layer"] == attn + 4096 + 2048 * 64 + 10 * 3 * 2048 * 1408
    assert p["embed"] == 2 * 20480 * 2048 + 2048
    assert 568.4e6 < p["total"] < 568.6e6
    # per token: 5 attentions, the dense FFN, 4 x (router, 0.75 held expert
    # by the expected routing share, 2 shared), the head
    matmul = (5 * (attn - 512) + 3 * 2048 * 11264
              + 4 * (2048 * 64 + 2.75 * 3 * 2048 * 1408) + 20480 * 2048)
    assert moe_counts.moonlight_matmul_per_token(cfg) == matmul
    per_tok = moe_counts.moonlight_train_flops_per_token(cfg, 4096)
    assert per_tok == 6 * matmul + 5 * 3 * 16 * 320 * 4097
    assert 1.96e9 < per_tok < 1.98e9
    assert moe_counts.expert_gmm_flops(384, cfg) == 384 * 3 * 2 * 2048 * 1408 * 3


def test_readers_sum_the_program_scopes(engine):
    """The window program's scope map names the model's scopes, and the
    readers sum a trace's op seconds by it."""
    import importlib.util
    eng, _, _ = engine
    cfg, tr, _, devices = tiny()
    live = lm_silo.Engine(cfg, tr, 7, devices, lambda m: None)
    counts = live.counts()
    scopes = set(counts["scope_map"].values())
    assert {"mla-attention", "moe-dispatch", "expert-compute"} <= scopes
    assert counts["moe_slots_held"] == 0.0       # no dispatch since set-up
    live.dispatch()
    counts = live.counts()
    assert counts["moe_slots_held"] > 0
    instr = {s: i for i, s in counts["scope_map"].items()}
    trace = {"op_s": {("client-compute", instr["mla-attention"]): 0.2,
                      ("client-compute", instr["expert-compute"]): 0.1,
                      ("client-compute", instr["moe-dispatch"]): 0.05,
                      ("other:jit_x", instr["mla-attention"]): 9.0},
             "window_s": 2.0}
    ctx = {"trace": trace, "counts": counts, "rounds": 2, "chips": 1,
           "peaks": peaks_for("TPU v5 lite")}

    def read(name):
        spec = importlib.util.spec_from_file_location(
            name, BENCH / "metrics" / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read(ctx)

    assert read("mla_attention_ms.lm") == pytest.approx(100.0)
    assert read("moe_dispatch_ms.lm") == pytest.approx(25.0)
    flops = counts["expert_gmm_flops_per_slot"] * counts["moe_slots_held"]
    assert read("expert_gmm_roofline") == pytest.approx(
        100 * flops / 197e12 / 0.1)
    assert read("mfu.fl_round") == pytest.approx(
        100 * counts["flops_per_round"] * 2 / (2.0 * 197e12))
