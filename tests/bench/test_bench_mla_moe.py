"""The latent-attention MoE cell's harness at a tiny size on the CPU: the
float32 reference against the program, the check rejecting a broken step
and the fp8 control, the scope split, the counts and the readers."""
import copy
import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import common, run, scopes
from bench.counts import mla_moe_lm
from bench.drive import train_mla_moe as drive
from bench.reference import mla_moe
from bench.traffic import lm_tokens

CELL = "deepseek-v2-lite-e16.ezo.b4s1024"
DS = common.load("configs", "deepseek-v2-lite-e16")


def tiny_config(dtype: str = "bfloat16") -> dict:
    """DeepSeek-V2-Lite's keys at d_model 64: one dense layer and two MoE
    layers, 8 routed experts (top 3) of which 4 are held, two shared."""
    return {"name": "tiny-mla-moe", "program_arch": "deepseek-v2-lite",
            "hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "kv_lora_rank": 32, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16, "q_lora_rank": None,
            "moe_intermediate_size": 32, "n_routed_experts": 4,
            "published": {"n_routed_experts": 8}, "num_experts_per_tok": 3,
            "n_shared_experts": 2, "norm_topk_prob": False,
            "routed_scaling_factor": 1, "first_k_dense_replace": 1,
            "num_hidden_layers": 3, "vocab_size": 256, "rope_theta": 10000,
            "rope_scaling": DS["rope_scaling"], "rms_norm_eps": 1e-6,
            "tie_word_embeddings": False, "torch_dtype": dtype}


def ctx(dtype="bfloat16", seed=2 ** 31 + 13) -> dict:
    tr = copy.deepcopy(common.load("traffic", "ezo.b4s1024"))
    tr.update(batch=2, seq=16)
    return {"cell": CELL, "seed": seed, "seconds": 0.5, "trace": False,
            "workload": common.load("workloads", CELL),
            "config": tiny_config(dtype), "traffic": tr,
            "device": {"platform": "cpu", "kind": "cpu", "count": 1}}


def test_configuration_is_the_published_model_with_16_experts_held():
    from repro.configs.base import LaneConfig, ShapeConfig
    from repro.core import api
    from repro.sharding.rules import ShardingRules
    cfg = drive.model_config(DS)
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_first) == (64, 16, 0)
    assert (cfg.d_model, cfg.d_ff, cfg.moe_d_ff, cfg.kv_lora_rank) \
        == (2048, 10944, 1408, 512)
    shape = ShapeConfig("t", 1024, 4, "train")
    m = api.build(cfg, shape, LaneConfig(), ShardingRules(None, cfg, shape))
    n = sum(a.size for a in jax.tree.leaves(m.abstract_params()))
    assert n == 4_910_345_728
    assert DS["reduced"] == ["n_routed_experts"]
    dims = mla_moe.Dims.from_config(DS)
    assert (dims.held, dims.experts, dims.layers) == (16, 64, 27)
    assert mla_moe.yarn_range(dims) == (10, 23)
    assert mla_moe.softmax_scale(dims) == pytest.approx(0.114721, abs=5e-7)


def test_forward_matches_program_in_float32():
    """Weights made as the harness makes them: the program's layers and
    the reference's layers from the seed agree to float32 rounding."""
    from repro.models import transformer as tf
    from repro.sharding.rules import ShardingRules
    c = ctx("float32")
    cell = drive.MlaMoeCell(c)
    cfg, seed = cell.model.cfg, 99
    params = drive.make_params(cell.model.abstract_params(), seed, cell.dims,
                               cell.zo_periods)
    b = lm_tokens.batch({"batch": 2, "seq": 16}, 7, 0, 256)
    tokens = jnp.asarray(b["tokens"])
    rules = ShardingRules(None, cfg, None)
    pos = jnp.broadcast_to(jnp.arange(16), (2, 16))
    x = tf.embed(params, tokens, cfg, rules, pos)
    x, _ = tf.run_periods(params["lead"], x, tf.lead_config(cfg), rules,
                          positions=pos, mode="train")
    for g in ("periods_zo", "periods_bp"):
        x, _ = tf.run_periods(params[g], x, cfg, rules, positions=pos,
                              mode="train")
    dims = cell.dims
    hw = mla_moe.head_weights(dims, jnp.uint32(seed))
    y = jnp.take(hw["embed"], tokens, axis=0).astype(jnp.float32)
    for i in range(dims.layers):
        y = mla_moe.layer(mla_moe.layer_weights(dims, jnp.uint32(seed), i), y,
                          dims)
    np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=2e-5,
                               atol=2e-5)


def test_elastic_step_matches_program_in_float32():
    out = drive.run(ctx("float32"))
    gaps = {c.name: c.value for c in out["checks"]}
    assert gaps["loss_rel_gap"] < 1e-5
    assert gaps["tail_grad_gap"] < 1e-4
    assert gaps["zo_step_gap"] < 1e-4
    assert gaps["zo_g_gap"] < 1e-4
    assert out["layer"]["moe_held_rows"] > 0
    assert 0 < out["layer"]["moe_max_expert_rows"] <= 32


def _broken(monkeypatch, break_step):
    from repro.core import api
    real = api.build

    def build(*a, **k):
        m = real(*a, **k)
        return dataclasses.replace(m, train_step=break_step(m.train_step))
    monkeypatch.setattr(api, "build", build)


def test_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from repro.core.elastic import TrainState

    def unchanged(step):
        def f(state, batch, mask):
            new, metrics = step(state, batch, mask)
            return TrainState(state.params, new.step, new.seed), metrics
        return f
    _broken(monkeypatch, unchanged)
    out = drive.run(ctx())
    assert not run.verdict(out)
    gaps = {c.name: c.value for c in out["checks"]}
    assert gaps["zo_step_gap"] == pytest.approx(1.0)


def test_step_with_the_leading_layer_left_unmoved_is_not_correct(
        monkeypatch):
    from repro.core.elastic import TrainState

    def frozen_lead(step):
        def f(state, batch, mask):
            new, metrics = step(state, batch, mask)
            params = dict(new.params, lead=state.params["lead"])
            return TrainState(params, new.step, new.seed), metrics
        return f
    _broken(monkeypatch, frozen_lead)
    out = drive.run(ctx())
    gaps = {c.name: c.value for c in out["checks"]}
    assert gaps["zo_step_gap"] > 0.5 and not run.verdict(out)


def test_step_that_reports_half_its_g_is_not_correct(monkeypatch):
    def halved(step):
        def f(state, batch, mask):
            new, metrics = step(state, batch, mask)
            return new, dict(metrics, zo_g=metrics["zo_g"] * 0.5)
        return f
    _broken(monkeypatch, halved)
    out = drive.run(ctx())
    gaps = {c.name: c.value for c in out["checks"]}
    assert gaps["zo_g_gap"] > 0.5 and gaps["zo_step_gap"] > 0.5
    assert not run.verdict(out)


def test_step_under_bf16_resolution_reads_its_g_exactly():
    """At lr 1e-6 most bf16 weights do not move: the bare projection of
    the change on z reads |g| far short, the read against the bf16
    replay reads it exactly."""
    c = ctx()
    c["traffic"]["lane"]["learning_rate"] = 1e-6
    cell = drive.MlaMoeCell(c)
    seed = 2 ** 31 + 17
    _, prog, _ = cell.start(seed)
    assert all(d == 0 for d, _ in prog["zo_counts"].values())
    short = abs(abs(prog["g_projected"]) - prog["zo_g"]) / prog["zo_g"]
    assert short > 0.05, short
    assert abs(abs(prog["g_applied"]) - prog["zo_g"]) / prog["zo_g"] < 1e-4


def test_fp8_control_and_half_batch_are_not_correct():
    cell = drive.MlaMoeCell(ctx())
    seed = 2 ** 31 + 21
    ref = cell.reference(seed)
    for kw in ({"precision": "fp8"}, {"half_batch": True}):
        checks = cell.compare(cell.as_program(cell.reference(seed, **kw)),
                              ref)
        assert not all(c.ok for c in checks), kw


# ------------------------------------------------------------------ #
# scopes, counts and readers
# ------------------------------------------------------------------ #
HLO = """HloModule jit_step, entry_computation_layout={()}

%body (p: f32[2]) -> f32[2] {
  %a = f32[2]{0} fusion(%p), kind=kLoop, calls=%fused_a, metadata={op_name="jit(step)/jvp(zo_forward)/while/body/moe_route/argsort"}
  %ragged-dot-metadata.1 = (s32[17]{0}) custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %ragged-dot-none.1 = bf16[8,4]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %b = f32[2]{0} fusion(%a), kind=kLoop, calls=%fused_b, metadata={op_name="jit(step)/jvp(zo_forward)/while/body/mla/dot_general"}
  ROOT %c = f32[2]{0} fusion(%b), kind=kLoop, calls=%fused_c
}

%fused_c (q: f32[2]) -> f32[2] {
  ROOT %m = f32[2]{0} multiply(%q, %q), metadata={op_name="jit(step)/transpose(jvp(bp_tail))/moe_experts/mul"}
}

ENTRY %main (x: f32[2]) -> f32[2] {
  %ragged-dot-none.2 = bf16[8,4]{1,0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %d = f32[2]{0} fusion(%x), kind=kLoop, calls=%fused_d, metadata={op_name="jit(step)/transpose(jvp(bp_tail))/moe_route/scatter"}
  %ragged-dot-none.3 = bf16[8,4]{1,0} custom-call(%d), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
}
"""


def test_scope_split_names_the_compiler_kernels_by_their_place():
    o = scopes.op_scopes(HLO)
    assert o["a"] == ("moe_route", "zo_forward", False)
    assert o["ragged-dot-none.1"] == ("moe_experts", "zo_forward", True)
    assert o["ragged-dot-metadata.1"] == ("moe_experts", "zo_forward", True)
    assert o["b"] == ("mla", "zo_forward", False)
    assert o["c"] == ("moe_experts", "bp_tail", False)
    assert o["ragged-dot-none.2"] == ("moe_experts", "unscoped", True)
    assert o["ragged-dot-none.3"] == ("moe_experts", "bp_tail", True)
    ops = {"a f32[2]": 1.0, "ragged-dot-none.1 bf16[8,4]": 2.0,
           "b f32[2]": 4.0, "c f32[2]": 8.0, "ragged-dot-none.3 bf16[8,4]":
           16.0, "other.7 f32[3]": 32.0}
    s = scopes.split(ops, [HLO])
    assert s["scopes"]["moe_route"] == {"zo_forward": 1.0}
    assert s["scopes"]["mla"] == {"zo_forward": 4.0}
    assert s["scopes"]["moe_experts"] == {"zo_forward": 2.0, "bp_tail": 24.0}
    assert s["gmm"] == {"zo_forward": 2.0, "bp_tail": 16.0}
    assert s["phases"] == {"zo_forward": 7.0, "bp_tail": 24.0}
    assert scopes.seconds(s, "moe_experts") == 26.0
    assert scopes.split({"other.7 f32[3]": 1.0}, [HLO]) is None


def test_counts_at_published_sizes():
    c = DS
    assert mla_moe_lm.mla_params(c) == 2048 * 16 * 192 + 2048 * 576 \
        + 512 * 16 * 256 + 16 * 128 * 2048
    assert mla_moe_lm.row_flops(c) == 6 * 2048 * 1408
    assert mla_moe_lm.expert_bytes(c) == 3 * 16 * 2048 * 1408 * 2
    # rows as the router spreads them evenly: 4096 tokens x 6 / 64 experts
    # x 16 held, over 25 ZO MoE layers and both passes
    rows = 4096 * 6 // 4 * 25 * 2
    f = mla_moe_lm.elastic_zo_step_flops(c, 4, 1024, 1, 1, rows)
    active = (mla_moe_lm.mla_params(c) * 27 + 3 * 2048 * 10944
              + 26 * (3 * 2048 * 2816 + 2048 * 64 + 6 / 4 * 3 * 2048 * 1408)
              + 2048 * 102400)
    # two forwards at 2 FLOPs per active parameter and token; attention
    # (about 5%) and the backward of the tail's layer and head (the head
    # is 14% of the active parameters: about 35% more) on top
    low = 2 * 2 * active * 4096
    assert 1.3 * low < f < 1.5 * low


def _layer(**kw) -> dict:
    tr = common.load("traffic", "ezo.b4s1024")
    d = {"config": DS, "traffic": tr, "steps": 10, "window_s": 8.0,
         "tokens": 10 * 4096, "zo_periods": 25,
         "peaks": common.peaks("TPU v5 lite"), "compiles": 0}
    d.update(kw)
    return d


@pytest.mark.parametrize("name", ["train.moe_route_ms_per_step",
                                  "train.moe_experts_ms_per_step",
                                  "train.moe_gmm_roofline", "train.moe_mfu"])
def test_readers_find_nothing_where_the_program_has_no_moe(name):
    reader = common.load_module("metrics", name)
    assert reader.read(_layer()) is None
    assert reader.read(_layer(scopes=None, moe_held_rows=0)) is None


def test_readers_from_scopes_and_counts():
    rows = 10 * 4096 * 6 // 4 * 25 * 2
    s = {"scopes": {"mla": {"zo_forward": 1.0},
                    "moe_route": {"zo_forward": 0.5, "bp_tail": 0.1},
                    "moe_experts": {"zo_forward": 0.4, "bp_tail": 0.2}},
         "gmm": {"zo_forward": 0.35, "bp_tail": 0.15}}
    lay = _layer(scopes=s, moe_held_rows=rows)
    read = {n: common.load_module("metrics", n).read(lay)
            for n in ("train.moe_route_ms_per_step",
                      "train.moe_experts_ms_per_step",
                      "train.moe_gmm_roofline", "train.moe_mfu")}
    assert read["train.moe_route_ms_per_step"] == pytest.approx(60.0)
    assert read["train.moe_experts_ms_per_step"] == pytest.approx(60.0)
    least = rows * 6 * 2048 * 1408 / 197e12
    assert read["train.moe_gmm_roofline"] == pytest.approx(
        100 * least / 0.35)
    flops = 10 * mla_moe_lm.elastic_zo_step_flops(DS, 4, 1024, 1, 1,
                                                  rows / 10)
    assert read["train.moe_mfu"] == pytest.approx(100 * flops / 8.0 / 197e12)


def test_benchmark_lists_the_new_metrics_for_the_cell_only():
    spec = json.loads((common.ROOT / "BENCHMARK.json").read_text())
    names = {m["name"] for m in run.metric_specs(CELL, traced=True)}
    assert {"train.moe_route_ms_per_step", "train.moe_experts_ms_per_step",
            "train.moe_gmm_roofline", "train.moe_mfu",
            "train.noise_ms_per_step"} <= names
    assert "train.mfu" not in names
    dense = {m["name"] for m in run.metric_specs("qwen3-4b-35L.ezo.b4s1024",
                                                 traced=True)}
    assert not any(n.startswith("train.moe") for n in dense)
    assert any(w["name"] == CELL and w["chips"] == 1
               for w in spec["workloads"])
