"""``laguna-train`` without the chip: the cell at a tiny Laguna-shaped size on
the CPU through ``run_cell``'s rehearsal argument (untraced and traced, in
float32: at a hundred tokens bf16 noise drowns a gradient), its arithmetic, its
new reducers on a made-up trace, and the lasting properties of what the cell
added (every name resolves, the two copies of the reference agree)."""

import json
import os

import pytest

from chipbench import arith_swa, harness, run
from chipbench.reducers import attn_core_roofline, scope_share, train_mfu_swa

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HF = {"model_type": "laguna", "hidden_size": 64, "num_attention_heads": 6,
      "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
      "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
      "num_experts": 16, "num_experts_per_tok": 4, "moe_routed_scaling_factor": 2.5,
      "num_hidden_layers": 5, "vocab_size": 256, "max_position_embeddings": 1024,
      "attention_bias": False, "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
      "gating": True, "sliding_window": 16, "partial_rotary_factor": 0.5,
      "rope_parameters": {
          "full_attention": {"rope_theta": 100.0, "rope_type": "yarn", "factor": 4.0,
                             "original_max_position_embeddings": 32, "beta_slow": 1,
                             "beta_fast": 4, "attention_factor": 1.1386,
                             "partial_rotary_factor": 0.5},
          "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0,
                                "partial_rotary_factor": 1.0}},
      "layer_types": ["full_attention", "sliding_attention", "sliding_attention",
                      "sliding_attention"] * 3,
      "mlp_layer_types": ["dense"] + ["sparse"] * 11,
      "num_attention_heads_per_layer": [6, 8, 8, 8] * 3,
      "num_experts_held": 8, "expert_first": 0, "expert_buffer_factor": 2.0,
      "aux_loss_alpha": 0.01}
JOINED = {"step_ms_p50", "attn_kernel_share", "device_idle_share.train",
          "compiles_in_window.train", "recompiles_in_window.train",
          "trainer_host_ms_per_step", "idle_attributed_share", "scope_share.attn",
          "scope_share.mlp", "scope_share.loss", "scope_share.optimizer",
          "scope_share.none", "scope_share.moe_experts", "scope_share.moe_route",
          "gmm_kernel_share", "moe_expert_load_max_over_mean",
          "moe_dropped_token_share", "moe_held_row_share",
          "gmm_roofline_share.held_routed", "pass_share.forward",
          "pass_share.recompute", "pass_share.backward", "pass_share.update",
          "pass_share.other", "recompute_ms_per_step", "device_step_ms_p50",
          "train_step_peak_gb"}
NEW = {"scope_share.swa", "swa_core_roofline_share", "full_core_roofline_share",
       "swa_block_visit_share", "swa_active_mfu_pct"}
DEVICE_TRACE = {"scope_share.swa", "swa_core_roofline_share", "full_core_roofline_share"}


def rehearsal(**traffic):
    from shuffle_exchange_tpu.models.hf import config_from_hf

    return {"model_cfg": config_from_hf(HF), "source_config": dict(HF),
            "train_config": {"bf16": {"enabled": False}},
            "traffic": {"seq": 64, "batch_per_chip": 2, "loss_tol": 1e-4,
                        "route_tol": 0.002, "grad_tol": 0.01, "grad_tol_routed": 0.01,
                        "router_tol": 1e-5, "mixer_tol": 1e-3, **traffic}}


def test_the_cell_is_files_and_entries():
    cell = harness.load_cell("laguna-train")
    assert cell["chips"] == 1
    assert cell["reduced"] == ["num_hidden_layers", "num_experts_held", "vocab_size"]
    assert cell["traffic"]["driver"] == "train_steps_swa"
    assert (cell["traffic"]["seq"], cell["traffic"]["batch_per_chip"]) == (16384, 1)
    names = {m["name"] for m in cell["per_layer"]}
    assert names == JOINED | NEW
    assert {m["name"] for m in cell["end_to_end"]} == {
        "train_tokens_per_s_chip", "setup_s"}
    src = cell["config"]
    assert src["published"] == {"num_hidden_layers": 40, "num_experts_held": 256,
                                "vocab_size": 100352}
    # every published width, unchanged
    assert (src["hidden_size"], src["head_dim"], src["num_attention_heads"],
            src["num_key_value_heads"], src["sliding_window"], src["intermediate_size"],
            src["num_experts"], src["num_experts_per_tok"], src["moe_intermediate_size"],
            src["shared_expert_intermediate_size"], src["moe_routed_scaling_factor"]) == (
        2048, 128, 48, 8, 512, 8192, 256, 8, 512, 512, 2.5)
    assert src["num_attention_heads_per_layer"][:5] == [48, 64, 64, 64, 48]
    assert (src["num_hidden_layers"], src["num_experts_held"], src["vocab_size"]) == (
        5, 32, 12544)
    for key in ("source", "assumed", "deployment"):
        assert src[key]


def test_every_number_of_the_catalog_row_is_there():
    """The guide's rule: the file holds every number of the row's ``config``
    under the same key (nested groups whole); what differs is in ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog) if '"Laguna-XS.2"' in line)
    cell = harness.load_cell("laguna-train")
    src = cell["config"]
    assert cell["source"] == row["source_url"] == src["source"]
    differs = {k for k, v in row["config"].items() if src.get(k, "missing") != v}
    assert differs == {"num_hidden_layers", "vocab_size"}
    assert differs | {"num_experts_held"} == set(cell["reduced"])


def test_every_name_the_cell_brought_resolves():
    import importlib

    cell = harness.load_cell("laguna-train")
    for name in NEW:
        spec = harness.read_json(f"{cell['bench_dir']}/layer_metrics/{name}.json")
        assert callable(importlib.import_module(
            "chipbench.reducers." + spec["reducer"]).reduce), name
    assert callable(importlib.import_module(
        "chipbench.drivers." + cell["traffic"]["driver"]).run)
    body = lambda path: open(os.path.join(ROOT, path)).read().split('"""', 2)[2]
    assert body("chipbench/reference_laguna.py") == body(
        "shuffle_exchange_tpu/models/reference_laguna.py")
    assert "shuffle_exchange_tpu" not in body("chipbench/reference_laguna.py").split(
        "import jax")[1]


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_laguna_train_at_tiny_size(trace, capsys):
    cell = harness.load_cell("laguna-train")
    out = json.loads(run.run_cell("laguna-train", 2 ** 31 + 4242, 3.0, trace,
                                  rehearsal=rehearsal()))
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith('{"phase"')]
    setup = next(x for x in lines if x["phase"] == "setup")
    assert setup["routes"]["swa_core"] == "reference"      # what the CPU runs
    assert out["correct"] is True, [x for x in lines if x["phase"] == "window"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    if not trace:
        assert set(out["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
        return
    assert set(out["metrics"]) <= {m["name"] for m in cell["per_layer"]}
    assert NEW - DEVICE_TRACE <= set(out["metrics"]), out["metrics"]
    assert out["metrics"]["moe_dropped_token_share"]["value"] == 0.0
    assert 25.0 < out["metrics"]["moe_held_row_share"]["value"] < 75.0   # 8 of 16 held
    assert out["metrics"]["swa_active_mfu_pct"]["value"] > 0.0
    assert out["metrics"]["swa_block_visit_share"]["value"] == 100.0     # 64 positions: one block


def test_a_wrong_window_fails_the_mixer_check():
    """The driver's own judgement: the reference at window 17 in the program's
    place (the mixer alone reads it; the whole model's band would not)."""
    from chipbench.drivers import train_steps_swa as driver

    got = {"losses": [5.0, 4.9], "first_loss_again": 4.8, "reference_loss": 5.0,
           "route_gap": 0.0, "held_gap": 0.0, "counters_add_up": True,
           "overflow": [0, 0], "grad_gaps": {"lead/wq": 0.001},
           "router_gaps": {"choice": 0.0, "weight": 0.0},
           "mixer_gaps": {"swa/y": 0.0005, "full/y": 0.0004},
           "window_route": "splash_window", "visit_share": 11.9}
    tol = rehearsal()["traffic"]
    assert driver.failed_checks(got, tol) == []
    far = dict(got, mixer_gaps={"swa/y": 0.02, "full/y": 0.0004})
    assert any("swa/y" in m for m in driver.failed_checks(far, tol))
    unmasked = dict(got, visit_share=100.0)
    assert any("did not reach the kernel" in m for m in driver.failed_checks(unmasked, tol))
    assert driver.failed_checks(dict(unmasked, window_route="reference"), tol) == []


def test_the_arithmetic_of_the_cell():
    """The cell's own shapes: what the issue counted, from the functions."""
    from shuffle_exchange_tpu.models.hf import config_from_hf

    cfg = config_from_hf(harness.load_cell("laguna-train")["config"])
    assert (arith_swa.layers_of(cfg, "swa"), arith_swa.layers_of(cfg, "attn")) == (3, 2)
    T, W = 16384, 512
    assert arith_swa.visible_pairs(T) == T * (T + 1) / 2
    assert arith_swa.visible_pairs(T, W) == W * (W + 1) / 2 + (T - W) * W
    # the window's own share of the causal pairs: 6.2%
    assert arith_swa.visible_pairs(T, W) / arith_swa.visible_pairs(T) == pytest.approx(
        0.0615, abs=2e-4)
    full = arith_swa.core_flops_per_step(cfg, "attn", 1, T)
    assert full == pytest.approx(3 * 2 * 48 * (T * (T + 1) / 2) * 2 * 128 * 2)
    swa = arith_swa.core_flops_per_step(cfg, "swa", 1, T)
    assert swa / full == pytest.approx(3 * 64 * 0.0615 / (2 * 48), rel=5e-3)
    assert arith_swa.core_bytes_per_step(cfg, "swa", 1, T) == 3 * T * 6 * 72 * 128 * 2
    per_token = arith_swa.matmul_params_per_token(cfg)
    attn = 2 * 29_360_128 + 3 * 37_748_736
    assert per_token == attn + 50_331_648 + 4 * (524_288 + 3_145_728) + 2048 * 12544
    flops = arith_swa.train_flops_per_token(cfg, T, 4 * 1.0)
    assert flops == pytest.approx(6 * per_token + 6 * 3 * 2048 * 512 * 4 + (full + swa) / T)


def _ctx(rows, facts):
    """A made-up traced run: ``rows`` [(op, scope path, ns)] on one device."""
    scopes = [""] + sorted({p for _, p, _ in rows})
    ops, t = [], 0
    for name, path, ns in rows:
        ops.append([name, t, ns, scopes.index(path)])
        t += ns
    table = {"devices": [{"name": "/device:TPU:0", "ops": ops,
                          "modules": [["jit_train_step", 0, t]]}],
             "scopes": scopes, "host": [], "program_ops": {}}
    return {"_xscope": table, "cell": {"name": "laguna-train"},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "result": {"facts": facts}, "trace_summary": {"ops": {}}}


def test_the_new_reducers_on_a_made_up_trace(capsys):
    from shuffle_exchange_tpu.models.hf import config_from_hf

    cfg = config_from_hf(harness.load_cell("laguna-train")["config"])
    base = "jit(train_step)/jvp(layers)/while/body/"
    rows = [("swa_fwd", base + "attn_core/swa_core/pallas_call", 30_000_000),
            ("full_fwd", base + "attn_core/pallas_call", 200_000_000),
            ("qkv", base + "attn_qkv/swa_qkv/dot_general", 10_000_000),
            ("mlp", base + "moe/moe_shared/dot_general", 160_000_000)]
    facts = {"model_cfg": cfg, "traced_steps": 1, "batch": 1, "seq": 16384,
             "tokens_per_step": 16384, "chips": 1, "step_s": [0.8],
             "swa_flops_per_token": 3.0e9}
    ctx = _ctx(rows, facts)
    spec = harness.read_json(os.path.join(ROOT, "chipbench/layer_metrics/scope_share.swa.json"))
    assert scope_share.reduce(ctx, **spec["args"]) == pytest.approx(10.0)
    swa = attn_core_roofline.reduce(ctx, mixer="swa")
    full = attn_core_roofline.reduce(ctx, mixer="attn")
    want_swa = arith_swa.core_flops_per_step(cfg, "swa", 1, 16384) / 197e12 / 0.030
    want_full = arith_swa.core_flops_per_step(cfg, "attn", 1, 16384) / 197e12 / 0.200
    assert swa == pytest.approx(100 * want_swa) and full == pytest.approx(100 * want_full)
    assert 0 < swa < 100 and 0 < full < 100
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["mixer"] for x in lines if x["phase"] == "attn_core_roofline"] == ["swa", "attn"]
    assert train_mfu_swa.reduce(ctx) == pytest.approx(100 * 3.0e9 * 16384 / 0.8 / 197e12)
    # a program without the scopes (the parent), or a model without a window
    assert attn_core_roofline.reduce(_ctx(rows[1:2], facts), mixer="swa") is None
    assert attn_core_roofline.reduce(_ctx(rows, {}), mixer="swa") is None


def test_the_band_script_refuses_every_wrong_model_at_tiny_size(capsys):
    """``laguna_band.measure`` at the tiny size: the reference itself passes,
    the program's own router and mixers read at rounding, and every wrong
    model and lower precision is refused by the driver's own checks (the
    rehearsal's limits are float32's, so bf16 itself is a lower precision
    here); the attention's wrong models read several times bf16's distance on
    the mixers alone."""
    from chipbench import laguna_band

    cell = harness.load_cell("laguna-train")
    names = ["bf16", "program_router", "program_mixers"] + laguna_band.WRONG + laguna_band.LOWER
    out = laguna_band.measure(cell, [5], names, rehearsal=rehearsal())
    capsys.readouterr()
    by = {x["variant"]: x for x in out}
    assert set(by) == set(names) | {"float32"}
    exact = by["float32"]
    assert exact["correct"] is True and exact["failed_checks"] == []
    assert (exact["loss_gap"], exact["route_gap"], exact["grad_gap"]) == (0, 0, 0)
    assert by["program_router"]["router_gap"] < 1e-5
    assert set(by["program_mixers"]["mixer_gaps"]) >= {"swa/y", "swa/dwq", "full/y", "full/dwo"}
    # (the program's mixers are handed bf16 inputs, as the trainer's are)
    assert by["program_mixers"]["mixer_gap"] < 2 * by["bf16"]["mixer_gap"]
    for name in ["bf16"] + laguna_band.WRONG + laguna_band.LOWER:
        assert by[name]["correct"] is False and by[name]["failed_checks"], name
    for name in ("softmax_router", "no_scale", "no_norm", "bf16_router"):
        assert by[name]["router_gap"] > 1e-3, name
        assert any("the router alone" in m for m in by[name]["failed_checks"]), name
    for name in ("window_511", "window_513", "window_ignored", "swa_table_on_full",
                 "full_table_on_swa", "yarn_no_factor", "yarn_all_dims",
                 "groups_of_8_on_full"):
        assert by[name]["mixer_gap"] > 3 * by["bf16"]["mixer_gap"], (
            name, by[name]["mixer_gap"], by["bf16"]["mixer_gap"])
        assert by[name]["whole_model_of"] == "bf16"
