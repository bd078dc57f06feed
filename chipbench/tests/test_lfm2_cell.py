"""``lfm2-train`` without the chip: the cell at a tiny LFM2-shaped size on the
CPU through ``run_cell``'s rehearsal argument (untraced and traced, in float32:
at a hundred tokens bf16 noise drowns a gradient), its arithmetic, its new
reducers on a made-up trace, and the lasting properties of what the cell added
(every name resolves, the two copies of the reference agree)."""

import json
import os

import pytest

from chipbench import arith_sconv, harness, run
from chipbench.reducers import scope_share, sconv_mix_roofline, train_mfu_sconv

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv",
         "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv",
         "conv", "conv", "full_attention", "conv", "conv", "full_attention", "conv", "conv"]
HF = {"model_type": "lfm2_moe", "conv_L_cache": 3, "conv_bias": False, "hidden_size": 64,
      "intermediate_size": 96, "layer_types": TYPES, "max_position_embeddings": 1024,
      "moe_intermediate_size": 32, "norm_eps": 1e-5, "norm_topk_prob": True,
      "num_attention_heads": 4, "num_dense_layers": 2, "num_experts": 8,
      "num_experts_per_tok": 2, "num_hidden_layers": 5, "layers_held": [0, 2, 3, 4, 5],
      "num_key_value_heads": 2, "rope_theta": 1000000, "routed_scaling_factor": 1,
      "use_expert_bias": True, "vocab_size": 128, "tie_word_embeddings": True,
      "num_experts_held": 4, "expert_first": 0, "expert_buffer_factor": 2.0,
      "bias_update_speed": 0.001}
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
NEW = {"scope_share.sconv", "sconv_mix_roofline_share", "sconv_active_mfu_pct"}
DEVICE_TRACE = {"scope_share.sconv", "sconv_mix_roofline_share"}


def rehearsal(**traffic):
    from shuffle_exchange_tpu.models.hf import config_from_hf

    return {"model_cfg": config_from_hf(HF), "source_config": dict(HF),
            "train_config": {"bf16": {"enabled": False}},
            "traffic": {"seq": 64, "batch_per_chip": 2, "loss_tol": 1e-4,
                        "route_tol": 0.002, "grad_tol": 0.01, "grad_tol_routed": 0.01,
                        "router_tol": 1e-5, "weight_tol": 1e-4, "mixer_tol": 1e-3,
                        "mixer_tol_attn": 1e-3, "select_bias_std": 0.05, **traffic}}


def test_the_cell_is_files_and_entries():
    cell = harness.load_cell("lfm2-train")
    assert cell["chips"] == 1
    assert cell["reduced"] == ["num_hidden_layers", "num_experts_held", "vocab_size"]
    assert cell["traffic"]["driver"] == "train_steps_sconv"
    assert cell["traffic_name"] == "pretrain-sconv-s4096"
    assert (cell["traffic"]["seq"], cell["traffic"]["batch_per_chip"]) == (4096, 8)
    assert {m["name"] for m in cell["per_layer"]} == JOINED | NEW
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tokens_per_s_chip", "setup_s"}
    src = cell["config"]
    assert {k: src["published"][k] for k in cell["reduced"]} == {
        "num_hidden_layers": 24, "num_experts_held": 32, "vocab_size": 65536}
    # every published width, unchanged
    assert (src["hidden_size"], src["conv_L_cache"], src["num_attention_heads"],
            src["num_key_value_heads"], src["intermediate_size"], src["num_experts"],
            src["num_experts_per_tok"], src["moe_intermediate_size"]) == (
        2048, 3, 32, 8, 7168, 32, 4, 1792)
    assert (src["num_hidden_layers"], src["layers_held"], src["num_experts_held"],
            src["vocab_size"]) == (5, [0, 2, 3, 4, 5], 8, 16384)
    assert [src["layer_types"][i] for i in src["layers_held"]] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    for key in ("source", "assumed", "deployment", "counts"):
        assert src[key]


def test_every_number_of_the_catalog_row_is_there():
    """The guide's rule: the file holds every number of the row's ``config``
    under the same key (nested groups whole); what differs is in ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog) if '"LFM2-8B-A1B"' in line)
    cell = harness.load_cell("lfm2-train")
    src = cell["config"]
    assert cell["source"] == row["source_url"] == src["source"]
    differs = {k for k, v in row["config"].items() if src.get(k, "missing") != v}
    assert differs == {"num_hidden_layers", "vocab_size"}
    assert differs | {"num_experts_held"} == set(cell["reduced"])


def test_every_name_the_cell_brought_resolves():
    import importlib

    cell = harness.load_cell("lfm2-train")
    for name in NEW:
        spec = harness.read_json(f"{cell['bench_dir']}/layer_metrics/{name}.json")
        assert callable(importlib.import_module(
            "chipbench.reducers." + spec["reducer"]).reduce), name
    assert callable(importlib.import_module(
        "chipbench.drivers." + cell["traffic"]["driver"]).run)
    body = lambda path: open(os.path.join(ROOT, path)).read().split('"""', 2)[2]
    assert body("chipbench/reference_lfm2.py") == body(
        "shuffle_exchange_tpu/models/reference_lfm2.py")
    assert "shuffle_exchange_tpu" not in body("chipbench/reference_lfm2.py").split(
        "import jax")[1]


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_lfm2_train_at_tiny_size(trace, capsys):
    cell = harness.load_cell("lfm2-train")
    out = json.loads(run.run_cell("lfm2-train", 2 ** 31 + 4242, 3.0, trace,
                                  rehearsal=rehearsal()))
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith('{"phase"')]
    setup = next(x for x in lines if x["phase"] == "setup")
    assert setup["routes"]["sconv_mix"] == "xla"           # what the CPU runs
    assert setup["routes"]["attn_core"] == "reference"
    window = next(x for x in lines if x["phase"] == "window")
    assert out["correct"] is True, window
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert window["first_step_bias_grad"] == 0.0 and window["first_step_bias_update_gap"] < 1e-7
    assert set(window["mixer_gaps"]) >= {"sconv/y", "sconv/dsconv_w", "attn/y", "attn/dq_norm_w"}
    if not trace:
        assert set(out["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
        return
    assert set(out["metrics"]) <= {m["name"] for m in cell["per_layer"]}
    assert NEW - DEVICE_TRACE <= set(out["metrics"]), out["metrics"]
    assert out["metrics"]["moe_dropped_token_share"]["value"] == 0.0
    assert 25.0 < out["metrics"]["moe_held_row_share"]["value"] < 75.0   # 4 of 8 held
    assert out["metrics"]["sconv_active_mfu_pct"]["value"] > 0.0


def test_a_wrong_mixer_fails_its_own_check():
    """The driver's own judgement: a reading over its limit in the program's
    place, each mixer against its own limit."""
    from chipbench.drivers import train_steps_sconv as driver

    got = {"losses": [5.0, 4.9], "first_loss_again": 4.8, "reference_loss": 5.0,
           "route_gap": 0.0, "held_gap": 0.0, "counters_add_up": True,
           "overflow": [0, 0], "grad_gaps": {"lead/sconv_w": 0.001},
           "bias_grad": 0.0, "bias_update_gap": 0.0, "weight_gap": 0.0,
           "router_gaps": {"choice": 0.0, "weight": 0.0},
           "mixer_gaps": {"sconv/y": 0.0005, "attn/y": 0.0015}}
    tol = dict(rehearsal()["traffic"], mixer_tol_attn=2e-3)
    assert driver.failed_checks(got, tol) == []
    far = dict(got, mixer_gaps={"sconv/y": 0.0015, "attn/y": 0.0015})
    assert any("sconv/y" in m for m in driver.failed_checks(far, tol))
    assert any("attn/y" in m for m in driver.failed_checks(
        dict(got, mixer_gaps={"sconv/y": 0.0, "attn/y": 0.003}), tol))
    assert any("it is a buffer" in m for m in driver.failed_checks(
        dict(got, bias_grad=1e-9), tol))
    assert any("aux-free update" in m for m in driver.failed_checks(
        dict(got, bias_update_gap=1e-3), tol))
    # the q/k gains' gradients (64 numbers each) have their own limit
    gains = dict(got, mixer_gaps={"sconv/y": 0.0, "attn/y": 0.0015, "attn/dq_norm_w": 0.003})
    assert any("attn/dq_norm_w" in m for m in driver.failed_checks(gains, tol))
    assert driver.failed_checks(gains, dict(tol, mixer_tol_attn_gain=0.005)) == []
    # the tied embedding's own limit: a gap another leaf may have, it may not
    untied = dict(got, grad_gaps={"lead/sconv_w": 0.008, "embed": 0.008})
    assert driver.failed_checks(untied, tol) == []
    assert any("gradient of embed" in m for m in driver.failed_checks(
        untied, dict(tol, grad_tol_embed=0.005)))


def test_the_arithmetic_of_the_cell():
    """The cell's own shapes: what the issue counted, from the functions."""
    from shuffle_exchange_tpu.models.hf import config_from_hf

    cfg = config_from_hf(harness.load_cell("lfm2-train")["config"])
    assert (arith_sconv.layers_of(cfg, "sconv"), arith_sconv.layers_of(cfg, "attn")) == (4, 1)
    B, T = 8, 4096
    # 537 MB forward and 940 MB backward a layer at 32,768 tokens: 5.9 GB a step
    one = B * T * 2048 * 2
    assert arith_sconv.mix_bytes_per_step(cfg, B, T) == 4 * (4 * one + 7 * one)
    assert 4 * one == pytest.approx(537e6, rel=1e-3) and 7 * one == pytest.approx(940e6, rel=1e-3)
    assert arith_sconv.mix_bytes_per_step(cfg, B, T) / 819e9 == pytest.approx(7.2e-3, rel=5e-3)
    assert arith_sconv.mix_flops_per_step(cfg, B, T) == 29 * B * T * 2048 * 4
    per_token = arith_sconv.matmul_params_per_token(cfg)
    assert per_token == (4 * 16_777_216 + 10_485_760 + 44_040_192 + 4 * 65_536
                         + 2048 * 16384)
    core = arith_sconv.attn_core_flops_per_step(cfg, 1, T)
    assert core == 3 * 32 * (T * (T + 1) / 2) * 2 * 64 * 2
    flops = arith_sconv.train_flops_per_token(cfg, T, 4 * 1.0)
    assert flops == pytest.approx(6 * per_token + 6 * 3 * 2048 * 1792 * 4 + core / T)
    assert flops == pytest.approx(1.25e9, rel=0.01)          # ISSUE 41's count


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
    return {"_xscope": table, "cell": {"name": "lfm2-train"},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "result": {"facts": facts}, "trace_summary": {"ops": {}}}


def test_the_new_reducers_on_a_made_up_trace(capsys):
    from shuffle_exchange_tpu.models.hf import config_from_hf

    cfg = config_from_hf(harness.load_cell("lfm2-train")["config"])
    base = "jit(train_step)/jvp(layers)/while/body/"
    rows = [("mix_fwd", base + "attn_core/sconv_mix/fusion", 20_000_000),
            ("mix_bwd", "jit(train_step)/transpose(jvp(layers))/while/body/attn_core/sconv_mix/fusion",
             20_000_000),
            ("in", base + "attn_qkv/sconv_in/dot_general", 60_000_000),
            ("core", base + "attn_core/pallas_call", 100_000_000),
            ("mlp", base + "moe/moe_experts/gmm", 200_000_000)]
    facts = {"model_cfg": cfg, "traced_steps": 1, "batch": 8, "seq": 4096,
             "tokens_per_step": 32768, "chips": 1, "step_s": [0.7],
             "sconv_route": "xla", "sconv_flops_per_token": 1.25e9}
    ctx = _ctx(rows, facts)
    spec = harness.read_json(os.path.join(ROOT, "chipbench/layer_metrics/scope_share.sconv.json"))
    assert scope_share.reduce(ctx, **spec["args"]) == pytest.approx(25.0)
    share = sconv_mix_roofline.reduce(ctx)
    assert share == pytest.approx(100 * arith_sconv.mix_bytes_per_step(cfg, 8, 4096)
                                  / 819e9 / 0.040)
    assert 0 < share < 100
    line = next(json.loads(x) for x in capsys.readouterr().out.splitlines()
                if '"sconv_mix_roofline"' in x)
    assert (line["binds"], line["route"], line["layers"]) == ("hbm_bytes_per_s", "xla", 4)
    assert train_mfu_sconv.reduce(ctx) == pytest.approx(100 * 1.25e9 * 32768 / 0.7 / 197e12)
    # a program without the scope (the parent), a run without facts, a model
    # without convolution layers
    assert sconv_mix_roofline.reduce(_ctx(rows[2:], facts)) is None
    assert sconv_mix_roofline.reduce(_ctx(rows, {})) is None
    laguna = config_from_hf(harness.load_cell("laguna-train")["config"])
    assert sconv_mix_roofline.reduce(_ctx(rows, dict(facts, model_cfg=laguna))) is None
    assert train_mfu_sconv.reduce(_ctx(rows, {})) is None


def test_the_band_script_refuses_every_wrong_model_at_tiny_size(capsys):
    """``lfm2_band.measure`` at the tiny size: the reference itself passes, the
    program's own router and mixers read at rounding, and every wrong model
    and lower precision is refused by the driver's own checks (the rehearsal's
    limits are float32's, so bf16 itself is a lower precision here)."""
    from chipbench import lfm2_band

    cell = harness.load_cell("lfm2-train")
    names = ["bf16", "program_router", "program_mixers"] + lfm2_band.WRONG + lfm2_band.LOWER
    out = lfm2_band.measure(cell, [5], names, rehearsal=rehearsal())
    capsys.readouterr()
    by = {x["variant"]: x for x in out}
    assert set(by) == set(names) | {"float32"}
    exact = by["float32"]
    assert exact["correct"] is True and exact["failed_checks"] == []
    assert (exact["loss_gap"], exact["route_gap"], exact["grad_gap"]) == (0, 0, 0)
    assert by["program_router"]["router_gap"] < 1e-5
    assert set(by["program_mixers"]["mixer_gaps"]) >= {
        "sconv/y", "sconv/dsconv_w_in", "attn/y", "attn/dk_norm_w"}
    # (the program's mixers are handed bf16 inputs, as the trainer's are)
    assert by["program_mixers"]["mixer_gap"] < 2 * by["bf16"]["mixer_gap"]
    for name in ["bf16"] + lfm2_band.WRONG + lfm2_band.LOWER:
        assert by[name]["correct"] is False and by[name]["failed_checks"], name
    for name in ("softmax_router", "bias_weighed", "no_norm", "top_8", "bf16_router"):
        assert by[name]["router_gap"] > 1e-3, name
        assert any("the router alone" in m for m in by[name]["failed_checks"]), name
    for name in ("taps_2", "taps_4", "taps_reversed", "one_late", "no_gate_before",
                 "no_gate_after", "c_x_exchanged", "silu_after_taps", "qk_norm_whole",
                 "qk_norm_after_rope", "no_qk_norm", "one_gain"):
        assert by[name]["mixer_gap"] > 3 * by["bf16"]["mixer_gap"], (
            name, by[name]["mixer_gap"], by["bf16"]["mixer_gap"])
        assert by[name]["whole_model_of"] == "bf16"
