"""``kanana2-train`` without the chip: the cell at a tiny kanana-2-shaped size
on the CPU through ``run_cell``'s rehearsal argument (untraced and traced, in
float32: at a hundred tokens bf16 noise drowns a gradient), its arithmetic, its
new reducers on a made-up trace, the band script with every wrong model and
lower precision run through the driver's own checks, and the lasting
properties of what the cell added (every name resolves, the two copies of the
reference agree)."""

import json
import os

import pytest

from chipbench import arith_mla, harness, run
from chipbench.reducers import gmm_roofline_held_routed, mla_core_roofline, train_mfu_mla

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HF = {"model_type": "deepseek_v3", "architectures": ["DeepseekV3ForCausalLM"],
      "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
      "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "qk_head_dim": 12,
      "v_head_dim": 8, "kv_lora_rank": 16, "q_lora_rank": None,
      "intermediate_size": 96, "moe_intermediate_size": 32,
      "n_routed_experts": 8, "num_experts_per_tok": 3, "n_shared_experts": 2,
      "first_k_dense_replace": 1, "moe_layer_freq": 1, "num_hidden_layers": 3,
      "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
      "scoring_func": "sigmoid", "norm_topk_prob": True,
      "routed_scaling_factor": 2.448, "rope_theta": 1000000,
      "rope_scaling": None, "rope_interleave": True, "rms_norm_eps": 1e-6,
      "attention_bias": False, "hidden_act": "silu", "vocab_size": 256,
      "max_position_embeddings": 128, "tie_word_embeddings": False,
      "num_experts_held": 4, "expert_first": 0, "expert_buffer_factor": 2.0,
      "aux_loss_alpha": 0.01, "seq_aux": True, "bias_update_speed": 0.01}
JOINED = {"step_ms_p50", "attn_kernel_share", "device_idle_share.train",
          "compiles_in_window.train", "recompiles_in_window.train",
          "trainer_host_ms_per_step", "idle_attributed_share", "scope_share.attn",
          "scope_share.mlp", "scope_share.loss", "scope_share.optimizer",
          "scope_share.none", "scope_share.moe_experts", "scope_share.moe_route",
          "gmm_kernel_share", "moe_expert_load_max_over_mean",
          "moe_dropped_token_share", "moe_held_row_share"}
NEW = {"scope_share.mla", "mla_core_roofline_share",
       "gmm_roofline_share.held_routed", "mla_active_mfu_pct"}
DEVICE_TRACE = NEW - {"mla_active_mfu_pct"}


def rehearsal(**traffic):
    from shuffle_exchange_tpu.models.hf import config_from_hf

    return {"model_cfg": config_from_hf(HF), "source_config": dict(HF),
            "train_config": {"bf16": {"enabled": False}},
            "traffic": {"seq": 64, "batch_per_chip": 2, "loss_tol": 1e-4,
                        "route_tol": 0.002, "grad_tol": 0.01, "grad_tol_routed": 0.01,
                        "router_tol": 1e-5, "weight_tol": 1e-4, "mixer_tol": 1e-3,
                        "select_bias_std": 0.05, **traffic}}


def test_the_cell_is_files_and_entries():
    cell = harness.load_cell("kanana2-train")
    assert cell["chips"] == 1
    assert cell["reduced"] == ["num_hidden_layers", "num_experts_held", "vocab_size"]
    assert cell["traffic"]["driver"] == "train_steps_mla"
    assert (cell["traffic"]["seq"], cell["traffic"]["batch_per_chip"]) == (8192, 2)
    names = {m["name"] for m in cell["per_layer"]}
    assert names == JOINED | NEW
    assert not names & {"mfu_pct", "moe_active_mfu_pct", "gmm_roofline_share",
                        "gmm_roofline_share.held", "hybrid_active_mfu_pct"}
    assert {m["name"] for m in cell["end_to_end"]} == {
        "train_tokens_per_s_chip", "setup_s"}
    src = cell["config"]
    assert src["published"] == {"num_hidden_layers": 48, "num_experts_held": 128,
                                "vocab_size": 128256}
    # every published width, unchanged
    assert (src["hidden_size"], src["num_attention_heads"], src["qk_nope_head_dim"],
            src["qk_rope_head_dim"], src["v_head_dim"], src["kv_lora_rank"],
            src["n_routed_experts"], src["num_experts_per_tok"],
            src["moe_intermediate_size"], src["n_shared_experts"],
            src["intermediate_size"], src["routed_scaling_factor"],
            src["first_k_dense_replace"]) == (
        2048, 32, 128, 64, 128, 512, 128, 6, 768, 2, 6144, 2.448, 1)
    assert (src["num_hidden_layers"], src["num_experts_held"], src["vocab_size"]) == (
        5, 16, 16032)
    for key in ("source", "assumed", "deployment"):
        assert src[key]


def test_every_number_of_the_catalog_row_is_there():
    """The guide's rule: the file holds every number of the row's ``config``
    under the same key; what differs is listed in ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog)
               if "kanana-2-30b-a3b-instruct-2601" in line)
    cell = harness.load_cell("kanana2-train")
    src = cell["config"]
    assert cell["source"] == row["source_url"] == src["source"]
    differs = {k for k, v in row["config"].items() if src.get(k, "missing") != v}
    assert differs == {"num_hidden_layers", "vocab_size"}
    assert differs | {"num_experts_held"} == set(cell["reduced"])


def test_every_name_the_cell_brought_resolves():
    import importlib

    cell = harness.load_cell("kanana2-train")
    for name in NEW:
        spec = harness.read_json(f"{cell['bench_dir']}/layer_metrics/{name}.json")
        assert callable(importlib.import_module(
            "chipbench.reducers." + spec["reducer"]).reduce), name
    assert callable(importlib.import_module(
        "chipbench.drivers." + cell["traffic"]["driver"]).run)
    body = lambda path: open(os.path.join(ROOT, path)).read().split('"""', 2)[2]
    assert body("chipbench/reference_kanana2.py") == body(
        "shuffle_exchange_tpu/models/reference_kanana2.py")
    assert "shuffle_exchange_tpu" not in body("chipbench/reference_kanana2.py").split(
        "import jax")[1]


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_kanana2_train_at_tiny_size(trace, capsys):
    cell = harness.load_cell("kanana2-train")
    out = json.loads(run.run_cell("kanana2-train", 2 ** 31 + 4242, 3.0, trace,
                                  rehearsal=rehearsal()))
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith('{"phase"')]
    setup = next(x for x in lines if x["phase"] == "setup")
    assert setup["routes"]["mla_core"] == "reference"      # what the CPU runs
    assert out["correct"] is True, [x for x in lines if x["phase"] == "window"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    if not trace:
        assert set(out["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
        return
    assert set(out["metrics"]) <= {m["name"] for m in cell["per_layer"]}
    # a CPU trace has no device plane: the metrics that read device ops are
    # left out here and read a made-up table below
    assert NEW - DEVICE_TRACE <= set(out["metrics"]), out["metrics"]
    assert out["metrics"]["moe_dropped_token_share"]["value"] == 0.0
    assert out["metrics"]["moe_expert_load_max_over_mean"]["value"] >= 1.0
    assert 25.0 < out["metrics"]["moe_held_row_share"]["value"] < 75.0   # 4 of 8 held
    assert out["metrics"]["mla_active_mfu_pct"]["value"] > 0.0
    assert out["metrics"]["compiles_in_window.train"]["value"] == 0.0


def test_a_dropped_row_makes_the_run_incorrect(capsys, monkeypatch):
    from shuffle_exchange_tpu.moe import layer

    monkeypatch.setattr(layer, "held_buffer_rows", lambda *a, **k: 64)
    reh = rehearsal(grad_tol=10.0, grad_tol_routed=10.0, loss_tol=10.0)
    out = json.loads(run.run_cell("kanana2-train", 4243, 2.0, False, rehearsal=reh))
    assert out["correct"] is False
    window = [json.loads(line) for line in capsys.readouterr().out.splitlines()
              if line.startswith('{"phase": "window"')][-1]
    assert window["moe_dropped_token_share"] > 0
    assert any("dropped" in m for m in window["failed_checks"])


def test_the_band_script_at_tiny_size():
    """Every wrong model and every lower precision, in the program's place,
    fails the driver's own checks (the rehearsal's are float32's, so bf16
    itself is a lower precision here); the reference itself and the program's
    router pass; the router's wrong forms fail the router's own reading."""
    from chipbench import kanana2_band as band

    names = ["program_router", "bf16"] + band.WRONG + band.LOWER
    got = {r["variant"]: r for r in band.measure(
        harness.load_cell("kanana2-train"), [2 ** 31 + 5], names, rehearsal())}
    assert set(got) == {"float32", *names}
    exact = got["float32"]
    assert (exact["loss_gap"], exact["route_gap"], exact["grad_gap"]) == (0, 0, 0)
    assert exact["correct"] is True and exact["failed_checks"] == []
    assert got["program_router"]["router_gap"] < 1e-5
    for name in ("bias_weighed", "softmax_router", "no_scale", "no_norm", "bf16_router"):
        assert got[name]["router_gap"] > 1e-3, name
        assert any("the router alone" in m for m in got[name]["failed_checks"]), name
    assert got["no_bias"]["route_gap"] > 0.02
    # the program's OWN counter tells a bias that is weighed, the mixer's own
    # reading a softmax below float32
    assert got["bias_weighed"]["weight_gap"] > 10 * got["bf16"]["weight_gap"]
    assert any("mean weight" in m for m in got["bias_weighed"]["failed_checks"])
    assert got["bf16_softmax"]["mixer_gap"] > got["bf16"]["mixer_gap"]
    assert exact["weight_gap"] == 0 and exact["mixer_gap"] == 0
    for name in ["bf16"] + band.WRONG + band.LOWER:
        assert got[name]["correct"] is False and got[name]["failed_checks"], name


def test_the_checks_refuse_each_reading_alone():
    from chipbench.drivers.train_steps_mla import failed_checks

    traffic = {"loss_tol": 0.001, "route_tol": 0.01, "grad_tol": 0.1,
               "grad_tol_routed": 0.3, "router_tol": 1e-4, "weight_tol": 1e-3,
               "mixer_tol": 0.01}
    sound = {"losses": [5.0, 4.9, 4.8], "first_loss_again": 4.7, "reference_loss": 5.0005, "route_gap": 0.005,
             "held_gap": 0.001, "counters_add_up": True, "overflow": [0, 0],
             "bias_grad": 0.0, "bias_update_gap": 0.0,
             "grad_gaps": {"embed": 0.05, "layers/moe_gate": 0.2},
             "router_gaps": {"choice": 0.0, "weight": 2e-7}, "weight_gap": 1e-5,
             "mixer_gaps": {"y": 0.004, "dmla_wq": 0.006}}
    assert failed_checks(sound, traffic) == []
    for change, said in (
            ({"first_loss_again": 5.01}, "did not fall"),
            ({"losses": [5.0, float("nan")]}, "non-finite"),
            ({"reference_loss": 5.01}, "first loss"),
            ({"route_gap": 0.02}, "expert counts"),
            ({"held_gap": 0.02}, "held rows differ"),
            ({"counters_add_up": False}, "do not add up"),
            ({"overflow": [0, 3]}, "dropped"),
            ({"bias_grad": 1e-9}, "selection bias"),
            ({"bias_update_gap": 3e-5}, "aux-free update"),
            ({"bias_update_gap": None}, "aux-free update"),
            ({"grad_gaps": {"embed": 0.11}}, "gradient of embed"),
            ({"grad_gaps": {"layers/moe_gate": 0.31}}, "moe_gate"),
            ({"grad_gaps": {"embed": float("nan")}}, "gradient of embed"),
            ({"router_gaps": {"choice": 0.0, "weight": 4e-3}}, "the router alone"),
            ({"router_gaps": {"choice": float("nan"), "weight": 0.0}}, "the router alone"),
            ({"weight_gap": 0.02}, "mean weight of a token-choice"),
            ({"weight_gap": None}, "mean weight of a token-choice"),
            ({"mixer_gaps": {"y": 0.004, "dmla_wq": 0.03}}, "mixer alone: dmla_wq"),
            ({"mixer_gaps": {"y": float("nan")}}, "mixer alone: y")):
        failed = failed_checks({**sound, **change}, traffic)
        assert len(failed) >= 1 and said in failed[0], (change, failed)
        assert len(failed) == 1 or "nan" in str(change), (change, failed)
    none = failed_checks({**sound, "route_gap": None, "held_gap": None,
                          "counters_add_up": False, "overflow": [None, None]}, traffic)
    assert any("handed out no" in m for m in none)


def published():
    from shuffle_exchange_tpu.models.hf import config_from_hf

    return config_from_hf(harness.load_cell("kanana2-train")["config"])


def test_the_arithmetic_counts_what_it_says():
    cfg = published()
    mla = 2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 32 * 128 * 2048
    assert arith_mla.mla_params(cfg) == mla == 26_345_472
    assert (arith_mla.mla_layers(cfg), arith_mla.dense_layers(cfg), cfg.routed_layers) == (5, 1, 4)
    assert arith_mla.matmul_params_per_token(cfg) == (
        5 * mla + 3 * 2048 * 6144 + 4 * (2048 * 128 + 3 * 2048 * 1536) + 2048 * 16032)
    # 2 rows of 8192: forward 2 x 32 x 8192^2 x 320, backward twice that, 5 layers
    assert arith_mla.mla_core_flops_per_step(cfg, 2, 8192) == 3 * 5 * 2 * 32 * 8192 ** 2 * 320
    assert arith_mla.mla_core_bytes_per_step(cfg, 2, 8192) == 5 * 2 * 8192 * 32 * 2 * (
        (2 * 192 + 2 * 128) + (2 * 192 + 3 * 128) + (2 * 192 + 128))
    assert arith_mla.train_flops_per_token(cfg, 8192, 3.0) == (
        6 * arith_mla.matmul_params_per_token(cfg) + 6 * 3 * 2048 * 768 * 3.0
        + 3 * 5 * 8192 * 32 * 320)
    # one rank's share of the grouped GEMMs: the ROUTED layers, the EXPERT width
    assert arith_mla.held_gemm_flops_per_step(cfg, 49152) == 9 * 2 * 49152 * 2048 * 768
    assert arith_mla.held_gemm_bytes_per_step(cfg, 49152) == 9 * 2 * (
        49152 * (2048 + 768) + 4 * 16 * 2048 * 768)


def test_the_new_reducers_on_a_made_up_trace(capsys):
    cfg = published()
    peaks = harness.chip_peaks("TPU v5 lite")
    cell = harness.load_cell("kanana2-train")
    paths = ["", "jit(train_step)/jvp(layers)/while/body/attn_core/pallas_call",
             "jit(train_step)/transpose(jvp(layers))/while/body/"
             "transpose(jvp(attn_core))/pallas_call",
             "jit(train_step)/jvp(layers)/while/body/attn_qkv/mla_kv_up/dot_general",
             "jit(train_step)/jvp(layers)/while/body/attn_qkv/mla_q/dot_general"]
    ms = 1_000_000
    table = {"devices": [{"name": "/device:TPU:0", "modules": [],
                          "ops": [["fusion.1", 0, 100 * ms, 1], ["fusion.2", 100 * ms, 200 * ms, 2],
                                  ["fusion.3", 300 * ms, 50 * ms, 3],
                                  ["fusion.4", 350 * ms, 50 * ms, 4]]}],
             "scopes": paths, "program_ops": {},
             "host": [["cb:window", 0, 400 * ms, 0, {}]]}
    facts = {"model_cfg": cfg, "traced_steps": 2, "tokens_per_step": 16384,
             "chips": 1, "seq": 8192, "batch": 2, "step_s": [0.9, 1.0, 0.9],
             "held_rows_per_step": 49152.0,
             "mla_flops_per_token": arith_mla.train_flops_per_token(cfg, 8192, 3.0)}
    ctx = {"cell": cell, "_xscope": table, "peaks": peaks,
           "result": {"facts": facts},
           "trace_summary": {"ops": {"fusion": 4e-3, "gmm.1": 20e-3, "tgmm": 10e-3}}}
    spec = harness.read_json(f"{cell['bench_dir']}/layer_metrics/scope_share.mla.json")
    from chipbench.reducers import scope_share

    assert scope_share.reduce(ctx, **spec["args"]) == 12.5      # mla_kv_up; mla_q is not in it
    share = mla_core_roofline.reduce(ctx, scope="attn_core")
    by_flops = arith_mla.mla_core_flops_per_step(cfg, 2, 8192) / peaks["bf16_flops_per_s"]
    assert share == pytest.approx(100.0 * by_flops / 0.150)     # 300 ms over 2 steps
    assert 60 < share < 100
    line = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()
            if '"mla_core_roofline"' in x][-1]
    assert line["binds"] == "bf16_flops_per_s"
    assert line["scope_ms_per_step"] == pytest.approx(150.0)
    assert train_mfu_mla.reduce(ctx) == pytest.approx(
        100.0 * facts["mla_flops_per_token"] * 16384 / 0.9 / peaks["bf16_flops_per_s"])
    held = gmm_roofline_held_routed.reduce(ctx, pattern="gmm|tgmm")
    # at 768 rows an expert the operations bind (7.1 ms against 5.3 of bytes)
    assert held == pytest.approx(100.0 * arith_mla.held_gemm_flops_per_step(cfg, 49152)
                                 / peaks["bf16_flops_per_s"] / 15e-3)
    # the older reading counts the dense layer's weights as a fifth routed one's
    assert arith_mla.held_gemm_bytes_per_step(cfg, 49152) < __import__(
        "chipbench.arith_hybrid", fromlist=["x"]).held_gemm_bytes_per_step(cfg, 49152)
    # nothing to read -> None, not an exception
    assert gmm_roofline_held_routed.reduce(
        dict(ctx, trace_summary={"ops": {"fusion": 1.0}}), pattern="gmm|tgmm") is None
    ctx["_xscope"] = dict(table, scopes=["", "a/b", "a/c", "a/d", "a/e"])
    assert mla_core_roofline.reduce(ctx, scope="attn_core") is None
    ctx["result"] = {}
    assert mla_core_roofline.reduce(ctx, scope="attn_core") is None
    assert gmm_roofline_held_routed.reduce(ctx, pattern="gmm|tgmm") is None
    assert train_mfu_mla.reduce(ctx) is None
    from shuffle_exchange_tpu.models.transformer import tiny

    ctx["_xscope"] = table
    ctx["result"] = {"facts": dict(facts, model_cfg=tiny())}
    assert mla_core_roofline.reduce(ctx, scope="attn_core") is None
