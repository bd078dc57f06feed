"""``granite4h-train`` without the chip: the cell at a tiny Granite-4.0-H shape
on the CPU through ``run_cell``'s rehearsal argument (untraced and traced, in
float32: at a hundred tokens bf16 noise drowns a gradient), its arithmetic,
its reducers, the band script with every wrong model and lower precision run
through the driver's own checks, and the lasting properties of what the cell
added (every name resolves, the two copies of the reference agree, the catalog
row's numbers are all there)."""

import json
import os

import pytest

from chipbench import arith_granite4h, harness, run
from chipbench.reducers import ssm_gate_norm_roofline, train_mfu_granite4h

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "granite4h-train"
HF = {"model_type": "granitemoehybrid", "vocab_size": 256, "hidden_size": 64,
      "intermediate_size": 96, "shared_intermediate_size": 96, "num_hidden_layers": 4,
      "layer_types": ["mamba", "mamba", "attention", "mamba"] * 2,
      "num_attention_heads": 4, "num_key_value_heads": 2, "hidden_act": "silu",
      "max_position_embeddings": 256, "attention_bias": False, "rms_norm_eps": 1e-5,
      "tie_word_embeddings": True, "mamba_n_heads": 8, "mamba_d_head": 16,
      "mamba_n_groups": 1, "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
      "mamba_conv_bias": True, "mamba_proj_bias": False, "mamba_chunk_size": 256,
      "num_local_experts": 0, "num_experts_per_tok": 0, "position_embedding_type": "nope",
      "normalization_function": "rmsnorm", "attention_multiplier": 0.03125,
      "embedding_multiplier": 12, "residual_multiplier": 0.22, "logits_scaling": 8}
NEW = {"granite4h_mfu_pct"}
# new in PR 55 too, read where the epilogue's KERNELS run: ``nemotron3-train``
# (on this cell's XLA route the scope's time leaves out part of the work)
ELSEWHERE = {"ssm_gate_norm_roofline_share"}
JOINED = {"step_ms_p50", "attn_kernel_share", "device_idle_share.train",
          "compiles_in_window.train", "recompiles_in_window.train",
          "trainer_host_ms_per_step", "idle_attributed_share", "scope_share.attn",
          "scope_share.mlp", "scope_share.loss", "scope_share.optimizer",
          "scope_share.none", "pass_share.forward", "pass_share.recompute",
          "pass_share.backward", "pass_share.update", "pass_share.other",
          "recompute_ms_per_step", "device_step_ms_p50", "train_step_peak_gb",
          "scope_share.ssm", "ssd_scan_roofline_share", "setup_init_s",
          "setup_step_build_s", "setup_trace_lower_s", "setup_backend_compile_s",
          "setup_cache_miss_programs"}
TINY_LIMITS = {"loss_tol": 1e-4, "grad_tol": 0.01, "grad_tol_embed": 0.01, "state_tol": 1e-3,
               "decay_tol": 1e-4, "stat_tol": 1e-5, "attn_tol": 1e-3}


def rehearsal(**traffic):
    from shuffle_exchange_tpu.models.hf import config_from_hf

    return {"model_cfg": config_from_hf(HF), "source_config": dict(HF),
            # (float32, and a warm-up of two steps: a handful of steps at the
            # cell's 200 move a tiny model's loss by less than a batch's noise)
            "train_config": {"bf16": {"enabled": False},
                             "scheduler": {"type": "WarmupCosineLR", "params": {
                                 "warmup_num_steps": 2, "total_num_steps": 1000}}},
            "traffic": {"seq": 64, "batch_per_chip": 2, **TINY_LIMITS, **traffic}}


def test_the_cell_is_files_and_entries():
    cell = harness.load_cell(CELL)
    assert cell["chips"] == 1
    assert cell["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert cell["traffic"]["driver"] == "train_steps_ssm_dense"
    assert (cell["traffic"]["seq"], cell["traffic"]["batch_per_chip"],
            cell["traffic"]["warmup_steps"], cell["traffic"]["trace_steps"]) == (8192, 1, 3, 4)
    assert {m["name"] for m in cell["per_layer"]} == JOINED | NEW
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tokens_per_s_chip", "setup_s"}
    src = cell["config"]
    assert (src["published"]["num_hidden_layers"], src["published"]["vocab_size"]) == (40, 100352)
    # every published width, unchanged
    assert (src["hidden_size"], src["shared_intermediate_size"], src["num_attention_heads"],
            src["num_key_value_heads"], src["mamba_n_heads"], src["mamba_d_head"],
            src["mamba_n_groups"], src["mamba_d_state"], src["mamba_d_conv"]) == (
        2048, 8192, 32, 8, 64, 64, 1, 128, 4)
    assert (src["embedding_multiplier"], src["residual_multiplier"],
            src["attention_multiplier"], src["logits_scaling"]) == (12, 0.22, 0.015625, 8)
    assert (src["num_hidden_layers"], src["vocab_size"]) == (10, 12544)
    assert src["layer_types"][:10] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    for key in ("source", "assumed", "deployment", "counts"):
        assert src[key]
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    for entry in bench["configs"] + bench["workloads"]:
        assert len(entry["why"]) <= 200 and len(entry.get("source", "")) <= 200


def test_every_number_of_the_catalog_row_is_there():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog)
               if '"granite-4.0-h-micro"' in line)
    cell = harness.load_cell(CELL)
    src = cell["config"]
    assert cell["source"] == row["source_url"] == src["source"]
    differs = {k for k, v in row["config"].items() if src.get(k, "missing") != v}
    assert differs == {"num_hidden_layers", "vocab_size"} == set(cell["reduced"])


def test_the_arithmetic_counts_the_issues_parameters():
    from shuffle_exchange_tpu.models.hf import config_from_hf

    src = harness.load_cell(CELL)["config"]
    counts = src["counts"]
    assert arith_granite4h.parameters(src) == 772_160_448 == counts["parameters"]
    assert arith_granite4h.parameters(src, 40, 100352) == src["published"]["parameters"]
    assert counts["mamba_layer"] == counts["mamba_mixer"] + counts["gated_mlp"] + counts["block_norms"]
    assert counts["attention_layer"] == (counts["attention_mixer"] + counts["gated_mlp"]
                                         + counts["block_norms"])
    assert counts["layers"] == 9 * counts["mamba_layer"] + counts["attention_layer"]
    assert counts["parameters"] == counts["layers"] + counts["embedding_and_final_norm"]
    cfg = config_from_hf(src)
    assert arith_granite4h.matmul_params_per_token(cfg) == 771_883_008 == counts["matmul_parameters"]
    # 6 x 771.9 M x 16,384 = 75.9 TFLOP, the attention layer's causal core 3.3,
    # the scans 1.4: ~81 TFLOP a step
    step = arith_granite4h.train_flops_per_token(cfg, 16384) * 16384
    assert 80e12 < step < 82e12
    # 10 reads and writes of [8192, 4096] bf16 a layer, nine layers: 6.04 GB
    assert arith_granite4h.gate_norm_bytes_per_step(cfg, 1, 8192) == 10 * 8192 * 4096 * 2 * 9


def test_every_name_the_cell_brought_resolves():
    import importlib

    cell = harness.load_cell(CELL)
    for name in NEW | ELSEWHERE:
        spec = harness.read_json(f"{cell['bench_dir']}/layer_metrics/{name}.json")
        assert callable(importlib.import_module(
            "chipbench.reducers." + spec["reducer"]).reduce), name
    assert callable(importlib.import_module(
        "chipbench.drivers." + cell["traffic"]["driver"]).run)
    body = lambda path: open(os.path.join(ROOT, path)).read().split('"""', 2)[2]
    assert body("chipbench/reference_granite4h.py") == body(
        "shuffle_exchange_tpu/models/reference_granite4h.py")
    assert "shuffle_exchange_tpu" not in "".join(
        line for line in body("chipbench/reference_granite4h.py").splitlines()
        if line.startswith(("import", "from")))


def test_the_reducers_read_nothing_from_a_program_without_the_configuration():
    """On the parent's side of a traced run of another cell the facts hold no
    such count and no state-space model: the reducers return None and do not
    raise."""
    assert train_mfu_granite4h.reduce({"result": {}, "peaks": {}}) is None
    assert train_mfu_granite4h.reduce(
        {"result": {"facts": {"step_s": [1.0]}}, "peaks": {}}) is None
    assert ssm_gate_norm_roofline.reduce({"result": {}, "peaks": {}}) is None
    assert ssm_gate_norm_roofline.reduce(
        {"result": {"facts": {"model_cfg": object(), "traced_steps": 4}}, "peaks": {}}) is None


def test_the_epilogues_share_is_read_where_its_kernels_run(monkeypatch, capsys):
    """The reducer on a recorded scope time: a share where the program states
    a kernel route, None (and the line all the same) where it states XLA's."""
    from shuffle_exchange_tpu.models.hf import config_from_hf

    cfg = config_from_hf(harness.load_cell(CELL)["config"])
    monkeypatch.setattr(ssm_gate_norm_roofline.xscope, "table", lambda ctx: object())
    monkeypatch.setattr(ssm_gate_norm_roofline.xscope, "op_self_times", lambda tab: [
        ("fusion.1", "jit(step)/layers/attn_out/ssm_out_norm/mul", 4 * 10_000_000),
        ("fusion.2", "jit(step)/layers/attn_out/ssm_out/dot_general", 4 * 50_000_000)])
    ctx = lambda route: {"cell": {"name": CELL}, "peaks": {"hbm_bytes_per_s": 819e9},
                         "result": {"facts": {"model_cfg": cfg, "traced_steps": 4, "batch": 1,
                                              "chips": 1, "seq": 8192,
                                              "ssm_gate_norm_route": route}}}
    least_ms = 10 * 8192 * 4096 * 2 * 9 / 819e9 * 1e3
    assert ssm_gate_norm_roofline.reduce(ctx("pallas")) == pytest.approx(100 * least_ms / 10.0)
    assert ssm_gate_norm_roofline.reduce(ctx("xla")) is None
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert [line["route"] for line in lines] == ["pallas", "xla"]
    assert all(line["scope_ms_per_step"] == pytest.approx(10.0) for line in lines)


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_granite4h_train_at_tiny_size(trace, capsys):
    cell = harness.load_cell(CELL)
    out = json.loads(run.run_cell(CELL, 2 ** 31 + 4242, 3.0, trace, rehearsal=rehearsal()))
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith('{"phase"')]
    window = next(line for line in lines if line["phase"] == "window")
    assert out["correct"] is True, window["failed_checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1
    setup = next(line for line in lines if line["phase"] == "setup")
    assert setup["zero_stage"] == 3
    assert set(setup["routes"]) >= {"ssd", "ssm_conv", "ssm_gate_norm", "attn_core"}
    assert setup["ssm_scan_chunks"] == 1 * 2 * 3      # chunks x rows x state-space layers
    assert setup["multipliers"] == {"embed_scale": 12.0, "residual_scale": 0.22,
                                    "attn_scale": 0.03125, "logit_divisor": 8.0}
    if not trace:
        assert set(out["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
        return
    assert set(out["metrics"]) <= {m["name"] for m in cell["per_layer"]}
    assert out["metrics"]["granite4h_mfu_pct"]["value"] > 0.0
    assert out["metrics"]["compiles_in_window.train"]["value"] == 0.0


def test_no_counter_makes_the_run_incorrect():
    from chipbench.drivers import train_steps_ssm_dense as driver

    routes = {"ssd": "xla", "ssm_conv": "xla", "ssm_gate_norm": "xla", "ssd_alone": "xla",
              "step_kernels": None}
    good = {"losses": [5.0, 4.9], "reference_loss": 5.0, "grad_gaps": {"a": 0.001, "embed": 0.001},
            "scan_gaps": {"scan/y": 1e-4, "scan32/y": 1e-5}, "stat_gap": 1e-7,
            "attn_gaps": {"attn/y": 1e-5}, "scan_chunks": 6, "scan_chunks_expected": 6,
            "routes": routes}
    traffic = rehearsal()["traffic"]
    assert driver.failed_checks(good, traffic) == []
    for chunks in (0, None, 5):
        failed = driver.failed_checks({**good, "scan_chunks": chunks}, traffic)
        assert len(failed) == 1 and "ssm_scan_chunks" in failed[0]
    # a compiled step that does not hold the kernels its routes state
    kernels = {"ssd_bwd": False, "ssm_conv_bwd": True, "ssm_gate_norm_bwd": False}
    failed = driver.failed_checks(
        {**good, "routes": {**routes, "ssd": "pallas", "ssd_alone": "pallas",
                            "ssm_conv": "pallas", "step_kernels": kernels}}, traffic)
    assert len(failed) == 1 and "bear out" in failed[0]


def test_the_band_refuses_every_wrong_model_and_passes_the_base():
    """Every wrong model and lower precision of the issue's list through the
    driver's own ``failed_checks`` at the tiny size in float32 (where the
    stated precision IS float32: ``bf16`` is then the reference itself and
    ``multipliers_bf16`` the same function, both correct at a gap of 0): each
    other one is refused by at least one check."""
    from chipbench import granite4h_band as band

    cell = harness.load_cell(CELL)
    out = band.measure(cell, [7], ["bf16", *band.WRONG, *band.LOWER], rehearsal=rehearsal())
    by = {line["variant"]: line for line in out}
    assert set(by) == {"bf16", *band.WRONG, *band.LOWER}
    for name in ("bf16", "multipliers_bf16"):
        assert by[name]["correct"] and by[name]["loss_gap"] == 0.0, by[name]["failed_checks"]
    for name in [*band.WRONG, "bf16_state", "bf16_decay", "bf16_stat"]:
        assert not by[name]["correct"], (name, by[name]["loss_gap"], by[name]["grad_gap"])
    # the mechanism a variant changes shows it alone
    assert by["attn_scale_1_8"]["attn_gaps"]["attn/y"] > 1e-3
    assert max(by["bf16_state"]["scan_gaps"].values()) > 1e-3
    for name in ("norm_before_gate", "eight_groups", "bf16_stat"):
        assert by[name]["stat_gap"] > 1e-5, (name, by[name]["stat_gap"])
    # a log judged again reads the same
    assert all(band.judged(line, rehearsal()["traffic"])["correct"] == line["correct"]
               for line in out)
