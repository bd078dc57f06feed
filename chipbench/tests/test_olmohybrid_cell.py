"""``olmohybrid-zero3-x4`` without the chip: the cell at a tiny Olmo-Hybrid
shape on four virtual CPU devices through ``run_cell``'s rehearsal argument
(untraced and traced, in float32: at a hundred tokens bf16 noise drowns a
gradient), its arithmetic, its reducer, the band script with every wrong model
and lower precision run through the driver's own checks, and the lasting
properties of what the cell added (every name resolves, the two copies of the
reference agree)."""

import json
import os

import pytest

from chipbench import arith_olmohybrid, harness, run
from chipbench.reducers import train_mfu_olmohybrid

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HF = {"model_type": "olmo_hybrid", "vocab_size": 256, "hidden_size": 64,
      "intermediate_size": 96, "num_hidden_layers": 4, "num_attention_heads": 4,
      "num_key_value_heads": 4, "hidden_act": "silu", "max_position_embeddings": 256,
      "attention_bias": False, "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
      "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 2,
      "linear_num_key_heads": 2, "linear_num_value_heads": 2,
      "linear_key_head_dim": 12, "linear_value_head_dim": 24,
      "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
      "rope_parameters": {"rope_theta": None}}
NEW = {"olmohybrid_mfu_pct"}
JOINED = {"step_ms_p50", "attn_kernel_share", "device_idle_share.train",
          "compiles_in_window.train", "recompiles_in_window.train",
          "trainer_host_ms_per_step", "idle_attributed_share", "scope_share.attn",
          "scope_share.mlp", "scope_share.loss", "scope_share.optimizer",
          "scope_share.none", "pass_share.forward", "pass_share.recompute",
          "pass_share.backward", "pass_share.update", "pass_share.other",
          "recompute_ms_per_step", "device_step_ms_p50", "train_step_peak_gb",
          "collective_sync_share", "collective_op_share", "scope_share.gdn",
          "gdn_scan_roofline_share"}


def rehearsal(**traffic):
    from shuffle_exchange_tpu.models.hf import config_from_hf

    return {"model_cfg": config_from_hf(HF), "source_config": dict(HF),
            "train_config": {"bf16": {"enabled": False}},
            "traffic": {"seq": 64, "batch_per_chip": 2, "loss_tol": 1e-4,
                        "grad_tol": 0.01, "state_tol": 1e-3, "stat_tol": 1e-5,
                        **traffic}}


def test_the_cell_is_files_and_entries():
    cell = harness.load_cell("olmohybrid-zero3-x4")
    assert cell["chips"] == 4
    assert cell["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert cell["traffic"]["driver"] == "train_steps_gdn"
    assert (cell["traffic"]["seq"], cell["traffic"]["batch_per_chip"],
            cell["traffic"]["warmup_steps"], cell["traffic"]["trace_steps"]) == (8192, 2, 3, 4)
    assert {m["name"] for m in cell["per_layer"]} == JOINED | NEW
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tokens_per_s_chip", "setup_s"}
    src = cell["config"]
    assert src["published"] == {"num_hidden_layers": 32, "vocab_size": 100352}
    # every published width, unchanged
    assert (src["hidden_size"], src["intermediate_size"], src["num_attention_heads"],
            src["num_key_value_heads"], src["linear_num_key_heads"],
            src["linear_num_value_heads"], src["linear_key_head_dim"],
            src["linear_value_head_dim"], src["linear_conv_kernel_dim"]) == (
        3840, 11008, 30, 30, 30, 30, 96, 192, 4)
    assert (src["num_hidden_layers"], src["vocab_size"]) == (4, 12544)
    assert src["chipbench"]["mesh"] == {"fsdp": "chips"}
    for key in ("source", "assumed", "deployment"):
        assert src[key]


def test_every_number_of_the_catalog_row_is_there():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog) if '"Olmo-Hybrid-7B"' in line)
    cell = harness.load_cell("olmohybrid-zero3-x4")
    src = cell["config"]
    assert cell["source"] == row["source_url"] == src["source"]
    differs = {k for k, v in row["config"].items() if src.get(k, "missing") != v}
    assert differs == {"num_hidden_layers", "vocab_size"} == set(cell["reduced"])


def test_the_arithmetic_counts_the_issues_parameters():
    from shuffle_exchange_tpu.models.hf import config_from_hf

    src = harness.load_cell("olmohybrid-zero3-x4")["config"]
    assert arith_olmohybrid.parameters(src) == 928_862_196
    assert arith_olmohybrid.parameters(src, 32, 100352) == 7_430_870_688
    cfg = config_from_hf(src)
    assert arith_olmohybrid.matmul_params_per_token(cfg) == 880_512_000
    # 6 x 880.5 M x 16,384 + the attention layer's causal core + the rule: ~91 TFLOP
    step = arith_olmohybrid.train_flops_per_token(cfg, 8192) * 16384
    assert 88e12 < step < 93e12


def test_every_name_the_cell_brought_resolves():
    import importlib

    cell = harness.load_cell("olmohybrid-zero3-x4")
    for name in NEW:
        spec = harness.read_json(f"{cell['bench_dir']}/layer_metrics/{name}.json")
        assert callable(importlib.import_module(
            "chipbench.reducers." + spec["reducer"]).reduce), name
    assert callable(importlib.import_module(
        "chipbench.drivers." + cell["traffic"]["driver"]).run)
    body = lambda path: open(os.path.join(ROOT, path)).read().split('"""', 2)[2]
    assert body("chipbench/reference_olmohybrid.py") == body(
        "shuffle_exchange_tpu/models/reference_olmohybrid.py")


def test_the_reducer_reads_nothing_from_a_program_without_the_configuration():
    """On the parent's side of a traced run of another cell the facts hold no
    DeltaNet model: the reducer returns None and does not raise."""
    assert train_mfu_olmohybrid.reduce({"result": {}, "peaks": {}}) is None
    assert train_mfu_olmohybrid.reduce(
        {"result": {"facts": {"step_s": [1.0], "model_cfg": object()}}, "peaks": {}}) is None


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_olmohybrid_zero3_x4_at_tiny_size(trace, capsys):
    cell = harness.load_cell("olmohybrid-zero3-x4")
    out = json.loads(run.run_cell("olmohybrid-zero3-x4", 2 ** 31 + 4242, 3.0, trace,
                                  rehearsal=rehearsal()))
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 4
    setup = next(json.loads(line) for line in capsys.readouterr().out.splitlines()
                 if line.startswith('{"phase": "setup"'))
    assert setup["mesh"] == {"fsdp": 4} and setup["zero_stage"] == 3
    assert set(setup["routes"]) >= {"gated_delta", "gdn_prologue"}
    assert setup["gdn_scan_chunks"] == 1 * 2 * 3      # chunks x rows a chip x layers
    if not trace:
        assert set(out["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
        return
    assert set(out["metrics"]) <= {m["name"] for m in cell["per_layer"]}
    assert out["metrics"]["olmohybrid_mfu_pct"]["value"] > 0.0
    assert out["metrics"]["compiles_in_window.train"]["value"] == 0.0


def test_no_counter_makes_the_run_incorrect():
    from chipbench.drivers import train_steps_gdn as driver

    good = {"losses": [5.0, 4.9], "reference_loss": 5.0, "grad_gaps": {"a": 0.001},
            "state_gaps": {"o": 1e-4}, "stat_gap": 1e-7, "scan_chunks": 6}
    traffic = rehearsal()["traffic"]
    assert driver.failed_checks(good, traffic) == []
    for chunks in (0, None):
        failed = driver.failed_checks({**good, "scan_chunks": chunks}, traffic)
        assert len(failed) == 1 and "gdn_scan_chunks" in failed[0]


def test_the_band_refuses_every_wrong_model_and_passes_the_program():
    """Every wrong model of the issue's list through the driver's own
    ``failed_checks`` at the tiny size in float32 (where the stated precision
    IS float32, so the lower-precision variants must fail and ``bf16`` itself
    is no band): each is refused by at least one check; the program passes."""
    from chipbench import olmohybrid_band as band

    cell = harness.load_cell("olmohybrid-zero3-x4")
    names = ["program_rule", "program", *band.WRONG]
    out = band.measure(cell, [7], names, rehearsal=rehearsal(
        loss_tol=1e-4, grad_tol=0.01, state_tol=1e-3))
    by = {line["variant"]: line for line in out}
    assert by["program"]["correct"] and by["float32"]["correct"]
    assert by["program_rule"]["state_gap"] < 1e-4
    assert by["program"]["stat_gap"] < 1e-6 < 1e-4 < by["bf16_qk_stat"]["stat_gap"]
    for name in band.WRONG:
        assert not by[name]["correct"], (name, by[name]["loss_gap"], by[name]["grad_gap"])
        assert by[name]["failed_checks"], name
