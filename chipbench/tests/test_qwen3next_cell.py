"""``qwen3next-train`` without the chip: the cell at a tiny Qwen3-Next-shaped
size on the CPU through ``run_cell``'s rehearsal argument (untraced and
traced, in float32: at a hundred tokens bf16 noise drowns a gradient), its
arithmetic, its new reducers on a made-up trace, the band script with every
wrong model and lower precision run through the driver's own checks, and the
lasting properties of what the cell added (every name resolves, the two
copies of the reference agree)."""

import json
import os

import pytest

from chipbench import arith_hybrid, harness, run
from chipbench.reducers import gdn_roofline, gmm_roofline_held, train_mfu_hybrid

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HF = {"model_type": "qwen3_next", "architectures": ["Qwen3NextForCausalLM"],
      "hidden_size": 64, "head_dim": 32, "num_attention_heads": 4,
      "num_key_value_heads": 2, "num_hidden_layers": 8,
      "full_attention_interval": 4, "partial_rotary_factor": 0.25,
      "rope_theta": 10000000, "rms_norm_eps": 1e-6,
      "linear_num_key_heads": 2, "linear_num_value_heads": 4,
      "linear_key_head_dim": 16, "linear_value_head_dim": 16,
      "linear_conv_kernel_dim": 4, "num_experts": 8, "num_experts_held": 4,
      "expert_first": 0, "expert_buffer_factor": 2.0, "num_experts_per_tok": 3,
      "norm_topk_prob": True, "moe_intermediate_size": 32,
      "shared_expert_intermediate_size": 32, "intermediate_size": 128,
      "vocab_size": 256, "max_position_embeddings": 128,
      "tie_word_embeddings": False, "router_aux_loss_coef": 0.001,
      "hidden_act": "silu", "decoder_sparse_step": 1, "mlp_only_layers": []}
JOINED = {"step_ms_p50", "attn_kernel_share", "device_idle_share.train",
          "compiles_in_window.train", "recompiles_in_window.train",
          "trainer_host_ms_per_step", "idle_attributed_share", "scope_share.attn",
          "scope_share.mlp", "scope_share.loss", "scope_share.optimizer",
          "scope_share.none", "scope_share.moe_experts", "scope_share.moe_route",
          "gmm_kernel_share", "moe_expert_load_max_over_mean",
          "moe_dropped_token_share"}
NEW = {"scope_share.gdn", "gdn_scan_roofline_share", "hybrid_active_mfu_pct",
       "moe_held_row_share", "gmm_roofline_share.held"}
DEVICE_TRACE = {"scope_share.gdn", "gdn_scan_roofline_share", "gmm_roofline_share.held"}


def rehearsal(**traffic):
    from shuffle_exchange_tpu.models.hf import config_from_hf

    return {"model_cfg": config_from_hf(HF), "source_config": dict(HF),
            "train_config": {"bf16": {"enabled": False}},
            "traffic": {"seq": 64, "batch_per_chip": 2, "loss_tol": 1e-4,
                        "route_tol": 0.002, "grad_tol": 0.01, "state_tol": 1e-3,
                        **traffic}}


def test_the_cell_is_files_and_entries():
    cell = harness.load_cell("qwen3next-train")
    assert cell["chips"] == 1
    assert cell["reduced"] == ["num_hidden_layers", "num_experts_held", "vocab_size"]
    assert cell["traffic"]["driver"] == "train_steps_hybrid"
    assert (cell["traffic"]["seq"], cell["traffic"]["batch_per_chip"]) == (8192, 2)
    names = {m["name"] for m in cell["per_layer"]}
    assert names == JOINED | NEW
    assert not names & {"mfu_pct", "moe_active_mfu_pct", "gmm_roofline_share"}
    assert {m["name"] for m in cell["end_to_end"]} == {
        "train_tokens_per_s_chip", "setup_s"}
    src = cell["config"]
    assert src["published"] == {"num_hidden_layers": 48, "num_experts_held": 512,
                                "vocab_size": 151936}
    # every published width, unchanged
    assert (src["hidden_size"], src["head_dim"], src["num_attention_heads"],
            src["num_key_value_heads"], src["linear_num_key_heads"],
            src["linear_num_value_heads"], src["linear_key_head_dim"],
            src["linear_value_head_dim"], src["linear_conv_kernel_dim"],
            src["moe_intermediate_size"], src["shared_expert_intermediate_size"],
            src["num_experts"], src["num_experts_per_tok"],
            src["partial_rotary_factor"], src["intermediate_size"]) == (
        2048, 256, 16, 2, 16, 32, 128, 128, 4, 512, 512, 512, 10, 0.25, 5120)
    assert (src["num_hidden_layers"], src["num_experts_held"], src["vocab_size"]) == (
        4, 32, 18992)
    for key in ("source", "assumed", "deployment"):
        assert src[key]


def test_every_number_of_the_catalog_row_is_there():
    """The guide's rule: the file holds every number of the row's ``config``
    under the same key; what differs is listed in ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog)
               if "Qwen3-Next-80B-A3B-Instruct" in line)
    cell = harness.load_cell("qwen3next-train")
    src = cell["config"]
    assert cell["source"] == row["source_url"] == src["source"]
    differs = {k for k, v in row["config"].items() if src.get(k, "missing") != v}
    assert differs == {"num_hidden_layers", "vocab_size"}
    assert differs | {"num_experts_held"} == set(cell["reduced"])


def test_every_name_the_cell_brought_resolves():
    """What the cell added, as lasting properties: each of its per-layer
    metrics has a file that names a reducer which imports, its driver and
    traffic load, and the benchmark's copy of the reference is the program's
    below the docstring."""
    import importlib

    cell = harness.load_cell("qwen3next-train")
    for name in NEW:
        spec = harness.read_json(f"{cell['bench_dir']}/layer_metrics/{name}.json")
        assert callable(importlib.import_module(
            "chipbench.reducers." + spec["reducer"]).reduce), name
    assert callable(importlib.import_module(
        "chipbench.drivers." + cell["traffic"]["driver"]).run)
    body = lambda path: open(os.path.join(ROOT, path)).read().split('"""', 2)[2]
    assert body("chipbench/reference_qwen3next.py") == body(
        "shuffle_exchange_tpu/models/reference_qwen3next.py")


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_qwen3next_train_at_tiny_size(trace):
    cell = harness.load_cell("qwen3next-train")
    out = json.loads(run.run_cell("qwen3next-train", 2 ** 31 + 4242, 3.0, trace,
                                  rehearsal=rehearsal()))
    assert out["correct"] is True
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
    assert 30.0 < out["metrics"]["moe_held_row_share"]["value"] < 70.0   # 4 of 8 held
    assert out["metrics"]["hybrid_active_mfu_pct"]["value"] > 0.0
    assert out["metrics"]["compiles_in_window.train"]["value"] == 0.0


def test_a_dropped_row_makes_the_run_incorrect(capsys, monkeypatch):
    """A buffer a third of the balanced share (192 rows at this size): rows
    overflow, are counted, and ``correct`` is false for that alone (the
    tolerances are lifted; a whole row tile of 512 would hold them all)."""
    from shuffle_exchange_tpu.moe import layer

    monkeypatch.setattr(layer, "held_buffer_rows", lambda *a, **k: 64)
    reh = rehearsal(grad_tol=10.0, loss_tol=10.0)
    out = json.loads(run.run_cell("qwen3next-train", 4243, 2.0, False, rehearsal=reh))
    assert out["correct"] is False
    window = [json.loads(line) for line in capsys.readouterr().out.splitlines()
              if line.startswith('{"phase": "window"')][-1]
    assert window["moe_dropped_token_share"] > 0
    assert any("dropped" in m for m in window["failed_checks"])


WRONG = ["no_decay", "no_beta", "no_l2norm", "plain_gain", "rope_all_dims",
         "no_attn_gate", "no_shared_gate", "weights_over_held"]
LOWER = ["bf16", "bf16_state", "bf16_router", "bf16_norms"]


def test_the_band_script_at_tiny_size():
    """Every wrong model and every lower precision, in the program's place,
    fails the driver's own checks (the rehearsal's are float32's, so bf16
    itself is a lower precision here); the reference itself and the
    program's rule pass; S in bf16 fails the rule's own reading."""
    from chipbench import qwen3next_band

    names = ["program_rule"] + LOWER + WRONG
    got = {r["variant"]: r for r in qwen3next_band.measure(
        harness.load_cell("qwen3next-train"), [2 ** 31 + 5], names, rehearsal())}
    assert set(got) == {"float32", *names}
    exact = got["float32"]
    assert (exact["loss_gap"], exact["route_gap"], exact["grad_gap"]) == (0, 0, 0)
    assert exact["correct"] is True and exact["failed_checks"] == []
    assert got["program_rule"]["state_gap"] < 1e-4 < 1e-3 < got["bf16_state"]["state_gap"]
    assert any("the rule alone" in m for m in got["bf16_state"]["failed_checks"])
    for name in LOWER + WRONG:
        assert got[name]["loss_gap"] > 0 and got[name]["grad_gap"] > 0, name
        assert got[name]["correct"] is False and got[name]["failed_checks"], name


def test_the_checks_refuse_each_reading_alone():
    """``failed_checks`` on made-up readings: sound ones pass, and each
    reading past its limit fails by its own check and no other."""
    from chipbench.drivers.train_steps_hybrid import failed_checks

    traffic = {"loss_tol": 0.001, "route_tol": 0.01, "grad_tol": 0.1,
               "grad_tol_routed": 0.3, "state_tol": 0.01}
    sound = {"losses": [5.0, 4.9, 4.8], "reference_loss": 5.0005, "route_gap": 0.005,
             "held_gap": 0.001, "counters_add_up": True, "overflow": [0, 0],
             "grad_gaps": {"embed": 0.05, "layers/gdn_moe/moe_gate": 0.2},
             "state_gaps": {"o": 0.004, "dg": 0.006}}
    assert failed_checks(sound, traffic) == []
    for change, said in (
            ({"losses": [5.0, 5.1, 5.2]}, "did not fall"),
            ({"losses": [5.0, float("nan")]}, "non-finite"),
            ({"reference_loss": 5.01}, "first loss"),
            ({"route_gap": 0.02}, "expert counts"),
            ({"held_gap": 0.02}, "held rows differ"),
            ({"counters_add_up": False}, "do not add up"),
            ({"overflow": [0, 3]}, "dropped"),
            ({"grad_gaps": {"embed": 0.11}}, "gradient of embed"),
            ({"grad_gaps": {"layers/gdn_moe/moe_gate": 0.31}}, "moe_gate"),
            ({"grad_gaps": {"embed": float("nan")}}, "gradient of embed"),
            ({"state_gaps": {"o": 0.004, "dg": 0.02}}, "the rule alone")):
        failed = failed_checks({**sound, **change}, traffic)
        assert len(failed) >= 1 and said in failed[0], (change, failed)
        assert len(failed) == 1 or "nan" in str(change), (change, failed)
    none = failed_checks({**sound, "route_gap": None, "held_gap": None,
                          "counters_add_up": False, "overflow": [None, None]}, traffic)
    assert any("handed out no" in m for m in none)


def published():
    from shuffle_exchange_tpu.models.hf import config_from_hf

    return config_from_hf(harness.load_cell("qwen3next-train")["config"])


def test_the_arithmetic_counts_what_it_says():
    cfg = published()
    gdn = 2048 * 12288 + 2048 * 64 + 4096 * 2048
    attn = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    ffn = 2048 * 512 + 3 * 2048 * 512 + 2048
    assert arith_hybrid.matmul_params_per_token(cfg) == (
        3 * gdn + attn + 4 * ffn + 2048 * 18992)
    per_chunk = (2 * 2 * 64 * 64 * 128 + 10 * 2 * 64 ** 3 + 2 * 64 * 64 * 256
                 + 3 * 2 * 64 * 128 * 128 + 2 * 64 * 64 * 128)
    assert per_chunk == 16_777_216
    assert arith_hybrid.gdn_scan_flops_per_token(cfg) == 32 * per_chunk / 64
    assert arith_hybrid.gdn_scan_flops_per_step(cfg, 16384) == \
        3 * 3 * 16384 * 32 * per_chunk / 64            # 1.24 TFLOP
    assert arith_hybrid.gdn_scan_bytes_per_step(cfg, 16384) == 3 * 16384 * (
        3 * 32 * (2 * 128 * 2 + 128 * 2 + 8) + 3 * 32 * 128 * 4)
    assert arith_hybrid.train_flops_per_token(cfg, 8192, 2.5) == (
        6 * arith_hybrid.matmul_params_per_token(cfg) + 6 * 3 * 2048 * 512 * 2.5
        + 6 * 8192 * 16 * 256 + 3 * 3 * 32 * per_chunk / 64)
    # one rank's share of the grouped GEMMs: the rows it had, the weights it holds
    assert arith_hybrid.held_gemm_flops_per_step(cfg, 40960) == 9 * 2 * 40960 * 2048 * 512
    assert arith_hybrid.held_gemm_bytes_per_step(cfg, 40960) == 9 * 2 * (
        40960 * (2048 + 512) + 4 * 32 * 2048 * 512)


def test_the_new_reducers_on_a_made_up_trace(capsys):
    cfg = published()
    peaks = harness.chip_peaks("TPU v5 lite")
    cell = harness.load_cell("qwen3next-train")
    paths = ["", "jit(train_step)/jvp(layers)/while/body/attn_core/gdn_scan/while/body/dot_general",
             "jit(train_step)/transpose(jvp(layers))/while/body/attn_core/"
             "transpose(jvp(gdn_scan))/dot_general",
             "jit(train_step)/jvp(layers)/while/body/attn_qkv/gdn_conv/mul",
             "jit(train_step)/jvp(layers)/while/body/moe/moe_shared/dot_general"]
    ms = 1_000_000
    table = {"devices": [{"name": "/device:TPU:0", "modules": [],
                          "ops": [["fusion.1", 0, ms, 1], ["fusion.2", ms, ms, 2],
                                  ["fusion.3", 2 * ms, ms, 3],
                                  ["fusion.4", 3 * ms, ms, 4]]}],
             "scopes": paths, "program_ops": {},
             "host": [["cb:window", 0, 4 * ms, 0, {}]]}
    facts = {"model_cfg": cfg, "traced_steps": 2, "tokens_per_step": 16384,
             "chips": 1, "seq": 8192, "step_s": [0.4, 0.5, 0.4],
             "held_rows_per_step": 40960.0,
             "flops_per_token": arith_hybrid.train_flops_per_token(cfg, 8192, 2.5)}
    ctx = {"cell": cell, "_xscope": table, "peaks": peaks,
           "result": {"facts": facts},
           "trace_summary": {"ops": {"fusion": 4e-3, "gmm.1": 20e-3, "tgmm": 10e-3}}}
    spec = harness.read_json(f"{cell['bench_dir']}/layer_metrics/scope_share.gdn.json")
    from chipbench.reducers import scope_share

    assert scope_share.reduce(ctx, **spec["args"]) == 75.0
    share = gdn_roofline.reduce(ctx, scope="gdn_scan")
    by_flops = arith_hybrid.gdn_scan_flops_per_step(cfg, 16384) / peaks["bf16_flops_per_s"]
    by_bytes = arith_hybrid.gdn_scan_bytes_per_step(cfg, 16384) / peaks["hbm_bytes_per_s"]
    assert by_bytes > by_flops          # 7.4 ms against 6.3: the bytes bind
    assert share == pytest.approx(100.0 * by_bytes / 1e-3)     # 2 ms over 2 steps
    line = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()
            if '"gdn_scan_roofline"' in x][-1]
    assert line["binds"] == "hbm_bytes_per_s"
    assert line["scope_ms_per_step"] == pytest.approx(1.0)
    assert train_mfu_hybrid.reduce(ctx) == pytest.approx(
        100.0 * facts["flops_per_token"] * 16384 / 0.4 / peaks["bf16_flops_per_s"])
    # the held share's grouped GEMMs: the weights' bytes bind at 320 rows an expert
    held = gmm_roofline_held.reduce(ctx, pattern="gmm|tgmm")
    assert held == pytest.approx(100.0 * arith_hybrid.held_gemm_bytes_per_step(cfg, 40960)
                                 / peaks["hbm_bytes_per_s"] / 15e-3)
    assert gmm_roofline_held.reduce(dict(ctx, trace_summary={"ops": {"fusion": 1.0}}),
                                    pattern="gmm|tgmm") is None
    # nothing to read -> None, not an exception: a program without the scope,
    # a driver without the facts, a model without DeltaNet layers
    ctx["_xscope"] = dict(table, scopes=["", "a/b", "a/c", "a/d", "a/e"])
    assert gdn_roofline.reduce(ctx, scope="gdn_scan") is None
    ctx["result"] = {}
    assert gdn_roofline.reduce(ctx, scope="gdn_scan") is None
    assert gmm_roofline_held.reduce(ctx, pattern="gmm|tgmm") is None
    assert train_mfu_hybrid.reduce(ctx) is None
    from shuffle_exchange_tpu.models.transformer import tiny

    ctx["result"] = {"facts": dict(facts, model_cfg=tiny())}
    assert gdn_roofline.reduce(ctx, scope="gdn_scan") is None
    assert train_mfu_hybrid.reduce(ctx) is None
