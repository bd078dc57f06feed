"""``nemotron3-train`` without the chip: the cell at a tiny Nemotron-H-shaped
size on the CPU through ``run_cell``'s rehearsal argument (untraced and traced,
in float32: at a hundred tokens bf16 noise drowns a gradient), its arithmetic,
its new reducers on a made-up trace, and the lasting properties of what the
cell added (every name resolves, the two copies of the reference agree)."""

import json
import os

import numpy as np
import pytest

from chipbench import arith_ssm, harness, run
from chipbench.reducers import (gmm_roofline_held_ungated, scope_share, ssd_scan_roofline,
                                train_mfu_ssm)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
HF = {"model_type": "nemotron_h", "hidden_size": 64, "hybrid_override_pattern": PATTERN,
      "num_hidden_layers": 9, "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2,
      "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16, "use_conv_bias": True,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
      "layer_norm_epsilon": 1e-5, "n_routed_experts": 8, "num_experts_per_tok": 2,
      "norm_topk_prob": True, "routed_scaling_factor": 2.5, "moe_intermediate_size": 32,
      "moe_shared_expert_intermediate_size": 64, "n_shared_experts": 1, "n_group": 1,
      "topk_group": 1, "vocab_size": 128, "tie_word_embeddings": False,
      "mlp_hidden_act": "relu2", "max_position_embeddings": 1024, "rope_theta": 10000,
      "time_step_min": 0.001, "time_step_max": 0.1, "num_experts_held": 4,
      "expert_first": 0, "expert_buffer_factor": 2.0, "bias_update_speed": 0.001,
      "aux_loss_alpha": 1e-4, "seq_aux": True}
JOINED = {"step_ms_p50", "attn_kernel_share", "device_idle_share.train",
          "compiles_in_window.train", "recompiles_in_window.train",
          "trainer_host_ms_per_step", "idle_attributed_share", "scope_share.attn",
          "scope_share.mlp", "scope_share.loss", "scope_share.optimizer",
          "scope_share.none", "scope_share.moe_experts", "scope_share.moe_route",
          "gmm_kernel_share", "moe_expert_load_max_over_mean",
          "moe_dropped_token_share", "moe_held_row_share", "pass_share.forward",
          "pass_share.recompute", "pass_share.backward", "pass_share.update",
          "pass_share.other", "recompute_ms_per_step", "device_step_ms_p50",
          "train_step_peak_gb"}
NEW = {"scope_share.ssm", "ssd_scan_roofline_share", "ssm_active_mfu_pct",
       "gmm_roofline_share.held_ungated"}
DEVICE_TRACE = {"scope_share.ssm", "ssd_scan_roofline_share",
                "gmm_roofline_share.held_ungated"}


def rehearsal(**traffic):
    from shuffle_exchange_tpu.models.hf import config_from_hf

    return {"model_cfg": config_from_hf(HF), "source_config": dict(HF),
            # the cell's peak rate moves nothing in a rehearsal's few steps
            # but the selection bias: a rate at which the tiny model learns
            "train_config": {"bf16": {"enabled": False},
                             "optimizer": {"type": "FusedAdam",
                                           "params": {"lr": 1e-3, "weight_decay": 0.1}}},
            "traffic": {"seq": 64, "batch_per_chip": 2, "warmup_steps": 3, "loss_tol": 1e-4,
                        "route_tol": 0.002, "grad_tol": 0.01, "grad_tol_routed": 0.01,
                        "router_tol": 1e-5, "weight_tol": 1e-4, "mixer_tol": 1e-3,
                        "state_tol": 1e-3, "decay_tol": 3e-4, "select_bias_std": 0.05,
                        **traffic}}


def test_the_cell_is_files_and_entries():
    cell = harness.load_cell("nemotron3-train")
    assert cell["chips"] == 1
    assert cell["reduced"] == ["num_hidden_layers", "num_experts_held", "vocab_size"]
    assert cell["traffic"]["driver"] == "train_steps_ssm"
    assert cell["traffic_name"] == "pretrain-ssm-s8192"
    assert (cell["traffic"]["seq"], cell["traffic"]["batch_per_chip"]) == (8192, 2)
    assert {m["name"] for m in cell["per_layer"]} == JOINED | NEW
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tokens_per_s_chip", "setup_s"}
    # nine GEMMs a layer is the gated cells' count: not this cell's
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    routed = next(m for m in bench["per_layer"] if m["name"] == "gmm_roofline_share.held_routed")
    assert "nemotron3-train" not in routed["workloads"]
    assert all(m["workloads"] == ["nemotron3-train"] for m in bench["per_layer"]
               if m["name"] in NEW)
    src = cell["config"]
    assert {k: src["published"][k] for k in cell["reduced"]} == {
        "num_hidden_layers": 52, "num_experts_held": 128, "vocab_size": 131072}
    # every published width, unchanged
    assert (src["hidden_size"], src["mamba_num_heads"], src["mamba_head_dim"], src["n_groups"],
            src["ssm_state_size"], src["conv_kernel"], src["num_attention_heads"],
            src["num_key_value_heads"], src["head_dim"], src["n_routed_experts"],
            src["num_experts_per_tok"], src["moe_intermediate_size"],
            src["moe_shared_expert_intermediate_size"]) == (
        2688, 64, 64, 8, 128, 4, 32, 2, 128, 128, 6, 1856, 3712)
    assert (src["num_hidden_layers"], src["num_experts_held"], src["vocab_size"]) == (
        9, 8, 16384)
    assert src["hybrid_override_pattern"] == PATTERN and PATTERN[:9] == "MEMEM*EME"
    for key in ("source", "assumed", "deployment", "counts"):
        assert src[key]
    assert "16 chips share each layer" in src["deployment"]


def test_every_number_of_the_catalog_row_is_there():
    """The guide's rule: the file holds every number of the row's ``config``
    under the same key (nested groups whole); what differs is in ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog)
               if '"NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in line)
    cell = harness.load_cell("nemotron3-train")
    src = cell["config"]
    assert cell["source"] == row["source_url"] == src["source"]
    differs = {k for k, v in row["config"].items() if src.get(k, "missing") != v}
    assert differs == {"num_hidden_layers", "vocab_size"}
    assert differs | {"num_experts_held"} == set(cell["reduced"])


def test_the_cut_and_its_counts():
    """The file's own counts, from the program's tree: 666,963,456 held here,
    31,577,940,288 uncut."""
    import jax

    from shuffle_exchange_tpu.models import Transformer
    from shuffle_exchange_tpu.models.hf import config_from_hf

    src = harness.load_cell("nemotron3-train")["config"]
    cfg = config_from_hf(src)
    assert cfg.pattern == (("ssm", "moe"), ("ssm", "moe"), ("ssm", "none"), ("attn", "moe"),
                           ("ssm", "moe"))
    assert (cfg.n_layers, cfg.routed_layers, cfg.experts_held, cfg.vocab_size) == (5, 4, 8, 16384)

    def count(cfg):
        flat = jax.tree_util.tree_flatten_with_path(
            jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0)))[0]
        unused = ("ln1_b", "ln2_b", "ln_f_b")        # the plain RMSNorms' bias leaves
        return (sum(x.size for p, x in flat if p[-1].key not in unused),
                sum(x.size for p, x in flat if p[-1].key in unused))

    held, unused = count(cfg)
    assert (held, unused) == (666_963_456, 26_880)
    counts = src["counts"]
    assert held == counts["parameters"] == (
        4 * counts["mamba_layer"] + counts["attention_layer"] + 4 * counts["expert_layer_held"]
        + counts["embedding_head_final_norm"])
    whole = dict(src, num_hidden_layers=52, vocab_size=131072)
    for key in ("num_experts_held", "expert_first", "expert_buffer_factor"):
        whole.pop(key)
    assert count(config_from_hf(whole))[0] == src["published"]["parameters"] == 31_577_940_288


def test_every_name_the_cell_brought_resolves():
    import importlib

    cell = harness.load_cell("nemotron3-train")
    for name in NEW:
        spec = harness.read_json(f"{cell['bench_dir']}/layer_metrics/{name}.json")
        assert callable(importlib.import_module(
            "chipbench.reducers." + spec["reducer"]).reduce), name
    assert callable(importlib.import_module(
        "chipbench.drivers." + cell["traffic"]["driver"]).run)
    body = lambda path: open(os.path.join(ROOT, path)).read().split('"""', 2)[2]
    assert body("chipbench/reference_nemotron3.py") == body(
        "shuffle_exchange_tpu/models/reference_nemotron3.py")
    assert "shuffle_exchange_tpu" not in body("chipbench/reference_nemotron3.py").split(
        "import jax")[1]


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_nemotron3_train_at_tiny_size(trace, capsys):
    cell = harness.load_cell("nemotron3-train")
    out = json.loads(run.run_cell("nemotron3-train", 2 ** 31 + 4242, 3.0, trace,
                                  rehearsal=rehearsal()))
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith('{"phase"')]
    setup = next(x for x in lines if x["phase"] == "setup")
    # the program's word for the cell's shapes, the stand-alone scan's own
    # route and what the compiled step holds: the CPU runs XLA's form
    assert [setup["routes"][k] for k in ("ssd", "ssd_alone", "ssd_step_kernels")] == [
        "xla", "xla", False]
    assert setup["routes"]["ssd_chunks_a_sequence"] == 1      # 64 positions, chunks of 128
    assert setup["ssm_scan_chunks"] == 1 * 2 * 4              # x 2 sequences x 4 layers
    assert setup["routes"]["attn_core"] == "reference"        # what the CPU runs
    window = next(x for x in lines if x["phase"] == "window")
    assert out["correct"] is True, window
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert window["first_step_bias_grad"] == 0.0 and window["first_step_bias_update_gap"] < 1e-7
    assert set(window["mixer_gaps"]) >= {"ssm/y", "ssm/dx", "ssm/dssm_conv_b", "ssm/dssm_A_log"}
    assert set(window["scan_gaps"]) == {prefix + k for prefix in ("scan/", "scan32/")
                                        for k in ("y", "dx", "ddt", "dB", "dC")}
    assert len(window["first_step_held_rows"]) == 4           # one row a ROUTED block
    assert len(window["moe_visited_rows"]) == 4
    if not trace:
        assert set(out["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
        return
    assert set(out["metrics"]) <= {m["name"] for m in cell["per_layer"]}
    assert NEW - DEVICE_TRACE <= set(out["metrics"]), out["metrics"]
    assert out["metrics"]["moe_dropped_token_share"]["value"] == 0.0
    assert 25.0 < out["metrics"]["moe_held_row_share"]["value"] < 75.0   # 4 of 8 held
    assert out["metrics"]["ssm_active_mfu_pct"]["value"] > 0.0


def test_a_wrong_mixer_fails_its_own_check():
    """The driver's own judgement: a reading over its limit in the program's
    place, the mixer and the scan each against its own limit."""
    from chipbench.drivers import train_steps_ssm as driver

    got = {"losses": [5.0, 4.9], "first_loss_again": 4.8, "reference_loss": 5.0,
           "route_gap": 0.0, "held_gap": 0.0, "counters_add_up": True,
           "overflow": [0, 0], "grad_gaps": {"layers/ssm_moe/ssm_w_in": 0.001},
           "bias_grad": 0.0, "bias_update_gap": 0.0, "weight_gap": 0.0,
           "router_gaps": {"choice": 0.0, "weight": 0.0},
           "mixer_gaps": {"ssm/y": 0.0005},
           "scan_gaps": {"scan/y": 0.0005, "scan32/y": 0.0001},
           "routes": {"ssd": "pallas", "ssd_alone": "pallas", "ssd_step_kernels": True}}
    tol = dict(rehearsal()["traffic"], state_tol=2e-3)
    assert driver.failed_checks(got, tol) == []
    # the scan alone on another route than the program states, or a compiled
    # step without the kernels the program says it runs: the limits were read
    # on another form than the timed one
    for routes in ({"ssd_alone": "xla"}, {"ssd_step_kernels": False},
                   {"ssd": "xla", "ssd_alone": "xla"}):
        assert any("another form than the timed step runs" in m for m in driver.failed_checks(
            dict(got, routes={**got["routes"], **routes}), tol)), routes
    assert driver.failed_checks(dict(got, routes={
        "ssd": "interpret", "ssd_alone": "interpret", "ssd_step_kernels": True}), tol) == []
    assert any("ssm/y" in m for m in driver.failed_checks(
        dict(got, mixer_gaps={"ssm/y": 0.0015}), tol))
    assert driver.failed_checks(
        dict(got, scan_gaps={"scan/y": 0.0015, "scan32/y": 0.0}), tol) == []
    assert any("scan/dB" in m and "state kept below float32" in m for m in driver.failed_checks(
        dict(got, scan_gaps={"scan/y": 0.0, "scan/dB": 0.003, "scan32/y": 0.0}), tol))
    # the same numbers as float32 operands, against a limit of their own
    assert any("scan32/ddt" in m and "exponent formed below float32" in m
               for m in driver.failed_checks(
        dict(got, scan_gaps={"scan/y": 0.0, "scan32/y": 0.0, "scan32/ddt": 0.0015}), tol))
    assert any("no such reading" in m for m in driver.failed_checks(
        dict(got, scan_gaps={"scan/y": 0.0}), tol))
    assert any("it is a buffer" in m for m in driver.failed_checks(
        dict(got, bias_grad=1e-9), tol))
    assert any("aux-free update" in m for m in driver.failed_checks(
        dict(got, bias_update_gap=1e-3), tol))
    assert any("gradient of layers/ssm_moe/ssm_w_in" in m for m in driver.failed_checks(
        dict(got, grad_gaps={"layers/ssm_moe/ssm_w_in": 0.02}), tol))


def test_the_weight_reading_leaves_out_an_expert_a_single_choice_reached():
    """One expert that a token chose on one side and none on the other reads
    0 against its weight over EVERY expert; left out, the reading is the
    others'. A weighed bias still reads, on every expert that is read."""
    from chipbench.drivers import train_steps_ssm as driver

    rng = np.random.default_rng(0)
    tokens = rng.integers(200, 1200, (4, 128)).astype(np.float64)
    tokens[2, 17] = 0
    weight = tokens * rng.uniform(0.35, 0.5, tokens.shape)
    got_tokens, got_weight = tokens.copy(), weight * (1 + 1e-4 * rng.standard_normal(weight.shape))
    got_tokens[2, 17], got_weight[2, 17] = 1, 0.4
    every, none_out = driver.weight_gap(got_weight, got_tokens, weight, tokens)
    read, left_out = driver.weight_gap(got_weight, got_tokens, weight, tokens, 32)
    assert none_out == 0 and left_out == 1
    assert every > 0.03 and read < 2e-4
    biased, _ = driver.weight_gap(got_weight * 1.02, got_tokens, weight, tokens, 32)
    assert 0.015 < biased < 0.025
    assert driver.weight_gap(got_weight, got_tokens, weight, tokens, 10 ** 6) == (None, 512)


def test_the_arithmetic_of_the_cell():
    """The cell's own shapes: what the issue counted, from the functions, and
    the scan's counts against a direct count at a small size."""
    from shuffle_exchange_tpu.models.hf import config_from_hf

    cfg = config_from_hf(harness.load_cell("nemotron3-train")["config"])
    assert (arith_ssm.layers_of(cfg, "ssm"), arith_ssm.layers_of(cfg, "attn")) == (4, 1)
    B, T = 2, 8192
    # 0.88 GB a layer: 26,816 elements a token in bf16
    assert arith_ssm.scan_elements_per_token(cfg) == 2 * (4096 + 2048 + 64 + 4096) + (
        4096 + 2048 + 64) == 26_816
    assert arith_ssm.scan_bytes_per_step(cfg, B, T) == 4 * B * T * 26_816 * 2
    assert arith_ssm.scan_bytes_per_step(cfg, B, T) / 4 == pytest.approx(0.879e9, rel=1e-3)
    assert arith_ssm.scan_bytes_per_step(cfg, B, T) / 819e9 == pytest.approx(4.29e-3, rel=5e-3)
    per_token = 64 * (2 * 128 * 64 + 4 * 128 * 64) + 8 * 2 * 128 * 128
    assert arith_ssm.scan_forward_flops_per_token(cfg) == per_token == 3_407_872
    assert arith_ssm.scan_flops_per_step(cfg, B, T) == 3 * per_token * B * T * 4
    assert arith_ssm.scan_flops_per_step(cfg, B, T) == pytest.approx(0.67e12, rel=5e-3)
    assert arith_ssm.scan_flops_per_step(cfg, B, T) / 197e12 == pytest.approx(3.4e-3, rel=5e-3)
    params = arith_ssm.matmul_params_per_token(cfg)
    assert params == (4 * (2688 * 10304 + 4096 * 2688) + 2 * 2688 * 128 * (32 + 2)
                      + 4 * 2688 * (128 + 2 * 3712) + 2688 * 16384)
    core = arith_ssm.attn_core_flops_per_step(cfg, 1, T)
    assert core == 3 * 32 * (T * (T + 1) / 2) * 2 * 128 * 2
    rows = 4 * 6 * 8 / 128                      # the balanced share a token
    flops = arith_ssm.train_flops_per_token(cfg, T, rows)
    assert flops == pytest.approx(6 * params + 6 * 2 * 2688 * 1856 * rows + core / T
                                  + 3 * 4 * per_token)
    # six grouped GEMMs a routed block, not nine
    held = 4 * 768.0
    assert arith_ssm.held_gemm_flops_per_step(cfg, held) == 6 * 2 * held * 2688 * 1856
    assert arith_ssm.held_gemm_bytes_per_step(cfg, held) == 6 * 2 * (
        held * (2688 + 1856) + 4 * 8 * 2688 * 1856)
    # the scan's forward products, counted directly at a small size: every
    # multiply-add of the chunked form's four einsums
    small = config_from_hf(HF)
    H, P, G, N, Q = 8, 8, 2, 16, 16
    direct = 2 * (G * Q * N            # C B^T a group: a row of Q products of N
                  + H * Q * P          # (L o C B^T) (dt x): Q products of P a head
                  + H * P * N          # the chunk's own state: P N a head and token
                  + H * P * N)         # the carried state read: N products of P a head
    assert arith_ssm.scan_forward_flops_per_token(small, chunk=Q) == direct


def _ctx(rows, facts, ops=None):
    """A made-up traced run: ``rows`` [(op, scope path, ns)] on one device."""
    scopes = [""] + sorted({p for _, p, _ in rows})
    table_ops, t = [], 0
    for name, path, ns in rows:
        table_ops.append([name, t, ns, scopes.index(path)])
        t += ns
    table = {"devices": [{"name": "/device:TPU:0", "ops": table_ops,
                          "modules": [["jit_train_step", 0, t]]}],
             "scopes": scopes, "host": [], "program_ops": {}}
    return {"_xscope": table, "cell": {"name": "nemotron3-train"},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "result": {"facts": facts}, "trace_summary": {"ops": ops or {}}}


def test_the_new_reducers_on_a_made_up_trace(capsys):
    from shuffle_exchange_tpu.models.hf import config_from_hf

    cfg = config_from_hf(harness.load_cell("nemotron3-train")["config"])
    base = "jit(train_step)/jvp(layers)/while/body/"
    rows = [("scan_fwd", base + "attn_core/ssm_scan/fusion", 10_000_000),
            ("scan_bwd", "jit(train_step)/transpose(jvp(layers))/while/body/attn_core/ssm_scan/fusion",
             10_000_000),
            ("in", base + "attn_qkv/ssm_in/dot_general", 60_000_000),
            ("core", base + "attn_core/pallas_call", 20_000_000),
            ("mlp", base + "moe/moe_experts/gmm", 100_000_000)]
    facts = {"model_cfg": cfg, "traced_steps": 1, "batch": 2, "seq": 8192,
             "tokens_per_step": 16384, "chips": 1, "step_s": [0.4],
             "ssd_route": "xla", "ssm_flops_per_token": 4.4e9,
             "held_rows_per_step": 3072.0}
    ctx = _ctx(rows, facts, ops={"gmm.1": 0.040, "tgmm.2": 0.020, "fusion.3": 1.0})
    spec = harness.read_json(os.path.join(ROOT, "chipbench/layer_metrics/scope_share.ssm.json"))
    assert scope_share.reduce(ctx, **spec["args"]) == pytest.approx(40.0)
    share = ssd_scan_roofline.reduce(ctx)
    assert share == pytest.approx(100 * arith_ssm.scan_bytes_per_step(cfg, 2, 8192)
                                  / 819e9 / 0.020)
    assert 0 < share < 100
    line = next(json.loads(x) for x in capsys.readouterr().out.splitlines()
                if '"ssd_scan_roofline"' in x)
    assert (line["binds"], line["route"], line["layers"], line["chunk"]) == (
        "hbm_bytes_per_s", "xla", 4, 128)
    assert train_mfu_ssm.reduce(ctx) == pytest.approx(100 * 4.4e9 * 16384 / 0.4 / 197e12)
    ungated = gmm_roofline_held_ungated.reduce(ctx, pattern="gmm|tgmm")
    assert ungated == pytest.approx(100 * arith_ssm.held_gemm_bytes_per_step(cfg, 3072.0)
                                    / 819e9 / 0.060)
    assert 0 < ungated < 100
    # a program without the scope (the parent), a run without facts, a model
    # without state-space layers or with gated experts
    assert ssd_scan_roofline.reduce(_ctx(rows[2:], facts)) is None
    assert ssd_scan_roofline.reduce(_ctx(rows, {})) is None
    lfm2 = config_from_hf(harness.load_cell("lfm2-train")["config"])
    assert ssd_scan_roofline.reduce(_ctx(rows, dict(facts, model_cfg=lfm2))) is None
    assert gmm_roofline_held_ungated.reduce(
        _ctx(rows, dict(facts, model_cfg=lfm2), ops={"gmm.1": 0.04}), pattern="gmm|tgmm") is None
    assert gmm_roofline_held_ungated.reduce(_ctx(rows, facts), pattern="gmm|tgmm") is None
    assert train_mfu_ssm.reduce(_ctx(rows, {})) is None


def test_the_band_script_refuses_every_wrong_model_at_tiny_size(capsys):
    """``nemotron3_band.measure`` at the tiny size: the reference itself
    passes, the program's own router, mixer and scan read at rounding, and
    every wrong model and lower precision is refused by the driver's own
    checks (the rehearsal's limits are float32's, so bf16 itself is a lower
    precision here)."""
    from chipbench import nemotron3_band as band

    cell = harness.load_cell("nemotron3-train")
    names = ["bf16", "program_router", "program_mixer"] + band.WRONG + band.LOWER
    out = band.measure(cell, [5], names, rehearsal=rehearsal())
    capsys.readouterr()
    by = {x["variant"]: x for x in out}
    assert set(by) == set(names) | {"float32"}
    exact = by["float32"]
    assert exact["correct"] is True and exact["failed_checks"] == []
    assert (exact["loss_gap"], exact["route_gap"], exact["grad_gap"]) == (0, 0, 0)
    assert by["program_router"]["router_gap"] < 1e-5
    assert set(by["program_mixer"]["mixer_gaps"]) >= {"ssm/y", "ssm/dssm_w_in", "ssm/dssm_D"}
    # (the program's mixer is handed bf16 inputs, as the trainer's is)
    assert by["program_mixer"]["mixer_gap"] < 2 * by["bf16"]["mixer_gap"]
    for name in ["bf16"] + band.WRONG + band.LOWER:
        assert by[name]["correct"] is False and by[name]["failed_checks"], name
    # a variant of the whole model took effect (a trace kept from the variant
    # before it, by a function's identity, would read as ``bf16`` to the digit)
    for name in ("gated_expert", "relu_not_squared", "bias_weighed", "rotation"):
        assert abs(by[name]["loss"] - by["bf16"]["loss"]) > 1e-6, name
    for name in ("bias_weighed", "bf16_router"):
        assert by[name]["router_gap"] > 1e-3, name
        assert any("the router alone" in m for m in by[name]["failed_checks"]), name
    for name in ("gate_after_norm", "one_norm", "wrong_group", "dt_clamped", "no_conv_bias",
                 "no_skip"):
        assert by[name]["mixer_gap"] > 3 * by["bf16"]["mixer_gap"], (
            name, by[name]["mixer_gap"], by["bf16"]["mixer_gap"])
        assert by[name]["whole_model_of"] == "bf16"
    for name in ("bf16_state", "bf16_decay", "wrong_group", "no_skip"):
        assert by[name]["scan_gap"] > 1e-3, (name, by[name]["scan_gap"])
        # the same numbers as float32 operands: no gradient comes back rounded
        assert by[name]["scan32_gap"] > 5e-4, (name, by[name]["scan32_gap"])
        assert any("the scan alone on float32" in m for m in by[name]["failed_checks"]), name
