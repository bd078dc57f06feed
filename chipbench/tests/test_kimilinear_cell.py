"""``kimilinear-train`` without the chip: the cell at a tiny Kimi-Linear-shaped
size on the CPU through ``run_cell``'s rehearsal argument (untraced and traced,
in float32: at a hundred tokens bf16 noise drowns a gradient), its arithmetic
(against a count taken from the XLA form's jaxpr), its new reducers on a
made-up trace, the band script with every wrong model and lower precision run
through the driver's own checks, and the lasting properties of what the cell
added (every name resolves, the two copies of the reference agree)."""

import json
import os

import pytest

from chipbench import arith_kda, arith_mla, harness, run
from chipbench.reducers import kda_roofline, mla_core_roofline, train_mfu_kda

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HF = {"model_type": "kimi_linear", "architectures": ["KimiLinearForCausalLM"],
      "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
      "head_dim": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
      "v_head_dim": 8, "kv_lora_rank": 16, "q_lora_rank": None, "mla_use_nope": True,
      "linear_attn_config": {"kda_layers": [1, 2, 3, 5], "full_attn_layers": [4],
                             "num_heads": 2, "head_dim": 16, "short_conv_kernel_size": 4},
      "intermediate_size": 96, "moe_intermediate_size": 32,
      "num_experts": 8, "num_experts_per_token": 3, "num_shared_experts": 1,
      "first_k_dense_replace": 1, "moe_layer_freq": 1, "num_hidden_layers": 5,
      "num_expert_group": 1, "topk_group": 1, "use_grouped_topk": True,
      "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
      "routed_scaling_factor": 2.446, "rope_theta": 10000, "rope_scaling": None,
      "rms_norm_eps": 1e-5, "hidden_act": "silu", "vocab_size": 256,
      "model_max_length": 128, "tie_word_embeddings": False,
      "num_nextn_predict_layers": 0,
      "num_experts_held": 4, "expert_first": 0, "expert_buffer_factor": 2.0,
      "aux_loss_alpha": 0.01, "seq_aux": True, "bias_update_speed": 0.001}
JOINED = {"step_ms_p50", "attn_kernel_share", "device_idle_share.train",
          "compiles_in_window.train", "recompiles_in_window.train",
          "trainer_host_ms_per_step", "idle_attributed_share", "scope_share.attn",
          "scope_share.mlp", "scope_share.loss", "scope_share.optimizer",
          "scope_share.none", "scope_share.moe_experts", "scope_share.moe_route",
          "gmm_kernel_share", "moe_expert_load_max_over_mean",
          "moe_dropped_token_share", "moe_held_row_share", "scope_share.mla",
          "gmm_roofline_share.held_routed",
          "scope_share.nope", "rope_layers_rotated", "pass_share.forward",
          "pass_share.recompute", "pass_share.backward", "pass_share.update",
          "pass_share.other", "recompute_ms_per_step", "device_step_ms_p50",
          "train_step_peak_gb", "setup_init_s", "setup_step_build_s",
          "setup_trace_lower_s", "setup_backend_compile_s", "setup_cache_miss_programs"}
NEW = {"scope_share.kda", "kda_scan_roofline_share", "kimilinear_mfu_pct", "kda_decay_mean",
       "mla_core_roofline_share.nope"}
DEVICE_TRACE = {"scope_share.kda", "kda_scan_roofline_share", "mla_core_roofline_share.nope"}


def rehearsal(**traffic):
    from shuffle_exchange_tpu.models.hf import config_from_hf

    return {"model_cfg": config_from_hf(HF), "source_config": dict(HF),
            # float32, and a rate at which a few steps' fall shows over what the
            # moving selection bias does to the loss of a hundred tokens
            "train_config": {"bf16": {"enabled": False}, "optimizer": {
                "type": "FusedAdam", "params": {"lr": 1e-2, "weight_decay": 0.1}}},
            "band_dtype": "float32",
            "traffic": {"seq": 64, "batch_per_chip": 2, "loss_tol": 1e-4,
                        "route_tol": 0.002, "grad_tol": 0.01, "grad_tol_routed": 0.01,
                        "router_tol": 1e-5, "weight_tol": 1e-4, "mixer_tol": 1e-3,
                        "rule_tol": 1e-3, "kda_mixer_tol": 2e-3,
                        "select_bias_std": 0.05, **traffic}}


def test_the_cell_is_files_and_entries():
    cell = harness.load_cell("kimilinear-train")
    assert cell["chips"] == 1
    assert cell["reduced"] == ["num_hidden_layers", "num_experts_held", "vocab_size",
                               "linear_attn_config"]
    assert cell["traffic"]["driver"] == "train_steps_kda"
    assert (cell["traffic"]["seq"], cell["traffic"]["batch_per_chip"]) == (16384, 1)
    names = {m["name"] for m in cell["per_layer"]}
    assert names == JOINED | NEW
    # mla_core_roofline_share divides by attn_core, which holds the four rules here
    assert not names & {"mfu_pct", "gdn_scan_roofline_share", "scope_share.gdn",
                        "hybrid_active_mfu_pct", "mla_active_mfu_pct",
                        "mla_core_roofline_share"}
    assert {m["name"] for m in cell["end_to_end"]} == {
        "train_tokens_per_s_chip", "setup_s"}
    src = cell["config"]
    assert src["published"]["num_hidden_layers"] == 27
    assert (src["published"]["num_experts_held"], src["published"]["vocab_size"]) == (
        256, 163840)
    # every published width, unchanged
    lin = src["linear_attn_config"]
    assert (src["hidden_size"], src["num_attention_heads"], src["qk_nope_head_dim"],
            src["qk_rope_head_dim"], src["v_head_dim"], src["kv_lora_rank"],
            src["num_experts"], src["num_experts_per_token"],
            src["moe_intermediate_size"], src["num_shared_experts"],
            src["intermediate_size"], src["routed_scaling_factor"],
            src["first_k_dense_replace"], lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (
        2304, 32, 128, 64, 128, 512, 256, 8, 1024, 1, 9216, 2.446, 1, 32, 128, 4)
    assert (src["num_hidden_layers"], src["num_experts_held"], src["vocab_size"],
            lin["kda_layers"], lin["full_attn_layers"]) == (5, 8, 20480, [1, 2, 3, 5], [4])
    for key in ("source", "assumed", "deployment", "reduced"):
        assert src[key]
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert len(bench["workloads"]) == 14
    assert sum(1 for w in bench["workloads"] if w["chips"] == 4) == 2


def test_every_number_of_the_catalog_row_is_there():
    """The guide's rule: the file holds every number of the row's ``config``
    under the same key; what differs is listed in ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog)
               if "Kimi-Linear-48B-A3B-Instruct" in line)
    cell = harness.load_cell("kimilinear-train")
    src = cell["config"]
    assert cell["source"] == row["source_url"] == src["source"]
    differs = {k for k, v in row["config"].items() if src.get(k, "missing") != v}
    assert differs == {"num_hidden_layers", "vocab_size", "linear_attn_config"}
    assert differs | {"num_experts_held"} == set(cell["reduced"])
    # inside the group only the two lists follow the cut: no width moves
    theirs, ours = row["config"]["linear_attn_config"], src["linear_attn_config"]
    assert {k for k in theirs if ours[k] != theirs[k]} == {"kda_layers", "full_attn_layers"}


def test_every_name_the_cell_brought_resolves():
    import importlib

    cell = harness.load_cell("kimilinear-train")
    for name in NEW:
        spec = harness.read_json(f"{cell['bench_dir']}/layer_metrics/{name}.json")
        assert callable(importlib.import_module(
            "chipbench.reducers." + spec["reducer"]).reduce), name
    assert callable(importlib.import_module(
        "chipbench.drivers." + cell["traffic"]["driver"]).run)
    body = lambda path: open(os.path.join(ROOT, path)).read().split('"""', 2)[2]
    assert body("chipbench/reference_kimilinear.py") == body(
        "shuffle_exchange_tpu/models/reference_kimilinear.py")
    assert "shuffle_exchange_tpu" not in body("chipbench/reference_kimilinear.py").split(
        "import jax")[1]


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_kimilinear_train_at_tiny_size(trace, capsys):
    cell = harness.load_cell("kimilinear-train")
    out = json.loads(run.run_cell("kimilinear-train", 2 ** 31 + 4242, 3.0, trace,
                                  rehearsal=rehearsal()))
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith('{"phase"')]
    setup = next(x for x in lines if x["phase"] == "setup")
    # what the CPU runs
    assert setup["routes"] == {"grouped_gemm": "ragged_dot", "mla_core": "reference",
                               "kda_rule": "xla", "kda_conv": "xla"}
    assert out["correct"] is True, [x for x in lines if x["phase"] == "window"]
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    if not trace:
        assert set(out["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
        return
    assert set(out["metrics"]) <= {m["name"] for m in cell["per_layer"]}
    # a CPU trace has no device plane: the metrics that read device ops are
    # left out here and read a made-up table below
    assert NEW - DEVICE_TRACE <= set(out["metrics"]), out["metrics"]
    assert out["metrics"]["moe_dropped_token_share"]["value"] == 0.0
    assert out["metrics"]["rope_layers_rotated"]["value"] == 0.0
    assert 0.0 < out["metrics"]["kda_decay_mean"]["value"] < 1.0
    assert out["metrics"]["kimilinear_mfu_pct"]["value"] > 0.0
    assert out["metrics"]["compiles_in_window.train"]["value"] == 0.0
    window = next(x for x in lines if x["phase"] == "window")
    assert (window["kda_layers"], window["rope_layers_rotated"]) == (4, 0)


def test_the_band_script_at_tiny_size():
    """Every wrong model and every lower precision, in the program's place,
    trips a NAMED check of the driver's (the rehearsal's limits are float32's);
    the reference itself and the program's rule, mixers and router pass."""
    from chipbench import kimilinear_band as band

    cell = harness.load_cell("kimilinear-train")
    names = (["program_rule", "program_router", "program_mixers"] + band.WRONG + band.LOWER
             + band.FINER)
    got = {r["variant"]: r for r in band.measure(
        cell, [2 ** 31 + 5], names, rehearsal(), alone=True)}
    assert set(got) == {"float32", *names}
    assert got["float32"]["correct"] is True and got["float32"]["failed_checks"] == []
    for name in ("program_rule", "program_router", "program_mixers"):
        assert got[name]["correct"] is True, got[name]
    said = {"scalar_rule": "the rule alone", "no_decay": "the rule alone",
            "no_beta": "the rule alone", "bf16_state": "the rule alone",
            "bf16_gamma": " alone", "no_l2norm": "the KDA mixer alone",
            "silu_gate": "the KDA mixer alone",
            "rope_on_mla": "latent-attention mixer alone",
            "no_latent_norm": "latent-attention mixer alone",
            "bias_weighed": "the router alone", "softmax_router": "the router alone",
            "no_scale": "the router alone", "bf16_router": "the router alone",
            "bf16_softmax": "latent-attention mixer alone"}
    for name, check in said.items():
        assert got[name]["correct"] is False, name
        assert any(check in m for m in got[name]["failed_checks"]), (name, got[name])
    # the three finer precisions are READ on the KDA mixer (float32 reads 0
    # there), and leave the latent mixer as it was
    for name in band.FINER:
        assert got[name]["kda_mixer_gap"] > 1e-3 and got[name]["mixer_gap"] == 0.0, (
            name, got[name])
    # a wrong rule shows in the mixer that runs it too
    assert any("the KDA mixer alone" in m for m in got["scalar_rule"]["failed_checks"])
    # what no mechanism alone can see is told by the whole model
    whole = {r["variant"]: r for r in band.measure(
        cell, [2 ** 31 + 5], ["no_shared", "layer0_routed", "scalar_rule"], rehearsal())}
    assert whole["float32"]["correct"] is True
    assert (whole["float32"]["loss_gap"], whole["float32"]["grad_gap"]) == (0, 0)
    for name in ("no_shared", "layer0_routed", "scalar_rule"):
        assert whole[name]["correct"] is False and whole[name]["failed_checks"], name
        assert whole[name]["grad_gap"] > 0.01
    for name in ("no_shared", "layer0_routed"):
        assert any("gradient of" in m for m in whole[name]["failed_checks"]), name
    # the leading layer's dense FFN has no gradient where the layer is routed
    assert whole["layer0_routed"]["grad_gaps"]["lead/w_up"] == pytest.approx(1.0)


def test_the_checks_refuse_each_reading_alone():
    from chipbench.drivers.train_steps_kda import failed_checks

    traffic = {"loss_tol": 0.001, "route_tol": 0.01, "grad_tol": 0.1,
               "grad_tol_routed": 0.3, "router_tol": 1e-4, "weight_tol": 1e-3,
               "mixer_tol": 0.01, "rule_tol": 0.02, "kda_mixer_tol": 0.03}
    sound = {"losses": [5.0, 4.9, 4.8], "first_loss_again": 4.7, "reference_loss": 5.0005,
             "route_gap": 0.005, "held_gap": 0.001, "counters_add_up": True,
             "overflow": [0, 0], "bias_grad": 0.0, "bias_update_gap": 0.0,
             "grad_gaps": {"embed": 0.05, "layers/kda_moe/moe_gate": 0.2},
             "router_gaps": {"choice": 0.0, "weight": 2e-7}, "weight_gap": 1e-5,
             "mixer_gaps": {"y": 0.004, "dmla_wq": 0.006},
             "rule_gaps": {"o": 0.004, "dg": 0.015}, "kda_mixer_gaps": {"y": 0.01},
             "rotated": 0.0, "kda_layers": [4.0, 4.0]}
    assert failed_checks(sound, traffic) == []
    for change, said in (
            ({"reference_loss": 5.01}, "first loss"),
            ({"grad_gaps": {"layers/kda_moe/moe_gate": 0.31}}, "moe_gate"),
            ({"mixer_gaps": {"y": 0.004, "dmla_wq": 0.03}}, "mixer alone: dmla_wq"),
            ({"rule_gaps": {"o": 0.004, "dg": 0.03}}, "the rule alone, at memories"),
            ({"rule_gaps": {"o": float("nan")}}, "the rule alone"),
            ({"kda_mixer_gaps": {"y": 0.04}}, "the KDA mixer alone: y"),
            ({"rotated": 1.0}, "rotates nothing"),
            ({"rotated": None}, "rotates nothing"),
            ({"kda_layers": [3.0, 4.0]}, "KDA rule"),
            ({"kda_layers": [None, 4.0]}, "KDA rule")):
        failed = failed_checks({**sound, **change}, traffic)
        assert len(failed) == 1 and said in failed[0], (change, failed)


def published():
    from shuffle_exchange_tpu.models.hf import config_from_hf

    return config_from_hf(harness.load_cell("kimilinear-train")["config"])


def test_the_arithmetic_counts_what_it_says():
    cfg = published()
    kda = (2304 * 3 * 4096 + 2304 * 32 + 2 * (2304 * 128 + 128 * 4096) + 4096 * 2304)
    assert arith_kda.kda_params(cfg) == kda == 39_514_272 - (3 * 4 * 4096 + 4096 + 32 + 128)
    mla = arith_mla.mla_params(cfg)
    assert mla == 29_114_880 - 512
    assert (arith_kda.layers_of(cfg, "kda"), arith_kda.layers_of(cfg, "mla"),
            cfg.routed_layers) == (4, 1, 4)
    assert arith_kda.matmul_params_per_token(cfg) == (
        4 * kda + mla + 3 * 2304 * 9216 + 4 * (2304 * 256 + 3 * 2304 * 1024) + 2304 * 20480)
    per_chunk = (2 * 2 * (64 * 64 - 64 * 16) // 2 * 128 + 6 * 64 * 17 // 2 * 128
                 + 10 * 2 * 64 ** 3 + 2 * 64 * 64 * 256 + 3 * 2 * 64 * 128 * 128
                 + 2 * 64 * 64 * 128)
    assert arith_kda.kda_scan_flops_per_token(cfg) == 32 * per_chunk / 64
    assert arith_kda.kda_scan_flops_per_step(cfg, 16384) == 3 * 32 * per_chunk / 64 * 16384 * 4
    # g in float32 at q's shape: 512 of a head's 1284 input bytes
    assert arith_kda.kda_scan_bytes_per_step(cfg, 16384) == (
        3 * 32 * (512 + 256 + 512 + 4) + 2 * 32 * 512) * 16384 * 4
    assert arith_kda.train_flops_per_token(cfg, 16384, 2.0) == (
        6 * arith_kda.matmul_params_per_token(cfg) + 6 * 3 * 2304 * 1024 * 2.0
        + 3 * 16384 * 32 * 320 + 3 * 4 * arith_kda.kda_scan_flops_per_token(cfg))
    # the ISSUE's estimate of a step's matmul work: 42.9 TFLOP at 16,384 tokens
    step = arith_kda.train_flops_per_token(cfg, 16384, 2.0) * 16384
    assert 40e12 < step < 47e12
    # the scalar rule's count at the same heads, less what a decay a channel adds
    from chipbench import arith_hybrid

    scalar = 2 * 2 * 64 * 64 * 128 + per_chunk - (
        2 * 2 * (64 * 64 - 64 * 16) // 2 * 128 + 6 * 64 * 17 // 2 * 128)
    assert arith_hybrid.CHUNK == arith_kda.CHUNK and scalar > 0


def test_the_arithmetic_is_the_xla_forms_products():
    """``kda_scan_flops_per_token``'s products (everything but the diagonal
    sub-blocks' sums, which are the vector unit's) are the dot_generals of
    ``ops/kda.py``'s XLA form, counted in its jaxpr at a small shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from shuffle_exchange_tpu.models.transformer import TransformerConfig
    from shuffle_exchange_tpu.ops import kda

    B, T, H, dk, dv = 1, 128, 2, 32, 16
    wide = lambda d: jnp.zeros((B, T, H, d), jnp.float32)
    jaxpr = jax.make_jaxpr(kda.kda_chunked)(wide(dk), wide(dk), wide(dv), wide(dk),
                                            jnp.zeros((B, T, H), jnp.float32))

    def products(jaxpr, times=1):
        total = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                (contract, _), _ = eqn.params["dimension_numbers"], None
                k = int(np.prod([eqn.invars[0].aval.shape[d] for d in contract[0]]))
                total += times * 2 * int(np.prod(eqn.outvars[0].aval.shape)) * k
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n = eqn.params.get("length", 1) if eqn.primitive.name == "scan" else 1
                total += products(sub, times * n)
        return total

    cfg = TransformerConfig(vocab_size=8, d_model=8, n_layers=1, n_heads=1, max_seq_len=8,
                            kda_heads=H, kda_key_dim=dk, kda_value_dim=dv, kda_gate_rank=4)
    diagonal = 6 * 64 * 17 // 2 * dk * H / 64
    assert products(jaxpr.jaxpr) == (
        arith_kda.kda_scan_flops_per_token(cfg) - diagonal) * B * T


def test_the_new_reducers_on_a_made_up_trace(capsys):
    cfg = published()
    peaks = harness.chip_peaks("TPU v5 lite")
    cell = harness.load_cell("kimilinear-train")
    paths = ["", "jit(train_step)/jvp(layers)/while/body/attn_core/kda_scan/pallas_call",
             "jit(train_step)/transpose(jvp(layers))/while/body/"
             "transpose(jvp(attn_core))/kda_scan/pallas_call",
             "jit(train_step)/jvp(layers)/while/body/attn_qkv/kda_conv/pallas_call",
             "jit(train_step)/jvp(layers)/while/body/attn_core/nope_core/pallas_call"]
    ms = 1_000_000
    table = {"devices": [{"name": "/device:TPU:0", "modules": [],
                          "ops": [["fusion.1", 0, 100 * ms, 1], ["fusion.2", 100 * ms, 300 * ms, 2],
                                  ["fusion.3", 400 * ms, 100 * ms, 3],
                                  ["fusion.4", 500 * ms, 300 * ms, 4]]}],
             "scopes": paths, "program_ops": {},
             "host": [["cb:window", 0, 800 * ms, 0, {}]]}
    ctx = {"cell": cell, "peaks": peaks, "_xscope": table,
           "trace_summary": {"ops": {}},
           "result": {"facts": {"model_cfg": cfg, "traced_steps": 4, "seq": 16384,
                                "batch": 1, "chips": 1, "tokens_per_step": 16384,
                                "step_s": [0.6, 0.62, 0.61], "held_rows_per_step": 16384.0,
                                "kda_flops_per_token": arith_kda.train_flops_per_token(
                                    cfg, 16384, 1.0)}}}
    spec = harness.read_json(f"{cell['bench_dir']}/layer_metrics/scope_share.kda.json")
    from chipbench.reducers import scope_share

    assert scope_share.reduce(ctx, **spec["args"]) == 62.5      # three of the four ops are KDA's
    share = kda_roofline.reduce(ctx, scope="kda_scan")
    flops = arith_kda.kda_scan_flops_per_step(cfg, 16384)
    nbytes = arith_kda.kda_scan_bytes_per_step(cfg, 16384)
    least = max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    assert share == pytest.approx(100.0 * least / 0.1)          # 400 ms over 4 steps
    assert 5 < share < 100
    line = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()
            if '"kda_scan_roofline"' in x][-1]
    assert line["binds"] == "hbm_bytes_per_s"                   # g's float32 bytes
    assert line["scope_ms_per_step"] == pytest.approx(100.0)
    # the ONE latent layer's core against its own scope, not attn_core's 700 ms
    spec = harness.read_json(
        f"{cell['bench_dir']}/layer_metrics/mla_core_roofline_share.nope.json")
    assert spec["args"] == {"scope": "nope_core"}
    core = mla_core_roofline.reduce(ctx, **spec["args"])
    assert core == pytest.approx(
        100.0 * arith_mla.mla_core_flops_per_step(cfg, 1, 16384)
        / peaks["bf16_flops_per_s"] / 0.075)                    # 300 ms over 4 steps
    assert core == pytest.approx(
        mla_core_roofline.reduce(ctx, scope="attn_core") * 700 / 300)
    assert arith_mla.mla_core_flops_per_step(cfg, 1, 16384) == 3.0 * 32 * 16384 ** 2 * 320
    mfu = train_mfu_kda.reduce(ctx)
    assert mfu == pytest.approx(
        100.0 * arith_kda.train_flops_per_token(cfg, 16384, 1.0) * 16384 / 0.61
        / peaks["bf16_flops_per_s"])
    assert 0.0 < mfu < 100.0
    # nothing to read -> None, not an exception
    ctx["result"] = {}
    assert kda_roofline.reduce(ctx, scope="kda_scan") is None
    assert train_mfu_kda.reduce(ctx) is None
    from shuffle_exchange_tpu.models.transformer import tiny

    ctx["result"] = {"facts": {"model_cfg": tiny(), "traced_steps": 4, "step_s": [1.0],
                               "kda_flops_per_token": 1.0, "tokens_per_step": 8, "chips": 1}}
    assert kda_roofline.reduce(ctx, scope="kda_scan") is None
    assert train_mfu_kda.reduce(ctx) is None
