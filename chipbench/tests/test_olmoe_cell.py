"""``olmoe-train`` without the chip: the cell at a tiny OLMoE-shaped size on
the CPU through ``run_cell``'s rehearsal argument (untraced and traced), its
arithmetic, and its roofline reducer on a made-up trace summary. The CPU has
no megablox kernel (the grouped GEMM is ``lax.ragged_dot`` there), so the two
kernel metrics are checked on what a trace with ``gmm`` ops gives them."""

import json

import pytest

from chipbench import arith_moe, harness, run
from chipbench.reducers import gmm_roofline, train_mfu_moe

HF = {"model_type": "olmoe", "architectures": ["OlmoeForCausalLM"],
      "hidden_size": 128, "intermediate_size": 64, "num_hidden_layers": 2,
      "num_attention_heads": 4, "num_key_value_heads": 4, "num_experts": 8,
      "num_experts_per_tok": 3, "norm_topk_prob": False, "vocab_size": 256,
      "max_position_embeddings": 64, "rms_norm_eps": 1e-5, "rope_theta": 10000,
      "tie_word_embeddings": False, "clip_qkv": None}
NEW = {"scope_share.moe_experts", "scope_share.moe_route", "gmm_kernel_share",
       "gmm_roofline_share", "moe_active_mfu_pct",
       "moe_expert_load_max_over_mean", "moe_dropped_token_share"}
DEVICE_TRACE = {"scope_share.moe_experts", "scope_share.moe_route",
                "gmm_kernel_share", "gmm_roofline_share"}


def rehearsal():
    from shuffle_exchange_tpu.models.hf import config_from_hf

    # bf16 compute against the float32 reference at 2 x 32 x 4 tokens: a
    # handful of near-tie choices flip, and the gradient of 128 tokens differs
    # from the float32 one by a tenth of its norm on every leaf
    return {"model_cfg": config_from_hf(HF), "source_config": dict(HF),
            "traffic": {"seq": 32, "batch_per_chip": 4, "route_tol": 0.05,
                        "grad_tol": 0.25}}


def test_the_cell_is_files_and_entries():
    cell = harness.load_cell("olmoe-train")
    assert cell["chips"] == 1 and cell["reduced"] == ["num_hidden_layers"]
    assert cell["traffic"]["driver"] == "train_steps_moe"
    assert NEW <= {m["name"] for m in cell["per_layer"]}
    assert "mfu_pct" not in {m["name"] for m in cell["per_layer"]}
    assert {m["name"] for m in cell["end_to_end"]} == {
        "train_tokens_per_s_chip", "setup_s"}
    src = cell["config"]
    assert src["published"] == {"num_hidden_layers": 16}
    assert (src["hidden_size"], src["intermediate_size"], src["num_experts"],
            src["num_experts_per_tok"]) == (2048, 1024, 64, 8)
    for key in ("source", "assumed", "deployment"):
        assert src[key]


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_olmoe_train_at_tiny_size(trace):
    cell = harness.load_cell("olmoe-train")
    out = json.loads(run.run_cell("olmoe-train", 2 ** 31 + 4242, 3.0, trace,
                                  rehearsal=rehearsal()))
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    if not trace:
        assert set(out["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
        return
    assert set(out["metrics"]) <= {m["name"] for m in cell["per_layer"]}
    # a CPU trace has no device plane: the four metrics that read device ops
    # are left out here and read a made-up table below
    assert NEW - DEVICE_TRACE <= set(out["metrics"]), out["metrics"]
    assert out["metrics"]["moe_dropped_token_share"]["value"] == 0.0
    assert out["metrics"]["moe_expert_load_max_over_mean"]["value"] >= 1.0
    assert out["metrics"]["moe_active_mfu_pct"]["value"] > 0.0
    assert out["metrics"]["compiles_in_window.train"]["value"] == 0.0


def test_a_wrong_backward_that_still_descends_is_refused(monkeypatch, capsys):
    """The dispatch's row gather with the right values and HALF the
    cotangent: the first loss and the routing match, the loss still falls,
    and only the gradient held to the reference's shows it."""
    import jax

    from shuffle_exchange_tpu.moe import layer

    plain = layer._permuted_rows

    def halved(x, perm, inverse, k=1):
        y = plain(x, perm, inverse, k)
        return 0.5 * y + 0.5 * jax.lax.stop_gradient(y)

    monkeypatch.setattr(layer, "_permuted_rows", halved)
    out = json.loads(run.run_cell("olmoe-train", 4243, 2.0, False,
                                  rehearsal=rehearsal()))
    assert out["correct"] is False
    window = [json.loads(line) for line in capsys.readouterr().out.splitlines()
              if line.startswith('{"phase": "window"')][-1]
    assert len(window["failed_checks"]) == 1
    assert "gradient of" in window["failed_checks"][0]
    assert window["last_losses_mean"] < window["first_loss"]
    assert window["first_step_route_gap"] <= 0.05


def test_the_band_script_at_tiny_size():
    """``olmoe_band.measure``: every variant against float32 on the cell's
    own weights and batch; lower precision reads further off."""
    from chipbench import olmoe_band

    got = {r["variant"]: r for r in olmoe_band.measure(
        harness.load_cell("olmoe-train"), [2 ** 31 + 5],
        ["bf16", "bf16_router", "fp8_experts"], rehearsal())}
    assert set(got) == {"float32", "bf16", "bf16_router", "fp8_experts"}
    exact = got["float32"]
    assert (exact["loss_gap"], exact["route_gap"], exact["grad_gap"]) == (0, 0, 0)
    for name in ("bf16", "bf16_router", "fp8_experts"):
        assert 0 < got[name]["loss_gap"] < 0.05 and 0 < got[name]["grad_gap"] < 0.5
    assert got["fp8_experts"]["grad_gaps"]["moe_w_down"] > \
        got["bf16"]["grad_gaps"]["moe_w_down"]


def test_the_device_trace_metrics_on_a_made_up_table():
    """Four ops of 1 ms each under the program's scopes as the compiler
    writes them (forward, and the backward's transpose(jvp(...)) wrappers),
    through each new metric's own file."""
    cell = harness.load_cell("olmoe-train")
    paths = ["", "jit(train_step)/jvp(layers)/while/body/moe/moe_router/dot_general",
             "jit(train_step)/transpose(jvp(layers))/while/body/moe/"
             "transpose(jvp(moe_experts))/jit(gmm)/pallas_call",
             "jit(train_step)/jvp(layers)/while/body/moe/moe_combine/scatter",
             "jit(train_step)/jvp(layers)/while/body/attn_qkv/attn_qk_norm/mul"]
    ms = 1_000_000
    table = {"devices": [{"name": "/device:TPU:0", "modules": [],
                          "ops": [["fusion.1", 0, ms, 1], ["gmm.1", ms, ms, 2],
                                  ["scatter.3", 2 * ms, ms, 3],
                                  ["fusion.9", 3 * ms, ms, 4]]}],
             "scopes": paths, "program_ops": {},
             "host": [["cb:window", 0, 4 * ms, 0, {}],
                      ["sxt:train/dispatch", 0, ms, 0, {}]]}
    ctx = {"cell": cell, "_xscope": table,
           "trace_summary": {"ops": {"fusion": 2e-3, "gmm": 1e-3, "scatter": 1e-3}}}
    got = {}
    for name in ("scope_share.moe_experts", "scope_share.moe_route",
                 "gmm_kernel_share", "scope_share.mlp", "scope_share.attn"):
        spec = harness.read_json(f"{cell['bench_dir']}/layer_metrics/{name}.json")
        reducer = harness.named_module("reducers", spec["reducer"], name)
        got[name] = reducer.reduce(ctx, **spec.get("args", {}))
    assert got == {"scope_share.moe_experts": 25.0, "scope_share.moe_route": 50.0,
                   "gmm_kernel_share": 25.0,
                   # the nested scopes are counted with their parents
                   "scope_share.mlp": 75.0, "scope_share.attn": 25.0}


def published():
    from shuffle_exchange_tpu.models.hf import config_from_hf

    return config_from_hf(harness.load_cell("olmoe-train")["config"])


def test_the_arithmetic_counts_what_it_says():
    cfg = published()
    # one layer: attention 4 x 2048^2, router 2048 x 64, 8 experts x 3 x
    # 2048 x 1024, head 2048 x 50304: the ISSUE's 170.3 M, the head 60% of it
    assert arith_moe.active_matmul_params(cfg) == (
        4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024 + 2048 * 50304)
    assert round(arith_moe.active_matmul_params(cfg) / 1e6, 1) == 170.3
    assert arith_moe.grouped_gemm_flops_per_step(cfg, 16384) == \
        9 * 2 * 16384 * 8 * 2048 * 1024
    assert arith_moe.grouped_gemm_bytes_per_step(cfg, 16384) == \
        9 * 2 * (16384 * 8 * (2048 + 1024) + 64 * 2048 * 1024)
    assert arith_moe.train_flops_per_token(cfg, 4096) == \
        6 * arith_moe.active_matmul_params(cfg) + 6 * 4096 * 16 * 128


def test_the_roofline_reducer_on_a_made_up_summary(capsys):
    cfg = published()
    peaks = harness.chip_peaks("TPU v5 lite")
    facts = {"model_cfg": cfg, "traced_steps": 4, "tokens_per_step": 16384,
             "chips": 1, "seq": 4096, "step_s": [0.4, 0.5, 0.4]}
    ctx = {"cell": {"name": "olmoe-train"}, "peaks": peaks,
           "result": {"facts": facts},
           "trace_summary": {"ops": {"gmm": 0.12, "tgmm": 0.08, "fusion": 1.0}}}
    share = gmm_roofline.reduce(ctx, pattern="gmm|tgmm")
    least = 9 * 2 * 16384 * 8 * 2048 * 1024 / peaks["bf16_flops_per_s"]
    assert share == pytest.approx(100.0 * least / (0.2 / 4))
    assert 0 < share <= 100
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "gmm_roofline" and line["binds"] == "bf16_flops_per_s"
    assert line["kernel_ms_per_step"] == pytest.approx(50.0)
    # nothing to read -> None, not an exception: no gmm op, or no MoE facts
    ctx["trace_summary"] = {"ops": {"fusion": 1.0}}
    assert gmm_roofline.reduce(ctx, pattern="gmm|tgmm") is None
    ctx["result"] = {}
    assert gmm_roofline.reduce(ctx, pattern="gmm|tgmm") is None
    assert train_mfu_moe.reduce(ctx) is None
    ctx["result"] = {"facts": facts}
    rate = 16384 / 0.4
    assert train_mfu_moe.reduce(ctx) == pytest.approx(
        100.0 * arith_moe.train_flops_per_token(cfg, 4096) * rate
        / peaks["bf16_flops_per_s"])
