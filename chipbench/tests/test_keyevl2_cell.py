"""``keyevl2-train`` without the chip: the cell at a tiny KeyeVL2-shaped size on
the CPU through ``run_cell``'s rehearsal argument (untraced and traced, in
float32: at a hundred tokens bf16 noise drowns a gradient), its arithmetic, its
new metrics on a made-up trace, the band script at the tiny size, and the
lasting properties of what the cell added (every name resolves, the two copies
of the reference are one)."""

import json
import os

import pytest

from chipbench import arith_dsa, harness, run
from chipbench.reducers import dsa_roofline, scope_share, train_mfu_dsa

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HF = {"model_type": "KeyeVL2", "hidden_size": 64, "num_attention_heads": 8,
      "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 32,
      "num_experts": 16, "num_experts_per_tok": 4, "norm_topk_prob": True,
      "num_hidden_layers": 2, "vocab_size": 256, "max_position_embeddings": 1024,
      "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
      "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                       "type": "default"},
      "tie_word_embeddings": False, "hidden_act": "silu", "attention_bias": False,
      "decoder_sparse_step": 1, "mlp_only_layers": [],
      "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                    "indexer_num_kv_heads": 1, "q_chunk_size": 512,
                    "kv_chunk_size": 512, "topk": 16},
      "num_experts_held": 8, "expert_first": 0, "expert_buffer_factor": 2.0,
      "router_aux_loss_coef": 0.01}
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
          "train_step_peak_gb", "setup_init_s", "setup_step_build_s",
          "setup_trace_lower_s", "setup_backend_compile_s", "setup_cache_miss_programs"}
NEW = {"scope_share.dsa_index", "scope_share.dsa_select", "scope_share.dsa_kl",
       "dsa_core_roofline_share", "dsa_index_roofline_share", "dsa_selected_pair_share",
       "dsa_block_visit_share", "dsa_active_mfu_pct"}
DEVICE_TRACE = {"scope_share.dsa_index", "scope_share.dsa_select", "scope_share.dsa_kl",
                "dsa_core_roofline_share", "dsa_index_roofline_share"}


def rehearsal(**traffic):
    from shuffle_exchange_tpu.models.hf import config_from_hf

    return {"model_cfg": config_from_hf(HF), "source_config": dict(HF),
            "train_config": {"bf16": {"enabled": False},
                             "optimizer": {"type": "FusedAdam",
                                           "params": {"lr": 1e-4, "weight_decay": 0.1}}},
            "traffic": {"seq": 64, "batch_per_chip": 2, "loss_tol": 1e-4, "kl_tol": 1e-3,
                        "route_tol": 0.002, "grad_tol": 0.01, "grad_tol_routed": 0.01,
                        "grad_tol_indexer": 0.01, "router_tol": 1e-5, "index_tol": 1e-4,
                        "select_tol": 0.0, "mixer_tol": 1e-3, "mixer_tol_indexer": 1e-3,
                        "mixer_score_gain": 6.0,
                        **traffic}}


def test_the_cell_is_files_and_entries():
    cell = harness.load_cell("keyevl2-train")
    assert cell["chips"] == 1
    assert cell["reduced"] == ["num_hidden_layers", "num_experts_held", "vocab_size"]
    assert cell["traffic"]["driver"] == "train_steps_dsa"
    assert (cell["traffic"]["seq"], cell["traffic"]["batch_per_chip"],
            cell["traffic"]["warmup_steps"]) == (16384, 1, 3)
    assert {m["name"] for m in cell["per_layer"]} == JOINED | NEW
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tokens_per_s_chip", "setup_s"}
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert len(bench["workloads"]) == 12 and bench["workloads"][-1]["name"] == "keyevl2-train"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2
    assert len(bench["workloads"][-1]["why"]) <= 200 and len(bench["configs"][-1]["why"]) <= 200
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == ["keyevl2-train"]
            assert m["moves"] == "train_tokens_per_s_chip"
    src = cell["config"]
    assert src["published"]["num_hidden_layers"] == 48
    assert (src["published"]["num_experts_held"], src["published"]["vocab_size"]) == (128, 151936)
    # every published width, unchanged
    assert (src["hidden_size"], src["head_dim"], src["num_attention_heads"],
            src["num_key_value_heads"], src["num_experts"], src["num_experts_per_tok"],
            src["moe_intermediate_size"], src["rope_theta"]) == (
        2048, 128, 32, 4, 128, 8, 768, 10000000)
    assert src["sa_config"] == {"indexer_head_dim": 64, "indexer_num_heads": 16,
                                "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                                "q_chunk_size": 512, "topk": 2048}
    assert src["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert (src["num_hidden_layers"], src["num_experts_held"], src["vocab_size"]) == (
        5, 16, 18992)
    assert src["counts"]["parameters"] == 562_290_560
    assert src["published"]["parameters"] == 30_640_656_384
    for key in ("source", "cut", "assumed", "deployment"):
        assert src[key]
    for item in ("block", "mrope_layout", "indexer", "chunk_sizes", "selection",
                 "indexer_loss", "balancing_loss", "gains", "sequence_length",
                 "deployment", "lr_schedule"):
        assert src["assumed"][item], item


def test_every_number_of_the_catalog_row_is_there():
    """The guide's rule: the file holds every number of the row's ``config``
    under the same key (nested groups whole); what differs is in ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog)
               if '"Keye-VL-2.0-30B-A3B"' in line)
    cell = harness.load_cell("keyevl2-train")
    src = cell["config"]
    assert cell["source"] == row["source_url"] == src["source"]
    differs = {k for k, v in row["config"].items() if src.get(k, "missing") != v}
    assert differs == {"num_hidden_layers", "vocab_size"}
    assert differs | {"num_experts_held"} == set(cell["reduced"])


def test_every_name_the_cell_brought_resolves():
    import importlib

    cell = harness.load_cell("keyevl2-train")
    for name in NEW:
        spec = harness.read_json(f"{cell['bench_dir']}/layer_metrics/{name}.json")
        assert callable(importlib.import_module(
            "chipbench.reducers." + spec["reducer"]).reduce), name
    assert callable(importlib.import_module(
        "chipbench.drivers." + cell["traffic"]["driver"]).run)
    read = lambda path: open(os.path.join(ROOT, path), "rb").read()
    assert read("chipbench/reference_keyevl2.py") == read(
        "shuffle_exchange_tpu/models/reference_keyevl2.py")
    assert b"shuffle_exchange_tpu" not in read(
        "chipbench/reference_keyevl2.py").split(b"import jax")[1]


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_keyevl2_train_at_tiny_size(trace, capsys):
    cell = harness.load_cell("keyevl2-train")
    out = json.loads(run.run_cell("keyevl2-train", 2 ** 31 + 4242, 3.0, trace,
                                  rehearsal=rehearsal()))
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith('{"phase"')]
    setup = next(x for x in lines if x["phase"] == "setup")
    assert setup["routes"]["dsa_core"] == "xla"            # what the CPU runs
    scopes = setup["step_scopes"]
    assert all(scopes[k] > 0 for k in ("dsa_index", "dsa_select", "dsa_core", "dsa_kl", "mrope"))
    mech = setup["mechanism_gaps"]
    assert mech["leak"] == 0.0 and mech["select"] == 0.0 and mech["index"] < 1e-5
    assert out["correct"] is True, [x for x in lines if x["phase"] == "window"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    end = next(x for x in lines if x["phase"] == "end")
    counters = end["counters"]
    assert (counters["dsa_selected_per_query_min"], counters["dsa_selected_per_query_max"]) == (16, 16)
    assert counters["dsa_selected_pair_share"] == pytest.approx(
        100.0 * arith_dsa.selected_pairs(64, 16) / arith_dsa.causal_pairs(64))
    if not trace:
        assert set(out["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
        return
    assert set(out["metrics"]) <= {m["name"] for m in cell["per_layer"]}
    assert NEW - DEVICE_TRACE <= set(out["metrics"]), out["metrics"]
    assert out["metrics"]["moe_dropped_token_share"]["value"] == 0.0
    assert out["metrics"]["dsa_active_mfu_pct"]["value"] > 0.0
    assert out["metrics"]["dsa_block_visit_share"]["value"] == 100.0


@pytest.mark.parametrize("fault", ["causal_core", "no_indexer_loss"])
def test_a_step_whose_mechanism_is_off_fails_correct(fault, capsys, monkeypatch):
    """The engagement counters are part of ``correct``: a program whose queries
    keep every causal key (topk over the sequence) and one whose indexer's loss
    never reaches its leaves both read as another model, and say so by name."""
    import dataclasses

    from shuffle_exchange_tpu.ops import dsa

    reh = rehearsal()
    if fault == "causal_core":
        reh["model_cfg"] = dataclasses.replace(reh["model_cfg"], dsa_topk=4096)
    else:
        loss = dsa.kl
        monkeypatch.setattr(dsa, "kl", lambda *args, **kw: 0.0 * loss(*args, **kw))
    out = json.loads(run.run_cell("keyevl2-train", 7, 1.0, False, rehearsal=reh))
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith('{"phase"')]
    window = next(x for x in lines if x["phase"] == "window")
    assert out["correct"] is False
    if fault == "causal_core":
        assert any("dsa_selected_pair_share reads 100" in m for m in window["failed_checks"])
        assert any("the selection alone" in m for m in window["failed_checks"])
    else:
        assert any("the indexer's leaves' first gradient" in m for m in window["failed_checks"])


def test_the_arithmetic_of_the_cell():
    """The cell's own shapes: what the issue counted, from the functions."""
    from shuffle_exchange_tpu.models.hf import config_from_hf

    cfg = config_from_hf(harness.load_cell("keyevl2-train")["config"])
    T = 16384
    assert arith_dsa.selected_pairs(T, 2048) == 31_458_304
    assert arith_dsa.causal_pairs(T) == 134_225_920
    assert 100 * arith_dsa.selected_pairs(T, 2048) / arith_dsa.causal_pairs(T) == pytest.approx(
        23.44, abs=5e-3)
    assert arith_dsa.attn_params(cfg) == 18_874_368
    assert arith_dsa.indexer_params(cfg) == 2_261_120 - 128
    per_token = arith_dsa.matmul_params_per_token(cfg)
    assert per_token == 5 * (18_874_368 + 2_260_992 + 262_144) + 2048 * 18992
    # the issue's operations a token and layer, forward multiply-adds
    layer = lambda f: f(cfg, 1, T) / 3 / 2 / T / cfg.n_layers
    assert layer(arith_dsa.core_flops_per_step) == pytest.approx(15.7e6, rel=5e-3)
    assert layer(arith_dsa.index_flops_per_step) == pytest.approx(8.4e6, rel=5e-3)
    held = 5 * 8 * 16 / 128                     # balanced: 1 row a token a layer
    flops = arith_dsa.train_flops_per_token(cfg, T, held)
    sparse = (arith_dsa.core_flops_per_step(cfg, 1, T)
              + arith_dsa.index_flops_per_step(cfg, 1, T)) / T
    assert flops == pytest.approx(6 * per_token + 6 * 3 * 2048 * 768 * held + sparse)
    # the bytes the model needs are far under its operations' time: compute binds
    assert (arith_dsa.core_flops_per_step(cfg, 1, T) / 197e12
            > arith_dsa.core_bytes_per_step(cfg, 1, T) / 819e9)


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
    return {"_xscope": table, "cell": {"name": "keyevl2-train"},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "result": {"facts": facts},
            "trace_summary": {"ops": {name: ns * 1e-9 for name, _, ns in rows}}}


def test_the_new_metrics_on_a_made_up_trace(capsys):
    from shuffle_exchange_tpu.models.hf import config_from_hf

    cfg = config_from_hf(harness.load_cell("keyevl2-train")["config"])
    base = "jit(train_step)/jvp(layers)/while/body/"
    rows = [("sxt_dsa_attention_fwd", base + "attn_core/dsa_core/pallas_call", 400_000_000),
            ("scores", base + "attn_qkv/dsa_index/dot_general", 200_000_000),
            ("count", base + "attn_qkv/dsa_select/while/reduce", 100_000_000),
            ("kl", base + "attn_core/dsa_kl/exp", 150_000_000),
            ("scores2", base + "attn_core/dsa_kl/dsa_index/dot_general", 50_000_000),
            ("gmm.1", base + "moe/moe_experts/pallas_call", 100_000_000)]
    facts = {"model_cfg": cfg, "traced_steps": 1, "batch": 1, "seq": 16384,
             "tokens_per_step": 16384, "chips": 1, "step_s": [1.0],
             "held_rows_per_step": 5 * 16384.0, "dsa_flops_per_token": 1.9e9}
    ctx = _ctx(rows, facts)
    spec = lambda name: harness.read_json(
        os.path.join(ROOT, f"chipbench/layer_metrics/{name}.json"))["args"]
    assert scope_share.reduce(ctx, **spec("scope_share.dsa_index")) == pytest.approx(25.0)
    assert scope_share.reduce(ctx, **spec("scope_share.dsa_select")) == pytest.approx(10.0)
    assert scope_share.reduce(ctx, **spec("scope_share.dsa_kl")) == pytest.approx(20.0)
    assert scope_share.reduce(ctx, **spec("scope_share.attn")) == pytest.approx(90.0)
    core = dsa_roofline.reduce(ctx, **spec("dsa_core_roofline_share"))
    index = dsa_roofline.reduce(ctx, **spec("dsa_index_roofline_share"))
    assert core == pytest.approx(100 * arith_dsa.core_flops_per_step(cfg, 1, 16384)
                                 / 197e12 / 0.400)
    assert index == pytest.approx(100 * arith_dsa.index_flops_per_step(cfg, 1, 16384)
                                  / 197e12 / 0.250)
    assert 0 < core < 100 and 0 < index < 100
    capsys.readouterr()
    assert train_mfu_dsa.reduce(ctx) == pytest.approx(100 * 1.9e9 * 16384 / 1.0 / 197e12)
    # a program without the scopes (the parent) or the count: nothing, no raise
    assert dsa_roofline.reduce(_ctx(rows[-1:], facts), stage="core") is None
    assert dsa_roofline.reduce(_ctx(rows, {}), stage="index") is None
    assert scope_share.reduce(_ctx(rows[-1:], facts), **spec("scope_share.dsa_kl")) == 0.0
    assert train_mfu_dsa.reduce(_ctx(rows, {})) is None


def test_the_band_script_refuses_every_wrong_model_at_tiny_size(capsys):
    """``keyevl2_band.measure`` at the tiny size: the reference itself passes,
    the program's own mixer reads at rounding, and every wrong model and lower
    precision is refused by the driver's own checks (the rehearsal's limits are
    float32's, so bf16 itself is a lower precision here)."""
    from chipbench import keyevl2_band as band

    cell = harness.load_cell("keyevl2-train")
    names = ["bf16", "program"] + band.WRONG + band.LOWER
    out = band.measure(cell, [5], names, rehearsal=rehearsal(select_tol=1e-3))
    capsys.readouterr()
    by = {x["variant"]: x for x in out}
    assert set(by) == set(names) | {"float32"}
    exact = by["float32"]
    assert exact["correct"] is True and exact["failed_checks"] == []
    assert (exact["loss_gap"], exact["route_gap"], exact["grad_gap"]) == (0, 0, 0)
    assert by["program"]["router_gaps"]["choice"] == 0.0
    assert by["program"]["mechanism"]["leak"] == 0.0
    assert by["program"]["mechanism"]["y"] < 2e-2          # its inputs are bf16 here
    for name in ["bf16"] + band.WRONG + band.LOWER:
        assert by[name]["correct"] is False and by[name]["failed_checks"], name
    told = lambda name, words: any(words in m for m in by[name]["failed_checks"])
    for name in ("select_unrotated", "relu_dropped", "weights_left_out", "bf16_index"):
        assert told(name, "the indexer alone"), (name, by[name]["failed_checks"])
    assert told("input_not_detached", "where none may arrive")
    assert told("target_unnormalised", "the indexer's loss on the first batch")
    assert told("topk_per_query_2047", "dsa_selected_per_query")
    assert told("bf16_threshold", "the selection alone")
    assert told("bf16_softmax", "the mixer alone")
