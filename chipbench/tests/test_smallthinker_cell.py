"""``smallthinker-train`` without the chip: the cell at a tiny SmallThinker-shaped
size on the CPU through ``run_cell``'s rehearsal argument (untraced and traced,
in float32: at a hundred tokens bf16 noise drowns a gradient), its arithmetic,
its new metrics on a made-up trace, and the lasting properties of what the cell
added (every name resolves, the two copies of the reference are one)."""

import json
import os

import pytest

from chipbench import arith_smallthinker, arith_swa, harness, run
from chipbench.reducers import (attn_core_roofline, gmm_roofline_held_routed, scope_share,
                                train_mfu_swa)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HF = {"model_type": "smallthinker", "hidden_size": 64, "num_attention_heads": 14,
      "num_key_value_heads": 2, "head_dim": 16, "moe_ffn_hidden_size": 32,
      "moe_num_primary_experts": 16, "moe_num_active_primary_experts": 3,
      "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
      "num_hidden_layers": 4, "vocab_size": 256, "max_position_embeddings": 1024,
      "rms_norm_eps": 1e-6, "rope_theta": 10000.0, "rope_scaling": None,
      "tie_word_embeddings": False, "sliding_window_size": 16,
      "rope_layout": [0, 1, 1, 1] * 3, "sliding_window_layout": [0, 1, 1, 1] * 3,
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
          "train_step_peak_gb", "scope_share.swa", "swa_core_roofline_share",
          "full_core_roofline_share", "swa_block_visit_share", "swa_active_mfu_pct",
          "setup_init_s",
          "setup_step_build_s", "setup_trace_lower_s", "setup_backend_compile_s",
          "setup_cache_miss_programs"}
NEW = {"scope_share.pre_router", "scope_share.nope", "rope_layers_rotated"}
DEVICE_TRACE = {"scope_share.pre_router", "scope_share.nope"}


def rehearsal(**traffic):
    from shuffle_exchange_tpu.models.hf import config_from_hf

    return {"model_cfg": config_from_hf(HF), "source_config": dict(HF),
            "train_config": {"bf16": {"enabled": False},
                             "optimizer": {"type": "FusedAdam",
                                           "params": {"lr": 1e-4, "weight_decay": 0.1}}},
            "traffic": {"seq": 64, "batch_per_chip": 2, "loss_tol": 1e-4,
                        "route_tol": 0.002, "grad_tol": 0.01, "grad_tol_routed": 0.01,
                        "router_tol": 1e-5, "mixer_tol": 1e-3, "mixer_tol_full": 1e-3,
                        **traffic}}


def test_the_cell_is_files_and_entries():
    cell = harness.load_cell("smallthinker-train")
    assert cell["chips"] == 1
    assert cell["reduced"] == ["num_hidden_layers", "num_experts_held", "vocab_size"]
    assert cell["traffic"]["driver"] == "train_steps_prerouter"
    assert (cell["traffic"]["seq"], cell["traffic"]["batch_per_chip"]) == (16384, 1)
    assert {m["name"] for m in cell["per_layer"]} == JOINED | NEW
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tokens_per_s_chip", "setup_s"}
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert len(bench["workloads"]) == 11
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == ["smallthinker-train"] and m["moves"]
    src = cell["config"]
    assert src["published"]["num_hidden_layers"] == 52
    assert (src["published"]["num_experts_held"], src["published"]["vocab_size"]) == (64, 151936)
    # every published width, unchanged
    assert (src["hidden_size"], src["head_dim"], src["num_attention_heads"],
            src["num_key_value_heads"], src["sliding_window_size"],
            src["moe_num_primary_experts"], src["moe_num_active_primary_experts"],
            src["moe_ffn_hidden_size"], src["rope_theta"]) == (
        2560, 128, 28, 4, 4096, 64, 6, 768, 1500000)
    assert (src["num_hidden_layers"], src["num_experts_held"], src["vocab_size"]) == (
        4, 16, 37984)
    assert src["counts"]["parameters"] == 656_529_920
    assert src["published"]["parameters"] == 21_506_562_560
    for key in ("source", "cut", "assumed", "deployment"):
        assert src[key]
    for item in ("model_type", "router_input", "window", "balancing_loss",
                 "sequence_length", "deployment", "lr_schedule"):
        assert src["assumed"][item], item


def test_every_number_of_the_catalog_row_is_there():
    """The guide's rule: the file holds every number of the row's ``config``
    under the same key (nested groups whole); what differs is in ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog)
               if '"SmallThinker-21BA3B-Instruct"' in line)
    cell = harness.load_cell("smallthinker-train")
    src = cell["config"]
    assert cell["source"] == row["source_url"] == src["source"]
    differs = {k for k, v in row["config"].items() if src.get(k, "missing") != v}
    assert differs == {"num_hidden_layers", "vocab_size"}
    assert differs | {"num_experts_held"} == set(cell["reduced"])


def test_every_name_the_cell_brought_resolves():
    import importlib

    cell = harness.load_cell("smallthinker-train")
    for name in NEW:
        spec = harness.read_json(f"{cell['bench_dir']}/layer_metrics/{name}.json")
        assert callable(importlib.import_module(
            "chipbench.reducers." + spec["reducer"]).reduce), name
    assert callable(importlib.import_module(
        "chipbench.drivers." + cell["traffic"]["driver"]).run)
    read = lambda path: open(os.path.join(ROOT, path), "rb").read()
    assert read("chipbench/reference_smallthinker.py") == read(
        "shuffle_exchange_tpu/models/reference_smallthinker.py")
    assert b"shuffle_exchange_tpu" not in read(
        "chipbench/reference_smallthinker.py").split(b"import jax")[1]


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_smallthinker_train_at_tiny_size(trace, capsys):
    cell = harness.load_cell("smallthinker-train")
    out = json.loads(run.run_cell("smallthinker-train", 2 ** 31 + 4242, 3.0, trace,
                                  rehearsal=rehearsal()))
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith('{"phase"')]
    setup = next(x for x in lines if x["phase"] == "setup")
    assert setup["routes"]["swa_core"] == "reference"      # what the CPU runs
    assert (setup["moe_router_input"], setup["rope_layers_rotated"]) == ("block", 3)
    scopes = setup["step_scopes"]
    assert scopes["pre_router"] > 0 and scopes["nope_core"] > 0 and scopes["swa_rope"] > 0
    assert scopes["rope_under_nope"] == 0
    assert setup["edge_gaps"]["outside"] == 0.0 and setup["edge_gaps"]["inside"] > 0.01
    assert out["correct"] is True, [x for x in lines if x["phase"] == "window"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    end = next(x for x in lines if x["phase"] == "end")
    assert end["counters"]["moe_router_input"] == "block"
    assert end["counters"]["rope_layers_rotated"] == 3
    if not trace:
        assert set(out["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
        return
    assert set(out["metrics"]) <= {m["name"] for m in cell["per_layer"]}
    assert NEW - DEVICE_TRACE <= set(out["metrics"]), out["metrics"]
    assert out["metrics"]["moe_dropped_token_share"]["value"] == 0.0
    assert 25.0 < out["metrics"]["moe_held_row_share"]["value"] < 75.0   # 8 of 16 held
    assert out["metrics"]["swa_active_mfu_pct"]["value"] > 0.0
    assert out["metrics"]["rope_layers_rotated"]["value"] == 3.0
    assert out["metrics"]["swa_block_visit_share"]["value"] == 100.0     # 64 positions: one block


def test_a_program_that_routes_on_y2_fails_correct(capsys):
    """The engagement counters are part of ``correct``: the same weights with
    the router on the post-attention norm (``moe_router_input`` "ffn") read as
    another model on every count, and say so by name."""
    import dataclasses

    reh = rehearsal()
    reh["model_cfg"] = dataclasses.replace(reh["model_cfg"], moe_router_input="ffn")
    out = json.loads(run.run_cell("smallthinker-train", 7, 1.0, False, rehearsal=reh))
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith('{"phase"')]
    window = next(x for x in lines if x["phase"] == "window")
    assert out["correct"] is False
    assert any("the router's input" in m for m in window["failed_checks"])
    assert any("expert counts differ" in m for m in window["failed_checks"])


@pytest.mark.parametrize("fault", ["full_kind_rotated", "window_table_lost"])
def test_a_step_that_rotates_by_the_wrong_kind_fails_correct(fault, capsys, monkeypatch):
    """The rotation by kind is held on the TIMED path, by the compiled step's
    own scopes: a program whose full kind rotates (no ``unrotated_mixers``: no
    ``nope_*`` scope opens) and a step without the window kind's rotation
    (planted in what ``step_scopes`` reads) both read as another model."""
    import dataclasses

    from chipbench.drivers import train_steps_prerouter as driver

    reh = rehearsal()
    if fault == "full_kind_rotated":
        reh["model_cfg"] = dataclasses.replace(reh["model_cfg"], unrotated_mixers=())
    else:
        read = driver.step_scopes
        monkeypatch.setattr(driver, "step_scopes", lambda: {**read(), "swa_rope": 0})
    out = json.loads(run.run_cell("smallthinker-train", 11, 1.0, False, rehearsal=reh))
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith('{"phase"')]
    window = next(x for x in lines if x["phase"] == "window")
    assert out["correct"] is False
    assert any("the compiled step holds" in m for m in window["failed_checks"])
    if fault == "full_kind_rotated":
        assert next(x for x in lines if x["phase"] == "setup")["step_scopes"]["nope_core"] == 0


def test_the_arithmetic_of_the_cell():
    """The cell's own shapes: what the issue counted, from the functions."""
    from shuffle_exchange_tpu.models.hf import config_from_hf

    cfg = config_from_hf(harness.load_cell("smallthinker-train")["config"])
    assert (arith_swa.layers_of(cfg, "swa"), arith_swa.layers_of(cfg, "attn")) == (3, 1)
    T, W = 16384, 4096
    # the window's own share of the causal pairs: 43.75%
    assert arith_swa.visible_pairs(T, W) / arith_swa.visible_pairs(T) == pytest.approx(
        0.4375, abs=2e-4)
    per_token = arith_swa.matmul_params_per_token(cfg)
    assert per_token == 4 * (20_971_520 + 163_840) + 2560 * 37984
    held = 4 * 6 * 16 / 64                      # balanced: 1.5 rows a token over 4 layers
    parts = arith_smallthinker.forward_matmul_flops_per_token(cfg, T, held)
    total = sum(parts.values())
    # the issue's table: cores 271 M (full 117, three windows 51 each),
    # projections 168 M (with the routers 169), held experts 71 M, head 194 M
    assert parts["full_cores"] == pytest.approx(117.4e6, rel=2e-3)
    assert parts["window_cores"] == pytest.approx(3 * 51.4e6, rel=2e-3)
    assert parts["projections"] == pytest.approx(169.1e6, rel=2e-3)
    assert parts["held_experts"] == pytest.approx(70.8e6, rel=2e-3)
    assert parts["head"] == pytest.approx(194.5e6, rel=2e-3)
    assert total == pytest.approx(706e6, rel=5e-3)
    assert parts["head"] / total == pytest.approx(0.275, abs=0.005)
    flops = arith_swa.train_flops_per_token(cfg, T, held)
    cores = sum(arith_swa.core_flops_per_step(cfg, m, 1, T) for m in ("swa", "attn")) / T
    assert flops == pytest.approx(6 * per_token + 6 * 3 * 2560 * 768 * held + cores)
    assert flops == pytest.approx(3 * total)        # forward once, backward twice


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
    return {"_xscope": table, "cell": {"name": "smallthinker-train"},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "result": {"facts": facts},
            "trace_summary": {"ops": {name: ns * 1e-9 for name, _, ns in rows}}}


def test_the_new_metrics_on_a_made_up_trace(capsys):
    from shuffle_exchange_tpu.models.hf import config_from_hf

    cfg = config_from_hf(harness.load_cell("smallthinker-train")["config"])
    base = "jit(train_step)/jvp(layers)/while/body/"
    rows = [("swa_fwd", base + "attn_core/swa_core/pallas_call", 150_000_000),
            ("full_fwd", base + "attn_core/nope_core/pallas_call", 100_000_000),
            ("qkv", base + "attn_qkv/nope_qkv/dot_general", 20_000_000),
            ("router", base + "moe/pre_router/moe_router/dot_general", 10_000_000),
            ("gmm.1", base + "moe/moe_experts/pallas_call", 120_000_000)]
    facts = {"model_cfg": cfg, "traced_steps": 1, "batch": 1, "seq": 16384,
             "tokens_per_step": 16384, "chips": 1, "step_s": [0.5],
             "held_rows_per_step": 4 * 24576.0, "swa_flops_per_token": 2.1e9}
    ctx = _ctx(rows, facts)
    spec = lambda name: harness.read_json(
        os.path.join(ROOT, f"chipbench/layer_metrics/{name}.json"))["args"]
    assert scope_share.reduce(ctx, **spec("scope_share.pre_router")) == pytest.approx(2.5)
    assert scope_share.reduce(ctx, **spec("scope_share.nope")) == pytest.approx(30.0)
    assert scope_share.reduce(ctx, **spec("scope_share.moe_route")) == pytest.approx(2.5)
    # the accepted reducers read this configuration's shapes
    swa = attn_core_roofline.reduce(ctx, mixer="swa")
    full = attn_core_roofline.reduce(ctx, mixer="attn")
    assert swa == pytest.approx(100 * arith_swa.core_flops_per_step(
        cfg, "swa", 1, 16384) / 197e12 / 0.150)
    assert full == pytest.approx(100 * arith_swa.core_flops_per_step(
        cfg, "attn", 1, 16384) / 197e12 / 0.100)
    gmm = gmm_roofline_held_routed.reduce(ctx, pattern="gmm|tgmm")
    assert 0 < swa < 100 and 0 < full < 100 and 0 < gmm < 100
    capsys.readouterr()
    assert train_mfu_swa.reduce(ctx) == pytest.approx(
        100 * 2.1e9 * 16384 / 0.5 / 197e12)
    # a program without the scopes (the parent) or the count: nothing, no raise
    assert scope_share.reduce(_ctx(rows[:1], facts), **spec("scope_share.pre_router")) == 0.0
    assert train_mfu_swa.reduce(_ctx(rows, {})) is None


def test_the_band_script_refuses_every_wrong_model_at_tiny_size(capsys):
    """``smallthinker_band.measure`` at the tiny size: the reference itself
    passes, the program's own router and mixers read at rounding, and every
    wrong model and lower precision is refused by the driver's own checks (the
    rehearsal's limits are float32's, so bf16 itself is a lower precision
    here). The window is one key off at 15 and 17 here, read on the edge."""
    from chipbench import smallthinker_band as band

    cell = harness.load_cell("smallthinker-train")
    names = ["bf16", "program_router", "program_mixers"] + band.WRONG + band.LOWER
    out = band.measure(cell, [5], names, rehearsal=rehearsal(
        mixer_score_gain=6.0, edge_min=0.01, edge_outside_tol=1e-6))
    capsys.readouterr()
    by = {x["variant"]: x for x in out}
    assert set(by) == set(names) | {"float32"}
    exact = by["float32"]
    assert exact["correct"] is True and exact["failed_checks"] == []
    assert (exact["loss_gap"], exact["route_gap"], exact["grad_gap"]) == (0, 0, 0)
    assert by["program_router"]["router_gap"] < 1e-5
    assert set(by["program_mixers"]["mixer_gaps"]) >= {"swa/y", "swa/dwq", "full/y", "full/dwo"}
    assert by["program_mixers"]["mixer_gap"] < 2 * by["bf16"]["mixer_gap"]
    assert by["program_mixers"]["edge_gaps"]["outside"] == 0.0
    for name in ["bf16"] + band.WRONG + band.LOWER:
        assert by[name]["correct"] is False and by[name]["failed_checks"], name
    # (the normed input differs from the input by the norm's gains alone: the
    # row's scale moves no choice; with the embedding at the stream's scale the
    # post-attention norm differs from it by little more)
    for name, least in (("router_reads_y2", 0.02), ("router_reads_normed", 0.02)):
        assert by[name]["route_gap"] > least, (name, by[name]["route_gap"])
        assert any("expert counts differ" in m for m in by[name]["failed_checks"]), name
    for name in ("no_renorm", "sigmoid_scores", "bf16_router"):
        assert any("the router alone" in m for m in by[name]["failed_checks"]), name
    for name in ("window_4095", "window_4097", "window_ignored"):
        assert any("the window's edge" in m for m in by[name]["failed_checks"]), name
    for name in ("full_rotated", "window_unrotated", "heads_7x4"):
        assert by[name]["mixer_gap"] > 3 * by["bf16"]["mixer_gap"], name
        assert by[name]["whole_model_of"] == "bf16"
