"""``ouro-train`` without the chip: the cell at a tiny Ouro shape on the CPU
through ``run_cell``'s rehearsal argument (untraced and traced, in float32: at
a hundred tokens bf16 noise drowns a gradient), its arithmetic, its reducers,
the band script with every wrong model and lower precision run through the
driver's own checks, and the lasting properties of what the cell added (every
name resolves, the two copies of the reference agree, the catalog row's
numbers are all there)."""

import dataclasses
import json
import os

import pytest

from chipbench import arith_loop, harness, run
from chipbench.reducers import loop_attn_core_roofline, scope_innermost_share, train_mfu_loop

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "ouro-train"
HF = {"model_type": "ouro", "hidden_size": 64, "num_attention_heads": 4,
      "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 96,
      "hidden_act": "silu", "num_hidden_layers": 3, "vocab_size": 256,
      "max_position_embeddings": 1024, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
      "rope_scaling": None, "tie_word_embeddings": False, "total_ut_steps": 4,
      "early_exit_threshold": 1, "use_sliding_window": False,
      "layer_types": ["full_attention"] * 6}
NEW = {"ouro_mfu_pct", "scope_share.loop", "scope_share.loop_norm", "scope_share.loop_exit",
       "loop_attn_core_roofline_share", "loop_layer_visits", "loop_expected_steps"}
JOINED = {"step_ms_p50", "attn_kernel_share", "device_idle_share.train",
          "compiles_in_window.train", "recompiles_in_window.train",
          "trainer_host_ms_per_step", "idle_attributed_share", "scope_share.attn",
          "scope_share.mlp", "scope_share.loss", "scope_share.optimizer",
          "scope_share.none", "pass_share.forward", "pass_share.recompute",
          "pass_share.backward", "pass_share.update", "pass_share.other",
          "recompute_ms_per_step", "device_step_ms_p50", "train_step_peak_gb",
          "setup_init_s", "setup_step_build_s", "setup_trace_lower_s",
          "setup_backend_compile_s", "setup_cache_miss_programs"}
TINY_LIMITS = {"loss_tol": 1e-4, "grad_tol": 0.01, "gate_tol": 0.01, "exit_tol": 1e-4,
               "pdf_tol": 1e-4, "alone_tol": 1e-5, "update_tol": 0.05}


def rehearsal(**traffic):
    from shuffle_exchange_tpu.models.hf import config_from_hf

    # (a loss that chunks, as the cell's does: the tiny size would take the
    # full logits and report no ``loss_rows``)
    return {"model_cfg": dataclasses.replace(config_from_hf(HF), loss_chunk=16),
            "source_config": dict(HF),
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
    assert (cell["config_name"], cell["traffic_name"]) == ("ouro-2.6b-train",
                                                           "pretrain-loop-s8192")
    assert cell["traffic"]["driver"] == "train_steps_loop"
    assert (cell["traffic"]["seq"], cell["traffic"]["batch_per_chip"],
            cell["traffic"]["warmup_steps"], cell["traffic"]["trace_steps"],
            cell["traffic"]["gradient_accumulation_steps"]) == (8192, 1, 3, 4, 1)
    assert {m["name"] for m in cell["per_layer"]} == JOINED | NEW
    assert {m["name"] for m in cell["end_to_end"]} == {"train_tokens_per_s_chip", "setup_s"}
    src = cell["config"]
    assert (src["published"]["num_hidden_layers"], src["published"]["vocab_size"]) == (48, 49152)
    # every published width, unchanged
    assert (src["hidden_size"], src["num_attention_heads"], src["num_key_value_heads"],
            src["head_dim"], src["intermediate_size"], src["total_ut_steps"]) == (
        2048, 16, 16, 128, 5632, 4)
    assert (src["num_hidden_layers"], src["vocab_size"]) == (12, 12288)
    settings = src["chipbench"]["train_config"]
    assert settings["zero_optimization"]["stage"] == 3 and settings["bf16"]["enabled"]
    assert settings["activation_checkpointing"] == {"enabled": True, "policy": "full"}
    for key in ("source", "assumed", "deployment", "counts", "published"):
        assert src[key]
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    # (no count of the benchmark's cells here: the next cell would break it,
    # as this one breaks ``test_keyevl2_cell``'s ``== 12``, a file of the
    # accepted benchmark that this PR may not edit)
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1
    for entry in bench["configs"] + bench["workloads"]:
        assert len(entry["why"]) <= 200 and len(entry.get("source", "")) <= 200
    # each limit of the traffic file is stated with its readings
    for limit in ("loss_tol", "grad_tol", "gate_tol", "exit_tol", "pdf_tol", "alone_tol",
                  "update_tol"):
        assert limit in cell["traffic"]["why"] and cell["traffic"][limit] > 0


def test_every_number_of_the_catalog_row_is_there():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog) if '"Ouro-2.6B"' in line)
    cell = harness.load_cell(CELL)
    src = cell["config"]
    assert cell["source"] == row["source_url"] == src["source"]
    differs = {k for k, v in row["config"].items() if src.get(k, "missing") != v}
    assert differs == {"num_hidden_layers", "vocab_size"} == set(cell["reduced"])


def test_the_arithmetic_counts_the_issues_parameters():
    from shuffle_exchange_tpu.models.hf import config_from_hf

    src = harness.load_cell(CELL)["config"]
    counts = src["counts"]
    assert arith_loop.parameters(src) == 666_996_737 == counts["parameters"]
    assert arith_loop.parameters(src, 48, 49152) == 2_667_974_657 == src["published"]["parameters"]
    assert arith_loop.parameters(src, 8) == 461_443_073          # the issue's fallback
    cfg = config_from_hf(src)
    assert arith_loop.layer_visits(cfg, 4) == 48
    assert arith_loop.matmul_params_per_token(cfg, 4) == 48 * 51_380_224 + 4 * 25_165_824
    # 15.40 + 4.83 GFLOP a token: ~165 TFLOP a step of 8,192
    step = arith_loop.train_flops_per_token(cfg, 8192, 4) * 8192
    assert 165e12 < step < 166e12
    # 6 x (16 + 16) x 128 elements a token and visit, bf16, 48 visits
    assert arith_loop.core_bytes_per_step(cfg, 4, 1, 8192) == 6 * 32 * 128 * 2 * 8192 * 48


def test_every_name_the_cell_brought_resolves():
    import importlib

    cell = harness.load_cell(CELL)
    for name in NEW:
        spec = harness.read_json(f"{cell['bench_dir']}/layer_metrics/{name}.json")
        assert callable(importlib.import_module(
            "chipbench.reducers." + spec["reducer"]).reduce), name
    assert callable(importlib.import_module(
        "chipbench.drivers." + cell["traffic"]["driver"]).run)
    read = lambda path: open(os.path.join(ROOT, path), "rb").read()
    assert read("chipbench/reference_ouro.py") == read(
        "shuffle_exchange_tpu/models/reference_ouro.py")
    assert "shuffle_exchange_tpu" not in "".join(
        line for line in read("chipbench/reference_ouro.py").decode().splitlines()
        if line.startswith(("import", "from")))


def test_the_reducers_read_nothing_from_a_program_without_the_configuration():
    """On the parent's side of a traced run of another cell the facts hold no
    such count and no looped model: the reducers return None and do not raise."""
    assert train_mfu_loop.reduce({"result": {}, "peaks": {}}) is None
    assert train_mfu_loop.reduce({"result": {"facts": {"step_s": [1.0]}}, "peaks": {}}) is None
    assert loop_attn_core_roofline.reduce({"result": {}, "peaks": {}}) is None
    assert loop_attn_core_roofline.reduce(
        {"result": {"facts": {"model_cfg": object(), "traced_steps": 4}}, "peaks": {}}) is None


def test_the_new_reducers_on_a_recorded_trace(monkeypatch, capsys):
    """The core's share of its roofline and the outer scan's own share, on
    recorded scope times: ``loop`` counts where it is the INNERMOST scope."""
    from shuffle_exchange_tpu.models.hf import config_from_hf

    cfg = config_from_hf(harness.load_cell(CELL)["config"])
    rows = [
        ("fusion.1", "jit(step)/jvp(loop)/while/body/layers/while/body/attn_core/splash",
         4 * 200_000_000),
        ("fusion.2", "jit(step)/transpose(jvp(loop))/while/body/dynamic_update_slice",
         4 * 10_000_000),
        ("fusion.3", "jit(step)/jvp(loop)/while/body/loop_norm/mul", 4 * 5_000_000),
        ("fusion.4", "jit(step)/optimizer/mul", 4 * 35_000_000)]
    for module in (loop_attn_core_roofline, scope_innermost_share):
        monkeypatch.setattr(module.xscope, "table", lambda ctx: object())
        monkeypatch.setattr(module.xscope, "op_self_times", lambda tab: rows)
    ctx = {"cell": {"name": CELL}, "peaks": {"hbm_bytes_per_s": 819e9,
                                             "bf16_flops_per_s": 197e12},
           "result": {"facts": {"model_cfg": cfg, "traced_steps": 4, "batch": 1, "chips": 1,
                                "seq": 8192, "loop_steps": 4}}}
    least_ms = arith_loop.core_flops_per_step(cfg, 4, 1, 8192) / 197e12 * 1e3
    assert loop_attn_core_roofline.reduce(ctx) == pytest.approx(100 * least_ms / 200.0)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["binds"] == "bf16_flops_per_s" and line["layer_visits"] == 48
    table = harness.read_json(os.path.join(
        ROOT, "chipbench/layer_metrics/scope_share.loop.json"))["args"]
    assert scope_innermost_share.reduce(ctx, **table) == pytest.approx(100 * 10 / 250)
    # a program without the scope: nothing to read
    monkeypatch.setattr(scope_innermost_share.xscope, "op_self_times", lambda tab: rows[3:])
    assert scope_innermost_share.reduce(ctx, **table) is None


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_ouro_train_at_tiny_size(trace, capsys):
    cell = harness.load_cell(CELL)
    out = json.loads(run.run_cell(CELL, 2 ** 31 + 4242, 3.0, trace, rehearsal=rehearsal()))
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith('{"phase"')]
    window = next(line for line in lines if line["phase"] == "window")
    assert out["correct"] is True, window["failed_checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1
    setup = next(line for line in lines if line["phase"] == "setup")
    assert setup["zero_stage"] == 3 and setup["loop_steps"] == 4
    assert setup["norm_order"] == "sandwich" and setup["remat"] == [True, "full"]
    assert setup["counters"]["loop_layer_visits"] == 12
    assert setup["counters"]["loss_rows"] == 4 * 2 * 64
    # the two mechanisms alone: the float32 exit block to rounding, and every
    # leaf that has a gradient moved by Adam's step at the second update
    assert set(window["exit_alone_gaps"]) == {"exit/p", "exit/H"}
    assert max(window["exit_alone_gaps"].values()) < 1e-6
    # (every leaf of the master but the gate's ONE-number bias: the plain
    # norms' unused biases too)
    assert set(window["update_gaps"]) > set(window["first_step_grad_gaps"]) - {"exit_gate_b"}
    assert "exit_gate_b" not in window["update_gaps"]
    assert 0.0 < window["update_gap"] < 0.02
    assert window["update_gaps"]["layers/ln1_b"] == 0.0          # no gradient: it stayed
    if not trace:
        assert set(out["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
        return
    assert set(out["metrics"]) <= {m["name"] for m in cell["per_layer"]}
    assert out["metrics"]["ouro_mfu_pct"]["value"] > 0.0
    assert out["metrics"]["loop_layer_visits"]["value"] == 12.0
    assert 1.0 < out["metrics"]["loop_expected_steps"]["value"] < 4.0
    assert out["metrics"]["compiles_in_window.train"]["value"] == 0.0


def test_no_counter_makes_the_run_incorrect():
    from chipbench.drivers import train_steps_loop as driver

    ref = {"loss": 5.0, "exit_ce": [5.0, 5.0], "exit_mass": [0.5, 0.5]}
    good = {"losses": [5.0, 4.9], "reference": ref, "grad_gaps": {"a": 0.001, "exit_gate_w": 0.001},
            "counters": {"loop_layer_visits": 6.0, "loss_rows": 256.0,
                         "loop_exit_ce": [5.0, 5.0], "loop_exit_mass": [0.5, 0.5]},
            "visits_expected": 6, "rows_expected": 256,
            "exit_alone_gaps": {"exit/p": 1e-7, "exit/H": 1e-7},
            "update_gaps": {"a": 1e-3, "exit_gate_w": 1e-3}}
    traffic = rehearsal()["traffic"]
    assert driver.failed_checks(good, traffic) == []
    # the mechanisms alone: a reading over its limit, a NaN's inf, none at all
    for key, bad, word in (("exit_alone_gaps", {"exit/p": 1e-7, "exit/H": 4e-3}, "exit/H"),
                           ("exit_alone_gaps", {"exit/p": float("inf")}, "exit/p"),
                           ("exit_alone_gaps", {}, "exit block alone"),
                           ("exit_alone_gaps", None, "exit block alone"),
                           ("update_gaps", {"a": 1.0, "exit_gate_w": 1e-3}, "change of a "),
                           ("update_gaps", {}, "did not move"),
                           ("update_gaps", None, "did not move")):
        failed = driver.failed_checks({**good, key: bad}, traffic)
        assert len(failed) == 1 and word in failed[0], (key, bad, failed)
    for name in ("loop_layer_visits", "loss_rows"):
        for value in (0, None, 3.0):
            failed = driver.failed_checks(
                {**good, "counters": {**good["counters"], name: value}}, traffic)
            assert len(failed) == 1 and name in failed[0]
    # a program that reports no exits, or another number of them
    for mass in (None, [1.0], [0.5, 0.5 + 1e-3]):
        failed = driver.failed_checks(
            {**good, "counters": {**good["counters"], "loop_exit_mass": mass}}, traffic)
        assert len(failed) == 1 and "mean mass" in failed[0]
    # the gate's leaves are held to their own limit
    failed = driver.failed_checks({**good, "grad_gaps": {"a": 0.001, "exit_gate_w": 0.02}},
                                  {**traffic, "gate_tol": 0.01, "grad_tol": 0.05})
    assert len(failed) == 1 and "exit_gate_w" in failed[0]


def test_the_band_refuses_every_wrong_model_and_passes_the_base():
    """Every wrong model and lower precision of the issue's list through the
    driver's own ``failed_checks`` at the tiny size in float32 (where the
    stated precision IS float32: ``bf16`` is then the reference itself,
    correct at a gap of 0): each wrong model is refused by at least one check,
    and so is the exit block formed in bf16."""
    from chipbench import ouro_band as band

    cell = harness.load_cell(CELL)
    out = band.measure(cell, [7], ["bf16", *band.WRONG, *band.LOWER], rehearsal=rehearsal())
    by = {line["variant"]: line for line in out}
    assert set(by) == {"bf16", *band.WRONG, *band.LOWER}
    assert by["bf16"]["correct"] and by["bf16"]["loss_gap"] == 0.0, by["bf16"]["failed_checks"]
    for name in [*band.WRONG, *band.LOWER]:
        assert not by[name]["correct"], (name, band.summary(by[name]))
    # the lower precisions are refused by their mechanism ALONE, which the
    # whole model's readings need not show: a bf16 exit block is thousandths
    # off at some token, a bf16 master does not move
    assert by["bf16_exit"]["alone_gap"] > 1e-3
    assert any("exit block alone" in m for m in by["bf16_exit"]["failed_checks"])
    assert by["bf16_master"]["update_gap"] > 0.9
    assert any("did not move" in m for m in by["bf16_master"]["failed_checks"])
    # what moves first: the gate's leaves under a changed weighting, an exit's
    # mass under a changed distribution, an exit's CE under a changed stream
    assert by["detached_weights"]["gate_gap"] > 0.1 and by["detached_weights"]["loss_gap"] < 1e-6
    assert by["last_times_lam"]["pdf_gap"] > 1e-2
    assert by["next_unnormed"]["exit_gap"] > 1e-3 and by["no_out_norms"]["exit_gap"] > 1e-3
    assert by["three_steps"]["pdf_gap"] == float("inf")
    # a log judged again reads the same
    assert all(band.judged(line, rehearsal()["traffic"])["correct"] == line["correct"]
               for line in out)


def test_the_mechanisms_alone_tell_each_lower_precision():
    """``--alone``: the float32 forms read what a sound program does (the exit
    block 0, a float32 master its rounding) and come out correct; the exit
    block in bf16, a master in bf16 and a lost update are each refused, by the
    limit of their own mechanism and by no other."""
    from chipbench import ouro_band as band

    out = band.measure_alone(harness.load_cell(CELL), [7], rehearsal=rehearsal())
    by = {line["variant"]: line for line in out}
    assert set(by) == {"float32", "bf16_exit", "bf16_master", "no_update"}
    assert by["float32"]["correct"], by["float32"]["failed_checks"]
    assert max(by["float32"]["exit_alone_gaps"].values()) == 0.0
    assert 0.0 < max(by["float32"]["update_gaps"].values()) < 0.02
    for name, word in (("bf16_exit", "exit block alone"), ("bf16_master", "did not move"),
                       ("no_update", "did not move")):
        assert len(by[name]["failed_checks"]) == 1 and word in by[name]["failed_checks"][0]
    assert all(gap == pytest.approx(1.0) for gap in by["no_update"]["update_gaps"].values())
    # (the rehearsal's rate is a hundred times the cell's: a matrix near 0.02
    # still moves in part; a gain near 1 does not at either)
    assert all(by["bf16_master"]["update_gaps"][leaf] == 1.0
               for leaf in ("ln_f_w", "layers/ln1_w", "layers/ln2_post_w"))


def test_adams_step_is_the_optimizers_own():
    """``adam_step`` against optax's adamw on two gradients (the second
    update, from the moments after it), and the schedule's rate of each
    update against the program's."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    from chipbench.drivers import train_steps_loop as driver
    from shuffle_exchange_tpu.runtime.lr_schedules import warmup_cosine_lr

    rng = np.random.default_rng(0)
    w, g1, g2 = (jnp.asarray(rng.normal(size=(5, 7)), jnp.float32) for _ in range(3))
    tx = optax.adamw(1e-3, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.1)
    state = tx.init(w)
    _, state = tx.update(g1, state, w)
    step, state = tx.update(g2, state, w)
    mine = driver.adam_step(w, state[0].mu, state[0].nu, 2, 1e-3, (0.9, 0.999), 1e-8, 0.1)
    # (optax forms 1 - beta2^2 in float32: 1e-5 of the step)
    np.testing.assert_allclose(mine, step, rtol=1e-4, atol=1e-9)
    scheduler = {"type": "WarmupCosineLR", "params": {"warmup_num_steps": 200,
                                                      "total_num_steps": 100000}}
    theirs = warmup_cosine_lr(lr=3e-4, **scheduler["params"])
    for update in (1, 2, 3, 200):
        assert driver.warmup_lr(scheduler, 3e-4, update) == pytest.approx(
            float(theirs(update - 1)), rel=1e-6)
    assert driver.warmup_lr(scheduler, 3e-4, 1) == 0.0
