"""IBM granite-4.0-h-micro (``model_type: granitemoehybrid``) through the normal
path against the plain reference (``models/reference_granite4h.py``), at a tiny
size on the CPU: one period of ``m m a m``, hidden 64, 8 Mamba heads of 16 in
ONE group of state 16 with 4 taps (the scan's chunk is ``ops/ssd.CHUNK``,
whatever ``mamba_chunk_size`` says), 4 attention heads of 16 over 2 KV heads
that rotate nothing at scale 1/2 (where 1 / sqrt(16) = 1/4 is the neutral one), a
gated SiLU MLP of 96 in every block, the four multipliers (12, 0.22, 1/2, 8), a TIED head over 256 rows, 48 positions.
The weights are drawn by ``Transformer.init`` (gains and skip redrawn, as the
cell's driver does) and reach the reference through the driver's own mapping
(``chipbench/drivers/train_steps_ssm_dense.py``), so that mapping is part of
what is compared; the reference itself is held to ``transformers``'
``GraniteMoeHybridForCausalLM``.

Tolerances, float32 against float32: the two sides compute the same equations
in another order of additions (the chunked scan against the recurrence). Loss
1e-5; gradients 2e-3 of each leaf's norm.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import shuffle_exchange_tpu as sxt  # noqa: E402
from chipbench.drivers import train_steps_ssm_dense as driver  # noqa: E402
from shuffle_exchange_tpu.models import Transformer  # noqa: E402
from shuffle_exchange_tpu.models import reference_granite4h as ref  # noqa: E402
from shuffle_exchange_tpu.models.hf import config_from_hf  # noqa: E402

HF = {"model_type": "granitemoehybrid", "vocab_size": 256, "hidden_size": 64,
      "intermediate_size": 96, "shared_intermediate_size": 96, "num_hidden_layers": 4,
      "layer_types": ["mamba", "mamba", "attention", "mamba"] * 2,
      "num_attention_heads": 4, "num_key_value_heads": 2, "hidden_act": "silu",
      "max_position_embeddings": 256, "attention_bias": False, "rms_norm_eps": 1e-5,
      "tie_word_embeddings": True, "mamba_n_heads": 8, "mamba_d_head": 16,
      "mamba_n_groups": 1, "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
      "mamba_conv_bias": True, "mamba_proj_bias": False, "mamba_chunk_size": 256,
      "num_local_experts": 0, "num_experts_per_tok": 0, "position_embedding_type": "nope",
      "normalization_function": "rmsnorm", "attention_multiplier": 0.5,
      "embedding_multiplier": 12, "residual_multiplier": 0.22, "logits_scaling": 8}
# each multiplier's neutral value: the function a model without it computes
NEUTRAL = {"embedding_multiplier": 1, "residual_multiplier": 1.0,
           "attention_multiplier": 0.25, "logits_scaling": 1}
SEQ, BATCH = 48, 2
UNUSED = ("ln1_b", "ln2_b", "ln_f_b")
CELL_CONFIG = os.path.join(ROOT, "chipbench", "configs", "granite-4.0-h-micro-train.json")


def gaps(ours, theirs):
    return {k: float(np.linalg.norm(np.asarray(ours[k]) - np.asarray(theirs[k]))
                     / np.linalg.norm(np.asarray(theirs[k]))) for k in theirs}


def build(hf, seed=5):
    cfg = config_from_hf(hf)
    model = Transformer(cfg)
    params = driver.initial_params(model, seed)
    return cfg, model, params, driver.to_source_names(params, hf)


@pytest.fixture(scope="module")
def case():
    cfg, model, params, weights = build(HF)
    ids = np.random.default_rng(3).integers(0, HF["vocab_size"],
                                            (BATCH, SEQ + 1)).astype(np.int32)
    loss = jax.jit(lambda w, i: ref.loss(w, HF, i))(weights, ids)
    grads = driver.from_source_names(
        jax.jit(lambda w, i: ref.grads(w, HF, i))(weights, ids), HF)
    return {"cfg": cfg, "model": model, "params": params, "weights": weights,
            "ids": ids, "ref_loss": float(loss), "ref_grads": grads}


# -- the configuration ----------------------------------------------------------------

def test_config_from_hf_on_the_catalogs_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog)
               if '"granite-4.0-h-micro"' in line)
    cfg = config_from_hf(row["config"])
    assert (cfg.n_layers, cfg.d_model, cfg.vocab_size, cfg.n_heads, cfg.kv_heads,
            cfg.head_dim, cfg.ff_dim) == (40, 2048, 100352, 32, 8, 64, 8192)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state,
            cfg.ssm_conv_kernel) == (64, 64, 1, 128, 4)
    assert (cfg.embed_scale, cfg.residual_scale, cfg.attn_scale, cfg.logit_divisor) == (
        12.0, 0.22, 0.015625, 8.0)
    assert (cfg.position, cfg.norm, cfg.activation, cfg.tie_embeddings, cfg.n_experts) == (
        "none", "rmsnorm", "swiglu", True, 0)
    # ONE period of ten: nine state-space blocks and one attention block
    assert cfg.layer_pattern == (("ssm", "mlp"),) * 5 + (("attn", "mlp"),) + (("ssm", "mlp"),) * 4
    assert cfg.ssm_layers == 36 and cfg.several_kinds


def test_config_from_hf_on_the_cells_cut_and_its_counts():
    """The cell's file: ten layers, an eighth of the vocabulary, and the
    file's ``counts`` held to the PROGRAM's tree (shapes only: nothing is
    drawn)."""
    src = json.load(open(CELL_CONFIG))
    cfg = config_from_hf(src)
    assert (cfg.n_layers, cfg.vocab_size, cfg.ssm_layers) == (10, 12544, 9)
    shapes = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0))
    flat = driver.flat_tree(shapes)
    size = lambda names: sum(int(np.prod(flat[k].shape)) for k in names)
    unused = [k for k in flat if k.endswith(UNUSED)]
    counts = src["counts"]
    assert size(flat) - size(unused) == counts["parameters"] == 772_160_448
    assert size(unused) == 43_008
    mixer = lambda kind, names: size(f"layers/{kind}/{n}" for n in names)
    assert mixer("ssm_mlp", driver._MIXER["ssm"]) == 9 * counts["mamba_mixer"]
    assert mixer("attn_mlp", driver._MIXER["attn"]) == counts["attention_mixer"]
    assert mixer("attn_mlp", ("w_gate", "w_up", "w_down")) == counts["gated_mlp"]
    assert size(["embed", "ln_f_w"]) == counts["embedding_and_final_norm"]
    assert "unembed" not in flat


def test_layers_held_names_the_layers_of_a_cut():
    cfg = config_from_hf(dict(HF, num_hidden_layers=2, layers_held=[2, 3]))
    assert cfg.layer_pattern == (("attn", "mlp"), ("ssm", "mlp"))
    with pytest.raises(ValueError, match="layers_held"):
        config_from_hf(dict(HF, num_hidden_layers=2, layers_held=[3, 2]))


@pytest.mark.parametrize("key, value", [
    ("num_local_experts", 8), ("attention_bias", True), ("mamba_proj_bias", True),
    ("mamba_conv_bias", False), ("position_embedding_type", "rope"),
    ("mamba_n_groups", 3), ("hidden_act", "gelu")])
def test_what_is_not_written_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=f"granitemoehybrid with {key}="):
        config_from_hf(dict(HF, **{key: value}))


# -- the program against the reference ------------------------------------------------

def test_first_loss_and_the_scans_counter(case):
    loss, stats = jax.jit(case["model"].loss_and_stats)(
        case["params"], {"input_ids": case["ids"]})
    assert abs(float(loss) - case["ref_loss"]) < 1e-5
    # 48 positions in one chunk of 128, 2 sequences, 3 state-space layers
    assert int(stats["ssm_scan_chunks"]) == 1 * 2 * 3


def test_logits_of_the_tied_scaled_head(case):
    logits = jax.jit(case["model"].apply)(case["params"], case["ids"][:, :-1])
    want = np.asarray(jax.jit(lambda w, i: ref.forward(w, HF, i))(
        case["weights"], case["ids"][:, :-1]))
    assert logits.shape == (BATCH, SEQ, 256)
    assert np.max(np.abs(np.asarray(logits) - want)) < 1e-4 * np.max(np.abs(want))


@pytest.mark.parametrize("form", ["plain", "remat_halves", "chunked_loss"])
def test_every_gradient_leaf(case, form):
    """Every leaf of both kinds of block and the TIED embedding (the sum of a
    lookup scaled by 12 and a head divided by 8) against the reference's, with
    and without per-half remat, and with the chunked loss (the head's custom
    backward: dx and dw of the divided logits)."""
    change = {"plain": {}, "remat_halves": {"remat": True, "remat_policy": "full"},
              "chunked_loss": {"loss_chunk": 16}}[form]
    model = Transformer(dataclasses.replace(case["cfg"], **change))
    loss, grad = jax.jit(jax.value_and_grad(model.loss))(
        case["params"], {"input_ids": case["ids"]})
    assert abs(float(loss) - case["ref_loss"]) < 1e-5
    got = driver.flat_tree(grad)
    unused = {k for k in got if k.endswith(UNUSED)}
    assert set(got) - unused == set(case["ref_grads"])
    assert {"layers/ssm_mlp/ssm_w_in", "layers/ssm_mlp/ssm_conv_b", "layers/ssm_mlp/ssm_A_log",
            "layers/ssm_mlp/ssm_dt_bias", "layers/ssm_mlp/ssm_D", "layers/ssm_mlp/ssm_norm_w",
            "layers/ssm_mlp/w_gate", "layers/attn_mlp/wq", "layers/attn_mlp/w_down",
            "embed", "ln_f_w"} <= set(got)
    worst = gaps(got, case["ref_grads"])
    assert max(worst.values()) < 2e-3, worst


@pytest.mark.parametrize("name", sorted(NEUTRAL))
def test_each_multiplier_alone(name):
    """ONE multiplier at the source's value and the other three neutral: the
    program's loss and its tied embedding's gradient (both of its paths) are
    the reference's, and they are NOT what the all-neutral model reads: a
    multiplier dropped, or applied twice, fails one of the two."""
    alone = {**HF, **NEUTRAL, name: HF[name]}
    ids = np.random.default_rng(4).integers(0, 256, (BATCH, SEQ + 1)).astype(np.int32)
    cfg, model, params, weights = build(alone)
    fields = {"embedding_multiplier": "embed_scale", "residual_multiplier": "residual_scale",
              "attention_multiplier": "attn_scale", "logits_scaling": "logit_divisor"}
    for key, field in fields.items():
        assert getattr(cfg, field) == float(alone[key])
    loss, grad = jax.jit(jax.value_and_grad(model.loss))(params, {"input_ids": ids})
    want = jax.jit(lambda w, i: ref.loss(w, alone, i))(weights, ids)
    want_grad = jax.jit(lambda w, i: ref.grads(w, alone, i))(weights, ids)
    assert abs(float(loss) - float(want)) < 1e-5
    embed = gaps({"e": grad["embed"]}, {"e": want_grad["model.embed_tokens.weight"]})
    assert embed["e"] < 2e-3, embed
    neutral = jax.jit(lambda w, i: ref.loss(w, {**HF, **NEUTRAL}, i))(weights, ids)
    assert abs(float(want) - float(neutral)) > 1e-4, (name, float(want), float(neutral))
    twice = {**alone, name: HF[name] ** 2 / NEUTRAL[name]}
    again = jax.jit(lambda w, i: ref.loss(w, twice, i))(weights, ids)
    assert abs(float(want) - float(again)) > 1e-4, (name, float(want), float(again))


def test_a_neutral_multiplier_emits_no_operation(case):
    """With the four neutral the program's text is the text of a model that
    has no such field: nothing multiplies by one."""
    neutral = dataclasses.replace(case["cfg"], embed_scale=1.0, residual_scale=1.0,
                                  attn_scale=0.0, logit_divisor=1.0)
    text = lambda cfg: jax.jit(Transformer(cfg).loss).lower(
        case["params"], {"input_ids": case["ids"]}).as_text()
    plain, scaled = text(neutral), text(case["cfg"])
    assert len(scaled.splitlines()) > len(plain.splitlines())
    for constant in ("1.200000e+01", "2.200000e-01"):
        assert constant in scaled and constant not in plain


def test_the_multipliers_sit_under_the_scopes_of_what_they_scale(case):
    """The products the multipliers add carry the scope of the operation they
    scale (``embed``, ``attn_qkv``, ``attn_out`` / ``mlp``, ``loss``), so that
    no op of theirs lands under no scope; the neutral model has none of them."""
    def scoped(cfg):
        text = jax.jit(Transformer(cfg).loss).lower(
            case["params"], {"input_ids": case["ids"]}).as_text(debug_info=True)
        return {name for name in ("embed/mul", "attn_qkv/mul", "attn_out/mul", "mlp/mul",
                                  "loss/loss/div")
                if any(line.startswith("#loc") and f'{name}"' in line
                       for line in text.splitlines())}

    assert scoped(case["cfg"]) == {"embed/mul", "attn_qkv/mul", "attn_out/mul", "mlp/mul",
                                   "loss/loss/div"}
    neutral = dataclasses.replace(case["cfg"], embed_scale=1.0, residual_scale=1.0,
                                  attn_scale=0.0, logit_divisor=1.0)
    # (the gated MLP's own silu(g) * u is a product under ``mlp`` in any model)
    assert scoped(neutral) == {"mlp/mul"}


def test_the_trainer_through_initialize(case):
    """``sxt.initialize(...).train_batch`` in float32 under ZeRO-3 and
    per-half remat: the first loss, the scans' counter and the first gradient
    out of Adam's moment."""
    model = Transformer(case["cfg"])
    rows = 8                                  # one per device of the test mesh
    ids = np.random.default_rng(9).integers(0, 256, (rows, SEQ + 1)).astype(np.int32)
    want = float(jax.jit(lambda w, i: ref.loss(w, HF, i))(case["weights"], ids))
    want_grads = driver.from_source_names(
        jax.jit(lambda w, i: ref.grads(w, HF, i))(case["weights"], ids), HF)
    engine = sxt.initialize(
        model=model, params=driver.initial_params(model, 5),
        config={"optimizer": {"type": "FusedAdam",
                              "params": {"lr": 1e-4, "weight_decay": 0.1}},
                "zero_optimization": {"stage": 3},
                "activation_checkpointing": {"enabled": True, "policy": "full"},
                "train_batch_size": rows, "steps_per_print": 10 ** 9}, seed=5)[0]
    assert model.config.remat
    loss = float(engine.train_batch({"input_ids": ids}))
    assert abs(loss - want) < 2e-5
    assert int(engine.last_step_stats()["ssm_scan_chunks"]) == 1 * rows * 3
    moment = driver.first_moment(engine.state.opt_state)
    got = {k: np.asarray(v) * 10.0 for k, v in moment.items()}     # / (1 - beta1)
    worst = gaps(got, want_grads)
    assert max(worst.values()) < 2e-3, worst


@pytest.mark.parametrize("engine", ["v1", "v2"])
def test_the_inference_engines_refuse_the_multipliers_by_name(case, engine):
    from shuffle_exchange_tpu.inference.engine import InferenceEngine
    from shuffle_exchange_tpu.inference.engine_v2 import InferenceEngineV2

    cls = InferenceEngine if engine == "v1" else InferenceEngineV2
    # the stack itself is refused for its state-space mixer
    with pytest.raises(NotImplementedError, match="mixer 'ssm'"):
        cls(case["model"], case["params"])
    # and a one-kind model with a multiplier for the multiplier
    for field in ("embed_scale", "residual_scale", "logit_divisor"):
        plain = Transformer(dataclasses.replace(
            case["cfg"], layer_pattern=(), **{field: 2.0}))
        with pytest.raises(NotImplementedError, match=f"multipliers.*{field}=2.0"):
            cls(plain, {})


def test_a_one_kind_model_takes_the_residual_multiplier():
    """The stack cut to its attention layers is ONE kind of layer (a flat
    ``params["layers"]``): its blocks scale their sublayers as the hybrid's do.
    Loss and every gradient against the reference on the same two layers, and
    not what the neutral multiplier reads."""
    hf = dict(HF, num_hidden_layers=2, layer_types=["attention"] * 2)
    cfg = config_from_hf(hf)
    assert not cfg.several_kinds and cfg.residual_scale == 0.22
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(5))
    assert params["layers"]["wq"].shape[0] == 2             # flat, [L, ...]
    # the driver's mapping reads a kind's leaves [periods, layers a period, ...]
    by_kind = lambda tree: dict(tree, layers={"attn_mlp": jax.tree.map(
        lambda a: a[:, None], tree["layers"])})
    weights = driver.to_source_names(by_kind(params), hf)
    ids = np.random.default_rng(3).integers(0, 256, (BATCH, SEQ + 1)).astype(np.int32)
    loss, grad = jax.jit(jax.value_and_grad(model.loss))(params, {"input_ids": ids})
    want = float(jax.jit(lambda w, i: ref.loss(w, hf, i))(weights, ids))
    assert abs(float(loss) - want) < 1e-5
    want_grad = driver.from_source_names(
        jax.jit(lambda w, i: ref.grads(w, hf, i))(weights, ids), hf)
    got = driver.flat_tree(by_kind(grad))
    assert {"layers/attn_mlp/wq", "layers/attn_mlp/w_down", "embed"} <= set(want_grad)
    worst = gaps(got, want_grad)
    assert max(worst.values()) < 2e-3, worst
    neutral = Transformer(dataclasses.replace(cfg, residual_scale=1.0))
    assert abs(float(jax.jit(neutral.loss)(params, {"input_ids": ids})) - want) > 1e-4


def test_checkpoint_import_is_not_written():
    from shuffle_exchange_tpu.models.hf import params_from_state_dict

    with pytest.raises(NotImplementedError, match="several layer kinds"):
        params_from_state_dict({}, config_from_hf(HF), "granitemoehybrid")


def test_the_two_reference_copies_agree():
    body = lambda path: open(os.path.join(ROOT, path)).read().split('"""', 2)[2]
    assert body("chipbench/reference_granite4h.py") == body(
        "shuffle_exchange_tpu/models/reference_granite4h.py")
    assert "shuffle_exchange_tpu" not in "".join(
        line for line in body("chipbench/reference_granite4h.py").splitlines()
        if line.startswith(("import", "from")))


# -- the one-group kernels and the wide gated norm ------------------------------------

@pytest.mark.parametrize("heads", [2, 4], ids=["one_lane_tile", "two_lane_tiles"])
def test_the_one_group_scan_through_the_interpreter(heads, monkeypatch):
    """The scan's three kernels at ONE group whose heads fill one and two lane
    tiles (heads of 64, a state of 128; 300 tokens: three chunks, the last
    ragged) against the XLA body and the recurrence, forward and backward."""
    from shuffle_exchange_tpu.ops import ssd

    B, T, P, N = 1, 300, 64, 128
    ks = jax.random.split(jax.random.PRNGKey(heads), 6)
    x = jax.nn.silu(jax.random.normal(ks[0], (B, T, heads, P)))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, heads)) - 3.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (heads,), jnp.float32, 0.0, 2.7))
    Bm, Cm = (jax.random.normal(k, (B, T, 1, N)) for k in ks[3:5])
    push = jax.random.normal(ks[5], x.shape)

    def answers(scan):
        out, back = jax.vjp(lambda x, dt, Bm, Cm: scan(x, dt, A, Bm, Cm), x, dt, Bm, Cm)
        return dict(zip(("dx", "ddt", "dB", "dC"), back(push)), out=out)

    assert ssd.ssd_route(x, Bm) == "xla"
    xla = answers(ssd.ssd_chunked)
    exact = answers(lambda *a: ssd.ssd_recurrent(*a, jnp.zeros((heads,))))
    monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    assert ssd.ssd_route(x, Bm) == "interpret"
    got = answers(ssd.ssd_chunked)
    assert max(gaps(got, xla).values()) < 2e-4, gaps(got, xla)
    assert max(gaps(got, exact).values()) < 2e-4, gaps(got, exact)


@pytest.mark.parametrize("inner, route", [(1024, "interpret"), (4096, "xla")],
                         ids=["eight_lane_tiles", "published_4096"])
def test_the_gated_norm_over_one_wide_group(inner, route, monkeypatch):
    """The epilogue over ONE group: at 4096 channels (32 lane tiles) the route
    is XLA's body whatever the backend (the kernels hold a group of at most 8
    tiles in registers; ISSUE 46's rule left the body that runs, PERF.md PR
    55), at 1024 the kernels take it; both against the plain float32 lines,
    forward and backward."""
    from shuffle_exchange_tpu.ops import ssm_gate_norm as gn

    B, T, H = 1, 72, inner // 64
    ks = jax.random.split(jax.random.PRNGKey(inner), 6)
    o, x, z = (jax.random.normal(k, (B, T, inner)) for k in ks[:3])
    D = jax.random.uniform(ks[3], (H,), jnp.float32, 0.5, 1.5)
    gain = jax.random.uniform(ks[4], (inner,), jnp.float32, 0.5, 1.5)
    push = jax.random.normal(ks[5], o.shape)

    def plain(o, x, z, D, gain):
        y = (o + jnp.repeat(D, inner // H) * x) * jax.nn.silu(z)
        return y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + 1e-5) * gain

    def answers(fn):
        out, back = jax.vjp(fn, o, x, z, D, gain)
        return dict(zip(("do", "dx", "dz", "dD", "dgain"), back(push)), out=out)

    monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    assert gn.ssm_gate_norm_route(o, 1) == route
    got = answers(lambda *a: gn.ssm_gate_norm(*a, 1, 1e-5))
    worst = gaps(got, answers(plain))
    assert max(worst.values()) < 1e-5, worst


# -- the reference against the family's published modelling code ----------------------

def test_the_reference_is_transformers_granitemoehybrid():
    """``transformers``' ``GraniteMoeHybridForCausalLM`` (its plain
    ``torch_forward`` path on the CPU) on the reference's own seeded weights,
    loaded under the same tensor names: the same logits and the same loss.
    What is held: the four multipliers, attention that rotates nothing at the
    multiplier's scale, the Mamba-2 layer at one group with the gate BEFORE a
    norm over all channels, the gated MLP's [gate | up] order, the tied head."""
    torch = pytest.importorskip("torch")
    modeling = pytest.importorskip(
        "transformers.models.granitemoehybrid.modeling_granitemoehybrid")
    from transformers.models.granitemoehybrid.configuration_granitemoehybrid import (
        GraniteMoeHybridConfig)

    hf = dict(HF, num_hidden_layers=4, layer_types=HF["layer_types"][:4])
    config = GraniteMoeHybridConfig(**{k: v for k, v in hf.items() if k != "model_type"})
    weights = ref.init_weights(hf, 11)
    model = modeling.GraniteMoeHybridForCausalLM(config).float().eval()
    state = {k: torch.tensor(np.asarray(v)) for k, v in weights.items()}
    state["lm_head.weight"] = state["model.embed_tokens.weight"]
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not unexpected and all("rotary" in k or "inv_freq" in k for k in missing), (
        missing, unexpected)
    ids = np.random.default_rng(2).integers(0, 256, (2, 41)).astype(np.int64)
    with torch.no_grad():
        want = model(input_ids=torch.tensor(ids[:, :-1])).logits.numpy()
    got = np.asarray(ref.forward(weights, hf, jnp.asarray(ids[:, :-1], jnp.int32)))
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()
    logp = torch.log_softmax(torch.tensor(want), dim=-1)
    loss = -logp.gather(-1, torch.tensor(ids[:, 1:])[..., None]).mean().item()
    assert abs(float(ref.loss(weights, hf, jnp.asarray(ids, jnp.int32))) - loss) < 1e-5
