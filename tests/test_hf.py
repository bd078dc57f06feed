"""HF model import (reference module_inject containers + v2 engine_factory
arch dispatch) and AutoTP spec inference (module_inject/auto_tp.py).

Parity strategy: build tiny randomly-initialized transformers models on CPU
torch, convert with models/hf.py, and compare logits against the HF forward
— a much stronger check than shape tests.
"""

import numpy as np
import pytest

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")

from shuffle_exchange_tpu.models.hf import config_from_hf, from_hf
from shuffle_exchange_tpu.parallel.autotp import classify, infer_partition_specs


def _compare(hf_model, ids, rtol=2e-3, atol=2e-3):
    import jax

    hf_model.eval()
    with torch.no_grad():
        expected = hf_model(torch.tensor(ids)).logits.float().numpy()
    model, params = from_hf(hf_model)
    got = np.asarray(jax.jit(model.apply)(params, ids), np.float32)
    np.testing.assert_allclose(got, expected, rtol=rtol, atol=atol)


def _ids(vocab, b=2, t=16, seed=0):
    return np.random.default_rng(seed).integers(0, vocab, size=(b, t)).astype(np.int32)


@pytest.mark.slow
def test_llama_logit_parity():
    cfg = transformers.LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=128,
                                   num_hidden_layers=2, num_attention_heads=4,
                                   num_key_value_heads=2, max_position_embeddings=64,
                                   rope_theta=10000.0, tie_word_embeddings=False)
    torch.manual_seed(0)
    _compare(transformers.LlamaForCausalLM(cfg), _ids(96))


def test_mistral_logit_parity():
    cfg = transformers.MistralConfig(vocab_size=96, hidden_size=64, intermediate_size=128,
                                     num_hidden_layers=2, num_attention_heads=4,
                                     num_key_value_heads=2, max_position_embeddings=64,
                                     sliding_window=None, tie_word_embeddings=False)
    torch.manual_seed(1)
    _compare(transformers.MistralForCausalLM(cfg), _ids(96))


def test_qwen2_logit_parity_with_qkv_bias():
    cfg = transformers.Qwen2Config(vocab_size=96, hidden_size=64, intermediate_size=128,
                                   num_hidden_layers=2, num_attention_heads=4,
                                   num_key_value_heads=2, max_position_embeddings=64,
                                   tie_word_embeddings=False)
    torch.manual_seed(2)
    model = transformers.Qwen2ForCausalLM(cfg)
    # make biases matter
    with torch.no_grad():
        for layer in model.model.layers:
            layer.self_attn.q_proj.bias.normal_(0, 0.1)
            layer.self_attn.k_proj.bias.normal_(0, 0.1)
            layer.self_attn.v_proj.bias.normal_(0, 0.1)
    _compare(model, _ids(96))


def test_gpt2_logit_parity():
    cfg = transformers.GPT2Config(vocab_size=96, n_embd=64, n_layer=2, n_head=4,
                                  n_positions=64, attn_pdrop=0.0, embd_pdrop=0.0,
                                  resid_pdrop=0.0)
    torch.manual_seed(3)
    _compare(transformers.GPT2LMHeadModel(cfg), _ids(96), rtol=5e-3, atol=5e-3)


def test_opt_logit_parity():
    cfg = transformers.OPTConfig(vocab_size=96, hidden_size=64, ffn_dim=128,
                                 num_hidden_layers=2, num_attention_heads=4,
                                 max_position_embeddings=64, do_layer_norm_before=True,
                                 dropout=0.0, activation_function="gelu")
    torch.manual_seed(4)
    _compare(transformers.OPTForCausalLM(cfg), _ids(96), rtol=5e-3, atol=5e-3)


def test_mixtral_logit_parity():
    cfg = transformers.MixtralConfig(vocab_size=96, hidden_size=64, intermediate_size=128,
                                     num_hidden_layers=2, num_attention_heads=4,
                                     num_key_value_heads=2, max_position_embeddings=64,
                                     num_local_experts=4, num_experts_per_tok=2,
                                     tie_word_embeddings=False)
    torch.manual_seed(5)
    # small batch so capacity (factor 8) routes without drops
    _compare(transformers.MixtralForCausalLM(cfg), _ids(96, b=1, t=8), rtol=5e-3, atol=5e-3)


def test_phi3_logit_parity():
    cfg = transformers.Phi3Config(vocab_size=96, hidden_size=64, intermediate_size=128,
                                  num_hidden_layers=2, num_attention_heads=4,
                                  num_key_value_heads=2, max_position_embeddings=64,
                                  tie_word_embeddings=False, pad_token_id=0)
    torch.manual_seed(6)
    _compare(transformers.Phi3ForCausalLM(cfg), _ids(96))


def test_gptj_logit_parity():
    """Parallel block + shared ln + interleaved partial rotary + lm_head bias."""
    cfg = transformers.GPTJConfig(vocab_size=96, n_embd=64, n_layer=2, n_head=4,
                                  rotary_dim=8, n_positions=64,
                                  attn_pdrop=0.0, embd_pdrop=0.0, resid_pdrop=0.0)
    torch.manual_seed(4)
    model = transformers.GPTJForCausalLM(cfg)
    with torch.no_grad():
        model.lm_head.bias.normal_(0, 0.1)   # make the head bias matter
    _compare(model, _ids(96), rtol=5e-3, atol=5e-3)


def test_gptneox_logit_parity():
    """Parallel residual with two norms + rotary_pct partial rope + fused
    interleaved QKV."""
    cfg = transformers.GPTNeoXConfig(vocab_size=96, hidden_size=64,
                                     intermediate_size=128, num_hidden_layers=2,
                                     num_attention_heads=4, rotary_pct=0.5,
                                     max_position_embeddings=64,
                                     use_parallel_residual=True,
                                     attention_dropout=0.0, hidden_dropout=0.0)
    torch.manual_seed(5)
    _compare(transformers.GPTNeoXForCausalLM(cfg), _ids(96), rtol=5e-3, atol=5e-3)


def test_gptneox_sequential_variant():
    cfg = transformers.GPTNeoXConfig(vocab_size=96, hidden_size=64,
                                     intermediate_size=128, num_hidden_layers=2,
                                     num_attention_heads=4, rotary_pct=0.25,
                                     max_position_embeddings=64,
                                     use_parallel_residual=False,
                                     attention_dropout=0.0, hidden_dropout=0.0)
    torch.manual_seed(6)
    _compare(transformers.GPTNeoXForCausalLM(cfg), _ids(96), rtol=5e-3, atol=5e-3)


def test_falcon_logit_parity_multiquery():
    """Falcon-7B shape: multi-query, parallel attn, shared ln, no biases."""
    cfg = transformers.FalconConfig(vocab_size=96, hidden_size=64,
                                    num_hidden_layers=2, num_attention_heads=4,
                                    multi_query=True, parallel_attn=True,
                                    new_decoder_architecture=False, bias=False,
                                    alibi=False, attention_dropout=0.0,
                                    hidden_dropout=0.0)
    torch.manual_seed(7)
    _compare(transformers.FalconForCausalLM(cfg), _ids(96), rtol=5e-3, atol=5e-3)


def test_falcon_logit_parity_new_arch_gqa():
    """Falcon-40B shape: new decoder architecture, GQA, ln_attn/ln_mlp."""
    cfg = transformers.FalconConfig(vocab_size=96, hidden_size=64,
                                    num_hidden_layers=2, num_attention_heads=4,
                                    num_kv_heads=2, new_decoder_architecture=True,
                                    bias=False, alibi=False,
                                    attention_dropout=0.0, hidden_dropout=0.0)
    torch.manual_seed(8)
    _compare(transformers.FalconForCausalLM(cfg), _ids(96), rtol=5e-3, atol=5e-3)


def test_falcon_rw_logit_parity():
    """Falcon-RW shape: sequential block, ALiBi, biases, per-head
    interleaved fused QKV (r3 review regression: the RW path was rejected
    by a guard and never loaded its bias tensors)."""
    cfg = transformers.FalconConfig(vocab_size=96, hidden_size=64,
                                    num_hidden_layers=2, num_attention_heads=4,
                                    multi_query=False, parallel_attn=False,
                                    new_decoder_architecture=False, bias=True,
                                    alibi=True, attention_dropout=0.0,
                                    hidden_dropout=0.0)
    torch.manual_seed(10)
    _compare(transformers.FalconForCausalLM(cfg), _ids(96), rtol=5e-3, atol=5e-3)


def test_bloom_logit_parity_alibi():
    """BLOOM: ALiBi positions, embedding layernorm, fused interleaved QKV."""
    cfg = transformers.BloomConfig(vocab_size=96, hidden_size=64, n_layer=2,
                                   n_head=4, attention_dropout=0.0,
                                   hidden_dropout=0.0)
    torch.manual_seed(9)
    _compare(transformers.BloomForCausalLM(cfg), _ids(96), rtol=5e-3, atol=5e-3)


def test_config_from_hf_rejects_unknown():
    with pytest.raises(ValueError):
        config_from_hf({"model_type": "space_transformer", "architectures": ["SpaceLM"]})


def test_converted_model_trains(devices8):
    """An imported HF model drops straight into sxt.initialize."""
    import shuffle_exchange_tpu as sxt
    from shuffle_exchange_tpu.parallel import reset_topology

    cfg = transformers.LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                                   num_hidden_layers=2, num_attention_heads=4,
                                   num_key_value_heads=2, max_position_embeddings=32,
                                   tie_word_embeddings=False)
    torch.manual_seed(7)
    model, params = from_hf(transformers.LlamaForCausalLM(cfg))
    reset_topology()
    engine, *_ = sxt.initialize(model=model, params=params, config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3},
        "steps_per_print": 10**9})
    batch = {"input_ids": _ids(64, b=8, t=32)}
    l0 = float(engine.train_batch(batch))
    l1 = float(engine.train_batch(batch))
    assert np.isfinite(l0) and l1 < l0


def test_init_inference_accepts_hf_model():
    import shuffle_exchange_tpu as sxt

    cfg = transformers.LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                                   num_hidden_layers=2, num_attention_heads=4,
                                   num_key_value_heads=2, max_position_embeddings=64,
                                   tie_word_embeddings=False)
    torch.manual_seed(8)
    eng = sxt.init_inference(model=transformers.LlamaForCausalLM(cfg),
                             config={"dtype": "fp32", "max_seq_len": 64})
    out = eng.generate(np.array([[1, 2, 3]], np.int32), max_new_tokens=4, temperature=0.0)
    assert out.shape == (1, 4)  # generate returns the new tokens


# ---------------------------------------------------------------------------
# AutoTP
# ---------------------------------------------------------------------------


def test_classify_names():
    assert classify(["layers", "0", "self_attn", "q_proj", "weight"]) == "column"
    assert classify(["layers", "0", "self_attn", "o_proj", "weight"]) == "row"
    assert classify(["model", "embed_tokens", "weight"]) == "vocab"
    assert classify(["lm_head", "weight"]) == "unembed"
    assert classify(["layers", "0", "input_layernorm", "weight"]) == "replicate"


def test_infer_partition_specs_on_hf_tree():
    from jax.sharding import PartitionSpec as P

    tree = {
        "layers": {
            "wq": np.zeros((2, 16, 32)),   # stacked column
            "wo": np.zeros((2, 32, 16)),   # stacked row
            "b_q": np.zeros((2, 32)),      # column bias
            "ln1_w": np.zeros((2, 16)),
        },
        "embed": np.zeros((100, 16)),
        "lm_head": np.zeros((16, 100)),
    }
    specs = infer_partition_specs(tree)
    assert specs["layers"]["wq"] == P(None, None, "tensor")
    assert specs["layers"]["wo"] == P(None, "tensor", None)
    assert specs["layers"]["b_q"] == P(None, "tensor")
    assert specs["layers"]["ln1_w"] == P(None, None)
    assert specs["embed"] == P("tensor", None)
    assert specs["lm_head"] == P(None, "tensor")


def test_qwen2moe_logit_parity():
    """Qwen2-MoE (v2 engine_factory's qwen-moe arch): top-4 softmax routing
    WITHOUT weight renormalization + a sigmoid-gated shared expert."""
    cfg = transformers.Qwen2MoeConfig(
        vocab_size=96, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=48, shared_expert_intermediate_size=80,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_experts=4, num_experts_per_tok=2, decoder_sparse_step=1,
        max_position_embeddings=64, tie_word_embeddings=False,
        attention_dropout=0.0)
    torch.manual_seed(11)
    _compare(transformers.Qwen2MoeForCausalLM(cfg), _ids(96), rtol=5e-3, atol=5e-3)


def _olmoe_model(**kw):
    cfg = transformers.OlmoeConfig(
        vocab_size=96, hidden_size=64, intermediate_size=48,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        num_experts=8, num_experts_per_tok=3, norm_topk_prob=False,
        max_position_embeddings=64, tie_word_embeddings=False,
        attention_dropout=0.0, **kw)
    torch.manual_seed(13)
    model = transformers.OlmoeForCausalLM(cfg)
    with torch.no_grad():           # the norms' gains start at 1: move them
        for name, p in model.named_parameters():
            if name.endswith("norm.weight"):
                p.add_(0.1 * torch.randn_like(p))
    return model


def test_olmoe_logit_parity():
    """OLMoE (allenai/OLMoE-1B-7B, ``model_type: olmoe``): RMSNorm over the
    whole q and k projections, 8 fine-grained experts top-3 with raw (not
    renormalised) weights, no shared expert: against transformers' own
    forward."""
    model = _olmoe_model()
    cfg = config_from_hf(model.config)
    assert (cfg.qk_norm, cfg.moe_impl, cfg.moe_aux, cfg.moe_norm_topk) == (
        True, "ragged", "all_choices", False)
    _compare(model, _ids(96), rtol=5e-3, atol=5e-3)


def test_olmoe_state_dict_round_trip_and_aux_loss():
    """The checkpoint-directory layout (config dict + state dict under the
    source's names) imports leaf for leaf, and the trainer's loss with the
    balancing term is transformers' ``load_balancing_loss_func`` on the same
    positions."""
    import jax

    from shuffle_exchange_tpu.models.hf import params_from_state_dict

    model = _olmoe_model(output_router_logits=True, router_aux_loss_coef=0.5)
    sd = {k: v for k, v in model.state_dict().items()}
    assert "model.layers.1.self_attn.q_norm.weight" in sd
    assert "model.layers.0.mlp.experts.7.down_proj.weight" in sd
    zoo, params = from_hf((model.config.to_dict(), sd))
    layers = params["layers"]
    assert layers["q_norm_w"].shape == (2, 64) and layers["k_norm_w"].shape == (2, 64)
    assert layers["moe_gate"].shape == (2, 64, 8)
    assert layers["moe_w_gate"].shape == (2, 8, 64, 48)
    assert layers["moe_w_down"].shape == (2, 8, 48, 64)
    np.testing.assert_array_equal(
        np.asarray(layers["moe_w_up"][1, 5]),
        sd["model.layers.1.mlp.experts.5.up_proj.weight"].numpy().T)
    again = params_from_state_dict(sd, zoo.config, "olmoe")
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(
            {k: v for k, v in params.items()})):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # HF scores all T positions and shifts; the trainer feeds T-1 positions.
    # Hand HF the same T-1 positions with the labels the trainer uses
    ids = _ids(96, b=2, t=17)
    model.eval()
    with torch.no_grad():
        out = model(torch.tensor(ids[:, :-1]), output_router_logits=True)
        logits = out.logits.float()
        ce = torch.nn.functional.cross_entropy(
            logits.reshape(-1, 96), torch.tensor(ids[:, 1:]).reshape(-1).long())
        from transformers.models.olmoe.modeling_olmoe import load_balancing_loss_func

        aux = load_balancing_loss_func(out.router_logits, 8, 3)
    want = float(ce) + 0.5 * float(aux)
    got = float(jax.jit(zoo.loss)(params, {"input_ids": ids}))
    assert abs(got - want) < 2e-4 * want, (got, want, float(aux))


def test_bert_mlm_logit_parity():
    """Encoder family (reference module_inject/containers/bert.py): post-LN
    bidirectional blocks + token types + the MLM transform head."""
    cfg = transformers.BertConfig(
        vocab_size=96, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128,
        max_position_embeddings=64, type_vocab_size=2,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    torch.manual_seed(7)
    _compare(transformers.BertForMaskedLM(cfg), _ids(96))


def test_distilbert_mlm_logit_parity():
    cfg = transformers.DistilBertConfig(
        vocab_size=96, dim=64, n_layers=2, n_heads=4, hidden_dim=128,
        max_position_embeddings=64, dropout=0.0, attention_dropout=0.0)
    torch.manual_seed(8)
    _compare(transformers.DistilBertForMaskedLM(cfg), _ids(96))


def test_gptneo_local_attention_logit_parity():
    """GPT-Neo (reference containers/gptneo.py): unscaled attention and the
    alternating global/local window pattern."""
    cfg = transformers.GPTNeoConfig(
        vocab_size=96, hidden_size=64, num_layers=4, num_heads=4,
        attention_types=[[["global", "local"], 2]], window_size=8,
        max_position_embeddings=64, intermediate_size=128,
        embed_dropout=0.0, attention_dropout=0.0, resid_dropout=0.0)
    torch.manual_seed(9)
    # t=24 > window 8 so local layers actually mask
    _compare(transformers.GPTNeoForCausalLM(cfg), _ids(96, t=24))


def test_internlm_family_structural():
    """InternLM v1 is llama wiring + qkvo biases; no HF class ships in
    transformers (remote code), so build the state dict by name."""
    rng = np.random.default_rng(0)
    L, D, H, KV, F, V = 2, 32, 4, 4, 64, 64
    Dh = D // H
    cfg = {"architectures": ["InternLMForCausalLM"], "model_type": "internlm",
           "vocab_size": V, "hidden_size": D, "num_hidden_layers": L,
           "num_attention_heads": H, "intermediate_size": F, "bias": True,
           "max_position_embeddings": 64, "rms_norm_eps": 1e-6,
           "tie_word_embeddings": False}
    sd = {"model.embed_tokens.weight": rng.normal(size=(V, D)).astype(np.float32) * 0.02,
          "model.norm.weight": np.ones((D,), np.float32),
          "lm_head.weight": rng.normal(size=(V, D)).astype(np.float32) * 0.02}
    for i in range(L):
        pre = f"model.layers.{i}."
        sd[pre + "input_layernorm.weight"] = np.ones((D,), np.float32)
        sd[pre + "post_attention_layernorm.weight"] = np.ones((D,), np.float32)
        for nm, shape in (("q_proj", (H * Dh, D)), ("k_proj", (KV * Dh, D)),
                          ("v_proj", (KV * Dh, D)), ("o_proj", (D, H * Dh))):
            sd[pre + f"self_attn.{nm}.weight"] = rng.normal(size=shape).astype(np.float32) * 0.05
            sd[pre + f"self_attn.{nm}.bias"] = rng.normal(size=(shape[0],)).astype(np.float32) * 0.01
        sd[pre + "mlp.gate_proj.weight"] = rng.normal(size=(F, D)).astype(np.float32) * 0.05
        sd[pre + "mlp.up_proj.weight"] = rng.normal(size=(F, D)).astype(np.float32) * 0.05
        sd[pre + "mlp.down_proj.weight"] = rng.normal(size=(D, F)).astype(np.float32) * 0.05
    import jax

    model, params = from_hf((cfg, sd))
    assert model.config.attn_qkv_bias and model.config.attn_out_bias
    logits = jax.jit(model.apply)(params, _ids(V, t=16))
    assert np.isfinite(np.asarray(logits)).all()
    assert logits.shape == (2, 16, V)


def test_internlm2_fused_wqkv_grouping():
    """InternLM2 fuses wqkv grouped per kv head (G q rows, then k, then v):
    verify the split against an equivalent hand-built llama state dict."""
    rng = np.random.default_rng(1)
    L, D, H, KV, F, V = 2, 32, 4, 2, 64, 64
    Dh = D // H
    G = H // KV
    # build per-head projections, then fuse them the internlm2 way
    wq = rng.normal(size=(L, H * Dh, D)).astype(np.float32) * 0.05
    wk = rng.normal(size=(L, KV * Dh, D)).astype(np.float32) * 0.05
    wv = rng.normal(size=(L, KV * Dh, D)).astype(np.float32) * 0.05
    sd = {"model.tok_embeddings.weight": rng.normal(size=(V, D)).astype(np.float32) * 0.02,
          "model.norm.weight": np.ones((D,), np.float32),
          "output.weight": rng.normal(size=(V, D)).astype(np.float32) * 0.02}
    for i in range(L):
        pre = f"model.layers.{i}."
        fused = np.concatenate([
            np.concatenate([wq[i].reshape(KV, G, Dh, D)[j],
                            wk[i].reshape(KV, 1, Dh, D)[j],
                            wv[i].reshape(KV, 1, Dh, D)[j]], axis=0)
            for j in range(KV)], axis=0).reshape((G + 2) * KV * Dh, D)
        sd[pre + "attention.wqkv.weight"] = fused
        sd[pre + "attention.wo.weight"] = rng.normal(size=(D, H * Dh)).astype(np.float32) * 0.05
        sd[pre + "attention_norm.weight"] = np.ones((D,), np.float32)
        sd[pre + "ffn_norm.weight"] = np.ones((D,), np.float32)
        sd[pre + "feed_forward.w1.weight"] = rng.normal(size=(F, D)).astype(np.float32) * 0.05
        sd[pre + "feed_forward.w3.weight"] = rng.normal(size=(F, D)).astype(np.float32) * 0.05
        sd[pre + "feed_forward.w2.weight"] = rng.normal(size=(D, F)).astype(np.float32) * 0.05
    cfg = {"architectures": ["InternLM2ForCausalLM"], "model_type": "internlm2",
           "vocab_size": V, "hidden_size": D, "num_hidden_layers": L,
           "num_attention_heads": H, "num_key_value_heads": KV,
           "intermediate_size": F, "bias": False,
           "max_position_embeddings": 64, "rms_norm_eps": 1e-6,
           "tie_word_embeddings": False}
    import jax

    model, params = from_hf((cfg, sd))
    np.testing.assert_allclose(np.asarray(params["layers"]["wq"]),
                               wq.transpose(0, 2, 1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(params["layers"]["wk"]),
                               wk.transpose(0, 2, 1), rtol=1e-6)
    logits = jax.jit(model.apply)(params, _ids(V, t=16))
    assert np.isfinite(np.asarray(logits)).all()


def test_headless_bert_model_imports():
    """Review r4: a BertModel checkpoint (no cls.* MLM head) must import —
    the MLM head is dropped and the tied unembed scores tokens."""
    cfg = transformers.BertConfig(
        vocab_size=96, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    torch.manual_seed(10)
    import jax

    model, params = from_hf(transformers.BertModel(cfg))
    assert not model.config.mlm_head
    logits = jax.jit(model.apply)(params, _ids(96))
    assert np.isfinite(np.asarray(logits)).all()


def test_gptneo_all_global_keeps_flash_path():
    """Review r4: an all-global GPT-Neo must not be routed through the
    quadratic windowed reference path."""
    from shuffle_exchange_tpu.models.hf import config_from_hf

    cfg = transformers.GPTNeoConfig(
        vocab_size=96, hidden_size=64, num_layers=2, num_heads=4,
        attention_types=[[["global"], 2]], window_size=256,
        max_position_embeddings=64, intermediate_size=128)
    c = config_from_hf(cfg.to_dict())
    assert c.local_attention_window == 0 and c.attention_pattern == ()
    assert c.attention_impl == "auto"


def test_megatron_gpt_import_structural():
    """Megatron-LM GPT state dict (reference containers/megatron_gpt.py):
    fused query_key_value in the v2 per-head interleave splits to q/k/v
    exactly — checked by building the fused tensor from known parts."""
    rng = np.random.default_rng(0)
    L, D, H, V, F = 2, 32, 4, 64, 128
    Dh = D // H
    wq = rng.normal(size=(L, H * Dh, D)).astype(np.float32) * 0.05
    wk = rng.normal(size=(L, H * Dh, D)).astype(np.float32) * 0.05
    wv = rng.normal(size=(L, H * Dh, D)).astype(np.float32) * 0.05
    sd = {"language_model.embedding.word_embeddings.weight":
          rng.normal(size=(V, D)).astype(np.float32) * 0.02,
          "language_model.embedding.position_embeddings.weight":
          rng.normal(size=(64, D)).astype(np.float32) * 0.02,
          "language_model.encoder.final_layernorm.weight": np.ones((D,), np.float32),
          "language_model.encoder.final_layernorm.bias": np.zeros((D,), np.float32)}
    for i in range(L):
        pre = f"language_model.encoder.layers.{i}."
        # fuse [H, 3, Dh] per-head interleave (megatron_v2)
        fused = np.stack([wq[i].reshape(H, Dh, D), wk[i].reshape(H, Dh, D),
                          wv[i].reshape(H, Dh, D)], axis=1).reshape(3 * D, D)
        sd[pre + "self_attention.query_key_value.weight"] = fused
        sd[pre + "self_attention.query_key_value.bias"] = np.zeros((3 * D,), np.float32)
        sd[pre + "self_attention.dense.weight"] = rng.normal(size=(D, D)).astype(np.float32) * 0.05
        sd[pre + "self_attention.dense.bias"] = np.zeros((D,), np.float32)
        sd[pre + "input_layernorm.weight"] = np.ones((D,), np.float32)
        sd[pre + "input_layernorm.bias"] = np.zeros((D,), np.float32)
        sd[pre + "post_attention_layernorm.weight"] = np.ones((D,), np.float32)
        sd[pre + "post_attention_layernorm.bias"] = np.zeros((D,), np.float32)
        sd[pre + "mlp.dense_h_to_4h.weight"] = rng.normal(size=(F, D)).astype(np.float32) * 0.05
        sd[pre + "mlp.dense_h_to_4h.bias"] = np.zeros((F,), np.float32)
        sd[pre + "mlp.dense_4h_to_h.weight"] = rng.normal(size=(D, F)).astype(np.float32) * 0.05
        sd[pre + "mlp.dense_4h_to_h.bias"] = np.zeros((D,), np.float32)
    cfg = {"model_type": "megatron-gpt", "vocab_size": V, "hidden_size": D,
           "num_layers": L, "num_attention_heads": H, "ffn_hidden_size": F,
           "max_position_embeddings": 64}
    import jax

    model, params = from_hf((cfg, sd))
    np.testing.assert_allclose(np.asarray(params["layers"]["wq"]),
                               wq.transpose(0, 2, 1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(params["layers"]["wk"]),
                               wk.transpose(0, 2, 1), rtol=1e-6)
    logits = jax.jit(model.apply)(params, _ids(V, t=16))
    assert np.isfinite(np.asarray(logits)).all()
    assert logits.shape == (2, 16, V)


def test_megatron_v0_layout_and_untied_output():
    """Review r4: the v0 [3, H, Dh] grouped qkv layout is selected via the
    config ("megatron_v2": false) and an untied output_layer is honored."""
    rng = np.random.default_rng(3)
    L, D, H, V, F = 2, 32, 4, 64, 128
    Dh = D // H
    wq = rng.normal(size=(L, H * Dh, D)).astype(np.float32) * 0.05
    wk = rng.normal(size=(L, H * Dh, D)).astype(np.float32) * 0.05
    wv = rng.normal(size=(L, H * Dh, D)).astype(np.float32) * 0.05
    out_head = rng.normal(size=(V, D)).astype(np.float32) * 0.02
    sd = {"language_model.embedding.word_embeddings.weight":
          rng.normal(size=(V, D)).astype(np.float32) * 0.02,
          "language_model.embedding.position_embeddings.weight":
          rng.normal(size=(64, D)).astype(np.float32) * 0.02,
          "language_model.output_layer.weight": out_head,
          "language_model.encoder.final_layernorm.weight": np.ones((D,), np.float32),
          "language_model.encoder.final_layernorm.bias": np.zeros((D,), np.float32)}
    for i in range(L):
        pre = f"language_model.encoder.layers.{i}."
        # v0 layout: [3, H, Dh] grouped by kind
        fused = np.concatenate([wq[i], wk[i], wv[i]], axis=0)
        sd[pre + "self_attention.query_key_value.weight"] = fused
        sd[pre + "self_attention.query_key_value.bias"] = np.zeros((3 * D,), np.float32)
        sd[pre + "self_attention.dense.weight"] = rng.normal(size=(D, D)).astype(np.float32) * 0.05
        sd[pre + "self_attention.dense.bias"] = np.zeros((D,), np.float32)
        sd[pre + "input_layernorm.weight"] = np.ones((D,), np.float32)
        sd[pre + "input_layernorm.bias"] = np.zeros((D,), np.float32)
        sd[pre + "post_attention_layernorm.weight"] = np.ones((D,), np.float32)
        sd[pre + "post_attention_layernorm.bias"] = np.zeros((D,), np.float32)
        sd[pre + "mlp.dense_h_to_4h.weight"] = rng.normal(size=(F, D)).astype(np.float32) * 0.05
        sd[pre + "mlp.dense_h_to_4h.bias"] = np.zeros((F,), np.float32)
        sd[pre + "mlp.dense_4h_to_h.weight"] = rng.normal(size=(D, F)).astype(np.float32) * 0.05
        sd[pre + "mlp.dense_4h_to_h.bias"] = np.zeros((D,), np.float32)
    cfg = {"model_type": "megatron-gpt", "vocab_size": V, "hidden_size": D,
           "num_layers": L, "num_attention_heads": H, "ffn_hidden_size": F,
           "max_position_embeddings": 64, "megatron_v2": False,
           "untie_embeddings_and_output_weights": True}
    model, params = from_hf((cfg, sd))
    assert not model.config.tie_embeddings
    np.testing.assert_allclose(np.asarray(params["layers"]["wq"]),
                               wq.transpose(0, 2, 1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(params["unembed"]), out_head.T, rtol=1e-6)


def _megatron_moe_sd(rng, L, D, H, V, F, E, biased=True, dense=False):
    """Synthetic Megatron(-MoE) state dict; with dense=True the MLP is the
    plain dense FFN carrying the same weights as expert 0."""
    sd = {"embedding.word_embeddings.weight": rng.normal(size=(V, D)).astype(np.float32) * 0.02,
          "embedding.position_embeddings.weight": rng.normal(size=(64, D)).astype(np.float32) * 0.02,
          "final_layernorm.weight": np.ones((D,), np.float32),
          "final_layernorm.bias": np.zeros((D,), np.float32)}
    w_up = rng.normal(size=(L, F, D)).astype(np.float32) * 0.05
    b_up = (rng.normal(size=(L, F)).astype(np.float32) * 0.1 if biased
            else np.zeros((L, F), np.float32))
    w_down = rng.normal(size=(L, D, F)).astype(np.float32) * 0.05
    b_down = (rng.normal(size=(L, D)).astype(np.float32) * 0.1 if biased
              else np.zeros((L, D), np.float32))
    for i in range(L):
        pre = f"layers.{i}."
        sd[pre + "self_attention.query_key_value.weight"] = \
            rng.normal(size=(3 * D, D)).astype(np.float32) * 0.05
        sd[pre + "self_attention.query_key_value.bias"] = np.zeros((3 * D,), np.float32)
        sd[pre + "self_attention.dense.weight"] = rng.normal(size=(D, D)).astype(np.float32) * 0.05
        sd[pre + "self_attention.dense.bias"] = np.zeros((D,), np.float32)
        for nm in ("input_layernorm", "post_attention_layernorm"):
            sd[pre + nm + ".weight"] = np.ones((D,), np.float32)
            sd[pre + nm + ".bias"] = np.zeros((D,), np.float32)
        if dense:
            sd[pre + "mlp.dense_h_to_4h.weight"] = w_up[i]
            sd[pre + "mlp.dense_h_to_4h.bias"] = b_up[i]
            sd[pre + "mlp.dense_4h_to_h.weight"] = w_down[i]
            sd[pre + "mlp.dense_4h_to_h.bias"] = b_down[i]
        else:
            for e in range(E):
                base = f"layers.{i}.mlp.deepspeed_moe.experts.deepspeed_experts.{e}."
                sd[base + "dense_h_to_4h.weight"] = w_up[i]
                sd[base + "dense_h_to_4h.bias"] = b_up[i]
                sd[base + "dense_4h_to_h.weight"] = w_down[i]
                sd[base + "dense_4h_to_h.bias"] = b_down[i]
            # dedicated rng: must not perturb the shared weight stream so
            # the dense variant draws identical attention weights
            sd[f"layers.{i}.mlp.deepspeed_moe.gate.wg.weight"] = \
                np.random.default_rng(1000 + i).normal(
                    size=(E, D)).astype(np.float32) * 0.05
    return sd


def test_megatron_moe_biased_experts_logit_parity():
    """VERDICT r4 #8: biased DeepSpeed-MoE experts import (reference
    containers/megatron_gpt_moe.py) instead of being rejected. Parity
    oracle: all experts carry IDENTICAL (nonzero-biased) weights, so with
    normalized top-k routing the MoE output equals the dense FFN — logits
    must match the dense-checkpoint import exactly."""
    import jax

    from shuffle_exchange_tpu.models.hf import params_from_state_dict

    L, D, H, V, F, E = 2, 32, 4, 64, 128, 4
    sd_moe = _megatron_moe_sd(np.random.default_rng(7), L, D, H, V, F, E)
    sd_dense = _megatron_moe_sd(np.random.default_rng(7), L, D, H, V, F, E,
                                dense=True)
    base_cfg = {"model_type": "megatron-gpt", "vocab_size": V, "hidden_size": D,
                "num_layers": L, "num_attention_heads": H,
                "ffn_hidden_size": F, "max_position_embeddings": 64}
    m_moe, p_moe = from_hf((dict(base_cfg, num_experts=[E]), sd_moe))
    m_dense, p_dense = from_hf((base_cfg, sd_dense))
    # bias leaves landed with exact values
    assert p_moe["layers"]["moe_b_up"].shape == (L, E, F)
    assert p_moe["layers"]["moe_b_down"].shape == (L, E, D)
    np.testing.assert_allclose(
        np.asarray(p_moe["layers"]["moe_b_up"][:, 0]),
        np.asarray(p_dense["layers"]["b_up"]), rtol=1e-6)
    ids = _ids(V, t=16)
    lg_moe = jax.jit(m_moe.apply)(p_moe, ids)
    lg_dense = jax.jit(m_dense.apply)(p_dense, ids)
    np.testing.assert_allclose(np.asarray(lg_moe), np.asarray(lg_dense),
                               rtol=2e-4, atol=2e-4)


def test_megatron_rotary_import():
    """Missing r4 #3 edge: --use-rotary-position-embeddings checkpoints
    (no position table) import with position='rope'."""
    import jax

    rng = np.random.default_rng(9)
    L, D, H, V, F = 2, 32, 4, 64, 128
    sd = _megatron_moe_sd(rng, L, D, H, V, F, E=0, dense=True)
    del sd["embedding.position_embeddings.weight"]
    cfg = {"model_type": "megatron-gpt", "vocab_size": V, "hidden_size": D,
           "num_layers": L, "num_attention_heads": H, "ffn_hidden_size": F,
           "max_position_embeddings": 64,
           "use_rotary_position_embeddings": True}
    model, params = from_hf((cfg, sd))
    assert model.config.position == "rope"
    assert "pos_embed" not in params
    logits = jax.jit(model.apply)(params, _ids(V, t=16))
    assert np.isfinite(np.asarray(logits)).all()


def test_megatron_num_experts_list_and_pattern_mismatch():
    """Review r4 + round 5: Megatron's nargs='+' num_experts list parses;
    a checkpoint whose expert layers disagree with the declared pattern
    gives a targeted error pointing at from_hf (which derives it)."""
    import pytest

    from shuffle_exchange_tpu.models.hf import config_from_hf, params_from_state_dict

    cfg = {"model_type": "megatron-gpt", "vocab_size": 64, "hidden_size": 32,
           "num_layers": 2, "num_attention_heads": 4,
           "max_position_embeddings": 64, "num_experts": [4]}
    c = config_from_hf(cfg)
    assert c.n_experts == 4
    # state dict with experts only on layer 1 but no declared pattern
    rng = np.random.default_rng(4)
    D, F, V, L = 32, 128, 64, 2
    sd = {"embedding.word_embeddings.weight": rng.normal(size=(V, D)).astype(np.float32),
          "embedding.position_embeddings.weight": rng.normal(size=(64, D)).astype(np.float32),
          "final_layernorm.weight": np.ones((D,), np.float32),
          "final_layernorm.bias": np.zeros((D,), np.float32)}
    for i in range(L):
        pre = f"layers.{i}."
        sd[pre + "self_attention.query_key_value.weight"] = rng.normal(size=(3 * D, D)).astype(np.float32)
        sd[pre + "self_attention.query_key_value.bias"] = np.zeros((3 * D,), np.float32)
        sd[pre + "self_attention.dense.weight"] = rng.normal(size=(D, D)).astype(np.float32)
        sd[pre + "self_attention.dense.bias"] = np.zeros((D,), np.float32)
        for nm in ("input_layernorm", "post_attention_layernorm"):
            sd[pre + nm + ".weight"] = np.ones((D,), np.float32)
            sd[pre + nm + ".bias"] = np.zeros((D,), np.float32)
    # experts only on layer 1
    for e in range(4):
        base = f"layers.1.mlp.deepspeed_moe.experts.deepspeed_experts.{e}."
        sd[base + "dense_h_to_4h.weight"] = rng.normal(size=(F, D)).astype(np.float32)
        sd[base + "dense_4h_to_h.weight"] = rng.normal(size=(D, F)).astype(np.float32)
    sd["layers.1.mlp.deepspeed_moe.gate.wg.weight"] = rng.normal(size=(4, D)).astype(np.float32)
    with pytest.raises(ValueError, match="moe_layer_pattern|from_hf"):
        params_from_state_dict(sd, c, "megatron")


def test_megatron_expert_interval_import_parity():
    """Missing r4 #3: --expert-interval interleaved dense layers import —
    dense layers land in expert slot 0 with a traced per-layer flag, and
    (with all experts identical) logits match the all-dense import."""
    import jax

    L, D, H, V, F, E = 4, 32, 4, 64, 128, 4
    sd_mixed = _megatron_moe_sd(np.random.default_rng(11), L, D, H, V, F, E)
    sd_dense = _megatron_moe_sd(np.random.default_rng(11), L, D, H, V, F, E,
                                dense=True)
    # make layers 0 and 2 dense in the mixed checkpoint: swap the expert
    # keys for the dense FFN keys (same weights — expert arrays are
    # identical per layer by construction)
    for i in (0, 2):
        for kind in ("dense_h_to_4h", "dense_4h_to_h"):
            for part in ("weight", "bias"):
                src = f"layers.{i}.mlp.deepspeed_moe.experts.deepspeed_experts.0.{kind}.{part}"
                sd_mixed[f"layers.{i}.mlp.{kind}.{part}"] = sd_mixed[src]
        for k in [k for k in sd_mixed if k.startswith(f"layers.{i}.mlp.deepspeed_moe")]:
            del sd_mixed[k]
    base_cfg = {"model_type": "megatron-gpt", "vocab_size": V, "hidden_size": D,
                "num_layers": L, "num_attention_heads": H,
                "ffn_hidden_size": F, "max_position_embeddings": 64}
    m_mixed, p_mixed = from_hf((dict(base_cfg, num_experts=[E]), sd_mixed))
    m_dense, p_dense = from_hf((base_cfg, sd_dense))
    assert m_mixed.config.moe_layer_pattern == (False, True, False, True)
    # moe_impl=auto resolves to the capacity path under scanned stacks,
    # which DROPS overflow tokens at the default capacity_factor — parity
    # with the dense import needs every token served, so give the experts
    # full capacity (identical experts make routing itself irrelevant)
    import dataclasses as _dc

    from shuffle_exchange_tpu.models import Transformer

    m_mixed = Transformer(_dc.replace(m_mixed.config,
                                      capacity_factor=float(E)))
    assert p_mixed["layers"]["moe_w_up"].shape == (L, E, D, F)
    # dense layers: slot 0 carries the FFN, other slots zero
    assert np.abs(np.asarray(p_mixed["layers"]["moe_w_up"][0, 1:])).max() == 0
    ids = _ids(V, t=16)
    lg_mixed = jax.jit(m_mixed.apply)(p_mixed, ids)
    lg_dense = jax.jit(m_dense.apply)(p_dense, ids)
    np.testing.assert_allclose(np.asarray(lg_mixed), np.asarray(lg_dense),
                               rtol=2e-4, atol=2e-4)
