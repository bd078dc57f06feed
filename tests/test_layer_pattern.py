"""The period scan of ``Transformer.stack_apply`` and the held-share branch of
``moe.layer.expert_mlp_ragged`` against what they replaced: a one-kind model
is a period of one layer and must give exactly what the plain layer scan gave
(loss and every gradient, bit for bit), and a model that holds every expert
must give exactly what the parent's dropless layer gave."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shuffle_exchange_tpu.models import Transformer
from shuffle_exchange_tpu.models.transformer import (_remat_policy, tiny,
                                                     tiny_moe)

CASES = {
    "tiny": lambda: tiny(),
    "tiny_rope_swiglu": lambda: tiny(activation="swiglu", norm="rmsnorm", position="rope",
                                     n_kv_heads=2),
    "tiny_moe": lambda: tiny_moe(),
    "tiny_moe_ragged_all_choices": lambda: tiny_moe(moe_impl="ragged", moe_aux="all_choices",
                                                    experts=8),
    "tiny_remat": lambda: tiny(remat=True, remat_policy="full"),
    "tiny_moe_remat": lambda: tiny_moe(remat=True),
}


class LayerScan(Transformer):
    """The stack as the parent ran it: one ``lax.scan`` of ``layer_apply``
    over the stacked rows."""

    def stack_apply(self, stacked_layers, x, rope, ltd_mask=None, layer_keep=None,
                    layer_ids=None, with_stats=False):
        cfg = self.config

        def layer_fn(h, lw):
            return self.layer_apply(lw, h, rope)

        if cfg.remat:
            layer_fn = jax.checkpoint(layer_fn, policy=_remat_policy(cfg.remat_policy))
        x, (aux_losses, stats) = jax.lax.scan(layer_fn, x, stacked_layers)
        aux = jnp.sum(aux_losses)
        return (x, aux, stats) if with_stats else (x, aux)


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_one_kind_model_is_a_period_of_one(name):
    cfg = CASES[name]()
    assert len(cfg.pattern) == 1 and cfg.layer_pattern == ()
    model, old = Transformer(cfg), LayerScan(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert isinstance(params["layers"]["wq"], jax.Array)       # flat, [L, ...]
    assert params["layers"]["wq"].shape[0] == cfg.n_layers
    batch = {"input_ids": jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                                             cfg.vocab_size)}
    got = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    want = jax.jit(jax.value_and_grad(old.loss))(params, batch)
    assert float(got[0]) == float(want[0])
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_array_equal(a, b)


def test_the_pattern_and_its_kinds():
    cfg = dataclasses.replace(
        tiny_moe(layers=8), head_size=32,
        layer_pattern=(("gdn", "moe"),) * 3 + (("gated_attn", "moe"),),
        gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=16, gdn_value_dim=16)
    model = Transformer(cfg)
    assert cfg.head_dim == 32 and tiny().head_dim == 16 and cfg.recurrent
    assert model.kinds() == {"gdn_moe": ("gdn", "moe"),
                             "gated_attn_moe": ("gated_attn", "moe")}
    assert [(n, i) for n, i, _ in model.slots()] == [
        ("gdn_moe", 0), ("gdn_moe", 1), ("gdn_moe", 2), ("gated_attn_moe", 0)]
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert shapes["layers"]["gdn_moe"]["w_qkvz"].shape[:2] == (2, 3)
    assert shapes["layers"]["gated_attn_moe"]["wq"].shape == (2, 1, 64, 4 * 32 * 2)
    specs = model.partition_specs(shapes)
    assert len(specs["layers"]["gdn_moe"]["w_qkvz"]) == 4
    assert specs["layers"]["gdn_moe"]["w_qkvz"][-1] == "tensor"
    assert specs["layers"]["gated_attn_moe"]["moe_w_up"][2] == "expert"
    with pytest.raises(ValueError, match="whole periods"):
        Transformer(dataclasses.replace(cfg, n_layers=6)).init(jax.random.PRNGKey(0))


@pytest.mark.parametrize("what", ["layer_keep", "ltd_mask", "layer_ids"])
def test_a_pattern_of_several_kinds_runs_the_plain_stack_only(what):
    cfg = dataclasses.replace(
        tiny_moe(layers=4), head_size=32,
        layer_pattern=(("gdn", "moe"), ("gated_attn", "moe")),
        gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=16, gdn_value_dim=16)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    x, rope = model.embed(params, jnp.zeros((2, 16), jnp.int32))
    extra = {"layer_keep": jnp.ones((4,), bool), "ltd_mask": jnp.ones((2, 16), bool),
             "layer_ids": jnp.arange(2)}[what]
    with pytest.raises(NotImplementedError, match="plain stack"):
        model.stack_apply(params["layers"], x, rope, **{what: extra})


def _parent_ragged(params, xs, topk_idx, topk_w):
    """``expert_mlp_ragged`` as the parent commit had it (swiglu, no bias)."""
    from shuffle_exchange_tpu.moe.layer import _permuted_rows
    from shuffle_exchange_tpu.ops.grouped_gemm import grouped_matmul

    S, M = xs.shape
    k = topk_idx.shape[1]
    E = params["w_up"].shape[0]
    flat_e = topk_idx.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(S * k, dtype=order.dtype), unique_indices=True)
    xsort = _permuted_rows(xs, order, inverse, k)
    group_sizes = jnp.bincount(flat_e, length=E).astype(jnp.int32)
    up = grouped_matmul(xsort, params["w_up"], group_sizes)
    gate = grouped_matmul(xsort, params["w_gate"], group_sizes)
    out_sorted = grouped_matmul(jax.nn.silu(gate) * up, params["w_down"], group_sizes)
    out_flat = _permuted_rows(out_sorted, inverse, order)
    return (out_flat.reshape(S, k, M) * topk_w[..., None].astype(xs.dtype)).sum(axis=1)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_holding_every_expert_is_the_parents_layer_bit_for_bit(dtype):
    from shuffle_exchange_tpu.moe.gating import topk_select
    from shuffle_exchange_tpu.moe.layer import expert_mlp_ragged, init_expert_mlp

    E, D, F, k, S = 8, 64, 32, 3, 96
    params = jax.tree.map(lambda a: a.astype(dtype),
                          init_expert_mlp(jax.random.PRNGKey(0), E, D, F))
    xs = jax.random.normal(jax.random.PRNGKey(1), (S, D)).astype(dtype)
    idx, w, _, _ = topk_select(jax.random.normal(jax.random.PRNGKey(2), (S, E)), k)

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda p, x: fn(p, x, idx, w).astype(jnp.float32).sum(), argnums=(0, 1)))(params, xs)

    got, want = both(lambda *a: expert_mlp_ragged(*a)[0]), both(_parent_ragged)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_a_share_of_every_expert_and_every_row_is_the_whole_layer(dtype):
    """``held == all`` keeps the parent's code (above). The held-share code
    given every expert and a buffer for every token-choice computes the same
    layer another way (a token's sum in float32, rounded once): equal to the
    compute dtype's rounding, value and gradients."""
    from shuffle_exchange_tpu.moe.gating import topk_select
    from shuffle_exchange_tpu.moe.layer import expert_mlp_ragged, init_expert_mlp

    E, D, F, k, S = 16, 64, 32, 10, 96
    params = jax.tree.map(lambda a: a.astype(dtype),
                          init_expert_mlp(jax.random.PRNGKey(0), E, D, F))
    xs = jax.random.normal(jax.random.PRNGKey(1), (S, D)).astype(dtype)
    idx, w, _, _ = topk_select(jax.random.normal(jax.random.PRNGKey(2), (S, E)), k)
    mix = jax.random.normal(jax.random.PRNGKey(3), (S, D))

    def both(**share):
        def loss(p, x, w):
            out, rows, dropped = expert_mlp_ragged(p, x, idx, w, **share)
            return (out.astype(jnp.float32) * mix).sum(), (rows, dropped)

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(params, xs, w)

    (got, (rows, dropped)), d_got = both(buffer_rows=S * k)
    (want, _), d_want = both()
    assert (int(rows), int(dropped)) == (S * k, 0)
    step = 2.0 ** -7 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(float(got), float(want), rtol=step)
    for a, b in zip(jax.tree.leaves(d_got), jax.tree.leaves(d_want)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=step, atol=step * np.abs(b).max())


def test_activation_checkpointing_reaches_the_model():
    import shuffle_exchange_tpu as sxt

    base = {"train_batch_size": 8, "steps_per_print": 10 ** 9,
            "optimizer": {"type": "FusedAdam", "params": {"lr": 1e-3}}}
    model = Transformer(tiny())
    sxt.initialize(model=model, config=base, seed=0)
    assert not model.config.remat
    sxt.initialize(model=model, config=dict(base, activation_checkpointing={
        "enabled": True, "policy": "save_ffn"}), seed=0)
    assert model.config.remat and model.config.remat_policy == "save_ffn"
    own = Transformer(tiny(remat=True, remat_policy="full"))
    sxt.initialize(model=own, config=dict(base, activation_checkpointing={
        "enabled": True, "policy": "dots_saveable"}), seed=0)
    assert own.config.remat_policy == "full"     # the model's own stays
