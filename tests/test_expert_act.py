"""The experts' activation pass (``ops/expert_act.py``): the two Pallas
kernels, interpreted on the CPU, against the plain text (``_text``, what
``moe/layer.expert_mlp_ragged`` had between its grouped GEMMs) on the rows
below ``fit``; what the ``custom_vjp`` keeps; what the share's traced program
holds; and the route. Blocks of 64 rows of 320 channels (two lane tiles and a
half), so ``fit`` 0 / 70 / 128 / 256 of 256 rows is none, inside the second,
a block's edge, all.

The interpreter leaves what a kernel does not write as NaN, so a row of the
blocks past ``fit`` that anything read would show in the layer's result: the
end-to-end cases lean on that."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shuffle_exchange_tpu.moe import layer
from shuffle_exchange_tpu.ops import expert_act as ea
from shuffle_exchange_tpu.models.transformer import gate_fn
from tests.test_held_share_rows import _drawn, _equations, _inner
from tests.test_saved_residuals import _scans

R, F, BLOCK = 256, 320, 64
BF16, F32 = jnp.bfloat16, jnp.float32


def operands(activation, dtype=BF16, rows=R, width=F):
    """((gate, up) or (up,), dh) as the projections leave them."""
    keys = jax.random.split(jax.random.PRNGKey(rows + width), 3)
    gate, up, dh = (jax.random.normal(k, (rows, width)).astype(dtype) for k in keys)
    return ((gate, up) if gate_fn(activation) else (up,)), dh


def kernels(activation, fit, rows=BLOCK):
    return lambda *arrays: ea._expert_act_pallas(arrays, fit, activation, rows=rows,
                                                 interpret=True)


def answers(fn, arrays, dh):
    """(h, the cotangent of each input) of ``fn``."""
    h, pull = jax.vjp(fn, *arrays)
    return (h,) + tuple(pull(dh))


@pytest.mark.parametrize("fit", [0, 70, 128, 256], ids=["none", "inside_a_block", "a_block_edge", "all"])
@pytest.mark.parametrize("activation", ["swiglu", "reglu", "relu2"])
def test_kernels_are_the_text_in_float32_rounded_once(activation, fit):
    """Forward and every cotangent, on the rows below ``fit``, bit for bit:
    the text (and ``jax.vjp`` of it) on the bf16 inputs taken to float32, each
    result rounded once. Nothing is asserted of the rows from ``fit`` on.
    Against the text in bf16: ReLU forms round once there too and are the same
    numbers; a SiLU in bf16 rounds after each of its operations on the CPU (a
    TPU's fusion does not), so there the kernels are the closer of the two to
    the float32 value, element for element, and the two within bf16 noise."""
    arrays, dh = operands(activation)
    got = answers(kernels(activation, fit), arrays, dh)
    exact = answers(ea._text(activation), tuple(a.astype(F32) for a in arrays), dh.astype(F32))
    plain = answers(ea._text(activation), arrays, dh)
    assert len(got) == len(exact) == 1 + len(arrays)
    for a, b, c in zip(got, exact, plain):
        assert a.shape == (R, F) and a.dtype == BF16
        a, b, c = (np.asarray(x[:fit], np.float32) for x in (a, b, c))
        np.testing.assert_array_equal(a, np.asarray(jnp.asarray(b).astype(BF16), np.float32))
        if activation == "swiglu":
            assert np.all(np.abs(a - b) <= np.abs(c - b))
            assert fit == 0 or np.linalg.norm(a - c) < 4e-3 * np.linalg.norm(c)
        else:
            np.testing.assert_array_equal(a, c)


@pytest.mark.parametrize("activation", ["silu", "relu"])
def test_the_other_ungated_activations_and_a_ragged_last_block(activation):
    """200 rows in blocks of 64 (the last holds 8), 190 of them read."""
    arrays, dh = operands(activation, rows=200)
    got = answers(kernels(activation, 190), arrays, dh)
    exact = answers(ea._text(activation), tuple(a.astype(F32) for a in arrays), dh.astype(F32))
    for a, b in zip(got, exact):
        np.testing.assert_array_equal(np.asarray(a[:190], np.float32),
                                      np.asarray(b[:190].astype(BF16), np.float32))


def test_a_block_past_fit_is_not_written_and_one_below_it_is_whole():
    """The interpreter's NaN stands where no step wrote: from the first block
    that starts at or past ``fit`` on; the block ``fit`` falls in is computed
    whole."""
    arrays, dh = operands("swiglu")
    for out in answers(kernels("swiglu", 70), arrays, dh):
        out = np.asarray(out, np.float32)
        assert np.isfinite(out[:128]).all() and np.isnan(out[128:]).all()


# ---- what the custom_vjp keeps -------------------------------------------


def stacked(activation, route, layers=2):
    """The arrays [layers, R, F] a forward scan over ``layers`` passes stacks
    for its backward (``tests/test_saved_residuals.py``'s census): each pass
    makes its own gate and up from the scanned input, so what a pass keeps has
    to be stacked. -> [dtype]."""
    arrays, _ = operands(activation)

    def loss(xs):
        def one(total, x):
            made = tuple(x * (1.0 + i) for i in range(len(arrays)))
            if route == "xla":
                h = ea._text(activation)(*made)
            else:
                h = kernels(activation, 100)(*made)
            return total + h[:100].astype(F32).sum(), None

        return jax.lax.scan(one, 0.0, xs)[0]

    xs = jnp.stack([arrays[0]] * layers)
    closed = jax.make_jaxpr(jax.grad(loss))(xs)
    forward = [s for s in _scans(closed.jaxpr, []) if s.params["length"] == layers][0]
    return [v.aval.dtype for v in forward.outvars[forward.params["num_carry"]:]
            if v.aval.shape == (layers, R, F)]


@pytest.mark.parametrize("activation", ["swiglu", "reglu", "relu2"])
def test_the_pass_keeps_its_inputs_and_nothing_else(activation):
    inputs = 2 if gate_fn(activation) else 1
    assert stacked(activation, "kernels") == [BF16] * inputs
    # autodiff of the text keeps more: the SiLU's sigmoid and product, a
    # ReLU's mask (reglu: the activated gate as well)
    assert len(stacked(activation, "xla")) > inputs


# ---- in the layer ---------------------------------------------------------


def share(activation, dtype, S=256, k=4, held=4, n_experts=16, width=128, ff=128, rows=768):
    """One rank's share at the smallest shapes the route admits: 256 tokens of
    4 choices over 16 experts, 4 held, a buffer of 768 rows of which ~256 hold
    a row; widths of one lane tile."""
    params = jax.tree.map(lambda a: a.astype(dtype), layer.init_expert_mlp(
        jax.random.PRNGKey(0), held, width, ff, activation))
    xs = jax.random.normal(jax.random.PRNGKey(1), (S, width)).astype(dtype)
    topk_idx = jnp.asarray(_drawn(3, S, k, n_experts), jnp.int32)
    weights = jnp.full((S, k), 1.0 / k)

    def loss(params, xs, weights, buffer=rows):
        out, computed, dropped = layer.expert_mlp_ragged(
            params, xs, topk_idx, weights, activation, buffer_rows=buffer)
        return out.astype(F32).sum(), (out, computed, dropped)

    return loss, (params, xs, weights)


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    monkeypatch.setattr(ea, "ROWS", BLOCK)


@pytest.mark.parametrize("buffer", [768, None], ids=["share", "every_expert_held"])
@pytest.mark.parametrize("activation", ["swiglu", "reglu", "relu2"])
def test_the_layer_through_the_kernels_is_the_layer_through_the_text(
        activation, buffer, interpreted, monkeypatch):
    """Value and gradients of one layer of experts, a rank's share and every
    expert held. Twelve blocks of 64 rows in the share's buffer, ~4 of them
    visited: the other eight are NaN in ``h``, ``dgate`` and ``dup``, and no
    NaN reaches the output, the weights' gradients, the rows' or the routing
    weights'. ReLU forms: the text's numbers to the bit; the SiLU's no further
    from the same program in float32 than the text's are (see above)."""
    loss, args = share(activation, BF16)
    if buffer is None:
        params = jax.tree.map(lambda a: a.astype(BF16), layer.init_expert_mlp(
            jax.random.PRNGKey(0), 16, 128, 128, activation))
        args = (params,) + args[1:]
    program = jax.jit(jax.value_and_grad(lambda *a: loss(*a, buffer=buffer), argnums=(0, 1, 2),
                                         has_aux=True))
    assert ea.expert_act_route(args[1], args[0]["w_up"], activation) == "interpret"
    (_, (out, computed, dropped)), grads = program(*args)
    monkeypatch.setattr(ea, "expert_act_route", lambda *a: "xla")
    jax.clear_caches()
    (_, (want, *_)), want_grads = program(*args)
    (_, (exact, *_)), exact_grads = program(*jax.tree.map(lambda a: a.astype(F32), args))
    assert int(computed) > 0 and int(dropped) == 0
    gap = lambda a, b: np.linalg.norm(a - b) / np.linalg.norm(b)
    for a, b, c in zip(*(jax.tree.leaves(t) for t in ((out, grads), (want, want_grads),
                                                      (exact, exact_grads)))):
        a, b, c = (np.asarray(x, np.float32) for x in (a, b, c))
        assert np.isfinite(a).all()
        if activation == "swiglu":
            assert gap(a, c) < 1.05 * gap(b, c) + 1e-3 and gap(a, b) < 2e-2, (gap(a, c), gap(b, c))
        else:
            np.testing.assert_array_equal(a, b)


def wide(jaxpr, shape):
    """The equations outside a kernel that make an array of ``shape`` and are
    no matrix product and hold no program of their own: the elementwise passes
    over it."""
    found = []
    for eqn in jaxpr.eqns:
        inner = [sub for _, sub in _inner(eqn)]
        if eqn.primitive.name == "pallas_call":
            continue
        if not inner and "dot" not in eqn.primitive.name and any(
                getattr(v.aval, "shape", None) == shape for v in eqn.outvars):
            found.append(eqn.primitive.name)
        for sub in inner:
            found += wide(sub, shape)
    return found


@pytest.mark.parametrize("activation", ["swiglu", "relu2"])
def test_the_shares_program_holds_no_elementwise_pass_over_the_buffer_of_width_f(
        activation, interpreted, monkeypatch):
    """Value and gradient of the share, traced: between the grouped GEMMs
    stand the two launches and nothing else of shape [R, F]; the text's route
    has its products, selects and converts there."""
    loss, args = share(activation, BF16, ff=256)
    # a function of its own a tracing: jax keeps a trace by the function's identity
    trace = lambda: jax.make_jaxpr(jax.value_and_grad(
        lambda *a: loss(*a)[0], argnums=(0, 1, 2)))(*args).jaxpr
    traced = trace()
    names = [eqn.params["name"] for eqn in _equations(traced)
             if eqn.primitive.name == "pallas_call"]
    assert sorted(names) == ["sxt_expert_act_bwd", "sxt_expert_act_fwd"]
    assert wide(traced, (768, 256)) == []
    monkeypatch.setattr(ea, "expert_act_route", lambda *a: "xla")
    assert len(wide(trace(), (768, 256))) >= 3


# ---- the route ------------------------------------------------------------


def route_of(activation="swiglu", dtype=BF16, rows=4096, width=256, ff=512):
    x = jax.ShapeDtypeStruct((rows, width), dtype)
    return ea.expert_act_route(x, jax.ShapeDtypeStruct((4, width, ff), dtype), activation)


def test_off_a_tpu_the_text_runs():
    assert route_of() == "xla"


@pytest.mark.parametrize("hook", ["tpu", "interpreter"])
def test_the_route_follows_the_grouped_gemms_and_the_rows(hook, monkeypatch):
    """Where ``grouped_matmul`` takes megablox for the call, 2-byte rows, an
    activation the kernels have and a whole block of rows: the kernels;
    today's text for float32, a GELU, a batch under one block, widths megablox
    refuses (the tiny stacks of ``tests/test_step_program_text.py``) and a
    kernel mesh over several devices."""
    from shuffle_exchange_tpu.ops import dispatch
    from shuffle_exchange_tpu.parallel import mesh

    if hook == "tpu":
        monkeypatch.setattr(dispatch, "pallas_enabled", lambda: True)
    else:
        monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    kernels_ = "pallas" if hook == "tpu" else "interpret"
    for activation in ea.ACTIVATIONS:
        assert route_of(activation) == kernels_
    assert route_of(dtype=jnp.float16) == kernels_
    assert route_of(rows=ea.ROWS) == kernels_
    assert route_of(width=2688, ff=1856) == kernels_        # nemotron3's 14.5 lane tiles
    assert route_of(dtype=F32) == "xla"
    for gelu in ("gelu", "gelu_new", "gelu_pytorch_tanh"):
        assert route_of(gelu) == "xla"
    assert route_of(rows=ea.ROWS - 1) == "xla"
    assert route_of(width=64, ff=32) == "xla"
    assert route_of(ff=200) == "xla"
    monkeypatch.setattr(mesh, "kernel_mesh_devices", lambda: 4)
    assert route_of() == "xla"


def test_the_text_route_is_the_layers_old_lines():
    """``expert_act`` on the text's route emits what ``expert_mlp_ragged``
    had: the gated product, or the activation alone."""
    (gate, up), _ = operands("swiglu", F32)
    np.testing.assert_array_equal(ea.expert_act(gate, up, 7, "swiglu"), jax.nn.silu(gate) * up)
    np.testing.assert_array_equal(ea.expert_act(None, up, 7, "relu2"),
                                  jnp.square(jax.nn.relu(up)))
    np.testing.assert_array_equal(ea.expert_act(None, up, 7, "gelu_new"),
                                  jax.nn.gelu(up, approximate=True))
