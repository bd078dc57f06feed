"""What GPT-2's two elementwise chains keep for their backward (PR 58).

A layer scan with no checkpointing stacks, for every layer, whatever a layer's
operations save. ``models/transformer._layernorm`` and ``_gelu`` save what they
were GIVEN (and, the norm, two float32 numbers a row) and recompute the chain:

(a) their values are the plain functions' to the bit, their gradients plain
    autodiff's to rounding;
(b) the census: of a tiny GPT-2's forward layer scan (``jax.grad`` of the
    loss, traced), no stacked float32 array of the stream's shape comes from
    ``_norm`` and at most two of the FFN's width from ``_ffn`` (before PR 58:
    six and six);
(c) the same census of a tiny ``rmsnorm`` + ``swiglu`` stack reads what it
    read before PR 58: the other cells' path was not touched.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import source_info_util
from jax.extend.core import Literal

from shuffle_exchange_tpu.models import Transformer
from shuffle_exchange_tpu.models.transformer import _norm, activation_fn, tiny

EPS = 1e-5


def plain_layernorm(x, weight, bias):
    """``_norm``'s layernorm branch as it stood before PR 58."""
    x32 = x.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    out = (x32 - mean) * (1.0 / jnp.sqrt(var + EPS))
    out = out * weight.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def plain_gelu(name):
    return lambda x: jax.nn.gelu(x, approximate=name != "gelu")


def assert_to_rounding(got, want, what):
    """Within 2 ulp of a 2-byte dtype, 1e-6 in float32, of the element or,
    where a sum cancelled, of the array's size."""
    assert got.dtype == want.dtype and got.shape == want.shape, what
    tol = 1e-6 if want.dtype == jnp.float32 else 2 * float(jnp.finfo(want.dtype).eps)
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * np.abs(want).max(),
                               err_msg=what)


def value_and_grads(fn, args, seed):
    out, vjp = jax.vjp(fn, *args)
    g = jax.random.normal(jax.random.PRNGKey(seed), out.shape, jnp.float32).astype(out.dtype)
    return out, vjp(g)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "float32"])
@pytest.mark.parametrize("name", ["gelu_new", "gelu_pytorch_tanh", "gelu"])
def test_a_gelu_is_the_plain_function_and_its_gradient(name, dtype):
    x = (3 * jax.random.normal(jax.random.PRNGKey(0), (4, 48, 256))).astype(dtype)
    out, (dx,) = value_and_grads(activation_fn(name), (x,), 1)
    want, (want_dx,) = value_and_grads(plain_gelu(name), (x,), 1)
    assert out.dtype == dtype and bool((out == want).all())
    assert_to_rounding(dx, want_dx, "dx")


# (x's shape, x's dtype, the parameters' dtype)
NORM_CASES = {"with_bias": ((3, 40, 96), jnp.float32, jnp.float32),
              "gpt2_shapes": ((2, 64, 1024), jnp.bfloat16, jnp.bfloat16),
              "bf16_stream_f32_gain": ((2, 64, 1024), jnp.bfloat16, jnp.float32)}


@pytest.mark.parametrize("case", list(NORM_CASES))
def test_a_layernorm_is_the_plain_function_and_its_gradient(case):
    shape, dtype, pdtype = NORM_CASES[case]
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(2), 3)
    # a stream with a mean and a scale of its own, as a residual stream has
    x = (0.7 + 2.5 * jax.random.normal(kx, shape)).astype(dtype)
    w = (1 + 0.2 * jax.random.normal(kw, shape[-1:])).astype(pdtype)
    b = (0.1 * jax.random.normal(kb, shape[-1:])).astype(pdtype)
    out, grads = value_and_grads(
        lambda x, w, b: _norm(x, w, b, "layernorm", eps=EPS), (x, w, b), 3)
    want, want_grads = value_and_grads(plain_layernorm, (x, w, b), 3)
    assert out.dtype == dtype and bool((out == want).all())
    for got, ref, what in zip(grads, want_grads, ("dx", "dgain", "dbias")):
        assert_to_rounding(got, ref, what)


# ---- the census ---------------------------------------------------------

B, T = 2, 32


def _scans(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _scans(sub, found)
    return found


def stacked_by_the_forward_scan(cfg, dtype):
    """[(shape, dtype, the functions of models/transformer.py on the producing
    equation's traceback)] of every output the FORWARD layer scan stacks, in
    ``jax.grad`` of the model's loss (traced, nothing runs)."""
    model = Transformer(cfg)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype if s.dtype == jnp.float32 else s.dtype),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))))
    ids = jax.ShapeDtypeStruct((B, T + 1), jnp.int32)
    closed = jax.make_jaxpr(jax.grad(lambda p, i: model.loss(p, {"input_ids": i})))(params, ids)
    forward = [s for s in _scans(closed.jaxpr, []) if s.params["length"] == cfg.n_layers][0]
    body, carried = forward.params["jaxpr"].jaxpr, forward.params["num_carry"]
    producer = {v: eqn for eqn in body.eqns for v in eqn.outvars}
    rows = []
    for inner, outer in zip(body.outvars[carried:], forward.outvars[carried:]):
        eqn = None if isinstance(inner, Literal) else producer.get(inner)
        frames = source_info_util.user_frames(eqn.source_info.traceback) if eqn else []
        rows.append((outer.aval.shape, outer.aval.dtype,
                     {f.function_name.split(".")[-1] for f in frames
                      if f.file_name.endswith("models/transformer.py")}))
    return rows


def count(rows, shape, function, dtype=None):
    return sum(1 for s, d, fns in rows
               if s == shape and function in fns and dtype in (None, d))


def test_b_the_gpt2_layer_scan_stacks_no_norm_intermediate_and_two_ffn_arrays():
    cfg = tiny(layers=2, d=64, heads=4, activation="gelu_new", norm="layernorm",
               position="learned", mlp_bias=True, attn_qkv_bias=True, attn_out_bias=True)
    rows = stacked_by_the_forward_scan(cfg, jnp.bfloat16)
    stream, ffn = (2, B, T, 64), (2, B, T, cfg.ff_dim)
    assert count(rows, stream, "_norm", jnp.float32) == 0      # before PR 58: 6
    assert count(rows, ffn, "_ffn") == 2                       # 6: 5 of them inside the GELU
    # what a norm does stack: its bf16 output (the projections' input) and two
    # float32 numbers a row, twice a layer; its input is the block's stream ...
    assert count(rows, stream, "_norm", jnp.bfloat16) == 2
    assert count(rows, (2, B, T, 1), "_norm", jnp.float32) == 4
    # ... so the stream is stacked as it entered each norm, in bf16: those two,
    # the norms' two outputs and the attention's output before its projection
    assert sum(1 for s, d, _ in rows if s == stream) == 5


# read at the parent of PR 58 (3436a44) by stacked_by_the_forward_scan itself
PARENT_RMSNORM_SWIGLU = {"stacked": 27, "stream_from_norm": 6, "ffn_from_ffn": 6, "bytes": 760840}


def test_c_the_rmsnorm_swiglu_layer_scan_stacks_what_it_did():
    cfg = tiny(layers=2, d=64, heads=4, activation="swiglu", norm="rmsnorm", position="rope")
    rows = stacked_by_the_forward_scan(cfg, jnp.bfloat16)
    stream, ffn = (2, B, T, 64), (2, B, T, cfg.ff_dim)
    assert len(rows) == PARENT_RMSNORM_SWIGLU["stacked"]
    assert count(rows, stream, "_norm") == PARENT_RMSNORM_SWIGLU["stream_from_norm"]
    assert count(rows, ffn, "_ffn") == PARENT_RMSNORM_SWIGLU["ffn_from_ffn"]
    assert sum(int(np.prod(s)) * d.itemsize for s, d, _ in rows) == PARENT_RMSNORM_SWIGLU["bytes"]
