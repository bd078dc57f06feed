"""The chunked loss across devices (ISSUE 27): in an engine's program whose
batch is split over ZeRO axes, final norm + unembed + CE run in a region
manual over those axes. The head is gathered by hand once before the loss
scan and its gradient reduce-scattered once after it; left to XLA's
partitioner both sit in the scan's body, once a chunk."""

import dataclasses
import re

import numpy as np
import pytest

import shuffle_exchange_tpu as sxt
from shuffle_exchange_tpu.models import Transformer, tiny
from shuffle_exchange_tpu.parallel import mesh as mesh_lib
from shuffle_exchange_tpu.profiling import trace

SEQ = 32
# the tolerances tests/test_models.py holds the chunked loss to
LOSS_RTOL, GRAD_RTOL, GRAD_ATOL = 1e-6, 1e-5, 1e-7


@pytest.fixture
def four(monkeypatch, devices8):
    """``sxt.initialize`` builds its mesh from ``jax.devices()``: hand it
    four of the eight virtual devices."""
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices8[:4])
    return devices8[:4]


def _config(stage=3, mesh=None, batch=4, **zero):
    return {"train_batch_size": batch, "steps_per_print": 10 ** 9,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": stage, **zero},
            "mesh": mesh or {"fsdp": 4}}


def _mcfg(vocab=131, **kw):
    # vocab 131: not a lane multiple, so pad_vocab_logits has columns to mask
    kw.setdefault("tie_embeddings", False)
    return tiny(vocab=vocab, d=64, layers=1, heads=4, seq=SEQ,
                loss_chunk=SEQ // 4, **kw)


def _batch(vocab=131, batch=4):
    ids = np.random.default_rng(0).integers(0, vocab, size=(batch, SEQ + 1))
    return {"input_ids": ids.astype(np.int32)}


def _loss_and_grads(engine, batch):
    """Loss and float32 gradients of one microbatch, as the train step takes
    them (same forward weights, same loss, before the optimizer)."""
    import jax

    micro = jax.tree_util.tree_map(lambda x: x[0], engine._reshape_batch(batch))
    g, loss = engine._grads_only(engine.state, micro, engine._mix_matrix(),
                                 jax.random.PRNGKey(0))
    return float(loss), jax.tree_util.tree_map(np.asarray, g)


def _assert_grads_close(got, want, rtol=GRAD_RTOL, atol=GRAD_ATOL):
    import jax

    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=rtol, atol=atol,
                                   err_msg=jax.tree_util.keystr(path))


def _has_scope(path, name):
    """Is ``name`` a named scope on the path (``jvp(name)`` and the like count)?"""
    return any(re.sub(r"^(?:\w+\()*|\)*$", "", c) == name
               for c in path.split("/"))


def _scoped(op, name):
    return _has_scope(op.scope, name)


def _loop_collectives(ops):
    """Instructions that are or hold a collective and run in the body of a
    loop under the scope ``loss`` (control flow itself excepted)."""
    return [n for n, op in ops.items()
            if op.contains_collective and _scoped(op, "loss")
            and "while/body" in op.scope
            and op.opcode not in ("while", "call", "conditional")]


HEADS = {"untied": {}, "tied": {"tie_embeddings": True},
         "untied_bias": {"unembed_bias": True}}


@pytest.mark.parametrize("stage", [2, 3])
@pytest.mark.parametrize("pad", [False, True])
@pytest.mark.parametrize("head", sorted(HEADS))
def test_region_equals_full_logits(four, head, pad, stage):
    """Loss and every gradient leaf of one step: chunked in the region
    against the same engine on full logits (``loss_chunk=0``)."""
    mcfg = _mcfg(pad_vocab_logits=pad, **HEADS[head])
    batch = _batch()
    chunked = sxt.initialize(model=Transformer(mcfg), config=_config(stage),
                             seed=3)[0]
    if head == "untied_bias":       # init() leaves the bias at zero
        bias = np.random.default_rng(2).standard_normal(131).astype(np.float32)
        put = lambda e: e.state._replace(master={
            **e.state.master, "unembed_b": e.state.master["unembed_b"] + bias})
        chunked.state = put(chunked)
    loss_c, grads_c = _loss_and_grads(chunked, batch)
    full = sxt.initialize(
        model=Transformer(dataclasses.replace(mcfg, loss_chunk=0)),
        config=_config(stage), seed=3)[0]
    if head == "untied_bias":
        full.state = put(full)
    loss_f, grads_f = _loss_and_grads(full, batch)
    np.testing.assert_allclose(loss_c, loss_f, rtol=LOSS_RTOL)
    _assert_grads_close(grads_c, grads_f)


def test_compiled_step_moves_the_head_once(four):
    """The compiled ZeRO-3 train step: no collective in the loss scan's
    body; the head's gather under ``zero3_gather`` and its gradient's
    reduce-scatter under ``zero3_reduce_scatter``, both inside ``loss``."""
    engine = sxt.initialize(model=Transformer(_mcfg(vocab=128)),
                            config=_config(3), seed=1)[0]
    ops = trace.program_ops(engine.compile(_batch(vocab=128)))
    assert _loop_collectives(ops) == []
    in_loss = [op for op in ops.values() if _scoped(op, "loss")]
    gathers = [op for op in in_loss if op.opcode.startswith("all-gather")
               and _scoped(op, "zero3_gather")]
    reductions = [op for op in in_loss if _scoped(op, "zero3_reduce_scatter")
                  and op.opcode.startswith(("reduce-scatter", "all-reduce"))]
    # unembed and the final norm's weight, each once
    assert len(gathers) >= 2 and len(reductions) >= 2, (gathers, reductions)


def test_parent_path_reduces_in_the_loop(four):
    """What the region replaces, kept visible: with no ``kernel_mesh`` specs
    to go by (a mesh whose ``seq`` axis is live keeps today's path) XLA's
    partitioner puts the head's collectives in the loop's body."""
    engine = sxt.initialize(model=Transformer(_mcfg(vocab=128)),
                            config=_config(3, mesh={"fsdp": 2, "seq": 2}),
                            seed=1)[0]
    ids = _batch(vocab=128)["input_ids"]     # seq splits the 32 positions
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    ops = trace.program_ops(engine.compile(batch))
    assert _loop_collectives(ops)
    assert not any("loss)/shard_map" in op.scope or "loss/shard_map" in op.scope
                   for op in ops.values())


def test_one_device_loss_holds_no_shard_map(monkeypatch, devices8):
    """On a one-device mesh ``chunked_loss`` is the code it was."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices8[:1])
    mcfg = _mcfg(vocab=128)
    engine = sxt.initialize(model=Transformer(mcfg),
                            config=_config(3, mesh={"fsdp": 1}), seed=1)[0]
    model = Transformer(mcfg)
    x = jnp.zeros((4, SEQ, 64), jnp.float32)
    labels = jnp.zeros((4, SEQ), jnp.int32)
    with mesh_lib.kernel_mesh(engine.topology.mesh, {}):
        jaxpr = jax.make_jaxpr(lambda p: model.chunked_loss(p, x, labels, 8))(
            engine.state.master)
    assert "shard_map" not in str(jaxpr)
    assert "shard_map" not in engine._train_step.lower(
        engine.state, engine._reshape_batch(_batch(vocab=128)),
        engine._mix_matrix(), jax.random.PRNGKey(0),
        np.asarray(1.0, np.float32)).as_text()


def test_skipped_not_nested_inside_the_int8_wire_region(four):
    """ZeRO++ qwZ's streamed region already took the ZeRO axes and hands the
    loss whole weights: the loss's own region is not entered inside it."""
    engine = sxt.initialize(
        model=Transformer(_mcfg(vocab=128)),
        config=dict(_config(3, zero_quantized_weights=True),
                    bf16={"enabled": True}), seed=1)[0]
    batch = _batch(vocab=128)
    ops = trace.program_ops(engine.compile(batch))
    scopes = [op.scope for op in ops.values() if _scoped(op, "loss")]
    assert scopes and all(s.count("shard_map") == 1 for s in scopes
                          if "shard_map" in s)
    assert not any(re.search(r"loss\)*/shard_map", s) for s in scopes)
    first = float(engine.train_batch(batch))
    for _ in range(3):
        last = float(engine.train_batch(batch))
    assert np.isfinite(last) and last < first


@pytest.mark.parametrize("mesh", [{"tensor": 2, "fsdp": 2},
                                  {"data": 2, "fsdp": 2}],
                         ids=["tensor2_fsdp2", "data2_fsdp2"])
def test_mesh_gives_the_one_device_loss_and_gradients(four, mesh):
    """``tensor`` stays automatic inside the region; ``data`` x ``fsdp``
    gathers over the compound entry the masters are sharded with."""
    import jax

    mcfg = _mcfg(vocab=128)
    engine = sxt.initialize(model=Transformer(mcfg), config=_config(3, mesh),
                            seed=5)[0]
    batch = _batch(vocab=128)
    loss, grads = _loss_and_grads(engine, batch)
    ops = trace.program_ops(engine.compile(batch))
    # what is left in the loop under ``tensor`` is the activations' own: the
    # softmax over vocabulary-split logits and dx's contraction over them
    assert all(ops[n].opcode.startswith("all-reduce") and "tensor" in mesh
               for n in _loop_collectives(ops))

    params = jax.tree_util.tree_map(np.asarray, engine.state.master)
    model = Transformer(mcfg)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, batch)))(params)
    np.testing.assert_allclose(loss, float(want_loss), rtol=1e-5)
    _assert_grads_close(grads, want, rtol=1e-4, atol=1e-6)


def test_gas_and_eval_use_the_region(four):
    """Gradient accumulation scans the region once per microbatch, and
    ``eval_batch`` runs it forward only: both give the full-logits loss."""
    mcfg = _mcfg(vocab=128)
    batch = _batch(vocab=128, batch=8)

    def engine_of(cfg):
        return sxt.initialize(
            model=Transformer(cfg),
            config=dict(_config(3, batch=8), gradient_accumulation_steps=2),
            seed=7)[0]

    chunked, full = engine_of(mcfg), engine_of(dataclasses.replace(mcfg, loss_chunk=0))
    np.testing.assert_allclose(float(chunked.eval_batch(batch)),
                               float(full.eval_batch(batch)), rtol=LOSS_RTOL)
    np.testing.assert_allclose(float(chunked.train_batch(batch)),
                               float(full.train_batch(batch)), rtol=1e-5)


@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("stage", [1, 2, 3])
def test_region_stages_and_devices(monkeypatch, devices8, stage, n):
    """ZeRO stages 1-3 on 2 and 4 devices, a tied head with padded columns
    and a LayerNorm bias, loss scaling on (a cotangent of 2**16 into the
    scan): loss and every gradient leaf against full logits."""
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices8[:n])
    mcfg = _mcfg(pad_vocab_logits=True, tie_embeddings=True)
    batch = _batch()

    def engine_of(cfg):
        return sxt.initialize(
            model=Transformer(cfg),
            config=dict(_config(stage, mesh={"fsdp": n}),
                        fp16={"enabled": True, "initial_scale_power": 16},
                        ), seed=3)[0]

    (loss_c, grads_c), (loss_f, grads_f) = (
        _loss_and_grads(e, batch) for e in
        (engine_of(mcfg), engine_of(dataclasses.replace(mcfg, loss_chunk=0))))
    # fp16 compute, gradients still scaled by 2**16: a half-precision head's
    # tolerances, the absolute one in units of each leaf's largest entry
    np.testing.assert_allclose(loss_c, loss_f, rtol=2e-3)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads_c)[0],
                            jax.tree_util.tree_leaves(grads_f)):
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=1e-2 * np.abs(b).max(),
                                   err_msg=jax.tree_util.keystr(path))


# -- the shape of the program ---------------------------------------------------


def _sub_jaxprs(value):
    if hasattr(value, "eqns"):
        yield value
    elif hasattr(getattr(value, "jaxpr", None), "eqns"):
        yield value.jaxpr
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def _eqns(jaxpr, stack=""):
    """Every equation of a jaxpr and of the jaxprs it holds, with the path
    of named scopes it was traced under."""
    for eqn in jaxpr.eqns:
        path = f"{stack}/{eqn.source_info.name_stack}"
        yield eqn, path
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                yield from _eqns(sub, path)


def _loss_scans(jaxpr):
    return [e for e, path in _eqns(jaxpr) if e.primitive.name == "scan"
            and _has_scope(path, "loss")]


@pytest.mark.parametrize("n", [1, 4])
def test_train_step_holds_one_loss_scan_of_three_matmuls(monkeypatch, devices8, n):
    """The traced train step, on one device and in the region: ONE scan
    under ``loss`` (no transposed second one), nothing of the head under
    ``checkpoint``, three ``dot_general`` in the scan's body, the unembed's
    gradient a float32 carry. ``eval_batch``'s program: one ``dot_general``
    under ``loss`` and no gradient carry."""
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices8[:n])
    engine = sxt.initialize(
        model=Transformer(_mcfg(vocab=128)),
        config=dict(_config(3, mesh={"fsdp": n}), bf16={"enabled": True}),
        seed=1)[0]
    batch = engine._reshape_batch(_batch(vocab=128))
    step = jax.make_jaxpr(engine._train_step)(
        engine.state, batch, engine._mix_matrix(), jax.random.PRNGKey(0),
        np.asarray(1.0, np.float32)).jaxpr
    scans = _loss_scans(step)
    assert len(scans) == 1
    body = scans[0].params["jaxpr"].jaxpr
    assert sum(e.primitive.name == "dot_general" for e, _ in _eqns(body)) == 3
    carries = [str(v.aval) for v in body.outvars[:scans[0].params["num_carry"]]]
    assert "float32[64,128]" in carries, carries       # d unembed [D, V]
    assert not any(c.startswith("bfloat16") for c in carries), carries
    assert not [e for e, path in _eqns(step) if _has_scope(path, "loss")
                and e.primitive.name in ("checkpoint", "remat", "remat2")]
    assert not [e for e, path in _eqns(step) if _has_scope(path, "loss")
                and e.primitive.name.startswith("custom_vjp")]  # resolved by grad

    micro = jax.tree_util.tree_map(lambda x: x[0], batch)
    evaluated = jax.make_jaxpr(engine._eval_step)(
        engine.state, micro, engine._mix_matrix(), jax.random.PRNGKey(0)).jaxpr
    scans = _loss_scans(evaluated)
    assert len(scans) == 1
    assert sum(e.primitive.name == "dot_general" and _has_scope(path, "loss")
               for e, path in _eqns(evaluated)) == 1
    assert [str(v.aval) for v in scans[0].params["jaxpr"].jaxpr.outvars[
        :scans[0].params["num_carry"]]] == ["float32[]", "int32[]"]


@pytest.mark.parametrize("n,gas", [(1, 1), (4, 1), (4, 2)])
def test_the_step_reports_its_loss_chunks(monkeypatch, devices8, n, gas):
    """``last_step_stats``: the chunks the loss scan made and the rows they
    held (pad rows too), as the device that ran them saw them; counts, so the
    microbatches of a step add up."""
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices8[:n])
    rows = 8
    engine = sxt.initialize(
        model=Transformer(dataclasses.replace(_mcfg(vocab=128), loss_chunk=12)),
        config=dict(_config(3, mesh={"fsdp": n}, batch=rows),
                    gradient_accumulation_steps=gas), seed=1)[0]
    engine.train_batch(_batch(vocab=128, batch=rows))
    stats = engine.last_step_stats()
    # 32 positions in chunks of 12: three chunks, the last one padded
    per_device = rows // gas // n
    assert int(stats["loss_chunks"]) == 3 * gas
    assert int(stats["loss_rows"]) == 3 * gas * 12 * per_device


# -- the helpers in parallel/mesh.py ------------------------------------------


def _topology(devices, **axes):
    from shuffle_exchange_tpu.config.config import MeshConfig

    return mesh_lib.MeshTopology.build(MeshConfig(data=-1, **axes),
                                       devices=devices)


@pytest.mark.parametrize("axes,batch,want", [
    ({"fsdp": 4}, 4, ("fsdp",)),
    ({"fsdp": 2}, 4, ("data", "fsdp")),          # data absorbs the other two
    ({"fsdp": 4}, 6, ()),                        # rows the axes do not divide
    ({"fsdp": 2, "seq": 2}, 4, ()),              # chunks run along a sharded dim
    ({"tensor": 4}, 4, ()),                      # no ZeRO axis is live
    ({"tensor": 2, "fsdp": 2}, 4, ("fsdp",)),
], ids=["fsdp4", "data2_fsdp2", "indivisible", "seq2", "tensor4", "tensor2_fsdp2"])
def test_zero_batch_axes(devices8, axes, batch, want):
    topo = _topology(devices8[:4], **axes)
    assert mesh_lib.zero_batch_axes(batch) == ()         # outside kernel_mesh
    with mesh_lib.kernel_mesh(topo.mesh):
        assert mesh_lib.zero_batch_axes(batch) == want


def test_zero_batch_axes_empty_inside_a_region_that_took_them(devices8):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    topo = _topology(devices8[:4], fsdp=2)
    seen = []

    def inner(x):
        seen.append(mesh_lib.zero_batch_axes(4))
        return x

    with mesh_lib.kernel_mesh(topo.mesh):
        jax.jit(mesh_lib.shard_map(inner, mesh=topo.mesh, in_specs=P("fsdp"),
                                   out_specs=P("fsdp"), axis_names={"fsdp"},
                                   check_vma=False))(jnp.zeros((4, 2)))
    assert seen == [()]


@pytest.mark.parametrize("spec,axes,want", [
    (("fsdp", "tensor"), {"fsdp"}, (0, "fsdp")),
    (("tensor", ("fsdp", "data")), {"fsdp", "data"}, (1, ("fsdp", "data"))),
    (("tensor", ("fsdp", "data")), {"fsdp"}, (1, "fsdp")),
    ((None, "tensor"), {"fsdp", "data"}, None),
    ((("tensor", "fsdp"), None), {"fsdp"}, (0, "fsdp")),
], ids=["dim0", "compound", "size1_axis_dropped", "unsharded", "shared_dim"])
def test_zero_sharded_dim(spec, axes, want):
    from jax.sharding import PartitionSpec as P

    assert mesh_lib.zero_sharded_dim(P(*spec), axes) == want
    if want is not None:
        dim, entry = want
        cut = mesh_lib.spec_subset(P(*spec), axes)
        assert cut[dim] == entry and all(
            e is None for i, e in enumerate(cut) if i != dim)


def test_gather_for_loop_reduces_once_in_float32(devices8):
    """The differentiable gather: forward a tiled all-gather, backward one
    reduce-scatter of the float32 cotangent, handed back in the leaf's
    dtype."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    topo = _topology(devices8[:4], fsdp=4)
    spec = P("fsdp", None)
    w = jnp.arange(32, dtype=jnp.bfloat16).reshape(8, 4)

    def loss(w, x):
        def local(w, x):
            full = mesh_lib.gather_for_loop(w, spec, ("fsdp",))
            return jax.lax.psum((x @ full.astype(jnp.float32)).sum(), "fsdp")

        return mesh_lib.shard_map(local, mesh=topo.mesh, in_specs=(spec, P("fsdp")),
                                  out_specs=P(), axis_names={"fsdp"},
                                  check_vma=False)(w, x)

    x = jnp.ones((4, 8), jnp.float32)
    value, grad = jax.jit(jax.value_and_grad(loss))(w, x)
    assert grad.dtype == jnp.bfloat16
    np.testing.assert_allclose(float(value), float((x @ w.astype(jnp.float32)).sum()))
    np.testing.assert_allclose(np.asarray(grad, np.float32), np.full((8, 4), 4.0))
    jaxpr = str(jax.make_jaxpr(jax.grad(loss))(w, x))
    reductions = re.findall(r"(\w+)\[[\d,]*\] = reduce_scatter", jaxpr)
    assert reductions == ["f32"], jaxpr


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_head_gradient_is_summed_and_reduced_in_float32(devices8, tied):
    """bf16 weights: the scan's carry for the unembed's gradient is float32
    (the matmul's own output, not rounded per chunk to bf16) and so is its
    one reduce-scatter; the shard comes back in the weight's dtype."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    topo = _topology(devices8[:4], fsdp=4)
    mcfg = _mcfg(vocab=128, tie_embeddings=tied)
    model = Transformer(mcfg)
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.bfloat16),
                                    model.init(jax.random.PRNGKey(0)))
    name, spec = ("embed", P("tensor", "fsdp")) if tied else \
        ("unembed", P("fsdp", "tensor"))
    x = jnp.ones((4, SEQ, 64), jnp.bfloat16)
    labels = jnp.zeros((4, SEQ), jnp.int32)

    def loss(p):
        with mesh_lib.kernel_mesh(topo.mesh, {name: spec, "ln_f_w": P("fsdp")}):
            return model.chunked_loss(p, x, labels, SEQ // 4)[0]

    jaxpr = str(jax.make_jaxpr(jax.grad(loss))(params))
    assert re.search(r"f32\[64,128\] = add ", jaxpr)             # the carry
    assert not re.search(r"bf16\[(64,128|128,64)\] = add(_any)? ", jaxpr)
    scattered = re.findall(r"(\w+\[[\d,]*\]) = reduce_scatter", jaxpr)
    assert ("f32[128,16]" if tied else "f32[16,128]") in scattered, scattered
    grads = jax.jit(jax.grad(loss))(params)
    assert grads[name].dtype == jnp.bfloat16
    want = jax.grad(lambda p: model.chunked_loss(p, x, labels, SEQ // 4)[0])(params)
    np.testing.assert_allclose(np.asarray(grads[name], np.float32),
                               np.asarray(want[name], np.float32),
                               rtol=2e-2, atol=1e-3)


def test_stage0_leaves_enter_whole(four):
    """No ZeRO sharding: every leaf of the head enters the region whole and
    its gradient is summed once on the way out; same loss and gradients as
    on full logits."""
    mcfg = _mcfg(unembed_bias=True)
    batch = _batch()
    chunked = sxt.initialize(model=Transformer(mcfg), config=_config(0), seed=3)[0]
    full = sxt.initialize(model=Transformer(dataclasses.replace(mcfg, loss_chunk=0)),
                          config=_config(0), seed=3)[0]
    (loss_c, grads_c), (loss_f, grads_f) = (_loss_and_grads(e, batch)
                                            for e in (chunked, full))
    np.testing.assert_allclose(loss_c, loss_f, rtol=LOSS_RTOL)
    _assert_grads_close(grads_c, grads_f)
    ops = trace.program_ops(chunked.compile(batch))
    assert _loop_collectives(ops) == []


@pytest.mark.parametrize("stage", [0, 3])
def test_bf16_engine_trains_through_the_region(four, stage):
    """bf16 forward weights, a LayerNorm head and a bias of 131 entries that
    four devices cannot split (it enters whole under ZeRO-3 too): the step
    compiles on the CPU (a bf16 sum of a whole leaf's gradient used to bring
    its compiler down), starts from the full-logits loss and trains."""
    mcfg = _mcfg(unembed_bias=True)
    batch = _batch()
    bf16 = lambda cfg: dict(_config(stage), bf16={"enabled": True})
    engine = sxt.initialize(model=Transformer(mcfg), config=bf16(mcfg), seed=3)[0]
    full = sxt.initialize(model=Transformer(dataclasses.replace(mcfg, loss_chunk=0)),
                          config=bf16(mcfg), seed=3)[0]
    losses = [float(engine.train_batch(batch)) for _ in range(4)]
    np.testing.assert_allclose(losses[0], float(full.train_batch(batch)), rtol=2e-3)
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
