"""Training cells of a sparse-expert stack whose attention is a LEARNED sparse
one (Keye-VL-2.0 shaped: ``sa_config``, an indexer of 16 heads over one key
head that scores every earlier key, 2,048 keys a query, the softmax over those
alone, the indexer trained by a KL loss of its own on a detached input; M-RoPE;
a per-head q/k norm; every layer routed, renormalised top-8 of a softmax over
128; one expert-parallel rank's share of the experts): ``train_steps``'s window
(``sxt.initialize(...).train_batch`` on a new seeded batch every step, steps
chained on the donated state, two in flight untraced, one at a time traced)
held to the benchmark's own plain float32 reference of the architecture
(``chipbench/reference_keyevl2.py``).

As in the other sparse drivers the reference runs FIRST and alone on the chip,
from the same initial weights relaid under the source's names: the first
batch's loss (cross-entropy + the balancing loss + the indexer's loss LI), LI
itself, the token-choices every expert receives in every layer, the rows that
fall on the held experts, and by ``jax.grad`` the gradient. The trainer's first
gradient is read out of Adam's first moment after one update. ``correct`` =
losses finite, the first batch's loss fell, the first loss within ``loss_tol``,
LI within ``kl_tol`` of the reference's (a share of it), expert counts and held
rows within ``route_tol``, every leaf's gradient within ``grad_tol`` /
``grad_tol_routed`` / ``grad_tol_indexer`` (the indexer's five leaves, which
only LI reaches), the counters add up, nothing dropped, the router alone within
``router_tol``, and on the MECHANISM ALONE (``mechanism_gaps``: the program's
own ``Transformer._dsa`` on the seed's first layer's leaves, the query and the
indexer's query projection times ``mixer_score_gain``, a seeded input and
cotangent of the cell's shape in the trainer's dtype, against the reference's
``attention`` of that layer in float32), because the whole model's band hides
it:

  (a) ``index``    the indexer's scores I over the causal pairs, against the
                   reference's: within ``index_tol`` of their norm;
  (b) ``select``   the share of the (t, s) the program chose that the reference
                   did not: within ``select_tol`` (a bf16 indexer moves the keys
                   that lie at the threshold; the limit is set from the
                   reference's own bf16 reading);
  (c) ``y`` / ``dx`` / ``d<leaf>`` / ``kl`` / ``dkl_<leaf>``  the core's output,
                   its gradients, LI's value and its gradients, with the
                   reference GIVEN the program's S_t (a moved key does not
                   excuse the arithmetic): within ``mixer_tol`` (LI's
                   gradients: ``mixer_tol_indexer``); and exactly 0
                   where no gradient may arrive (``leak``: the indexer's leaves
                   under the output's cotangent, the main leaves and the input
                   under LI's);
  (d) the TIMED step's counters: every query past position topk - 2 holds
                   exactly ``topk`` keys in every layer
                   (``dsa_selected_per_query``), ``dsa_selected_pair_share``
                   equals the arithmetic's (23.44 at 16,384 / 2,048: a step whose
                   core saw the causal mask reads 100), the indexer's leaves'
                   first gradient is not zero, and the compiled step holds
                   instructions under ``dsa_select``, ``dsa_core`` and ``dsa_kl``.

Weights: ``initial_params`` (``Transformer.init`` from ``--seed``, every gain
and the indexer's bias redrawn so that leaving one out shows, the embedding at
the residual stream's scale so that the routers read the token). Traffic
parameters: ``train_steps``'s, and the limits above.
``chipbench/keyevl2_band.py`` measures the band the limits are set from and runs
wrong models and the lower precision through ``failed_checks`` below, in the
program's place.
"""

from __future__ import annotations

import contextlib
import math
import time

from chipbench import arith_dsa, harness
from chipbench.drivers.train_steps import batches, compiled_step_bytes
from chipbench.drivers.train_steps_hybrid import first_moment, flat_tree  # noqa: F401 (flat_tree: the tests')
from chipbench.drivers.train_steps_mla import (_relaid, is_routed, router_forms,
                                               router_gaps)
from chipbench.drivers.train_steps_moe import grad_gaps, route_gap
from chipbench.drivers.train_steps_swa import router_inputs, source_config

# the program's leaves under the source's names
_BLOCK = {"ln1_w": "input_layernorm.weight",
          "ln2_w": "post_attention_layernorm.weight",
          "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
          "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
          "q_norm_w": "self_attn.q_norm.weight", "k_norm_w": "self_attn.k_norm.weight",
          "dsa_wq": "self_attn.indexer.wq.weight", "dsa_wk": "self_attn.indexer.wk.weight",
          "dsa_ww": "self_attn.indexer.weights_proj.weight",
          "dsa_k_norm_w": "self_attn.indexer.k_norm.weight",
          "dsa_k_norm_b": "self_attn.indexer.k_norm.bias",
          "moe_gate": "mlp.gate.weight"}
_PER_EXPERT = {"moe_w_gate": "gate_proj.weight", "moe_w_up": "up_proj.weight",
               "moe_w_down": "down_proj.weight"}
_TOP = {"embed": "model.embed_tokens.weight", "ln_f_w": "model.norm.weight",
        "unembed": "lm_head.weight"}
GAINS = ("ln1_w", "ln2_w", "q_norm_w", "k_norm_w", "dsa_k_norm_w")
INDEXER_LEAVES = ("dsa_wq", "dsa_wk", "dsa_ww", "dsa_k_norm_w", "dsa_k_norm_b")
MAIN_LEAVES = ("wq", "wk", "wv", "wo", "q_norm_w", "k_norm_w")
MIXER_LEAVES = MAIN_LEAVES + INDEXER_LEAVES


def is_indexer(leaf: str) -> bool:
    """One of the indexer's five leaves: the ones ``grad_tol_indexer`` is for."""
    return leaf.rsplit("/", 1)[-1] in INDEXER_LEAVES


def initial_params(model, seed: int) -> dict:
    """``model.init`` from ``seed`` with every gain drawn from [0.5, 1.5) and
    the indexer's bias from [-0.5, 0.5) (at their initial 1 and 0 a model
    without them computes the same function), and the embedding drawn again at
    the residual stream's scale, a standard normal (``train_steps_prerouter``'s
    reason: at the init's 0.02 what a router reads after the first attention
    is attention's average over the prefix, nearly one vector for every token;
    a few experts then take 14-16 x the mean load, the held rows read 64-91 k
    a step with the seed's draw of WHICH experts, and the rate follows them)."""
    import jax
    import jax.numpy as jnp

    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    params["embed"] = jax.random.normal(jax.random.PRNGKey(seed + 6),
                                        params["embed"].shape, jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))
    draw = lambda x, lo: jax.random.uniform(next(keys), x.shape, jnp.float32, lo, lo + 1.0)
    params["ln_f_w"] = draw(params["ln_f_w"], 0.5)
    for name in GAINS:
        params["layers"][name] = draw(params["layers"][name], 0.5)
    params["layers"]["dsa_k_norm_b"] = draw(params["layers"]["dsa_k_norm_b"], -0.5)
    return params


def source_names(src: dict) -> list:
    """[(path into the program's tree, index into that stacked leaf, the
    source's name)] for every tensor of the model held here (a one-kind stack:
    every leaf under ``layers`` is stacked [layer, ...])."""
    out = [((leaf,), (), name) for leaf, name in _TOP.items()]
    first = int(src.get("expert_first", 0))
    held = int(src.get("num_experts_held") or src["num_experts"])
    for i in range(src["num_hidden_layers"]):
        p = f"model.layers.{i}."
        out += [(("layers", leaf), (i,), p + theirs) for leaf, theirs in _BLOCK.items()]
        out += [(("layers", leaf), (i, e), f"{p}mlp.experts.{first + e}.{theirs}")
                for e in range(held) for leaf, theirs in _PER_EXPERT.items()]
    return out


def to_source_names(params: dict, src: dict) -> dict:
    """The program's tree -> a flat dict under the source's names, each tensor
    as torch stores it. Stays on the device; float32 as the master is."""
    out = {}
    for path, index, name in source_names(src):
        leaf = params
        for key in path:
            leaf = leaf[key]
        out[name] = _relaid(path, leaf[index])
    return out


def from_source_names(named: dict, src: dict) -> dict:
    """``to_source_names`` back, flat: {"/".join(path): the program's stacked
    array}. A name ``named`` lacks (a wrong model without that tensor) counts
    as zeros of its neighbours' shape: a gradient that is not there."""
    import jax.numpy as jnp

    cells = {}
    for path, index, name in source_names(src):
        cells.setdefault(path, {})[index] = (
            None if name not in named else _relaid(path, named[name]))
    for at in cells.values():
        some = next((x for x in at.values() if x is not None), None)
        for index, x in at.items():
            if x is None:
                at[index] = jnp.zeros_like(some) if some is not None else jnp.zeros(())

    def stacked(at, depth, prefix=()):
        if depth == 0:
            return at[prefix]
        n = 1 + max(index[len(prefix)] for index in at
                    if index[:len(prefix)] == prefix)
        return jnp.stack([stacked(at, depth - 1, prefix + (i,)) for i in range(n)])

    return {"/".join(path): stacked(at, len(next(iter(at))))
            for path, at in cells.items()}


def reference_program(src: dict, dtype=None):
    """The reference on a whole batch as ONE jitted program that takes one
    row at a time (``lax.map``; each row, and inside it each layer, each query
    block, each head and each expert, is computed again in the backward):
    (weights, ids [B, T + 1]) -> loss, LI, expert_tokens [layers, E],
    held_rows [layers], d loss / d weights in the program's layout. The
    balancing loss is over ALL rows' tokens together, as HF's is; LI is the
    mean over rows of each row's."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_keyevl2 as ref

    def batch_loss(w, ids):
        def row(one):
            parts = ref.loss_parts(w, src, one[None], dtype or jnp.float32, remat=True)
            return (parts["ce"], parts["kl"], parts["expert_tokens"], parts["held_rows"],
                    [{k: r[k] for k in ("p", "chosen")} for r in parts["routing"]])

        ce, li, tokens, held, routing = jax.lax.map(jax.checkpoint(row), ids)
        every = [{k: v.reshape((-1,) + v.shape[2:]) for k, v in layer.items()}
                 for layer in routing]
        loss = (ce.mean() + float(src.get("router_aux_loss_coef") or 0.0)
                * ref.balancing_loss(every, src) + li.mean())
        return loss, (li.mean(), tokens.sum(axis=0), held.sum(axis=0))

    def first(w, ids):
        (loss, (li, tokens, held)), grad = jax.value_and_grad(
            batch_loss, has_aux=True)(w, ids)
        return loss, li, tokens, held, from_source_names(grad, src)

    return jax.jit(first)


def reference_first_step(program, weights: dict, ids) -> dict:
    import jax

    loss, li, tokens, held, grads = jax.device_get(program(weights, ids))
    return {"loss": float(loss), "kl": float(li), "expert_tokens": tokens,
            "held_rows": held, "grads": grads}


def program_router(mcfg):
    """(logits, unused) -> (chosen [N, k], weight [N, k]) as the program routes."""
    from shuffle_exchange_tpu.moe.gating import topk_select

    forms = router_forms(mcfg)

    def router(logits, _):
        idx, w, *_rest = topk_select(logits, **forms)
        return idx, w

    return router


def reference_router(src: dict):
    """The same of the reference's ``choose`` (looked up when called: the band
    script swaps it)."""
    from chipbench import reference_keyevl2 as ref

    def router(logits, _):
        _, chosen, weight = ref.choose(logits, src)
        return chosen, weight

    return router


# -- the mechanism alone -------------------------------------------------------------

def mixer_inputs(params: dict, src: dict, seed: int, batch: int, seq: int,
                 score_gain: float, dtype):
    """(leaves, x [B, T, D], cotangent [B, T, D]) for the mixer alone, from
    ``seed``: the first layer's eleven attention and indexer leaves of the
    seed's weights, the query projection and the indexer's query projection
    times ``score_gain`` (at the init's scale every softmax is nearly flat and
    every indexer score nearly alike, so no arithmetic inside them can show:
    a trained head's spread over several units), a standard normal x as a
    normed residual is; leaves and x rounded to ``dtype`` as the trainer hands
    them over, the cotangent float32."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed + 3), 2)
    lw = {name: params["layers"][name][0] for name in MIXER_LEAVES}
    lw["wq"], lw["dsa_wq"] = lw["wq"] * score_gain, lw["dsa_wq"] * score_gain
    x = jax.random.normal(keys[0], (batch, seq, src["hidden_size"]), jnp.float32)
    return (jax.tree.map(lambda a: a.astype(dtype), lw), x.astype(dtype),
            jax.random.normal(keys[1], x.shape, jnp.float32))


def _named(lw):
    """The mixer's leaves under the reference's names, float32."""
    import jax.numpy as jnp

    return {"a." + _BLOCK[k]: _relaid((k,), v.astype(jnp.float32)) for k, v in lw.items()}


def _answers(value_and_kl, lw, x, cotangent, tokens: int) -> dict:
    """{"y", "kl", "dx", "d<leaf>", "dkl_x", "dkl_<leaf>"} of a mixer
    (lw, x) -> (y [B, T, D], LI's sum over b, t): y's gradients under the
    cotangent and, apart, the gradients of the mean LI."""
    import jax
    import jax.numpy as jnp

    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    (y, kl), back = jax.vjp(lambda lw, x: f32(value_and_kl(lw, x)), lw, x)
    dlw, dx = back((cotangent, jnp.zeros((), jnp.float32)))
    klw, klx = back((jnp.zeros_like(y), jnp.ones((), jnp.float32) / tokens))
    return f32({"y": y, "kl": kl / tokens, "dx": dx,
                **{"d" + k: v for k, v in dlw.items()}, "dkl_x": klx,
                **{"dkl_" + k: v for k, v in klw.items()}})


@contextlib.contextmanager
def swapped(module, **fns):
    """``module``'s attributes replaced while a program is traced (the band
    script's wrong models: pieces of the reference)."""
    plain = {k: getattr(module, k) for k in fns}
    for k, fn in fns.items():
        setattr(module, k, fn)
    try:
        yield
    finally:
        for k, fn in plain.items():
            setattr(module, k, fn)


def mechanism_gaps(model, src: dict, lw, x, cotangent, dtype=None, variant=None) -> dict:
    """The readings (a), (b), (c) of the module's docstring, as ONE jitted
    program: the program's mixer (``model._dsa`` and, for (a) and (b), its own
    ``dsa_index`` / ``ops.dsa`` pieces) against the reference's ``attention``
    in float32 (``dtype``: in that one instead; the band's lower precision).
    -> {"index", "select", "leak", "y", "dx", "kl", "d<leaf>", "dkl_<leaf>"}:
    shares of the reference's norms ("select": of the program's chosen pairs;
    "leak": the largest absolute value where exactly 0 is due). ``model``
    None: the reference in ``dtype``, with the functions ``variant`` names in
    place of its own, stands in the program's place (the band)."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_keyevl2 as ref

    B, T, _ = x.shape
    named = _named(lw)
    positions = ref.text_positions(B, T)
    block = ref.QUERY_BLOCK if T % ref.QUERY_BLOCK == 0 else T
    f32 = jnp.float32
    norm = lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(f32))))

    def reference_rows(inputs, start):
        """(I [B, q, T], S_t [B, q, T], the causal pairs [q, T]) of a block of
        queries from an indexer's (qI, kI, w)."""
        qi, ki, wi = inputs
        part = lambda a: jax.lax.dynamic_slice_in_dim(a, start, block, axis=1)
        seen = ref.causal(start + jnp.arange(block), T)
        scores = ref.index_scores(part(qi), ki, part(wi))
        return scores, ref.choose_keys(scores, seen, src["sa_config"]["topk"]), seen

    starts = jnp.arange(0, T, block)
    if model is not None:
        from shuffle_exchange_tpu.ops import dsa

        rope = model.rope_for("dsa", T)
        scale = model.dsa_index_scale

        def own(lw, x):
            """The program's (scores of a block from ``start``, S_t whole), both
            queries-major as the reference has them (the program's are
            keys-major)."""
            qi, ki, wi = model.dsa_index(lw, x, rope)
            mask_t, _ = dsa.select(qi, ki, wi, model.config.dsa_topk, scale)
            part = lambda a, start: jax.lax.dynamic_slice_in_dim(a, start, block, axis=0)
            scores = lambda start: jnp.stack([dsa.index_scores(
                part(qi[b], start), ki[b], part(wi[b], start), scale, start).T
                for b in range(B)])
            return scores, mask_t.swapaxes(-1, -2)

        def mixer(lw, x):
            out, found = model._dsa(lw, x, rope)
            return out, found["dsa_kl"] * (B * T)
    else:
        variant = variant or {}

        def own(lw, x):
            low = {k: v.astype(dtype) for k, v in _named(lw).items()}
            with swapped(ref, **variant):
                inputs = ref.indexer_inputs(low, "a.self_attn.", x.astype(dtype), src,
                                            positions)
                both = jax.lax.map(lambda start: reference_rows(inputs, start)[:2], starts)
            whole = lambda a: jnp.moveaxis(a, 0, 1).reshape(B, T, T)
            scores = whole(both[0])
            return (lambda start: jax.lax.dynamic_slice_in_dim(scores, start, block, axis=1),
                    whole(both[1]))

        def mixer(lw, x, mask):
            with swapped(ref, **variant):
                out, parts = ref.attention(
                    {k: v.astype(dtype) for k, v in _named(lw).items()}, "a.self_attn.",
                    x.astype(dtype), src, remat=True, selected=mask)
            return out, parts["kl"]

    def exact(lw, x, mask):
        with jax.default_matmul_precision("highest"):
            out, parts = ref.attention(_named(lw), "a.self_attn.", x.astype(f32), src,
                                       remat=True, selected=mask)
        return out, parts["kl"]

    def both(lw, x, cotangent):
        ours, mask = own(lw, x)
        mask = jax.lax.stop_gradient(mask)
        with jax.default_matmul_precision("highest"):
            theirs = ref.indexer_inputs(_named(lw), "a.self_attn.", x.astype(f32), src,
                                        positions)

        def rows(start):
            with jax.default_matmul_precision("highest"):
                want, chosen, seen = reference_rows(theirs, start)
            got = ours(start).astype(f32)
            mine = jax.lax.dynamic_slice_in_dim(mask, start, block, axis=1) != 0
            off = jnp.where(seen[None], got - want, 0.0)
            return (jnp.sum(off * off), jnp.sum(jnp.where(seen[None], want * want, 0.0)),
                    jnp.sum(mine & ~chosen), jnp.sum(mine))

        d2, w2, moved, held = jax.lax.map(rows, starts)
        run = mixer if model is not None else (lambda lw, x: mixer(lw, x, mask))
        got = _answers(run, lw, x, cotangent, B * T)
        want = _answers(lambda lw, x: exact(lw, x, mask), lw, x, cotangent, B * T)
        # where no gradient may arrive: exactly 0
        none = ({"d" + k for k in INDEXER_LEAVES} | {"dkl_" + k for k in MAIN_LEAVES}
                | {"dkl_x"})
        gaps = {k: norm(got[k] - want[k]) / norm(want[k]) for k in want if k not in none}
        return {"index": jnp.sqrt(d2.sum() / w2.sum()),
                "select": moved.sum() / jnp.maximum(held.sum(), 1),
                "leak": jnp.max(jnp.stack([jnp.max(jnp.abs(got[k])) for k in sorted(none)])),
                **gaps}

    return {k: float(v) for k, v in jax.device_get(jax.jit(both)(lw, x, cotangent)).items()}


def step_scopes(names=("dsa_index", "dsa_select", "dsa_core", "dsa_kl", "mrope")):
    """{scope: the COMPILED train step's instructions under it}, read off the
    program the engine registered with the tracer; None where none is."""
    from shuffle_exchange_tpu.profiling import trace

    ops = trace.registered_ops("train_step")
    if ops is None:
        return None
    paths = [op.scope.split("/") for op in ops.values()]
    return {name: sum(1 for path in paths if name in path) for name in names}


def failed_checks(got: dict, traffic: dict) -> list:
    """What ``correct`` holds a run to, as the messages of the checks that
    failed (none: correct). ``got``: ``losses`` (every step's, the first
    first), ``first_loss_again`` (None: nothing to fall), ``reference_loss``,
    ``kl`` = (the program's LI on the first batch, the reference's),
    ``route_gap`` / ``held_gap`` (None: the program handed out no counters),
    ``counters_add_up``, ``overflow`` [first step, last step], ``grad_gaps``
    {leaf: share of the reference's norm}, ``router_gaps``, ``mechanism``
    (``mechanism_gaps``), ``selected`` = (min, max, the topk; None: no
    counter), ``pair_share`` = (the step's, the arithmetic's), ``scopes``
    (``step_scopes``; None: no compiled step to read, as in the band script).
    The band script hands it a wrong model's or a lower precision's answers in
    the program's place."""
    vals = got["losses"]
    lim = {k: float(traffic[k]) for k in (
        "loss_tol", "kl_tol", "route_tol", "grad_tol", "grad_tol_routed",
        "grad_tol_indexer", "router_tol", "index_tol", "select_tol", "mixer_tol",
        "mixer_tol_indexer")}
    nan_last = lambda gaps: lambda key: gaps[key] if gaps[key] == gaps[key] else math.inf
    tol_of = lambda leaf: lim["grad_tol_indexer" if is_indexer(leaf) else
                              "grad_tol_routed" if is_routed(leaf) else "grad_tol"]
    over = {leaf: gap / tol_of(leaf) for leaf, gap in got["grad_gaps"].items()}
    worst = max(over, key=nan_last(over))         # a NaN gap is the worst of all
    part = max(got["router_gaps"], key=nan_last(got["router_gaps"]))
    mech = got["mechanism"]
    # LI's gradients have a limit of their own: d I = softmax - p is a
    # difference of nearly equal numbers, so the operands' rounding shows in it
    # at five times what it does anywhere else, in every precision
    limit = lambda k: lim["mixer_tol_indexer" if k.startswith("dkl_") else "mixer_tol"]
    alone = {k: v / limit(k) for k, v in mech.items() if k not in ("index", "select", "leak")}
    piece = max(alone, key=nan_last(alone))
    again = got.get("first_loss_again")
    have = got["route_gap"] is not None
    ours, theirs = got["kl"]
    indexer = [got["grad_gaps"][leaf] for leaf in got["grad_gaps"] if is_indexer(leaf)]
    checks = [
        (all(math.isfinite(v) for v in vals), f"non-finite loss in {vals[:8]}..."),
        (abs(vals[0] - got["reference_loss"]) <= lim["loss_tol"],
         f"first loss {vals[0]} vs the float32 reference {got['reference_loss']}: "
         f"off by more than {lim['loss_tol']}"),
        (ours is not None and abs(ours - theirs) <= lim["kl_tol"] * abs(theirs),
         f"the indexer's loss on the first batch {ours} vs the reference's {theirs}: "
         f"off by more than {lim['kl_tol']} of it"),
        (again is None or again < vals[0],
         f"loss did not fall: the first batch read {vals[0]} before the run's "
         f"steps and {again} after them"),
        (have, "the program handed out no moe_expert_tokens / moe_held_rows / "
         "moe_overflow_rows"),
        (have and got["route_gap"] <= lim["route_tol"],
         f"first step's expert counts differ from the reference's in "
         f"{got['route_gap']} of the token-choices: more than {lim['route_tol']}"),
        (have and got["held_gap"] <= lim["route_tol"],
         f"first step's held rows differ from the reference's in {got['held_gap']} "
         f"of them: more than {lim['route_tol']}"),
        (got["counters_add_up"],
         "the held-row counter and the overflow counter do not add up to the "
         "router's own counts over the held experts, or the router's counts "
         "to tokens x k a routed layer"),
        (over[worst] <= 1.0,
         f"first step's gradient of {worst} differs from the reference's by "
         f"{got['grad_gaps'][worst]:.5f} of its norm: more than {tol_of(worst)} "
         f"(1 = no such gradient, or the optimizer's state held no first moment)"),
        (bool(indexer) and all(g < 1.0 for g in indexer),
         f"the indexer's leaves' first gradient reads {indexer} of the reference's "
         f"norm: 1 = none arrived (the indexer's loss did not reach them)"),
        (have and got["overflow"] == [0, 0],
         f"held rows dropped (did not fit the buffer): {got['overflow'][0]} in "
         f"the first step, {got['overflow'][1]} in the last"),
        (got["router_gaps"][part] <= lim["router_tol"],
         f"the router alone: {part} differs from the reference's by "
         f"{got['router_gaps'][part]:.3g}: more than {lim['router_tol']}"),
        (mech["index"] <= lim["index_tol"],
         f"the indexer alone: its scores differ from the reference's by "
         f"{mech['index']:.3g} of their norm: more than {lim['index_tol']} (relu "
         f"dropped, the heads' weights left out, a rotation missing, a key unnormed)"),
        (mech["select"] <= lim["select_tol"],
         f"the selection alone: {mech['select']:.3g} of the (t, s) the program chose "
         f"the reference did not: more than {lim['select_tol']} (top-k per head, a "
         f"selection before the rotation, a threshold below float32)"),
        (alone[piece] <= 1.0,
         f"the mixer alone, on the program's own selection: {piece} differs from "
         f"the reference's by {mech[piece]:.3g} of its norm: more than "
         f"{limit(piece)} (a softmax below float32, a target not normalised, "
         f"an M-RoPE section off)"),
        (mech["leak"] == 0.0,
         f"the mixer alone: a gradient of {mech['leak']:.3g} where none may arrive "
         f"(the indexer's leaves under the output, the main leaves or the input "
         f"under the indexer's loss): the indexer's input is not detached"),
    ]
    selected = got.get("selected")
    if selected is not None:
        lo, hi, topk = selected
        checks.append((lo == hi == topk,
                       f"dsa_selected_per_query reads {lo} to {hi}: every query past "
                       f"position {topk - 2} holds exactly {topk} keys"))
        have_share, want_share = got["pair_share"]
        checks.append((abs(have_share - want_share) < 5e-3,
                       f"dsa_selected_pair_share reads {have_share:.4f}, the "
                       f"arithmetic's {want_share:.4f} (100: the core saw the "
                       f"causal mask)"))
    else:
        checks.append((False, "the program handed out no dsa_selected_min / "
                       "dsa_selected_max / dsa_pairs"))
    scopes = got.get("scopes")
    if scopes is not None:
        empty = [k for k in ("dsa_select", "dsa_core", "dsa_kl") if not scopes.get(k)]
        checks.append((not empty, f"the compiled step holds no instruction under "
                       f"{empty}: {scopes}"))
    return [message for ok, message in checks if not ok]


def run(ctx: dict) -> dict:
    cell = ctx["cell"]
    rehearsal = ctx.get("rehearsal") or {}
    # first: a program that cannot build the configuration says so at once
    mcfg = harness.model_config(cell, rehearsal)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import shuffle_exchange_tpu as sxt
    from shuffle_exchange_tpu.models import Transformer
    from shuffle_exchange_tpu.ops import dsa
    from shuffle_exchange_tpu.ops.dispatch import pallas_enabled

    meter, spans = ctx["meter"], ctx["spans"]
    traffic = {**cell["traffic"], **rehearsal.get("traffic", {})}
    settings = cell["config"]["chipbench"]
    devices = ctx["devices"]
    chips = len(devices)
    if chips != 1:
        raise harness.BenchError("train_steps_dsa holds the whole state on one chip "
                                 f"for its reference; the cell asks for {chips}")
    seq, batch = int(traffic["seq"]), int(traffic["batch_per_chip"])
    src = source_config(cell, rehearsal)
    config = dict(settings["train_config"], train_batch_size=batch,
                  gradient_accumulation_steps=1, steps_per_print=10 ** 9,
                  **rehearsal.get("train_config", {}))
    bf16 = bool(config.get("bf16", {}).get("enabled"))
    dtype = jnp.bfloat16 if bf16 else jnp.float32

    mark = meter.mark()
    seed = harness.seed32(ctx["seed"])
    model = Transformer(mcfg)
    data = batches(mcfg.vocab_size, batch, seq, ctx["seed"])
    first = next(data)
    # the reference first, alone on the chip; the weights are drawn again for
    # the trainer: the same seed, the same weights
    drawn = initial_params(model, seed)
    inputs = mixer_inputs(drawn, src, seed, batch, seq,
                          float(traffic["mixer_score_gain"]), dtype)
    weights = to_source_names(drawn, src)
    del drawn
    reference = reference_first_step(reference_program(src), weights,
                                     jnp.asarray(first["input_ids"]))
    del weights
    # the router alone, in float32 on both sides
    route_gaps = router_gaps(program_router(mcfg),
                             router_inputs(seed, batch * seq, mcfg.n_experts),
                             reference_router(src))
    # the mechanism alone, in the trainer's compute dtype against float32
    mechanism = mechanism_gaps(model, src, *inputs)
    del inputs
    engine = sxt.initialize(model=model, params=initial_params(model, seed),
                            config=config, seed=seed)[0]
    mcfg = model.config          # with what the train_config's sections set
    n_params = sum(int(x.size) for x in jax.tree.leaves(engine.state.master))
    step_bytes = compiled_step_bytes(engine, first)
    compiled = engine.compile(first)            # cached: the analysis again
    analysis = compiled.memory_analysis() if compiled is not None else None
    peak_bytes = int(getattr(analysis, "peak_memory_in_bytes", 0) or 0)
    shape = lambda heads: jax.ShapeDtypeStruct((batch, seq, heads, mcfg.head_dim), dtype)
    routes = {"grouped_gemm": "megablox" if pallas_enabled() else "ragged_dot",
              "dsa_core": dsa.route(shape(mcfg.n_heads), shape(mcfg.kv_heads), seq)}
    scopes = step_scopes()
    topk = int(src["sa_config"]["topk"])          # the SOURCE's, not the program's
    pairs_want = arith_dsa.selected_pairs(seq, topk)
    causal_pairs = seq * (seq + 1) // 2

    def stats_now():
        got = engine.last_step_stats()
        return {k: np.asarray(got[k]) for k in
                ("moe_expert_tokens", "moe_held_rows", "moe_overflow_rows", "dsa_kl",
                 "dsa_selected_min", "dsa_selected_max", "dsa_pairs",
                 "dsa_block_visit_share") if k in got}

    losses = [engine.train_batch(first)]
    first_stats = stats_now()
    beta1 = settings["train_config"]["optimizer"]["params"].get("betas", (0.9,))[0]
    moment = first_moment(engine.state.opt_state)
    first_gaps = ({leaf: 1.0 for leaf in reference["grads"]} if moment is None
                  else grad_gaps(moment, reference.pop("grads"), 1.0 / (1.0 - beta1)))
    del moment
    for _ in range(max(1, int(traffic["warmup_steps"])) - 1):
        losses.append(engine.train_batch(next(data)))
    jax.block_until_ready(losses[-1])
    warm = meter.since(mark)
    first_kl = (float(first_stats["dsa_kl"].mean()) if "dsa_kl" in first_stats else None)
    harness.emit(phase="setup", cell=cell["name"], model=cell["config_name"],
                 reduced=cell["reduced"], params=n_params, seq=seq,
                 batch=batch, chips=chips, zero_stage=engine.zero_stage,
                 routes=routes, step_scopes=scopes, remat=[mcfg.remat, mcfg.remat_policy],
                 reference_loss=reference["loss"], first_loss=float(losses[0]),
                 reference_kl=reference["kl"], first_kl=first_kl,
                 router_gaps=route_gaps, mechanism_gaps=mechanism,
                 compiled_step_bytes=step_bytes,
                 peak_memory_in_bytes=peak_bytes, **warm)

    # -- the window (train_steps's) -------------------------------------------
    traced = bool(ctx["trace"])
    trace_steps = int(traffic.get("trace_steps", 4))
    in_window = meter.mark()
    window_losses = []
    tracing, trace_at, traced_steps, traced_stats = False, None, 0, {}
    t0 = time.perf_counter()
    ctx["window_start"](t0)
    while True:
        now = time.perf_counter()
        if now - t0 >= ctx["seconds"]:
            break
        if traced and not tracing and trace_at is None \
                and now - t0 >= ctx["seconds"] / 3:
            jax.block_until_ready(window_losses[-1:] or losses[-1:])
            ctx["start_trace"]()
            tracing, trace_at = True, len(window_losses)
        if traced:
            # a traced run times each step alone; the untraced run below
            # keeps two steps in flight and times the window as a whole
            with spans.span("train_step"):
                loss = engine.train_batch(next(data))
                jax.block_until_ready(loss)
            window_losses.append(loss)
            if tracing:
                traced_steps += 1
                if traced_steps >= trace_steps:
                    ctx["stop_trace"]()
                    tracing = False
                    # the rows the traced kernels had (the router moves on
                    # over a window: the last step's are not theirs)
                    traced_stats = stats_now()
        else:
            window_losses.append(engine.train_batch(next(data)))
            if len(window_losses) >= 2:
                jax.block_until_ready(window_losses[-2])
    jax.block_until_ready(window_losses[-1])
    t1 = time.perf_counter()
    if tracing:
        ctx["stop_trace"]()
    window_s = t1 - t0
    in_win = meter.since(in_window)
    steps = len(window_losses)

    # -- correct, outside the window ------------------------------------------
    vals = [float(x) for x in losses + window_losses]
    tail = vals[-max(1, min(20, len(vals) - 1)):]
    per_layer = batch * seq * mcfg.moe_top_k
    routed_layers = mcfg.routed_layers
    last_stats = stats_now()
    # "the loss fell", read on the SAME ids
    again = float(engine.train_batch(first))
    counted = {"moe_expert_tokens", "moe_held_rows", "moe_overflow_rows"}
    have = counted <= set(first_stats) and counted <= set(last_stats)
    lo = int(src.get("expert_first", 0))
    hi = lo + int(src.get("num_experts_held") or src["num_experts"])
    first_gap = held_gap = load = dropped = held_share = held_rows_step = None
    overflow, counters_add_up = [None, None], False
    if have:
        first_gap = route_gap(first_stats["moe_expert_tokens"],
                              reference["expert_tokens"])
        held_gap = route_gap(first_stats["moe_held_rows"], reference["held_rows"])
        counters_add_up = all(
            s["moe_expert_tokens"].shape[0] == routed_layers
            and np.array_equal(s["moe_held_rows"] + s["moe_overflow_rows"],
                               s["moe_expert_tokens"][:, lo:hi].sum(axis=1))
            and np.array_equal(s["moe_expert_tokens"].sum(axis=1),
                               np.full(routed_layers, per_layer))
            for s in (first_stats, last_stats))
        overflow = [int(s["moe_overflow_rows"].sum()) for s in (first_stats, last_stats)]
        counts = last_stats["moe_expert_tokens"]
        load = float((counts.max(axis=1) / counts.mean(axis=1)).max())
        dropped = 100.0 * overflow[1] / (per_layer * routed_layers)
        held_share = 100.0 * float(last_stats["moe_held_rows"].max()) / per_layer
        held_rows_step = float(traced_stats.get(
            "moe_held_rows", last_stats["moe_held_rows"]).sum())
    # the selection's counters, of the first AND the last step of the window
    selected = pair_share = visit = None
    sparse = {"dsa_selected_min", "dsa_selected_max", "dsa_pairs"}
    if sparse <= set(first_stats) and sparse <= set(last_stats):
        both = (first_stats, last_stats)
        selected = (int(min(s["dsa_selected_min"].min() for s in both)),
                    int(max(s["dsa_selected_max"].max() for s in both)), min(topk, seq))
        # the layer whose count lies farthest from the arithmetic's
        far = max((int(x) for s in both for x in s["dsa_pairs"]),
                  key=lambda n: abs(n - batch * pairs_want))
        pair_share = (100.0 * far / (batch * causal_pairs),
                      100.0 * pairs_want / causal_pairs)
        visit = float(last_stats["dsa_block_visit_share"].mean())
    failed = failed_checks(
        {"losses": vals, "first_loss_again": again,
         "reference_loss": reference["loss"], "kl": (first_kl, reference["kl"]),
         "route_gap": first_gap, "held_gap": held_gap,
         "counters_add_up": counters_add_up, "overflow": overflow,
         "grad_gaps": first_gaps, "router_gaps": route_gaps, "mechanism": mechanism,
         "selected": selected, "pair_share": pair_share, "scopes": scopes},
        traffic)
    worst = max(first_gaps, key=lambda leaf: first_gaps[leaf]
                if first_gaps[leaf] == first_gaps[leaf] else math.inf)
    correct = not failed
    tokens = steps * batch * seq
    per_chip_rate = tokens / window_s / chips
    of = lambda pick: max((g for leaf, g in first_gaps.items() if pick(leaf)), default=None)
    harness.emit(phase="window", steps=steps, window_s=window_s,
                 tokens=tokens, tokens_per_s_chip=per_chip_rate,
                 first_loss=vals[0], last_losses_mean=sum(tail) / len(tail),
                 first_loss_again=again,
                 first_loss_abs_err=abs(vals[0] - reference["loss"]),
                 first_kl=first_kl, reference_kl=reference["kl"],
                 last_kl=(float(last_stats["dsa_kl"].mean())
                          if "dsa_kl" in last_stats else None),
                 first_step_route_gap=first_gap, first_step_held_gap=held_gap,
                 first_step_grad_gap=first_gaps[worst],
                 first_step_grad_gap_leaf=worst,
                 first_step_grad_gap_routed=of(is_routed),
                 first_step_grad_gap_indexer=of(is_indexer),
                 first_step_grad_gap_others=of(
                     lambda leaf: not is_routed(leaf) and not is_indexer(leaf)),
                 first_step_grad_gaps=first_gaps, router_gaps=route_gaps,
                 mechanism_gaps=mechanism, dsa_selected_per_query=selected,
                 dsa_selected_pair_share=pair_share, dsa_block_visit_share=visit,
                 first_step_held_rows=[int(x) for x in first_stats.get("moe_held_rows", ())],
                 reference_held_rows=[int(x) for x in reference["held_rows"]],
                 moe_expert_load_max_over_mean=load,
                 moe_dropped_token_share=dropped,
                 moe_held_row_share=held_share, traced_steps=traced_steps,
                 step_ms=[round(1e3 * (b - a), 2) for a, b in spans.named("train_step")][:64],
                 failed_checks=failed, **in_win)
    counters = {"compiles_in_window": in_win["programs_compiled"], "steps": steps}
    if selected is not None:
        counters.update(dsa_selected_per_query_min=selected[0],
                        dsa_selected_per_query_max=selected[1],
                        dsa_selected_pair_share=pair_share[0],
                        dsa_block_visit_share=visit,
                        dsa_kl=float(last_stats["dsa_kl"].mean()))
    if have:
        counters.update(moe_expert_load_max_over_mean=load,
                        moe_dropped_token_share=dropped,
                        moe_held_row_share=held_share)
    held_share_of_tokens = None if held_rows_step is None else held_rows_step / (batch * seq)
    return {
        "correct": correct, "attempted": steps,
        "failed": sum(1 for v in vals[len(losses):] if not math.isfinite(v)),
        "end_to_end": {"train_tokens_per_s_chip": per_chip_rate},
        "window_s": window_s, "program_bytes": step_bytes,
        "counters": counters,
        "facts": {"model_cfg": mcfg, "seq": seq, "batch": batch,
                  "chips": chips, "tokens_per_step": batch * seq,
                  "step_s": [b - a for a, b in spans.named("train_step")],
                  "traced_steps": traced_steps,
                  "held_rows_per_step": held_rows_step,
                  "dsa_flops_per_token": None if held_share_of_tokens is None else
                  arith_dsa.train_flops_per_token(mcfg, seq, held_share_of_tokens)},
    }
