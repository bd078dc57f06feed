"""The programs of the hybrid cells' blocks do not move: the jaxpr text of
``Transformer._gdn`` and of one block of every kind (``layer_apply``, per-half
remat on, as ``stack_apply`` calls it) at the tiny presets of
``qwen3next-train``, ``nemotron3-train``, ``lfm2-train``,
``granite4h-train`` and ``smallthinker-train`` (the HF dicts of their own test files), and the bits of their outputs and gradients at a fixed
seed, against ``tests/data/block_program_text.json``, which was written from
the commit BEFORE the output-normed block, the doubled beta and the
whole-projection q/k norm among several kinds came in (PR 50). The halves'
seam (``mixer_half`` / ``ffn_half``) is shared by all three; a multiply by 1.0
or a branch XLA has to fold would change the text: the Granite family's
multipliers (PR 55) are neutral in the three older cells and emit nothing there.

PR 63 made a routed layer's index arrays from sorts and compare-sums
(``moe/layer.py`` ``_route_index``): the ``*_moe`` blocks' ``text`` and
``grad_text`` were written again from that PR's tree, their ``bits`` and every
entry of a block that routes nothing (``ssm_none``, ``*_mlp``, ``_gdn``) are
the parent's (02174fc), unchanged.

The text is jaxpr text of this container's JAX: after a JAX upgrade that
changes the printer, write the file again from a commit that is known good
(``SXT_WRITE_GOLDEN=1 pytest tests/test_block_program_text.py``) and say so.
"""

import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shuffle_exchange_tpu.models import Transformer
from shuffle_exchange_tpu.models.hf import config_from_hf

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "block_program_text.json")
B, T = 2, 64


def _hf(name):
    """The tiny HF dict of a cell's own test file."""
    import importlib

    return importlib.import_module(f"test_{name}").HF


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


def _text(fn, *args) -> str:
    """The jaxpr's text without what moves from process to process."""
    return re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))


def _bits(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


def _one_layer(params, name):
    """The leaves of layer (period 0, index 0) of kind ``name``."""
    return jax.tree.map(lambda x: x[0, 0], params["layers"][name])


def readings(cell: str) -> dict:
    cfg = config_from_hf(_hf(cell))
    model = Transformer(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(7))
    h = jax.random.normal(jax.random.PRNGKey(8), (B, T, cfg.d_model), jnp.float32)
    out = {}
    for name, _, kind in model.slots():
        if name in out:
            continue
        lw = _one_layer(params, name)
        rope = ((None, None) if cfg.position == "none" or kind[0] in (
            "gdn", "sconv", "ssm") else model.rope_for(kind[0], T))

        def block(lw, h):
            y, (aux, _) = model.layer_apply(lw, h, rope, kind=kind, remat_halves=True)
            return (y * y).sum() + aux

        grads = jax.jit(jax.grad(block, argnums=(0, 1)))(lw, h)
        out[name] = {"text": _sha(_text(block, lw, h)),
                     "grad_text": _sha(_text(jax.grad(block, argnums=(0, 1)), lw, h)),
                     "bits": _bits((jax.jit(block)(lw, h), grads))}
        if kind[0] == "gdn":
            mixer = lambda lw, y: model._gdn(lw, y, rope)
            out[name + "/_gdn"] = {
                "text": _sha(_text(mixer, lw, h)),
                "bits": _bits(jax.jit(mixer)(lw, h))}
    return out


# (``granite4h``: written from the commit that brought the block, PR 55; it
# holds LATER changes to the multipliers' seams. ``smallthinker``: likewise
# from PR 57, the feed-forward half that takes (x, h) and the unrotated full
# layer beside rotated window layers)
CELLS = ("qwen3next", "nemotron3", "lfm2", "granite4h", "smallthinker")


@pytest.mark.parametrize("cell", CELLS)
def test_block_programs_are_the_parents(cell):
    got = readings(cell)
    if os.environ.get("SXT_WRITE_GOLDEN"):
        held = json.load(open(GOLDEN)) if os.path.exists(GOLDEN) else {}
        held[cell] = got
        held["jax"] = jax.__version__
        os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
        with open(GOLDEN, "w") as f:
            json.dump(held, f, indent=1, sort_keys=True)
    held = json.load(open(GOLDEN))
    if held["jax"] != jax.__version__:
        pytest.skip(f"golden text is of JAX {held['jax']}, this is {jax.__version__}")
    assert set(got) == set(held[cell])
    for name in got:
        assert got[name] == held[cell][name], (cell, name)
