"""The train steps PR 56 must not move, as program text: the jaxpr of
``engine._train_step`` (through ``sxt.initialize``, bf16, ZeRO-3, per-half
remat "full", the Pallas routes steered on as on a TPU; traced, never
lowered) of

- ``mha_stack_on_4``: a small several-kinds MHA stack (the tiny
  ``olmohybrid-zero3-x4``: DeltaNet + attention at 4 / 4 heads of 64) under a
  kernel mesh of 4 host devices: the per-shard call, the stock kernels;
- ``gqa_stack_on_1``: a small several-kinds GQA stack (the tiny
  ``lfm2-train``: convolution + attention at 4 over 2 heads of 64) on one
  device: the splash route it had;

against ``tests/data/step_program_text.json``, written from the commit BEFORE
one-device MHA took the splash route (a432495, PR 55's). Memory addresses
and source locations are dropped, a set's members sorted. A third stack,
the MHA one on ONE device, is the program PR 56 changes: the same test reads
the attention kernels of all three out of the text's own ``pallas_call``s.

PR 63 made a routed layer's index arrays from sorts and compare-sums
(``moe/layer.py`` ``_route_index``): ``gqa_stack_on_1`` routes, so its text was
written again from that PR's tree, and the tiny forms of the four cells that
route NOTHING (``gpt2m-train``, ``mistral7b-zero3-x4``, ``granite4h-train``;
``olmohybrid-zero3-x4`` is ``mha_stack_on_4``) are held to the text PR 63's
parent (02174fc) gave them.

The text is of this container's JAX: after an upgrade that changes the
printer, write the file again from a commit known good
(``SXT_WRITE_GOLDEN=1 pytest tests/test_step_program_text.py``) and say so.
"""

import hashlib
import importlib
import json
import os
import re

import jax
import numpy as np
import pytest

from test_mha_route import SPLASH, STOCK, _kernels

import shuffle_exchange_tpu as sxt
from shuffle_exchange_tpu.models import Transformer
from shuffle_exchange_tpu.models.hf import config_from_hf

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "step_program_text.json")
SEQ, BATCH = 128, 4

# stacks that have no test file of their own: heads of 64, the widths small
GPT2 = {"model_type": "gpt2", "vocab_size": 256, "n_positions": 256, "n_embd": 256, "n_layer": 2,
        "n_head": 4, "activation_function": "gelu_new", "layer_norm_epsilon": 1e-5,
        "tie_word_embeddings": True}
MISTRAL = {"model_type": "mistral", "vocab_size": 256, "hidden_size": 256, "intermediate_size": 512,
           "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
           "max_position_embeddings": 1024, "rms_norm_eps": 1e-5, "rope_theta": 1000000.0,
           "hidden_act": "silu", "tie_word_embeddings": False}

# stack -> (the cell's own test file or an HF dict, the keys that make its
# heads 64 wide: the narrowest the Pallas gate admits, devices)
STACKS = {
    "mha_stack_on_4": ("olmohybrid", {"hidden_size": 256}, 4),
    "gqa_stack_on_1": ("lfm2", {"hidden_size": 256}, 1),
    "mha_stack_on_1": ("olmohybrid", {"hidden_size": 256}, 1),
    "gpt2_stack_on_1": (GPT2, {}, 1),
    "mistral_stack_on_4": (MISTRAL, {}, 4),
    "granite4h_stack_on_1": ("granite4h", {}, 1),
}
ATTENTION = {"mha_stack_on_4": STOCK, "gqa_stack_on_1": SPLASH,
             "mha_stack_on_1": SPLASH}


def reading(stack: str, monkeypatch, devices) -> dict:
    from shuffle_exchange_tpu.ops import dispatch

    cell, keys, n = STACKS[stack]
    hf = dict(cell if isinstance(cell, dict) else importlib.import_module(f"test_{cell}").HF,
              **keys)
    monkeypatch.setattr(dispatch, "pallas_enabled", lambda: True)
    monkeypatch.setattr(jax, "devices", lambda *a, **kw: devices[:n])
    engine = sxt.initialize(
        model=Transformer(config_from_hf(hf)), seed=7,
        config={"train_batch_size": BATCH, "steps_per_print": 10 ** 9,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True},
                "activation_checkpointing": {"enabled": True, "policy": "full"},
                "zero_optimization": {"stage": 3}, "mesh": {"fsdp": n}})[0]
    batch = {"input_ids": np.zeros((BATCH, SEQ + 1), np.int32)}
    closed = jax.make_jaxpr(engine._train_step)(
        engine.state, engine._reshape_batch(batch), engine._mix_matrix(),
        engine._next_rng_peek(), np.asarray(1.0, np.float32))
    text = re.sub(r"0x[0-9a-f]+", "0x", str(closed))
    text = re.sub(r"/[\w./-]+\.py(:\d+)*", "<file>", text)
    # a set prints in the order of its hashes, which a process draws
    text = re.sub(r"frozenset\(\{([^}]*)\}\)", lambda m: "frozenset({%s})" % ", ".join(
        sorted(m.group(1).split(", "))), text)
    return {"text": hashlib.sha256(text.encode()).hexdigest(), "chars": len(text),
            "kernels": sorted(_kernels(closed.jaxpr))}


@pytest.mark.parametrize("stack", list(STACKS))
def test_the_steps_pr56_must_not_move_are_the_parents(stack, monkeypatch, devices8):
    got = reading(stack, monkeypatch, devices8)
    attention = {k for k in got["kernels"] if "flash" in k or "splash" in k}
    if stack in ATTENTION:
        assert attention == ATTENTION[stack]
    if stack == "mha_stack_on_1":
        return                      # the one program PR 56 changes
    if os.environ.get("SXT_WRITE_GOLDEN"):
        held = json.load(open(GOLDEN)) if os.path.exists(GOLDEN) else {}
        held[stack] = got
        held["jax"] = jax.__version__
        with open(GOLDEN, "w") as f:
            json.dump(held, f, indent=1, sort_keys=True)
    held = json.load(open(GOLDEN))
    if held["jax"] != jax.__version__:
        pytest.skip(f"golden text is of JAX {held['jax']}, this is {jax.__version__}")
    assert got == held[stack], stack
