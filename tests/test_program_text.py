"""``testing/program_text.py``, the instrument that says whether two trees
lower a cell's train step to the same program: the kernels' serialized bodies
embed their callers' source lines, so the raw hash of a lowered step moves
with any edit above a kernel's call; the canonical one moves only with the
program."""

import re

import jax
import numpy as np
import pytest

import shuffle_exchange_tpu as sxt
from shuffle_exchange_tpu.models import Transformer
from shuffle_exchange_tpu.models.transformer import tiny
from shuffle_exchange_tpu.testing import program_text

# a model whose every layer is entered from a line of this source
CALLER = ("class Called(Transformer):\n"
          "    def layer_apply(self, *args, **kwargs):\n"
          "        return super().layer_apply(*args, **kwargs)\n")


def lowered(monkeypatch, devices, line=0, **fields) -> str:
    """The text of a small bf16 train step lowered for the TPU with the
    Pallas routes on (norm and attention kernels), its layers called from
    ``line`` lines further down a file."""
    from shuffle_exchange_tpu.ops import dispatch

    monkeypatch.setattr(dispatch, "pallas_enabled", lambda: True)
    monkeypatch.setattr(jax, "devices", lambda *a, **kw: devices[:1])
    scope = {"Transformer": Transformer}
    exec(compile("\n" * line + CALLER, "caller.py", "exec"), scope)
    cfg = tiny(d=128, heads=2, seq=128, norm="rmsnorm", position="rope",
               activation="swiglu", **fields)
    engine = sxt.initialize(
        model=scope["Called"](cfg), seed=7,
        config={"train_batch_size": 4, "steps_per_print": 10 ** 9,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True}})[0]
    batch = {"input_ids": np.zeros((4, 129), np.int32)}
    return program_text.train_step_lowered(engine, batch, ("tpu",)).as_text()


def test_a_callers_line_moves_the_raw_hash_and_a_kernel_parameter_the_canonical(
        monkeypatch, devices8):
    texts = (lowered(monkeypatch, devices8), lowered(monkeypatch, devices8, line=7),
             lowered(monkeypatch, devices8, norm_eps=1e-6))
    here, below, other = map(program_text.hashes, texts)
    assert here["mosaic_bodies"] >= 3 and here["distinct_bodies"] >= 3
    assert here["lowered_sha"] != below["lowered_sha"]
    assert here["lowered_no_locations_sha"] == below["lowered_no_locations_sha"]
    # the norm kernel closes over eps: nothing but a body's constant differs
    assert here["lowered_len"] == other["lowered_len"]
    assert here["lowered_no_locations_sha"] != other["lowered_no_locations_sha"]
    # a compiled program's text: the same bodies under JSON's quotes, the
    # callers' lines in ``metadata`` and in the tables under the module line
    compiled = lambda text, line: (
        'HloModule m\n\nFileNames\n1 "caller.py"\n\nStackFrames\n1 1 %d\n\n'
        'ENTRY %%e { %%k = f32[] custom-call(), backend_config={"custom_call_config":'
        '{"body":"%s"}}, metadata={op_name="k" source_line=%d} }\n'
        % (line, re.search(r'body\\22: \\22([A-Za-z0-9+/=]+)', text).group(1), line))
    bare = [program_text.bare_compiled(compiled(text, line))
            for text, line in zip(texts, (3, 10, 3))]
    assert bare[0] == bare[1] != bare[2]
    assert "caller.py" not in bare[0] and "metadata" not in bare[0] and "sha256:" in bare[0]


def test_a_body_prints_without_its_locations_and_a_text_without_bodies_is_itself():
    text = 'module { func.func @main() { return } }'
    assert program_text.canonical(text) == (text, 0, 0)
    with pytest.raises(Exception):
        program_text.bare_body("bm90IGEgbW9kdWxl")      # "not a module"
