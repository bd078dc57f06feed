"""The lowered train step as a hash that survives an edit elsewhere in a file.

A lowered program's text embeds every Pallas kernel as a serialized Mosaic
module (``body``), and that module carries the whole Python traceback of the
kernel's call: a line that moves in ANY caller's file changes the bytes of an
otherwise identical program. ``canonical`` parses each body, prints it without
its debug info and puts the sha of that in its place, so two trees whose
steps emit the same operations read the same hash whatever moved around them.

From the tree under test, on the machine the cell runs on (the Pallas routes
are chosen by the backend)::

    python -m shuffle_exchange_tpu.testing.program_text [--compile] out.json <cell>...

writes one row a cell: the sha256 of ``engine._train_step``'s lowered text, raw
and canonical, built as the cell's driver builds the engine ("tiny": the
``tiny()`` preset, no benchmark needed). ``--compile`` also compiles the step
and adds the sha256 of the optimised, scheduled HLO without its metadata
(``bare_compiled``) and XLA's memory analysis: two trees whose lowered texts
differ by the ORDER of their operations alone read the same hash there.
"""

from __future__ import annotations

import base64
import hashlib
import re
from typing import Tuple

# a kernel's serialized module, in MLIR's escaped form and in plain JSON
_BODIES = (r'(body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)', r'("body": ?")([A-Za-z0-9+/=]+)(")')


def bare_body(b64: str) -> str:
    """One serialized Mosaic module, printed without source locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True   # the bodies are serialized as 'stable_mosaic'
    with ctx:
        return ir.Module.parse(base64.b64decode(b64)).operation.get_asm(
            enable_debug_info=False)


def canonical(text: str) -> Tuple[str, int, int]:
    """(``text`` with each kernel body replaced by the sha of its
    location-free form, the number of bodies, the number of distinct raw
    bodies)."""
    raw, shas = [], {}      # a layer scan's kernels repeat: each raw body is parsed once

    def swap(m):
        body = m.group(2)
        raw.append(body)
        if body not in shas:
            shas[body] = hashlib.sha256(bare_body(body).encode()).hexdigest()
        return m.group(1) + "sha256:" + shas[body] + m.group(3)

    for pattern in _BODIES:
        text = re.sub(pattern, swap, text)
    return text, len(raw), len(shas)


def bare_compiled(text: str) -> str:
    """A compiled program's text (``compiled.as_text()``) without what names
    source lines: every instruction's ``metadata``, the stack-frame tables
    under the module line, the kernel bodies' locations."""
    text = re.sub(r'\n(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:\d+ .*\n)+',
                  '\n', text)
    return canonical(re.sub(r', metadata=\{[^{}]*\}', '', text))[0]


def hashes(text: str) -> dict:
    """The row of one lowered program."""
    bare, bodies, distinct = canonical(text)
    return {"lowered_len": len(text),
            "lowered_sha": hashlib.sha256(text.encode()).hexdigest(),
            "lowered_no_locations_sha": hashlib.sha256(bare.encode()).hexdigest(),
            "mosaic_bodies": bodies, "distinct_bodies": distinct}


def train_step_lowered(engine, batch: dict, platforms=None):
    """The engine's train step lowered on ``batch``; nothing runs.
    ``platforms``: lower for these (("tpu",) from a CPU process: the kernels
    are then serialized as on the chip) instead of the process's backend."""
    import numpy as np

    return engine._train_step.trace(
        engine.state, engine._reshape_batch(batch), engine._mix_matrix(),
        engine._next_rng_peek(), np.asarray(1.0, np.float32)).lower(
            lowering_platforms=platforms)


def _cell_engine(cell: str):
    """(engine, batch) of a benchmark cell (``chipbench`` of the working
    directory) or of the ``tiny()`` preset."""
    import jax
    import numpy as np

    import shuffle_exchange_tpu as sxt
    from shuffle_exchange_tpu.models import Transformer
    from shuffle_exchange_tpu.models.transformer import tiny

    chips = len(jax.devices())
    if cell == "tiny":
        mcfg, seq, batch = tiny(), 32, 8
        config = {"optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                  "gradient_clipping": 1.0, "train_batch_size": batch,
                  "steps_per_print": 10 ** 9}
    else:
        from chipbench import harness

        c = harness.load_cell(cell)
        bench = c["config"]["chipbench"]
        mcfg = harness.model_config(c, None)
        seq, batch = int(c["traffic"]["seq"]), int(c["traffic"]["batch_per_chip"]) * chips
        config = dict(bench["train_config"], train_batch_size=batch,
                      gradient_accumulation_steps=1, steps_per_print=10 ** 9)
        if bench.get("mesh"):
            config["mesh"] = {k: (chips if v == "chips" else v)
                              for k, v in bench["mesh"].items()}
    engine = sxt.initialize(model=Transformer(mcfg), config=config, seed=7)[0]
    return engine, {"input_ids": np.zeros((batch, seq + 1), np.int32)}


def main(argv) -> int:
    import gc
    import json
    import os

    import jax

    import shuffle_exchange_tpu as sxt

    compile_too = argv[0] == "--compile"
    out, cells = argv[compile_too], argv[compile_too + 1:]
    rows = []
    for cell in cells:
        engine, batch = _cell_engine(cell)
        lowered = train_step_lowered(engine, batch)
        rows.append({"cell": cell, "platform": jax.devices()[0].platform,
                     "devices": len(jax.devices()), "pkg": os.path.dirname(sxt.__file__),
                     **hashes(lowered.as_text())})
        if compile_too:
            compiled = lowered.compile()
            memory = compiled.memory_analysis()
            rows[-1].update(
                compiled_bare_sha=hashlib.sha256(
                    bare_compiled(compiled.as_text()).encode()).hexdigest(),
                peak_memory_in_bytes=int(memory.peak_memory_in_bytes),
                temp_size_in_bytes=int(memory.temp_size_in_bytes),
                argument_size_in_bytes=int(memory.argument_size_in_bytes))
            del compiled
        print(json.dumps(rows[-1]), flush=True)
        del engine, lowered
        gc.collect()
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main(sys.argv[1:]))
