"""The plain references a cell's result is held to, copied from
``chip_smoke.py`` (PR 23, where they ran on the chip): the same model by the
plainest route the repo has - ``Transformer`` with ``attention_impl=
"reference"`` (jnp attention, no Pallas kernel, no paged cache, no scheduler,
no sharding), one jitted forward.
"""

from __future__ import annotations

import dataclasses


def reference_loss(model_cfg, params, batch, device=None, dtype=None,
                   rows: int = 1) -> float:
    """Mean loss of the first ``rows`` rows of ``batch`` by the plain
    forward, one row at a time (whole [H, T, T] scores are kept, so a row is
    what fits) - in float32 at full matmul precision unless ``dtype`` says
    otherwise."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from shuffle_exchange_tpu.models import Transformer

    plain = Transformer(dataclasses.replace(model_cfg,
                                            attention_impl="reference"))
    dtype = dtype or jnp.float32
    params = jax.tree.map(lambda p: p.astype(dtype), params)
    if device is not None:
        params = jax.device_put(params, device)
    loss = jax.jit(plain.loss)
    with jax.default_matmul_precision("float32"):
        out = [float(loss(params, {"input_ids": jax.device_put(
            batch["input_ids"][i:i + 1], device)})) for i in range(rows)]
    return float(np.mean(out))     # rows are equally long: mean of means


def reference_logit_check(model_cfg, params, prompts, served, pad_to: int):
    """Teacher-forced check of served tokens against a plain forward of the
    same weights. For every generated position: the gap between the
    reference's best logit and the logit of the token that was served, in
    units of that position's logit standard deviation - 0 where the served
    token IS the reference's argmax. Greedy decoding is exact in float32; in
    bf16 two correct computations can differ at a near-tie, so the caller
    bounds the gap instead of demanding equality (a wrong KV row would put a
    token ~4 sigma under the best)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from shuffle_exchange_tpu.models import Transformer

    plain = Transformer(dataclasses.replace(model_cfg,
                                            attention_impl="reference"))

    @jax.jit
    def gaps(params, ids, pos, toks):
        logits = plain.apply(params, ids)[0].astype(jnp.float32)   # [T, V]
        rows = logits[pos]                                         # [n, V]
        took = jnp.take_along_axis(rows, toks[:, None], axis=1)[:, 0]
        return ((rows.max(axis=1) - took) / rows.std(axis=1),
                rows.argmax(axis=1))

    worst, exact, total = 0.0, 0, 0
    for prompt, toks in zip(prompts, served):
        n = len(toks)
        ids = np.zeros((1, pad_to), np.int32)
        seq = list(prompt) + list(toks[:-1])
        ids[0, :len(seq)] = seq
        # token j of the answer is predicted at position len(prompt)-1+j;
        # positions are padded to one length so that one program serves
        # every request (the padding repeats the last position)
        pos = np.full((pad_to,), len(prompt) - 1 + n - 1, np.int32)
        pos[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        tk = np.full((pad_to,), toks[-1], np.int32)
        tk[:n] = toks
        gap, arg = gaps(params, ids, pos, tk)
        worst = max(worst, float(np.asarray(gap)[:n].max()))
        exact += int((np.asarray(arg)[:n] == np.asarray(toks)).sum())
        total += n
    return {"tokens": total, "exact_argmax": exact,
            "worst_gap_sigma": round(worst, 5)}
