"""The training autotuner: search training configs, measure, emit the best.

Capability analog of the reference autotuner (``autotuning/autotuner.py``,
2,722 LoC; workflow in ``autotuning/README.md``): given a model and a base
DS-style config, it explores micro-batch size, gradient-accumulation steps,
ZeRO stage, and remat policy, prunes candidates with a first-principles
HBM-memory model (the reference prunes with its ``model_info`` param-count
estimate), then short-profiles the survivors through the real engine and
returns/writes the measured-best config (reference result tables:
``autotuning/README.md:240-245``).

TPU-native differences: no multi-process experiment launcher is needed —
candidates compile+run in-process through jit; memory pruning uses the known
HBM capacity per device instead of CUDA allocator probing; "mp_size" maps to
the mesh's tensor axis.

Since ISSUE 14 this class is a thin driver over the shared subsystem
machinery: measurement lives in :class:`~.objectives.TrainingObjective`,
execution rides :class:`~.runner.ExperimentRunner` (pass ``journal_dir``
to make a tune crash-safe — completed trials journal tmp+rename and a
restarted tune re-runs nothing), and result files commit atomically. The
serving half of the subsystem (``space.py``/``search.py``/
``objectives.ServingObjective``) shares the same runner/journal, so one
results dir (and one chip session) retunes training AND serving.
"""

from __future__ import annotations

import dataclasses
import itertools
import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..config.config_utils import ConfigError
from ..utils.logging import log_dist, logger
from .runner import ExperimentRunner, TrialJournal, atomic_write_json, \
    sweep_stale_tmp

# bytes per element
_F32 = 4
_BF16 = 2


def _hbm_bytes_per_device(default: int = 16 * 1024**3) -> int:
    """Best-effort per-device memory budget (HBM on TPU, heap on CPU)."""
    import jax

    try:
        stats = jax.devices()[0].memory_stats()
        if stats and "bytes_limit" in stats:
            return int(stats["bytes_limit"])
    except Exception:
        pass
    return default


def estimate_step_memory(n_params: int, *, mbs: int, seq_len: int,
                         d_model: int, n_layers: int, vocab_size: int,
                         zero_stage: int, world: int, remat: bool,
                         loss_chunk: int = -1, tensor: int = 1,
                         seq_par: int = 1,
                         offload: Optional[str] = None) -> int:
    """First-principles peak-HBM estimate (bytes) for one fused train step.

    Mirrors the reference autotuner's memory-per-GPU estimate
    (``autotuning/autotuner.py`` model_info path) with TPU specifics: bf16
    forward weights + fp32 master/m/v (ZeRO-sharded over ``world`` when
    stage >= 1), activations ~ per-layer residual+ffn working set (halved
    by remat to the saved-dots set), chunked-CE logits block (``loss_chunk``
    as the model's: positions a chunk, 0 = full logits, < 0 = the model's own
    rule, which sizes a chunk by its rows under a byte budget). ``tensor``
    divides param/activation terms (mp_size); ``seq_par`` divides only the
    token-dependent terms (activations/logits — params replicate across the
    seq axis); ``offload`` = "cpu"/"nvme" moves master+moments off device
    entirely (host-optimizer tier).
    """
    shard = world if zero_stage >= 1 else 1
    p_shard = world if zero_stage >= 3 else 1
    master_opt = 3 * n_params * _F32 // (shard * tensor)   # master + m + v
    if offload in ("cpu", "nvme"):
        master_opt = 0
    fwd_params = n_params * _BF16 // (p_shard * tensor)    # bf16 forward copy
    grads = n_params * _F32 // max(1, (shard if zero_stage >= 2 else 1) * tensor)
    tokens = mbs * seq_len // seq_par
    # activation working set per layer: attn qkv+out (4d) + ffn (~8d) in bf16
    act_per_layer = tokens * d_model * 12 * _BF16 // tensor
    acts = act_per_layer * (2 if remat else n_layers)
    if loss_chunk < 0:
        from ..models.transformer import auto_loss_chunk

        loss_chunk = auto_loss_chunk(mbs, seq_len // seq_par,
                                     vocab_size + -vocab_size % 128)
    logits = (mbs * loss_chunk if loss_chunk else tokens) * vocab_size * _F32
    return master_opt + fwd_params + grads + acts + logits


@dataclasses.dataclass
class Candidate:
    micro_batch_size: int
    gradient_accumulation_steps: int
    zero_stage: int
    remat: Optional[bool]          # None = leave the model as built
    tensor: int = 1                # mesh tensor split (reference mp_size)
    seq_par: int = 1               # mesh seq split (Ulysses sequence parallel)
    offload: Optional[str] = None  # optimizer offload tier: None | cpu | nvme
    seq_len: Optional[int] = None  # None = the tuner's base sequence length
    bucket_mb: Optional[int] = None  # zeropp.bucket_mb (quantized-wire
                                     # launch coalescing); None = config default
    est_bytes: int = 0
    metric_val: float = float("nan")
    status: str = "pending"        # pending | pruned | ok | oom | error

    @property
    def name(self) -> str:
        r = {None: "asis", True: "remat", False: "noremat"}[self.remat]
        n = f"z{self.zero_stage}_mbs{self.micro_batch_size}_gas{self.gradient_accumulation_steps}_{r}"
        if self.tensor > 1:
            n += f"_tp{self.tensor}"
        if self.seq_par > 1:
            n += f"_sp{self.seq_par}"
        if self.offload:
            n += f"_off{self.offload}"
        if self.seq_len:
            n += f"_sl{self.seq_len}"
        if self.bucket_mb is not None:
            n += f"_bkt{self.bucket_mb}"
        return n

    def as_config_patch(self) -> Dict[str, Any]:
        patch: Dict[str, Any] = {
            "train_micro_batch_size_per_gpu": self.micro_batch_size,
            "gradient_accumulation_steps": self.gradient_accumulation_steps,
            "zero_optimization": {"stage": self.zero_stage},
        }
        # Always emit the tuned mesh axes (with explicit 1s) AND the
        # size-style knobs: _merge must OVERRIDE any parallelism settings
        # lingering in the base config (e.g. a previously written
        # optimal-config file), not inherit them. The batch wildcard axis
        # is placed by the runner (base configs may use fsdp=-1).
        patch["mesh"] = {"data": -1, "tensor": self.tensor, "seq": self.seq_par}
        patch["sequence_parallel_size"] = self.seq_par
        patch["tensor_parallel"] = {"tp_size": self.tensor}
        if self.offload:
            patch["zero_optimization"]["offload_optimizer"] = {"device": self.offload}
        if self.bucket_mb is not None:
            patch["zeropp"] = {"bucket_mb": self.bucket_mb}
        return patch


def _merge(base: Dict[str, Any], patch: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    for k, v in patch.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = v
    return out


class Autotuner:
    """Searches (mbs, gas, zero stage, remat) for a model + base config.

    ``model`` is a model-zoo Transformer (or any object with ``init``/
    ``loss`` and a dataclass ``config`` carrying ``remat``); ``batch_fn``
    makes a host batch for a global batch size: ``batch_fn(global_bs) ->
    dict``. Candidates that do not fit the per-device memory budget are
    pruned before compiling anything (reference: experiment pruning by
    model_info); survivors run ``profile_steps`` measured steps.
    """

    def __init__(self, model, base_config: Dict[str, Any],
                 batch_fn: Callable[[int], Dict[str, Any]],
                 tuning_config=None, world_size: Optional[int] = None,
                 profile_steps: int = 3, seq_len: Optional[int] = None,
                 journal_dir: Optional[str] = None):
        import jax

        self.model = model
        self.base = dict(base_config)
        self.base.pop("autotuning", None)
        self.batch_fn = batch_fn
        self.at = tuning_config
        self.world = world_size if world_size is not None else len(jax.devices())
        self.profile_steps = profile_steps
        mcfg = getattr(model, "config", None)
        self.seq_len = seq_len or getattr(mcfg, "max_seq_len", 1024)
        self.results: List[Candidate] = []
        # crash-safe tuning (ISSUE 14): with a journal_dir every measured
        # trial commits tmp+rename and a restarted tune resumes without
        # re-running it; None keeps the historical in-memory behavior.
        # Keys are namespaced by a fingerprint of everything the metric
        # depends on (base config, model geometry, world/seq/profile
        # setup) — a journal from a tune of a DIFFERENT config or model
        # must miss, not restore stale measurements under the same
        # candidate names.
        self.runner = ExperimentRunner(
            TrialJournal(journal_dir) if journal_dir else None)
        import hashlib
        import json as _json

        mdesc = repr(mcfg) if mcfg is not None else type(model).__name__
        self._journal_ns = hashlib.blake2b(
            _json.dumps([self.base, mdesc, self.world, self.seq_len,
                         self.profile_steps,
                         self.at.metric if self.at else "throughput",
                         # environment: a CPU-box journal must never
                         # satisfy the TPU-window tune (or survive a jax
                         # upgrade) — throughput is a property of the
                         # backend, not just the config
                         jax.default_backend(), jax.__version__,
                         getattr(jax.devices()[0], "device_kind", "")],
                        sort_keys=True, default=repr).encode(),
            digest_size=6).hexdigest()
        from .objectives import TrainingObjective

        self._objective = TrainingObjective(
            model, self.base, batch_fn, profile_steps=profile_steps,
            seq_len=self.seq_len,
            metric=(self.at.metric if self.at else "throughput"))

    # -- search space --------------------------------------------------

    def candidates(self, mbs_list: Optional[Sequence[int]] = None,
                   gas_list: Sequence[int] = (1, 2),
                   stages: Sequence[int] = (1, 3),
                   remat_opts: Sequence[Optional[bool]] = (False, True),
                   tensor_list: Optional[Sequence[int]] = None,
                   offload_opts: Sequence[Optional[str]] = (None,),
                   seq_lens: Sequence[Optional[int]] = (None,),
                   seq_par_list: Sequence[int] = (1,),
                   bucket_mb_list: Sequence[Optional[int]] = (None,)) -> List[Candidate]:
        if mbs_list is None:
            lo = self.at.min_train_micro_batch_size_per_gpu if self.at else 1
            hi = self.at.max_train_micro_batch_size_per_gpu if self.at and \
                self.at.max_train_micro_batch_size_per_gpu else lo * 8
            n = self.at.num_tuning_micro_batch_sizes if self.at else 3
            mbs_list, m = [], lo
            while m <= hi and len(mbs_list) < n:
                mbs_list.append(m)
                m *= 2
        if tensor_list is None:
            # mp_size from the autotuning section (the reference tunes it,
            # autotuning/README.md); only splits that divide the device
            # count AND the head count are runnable
            mp = self.at.mp_size if self.at else 1
            tensor_list = [1] if mp <= 1 else [1, mp]
        heads = getattr(getattr(self.model, "config", None), "n_heads", None)
        tensor_list = [t for t in tensor_list
                       if self.world % t == 0 and (heads is None or heads % t == 0)]
        # tp x sp combos must jointly divide the device count (batch
        # shards over the remaining data extent)
        out = []
        for mbs, gas, z, r, t, off, sl, sp_, bkt in itertools.product(
                mbs_list, gas_list, stages, remat_opts, tensor_list,
                offload_opts, seq_lens, seq_par_list, bucket_mb_list):
            if self.world % (t * sp_):
                continue
            if self.at and self.at.max_train_batch_size and \
                    mbs * gas * (self.world // (t * sp_)) > self.at.max_train_batch_size:
                continue
            out.append(Candidate(mbs, gas, z, r, tensor=t, seq_par=sp_,
                                 offload=off, seq_len=sl, bucket_mb=bkt))
        return out

    # -- memory pruning ------------------------------------------------

    def _estimate(self, c: Candidate) -> int:
        import jax

        import numpy as np

        mcfg = getattr(self.model, "config", None)
        if mcfg is None:
            return 0  # no model info — skip pruning
        abstract = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        n_params = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(abstract))
        remat = mcfg.remat if c.remat is None else c.remat
        return estimate_step_memory(
            n_params, mbs=c.micro_batch_size, seq_len=c.seq_len or self.seq_len,
            d_model=mcfg.d_model, n_layers=mcfg.n_layers, vocab_size=mcfg.vocab_size,
            zero_stage=c.zero_stage, world=self.world // (c.tensor * c.seq_par),
            remat=remat, loss_chunk=mcfg.loss_chunk, tensor=c.tensor,
            seq_par=c.seq_par, offload=c.offload)

    # -- measurement ---------------------------------------------------

    def _run_one(self, c: Candidate) -> float:
        """One measured trial through the shared TrainingObjective
        (kept for API compatibility; tune() journals via the runner)."""
        return float(self._objective(c)["metric"])

    def _trial(self, c: Candidate) -> Dict[str, Any]:
        """Journal-shaped payload for one candidate: errors are recorded
        (and resumed) exactly like successes — a deterministic rerun
        must not re-pay a failed compile either."""
        try:
            detail = self._objective(c)
            return {"status": "ok", "metric": float(detail["metric"]),
                    "detail": {k: v for k, v in detail.items()
                               if k != "metric"}}
        except Exception as e:  # OOM or compile failure: record, move on
            status = "oom" if "memory" in str(e).lower() else "error"
            logger.warning(
                f"autotuning: {c.name} failed ({status}): {str(e)[:200]}")
            return {"status": status, "metric": None,
                    "detail": {"error": str(e)[:500]}}

    # -- main loop -----------------------------------------------------

    def tune(self, cands: Optional[List[Candidate]] = None) -> Tuple[Candidate, List[Candidate]]:
        budget = _hbm_bytes_per_device()
        cands = list(cands if cands is not None else self.candidates())
        if not cands:
            raise ConfigError("autotuning: empty candidate set")
        early_stop = self.at.tuner_early_stopping if self.at else 0
        best: Optional[Candidate] = None
        since_best = 0
        for c in cands:
            c.est_bytes = self._estimate(c)
            if c.est_bytes > budget:
                c.status = "pruned"
                log_dist(f"autotuning: {c.name} pruned "
                         f"({c.est_bytes/1e9:.1f}GB est > {budget/1e9:.1f}GB)", ranks=[0])
                continue
            payload, cached = self.runner.run_one(
                f"train:{self._journal_ns}:{c.name}",
                lambda c=c: self._trial(c))
            c.status = str(payload["status"])
            if cached:
                log_dist(f"autotuning: {c.name} restored from journal "
                         f"({c.status})", ranks=[0])
            if payload["metric"] is None:
                continue
            c.metric_val = float(payload["metric"])
            if best is None or c.metric_val > best.metric_val:
                best, since_best = c, 0
            else:
                since_best += 1
                if early_stop and since_best >= early_stop:
                    log_dist(f"autotuning: early stop after {since_best} non-improving", ranks=[0])
                    break
        self.results = cands
        if best is None:
            raise ConfigError("autotuning: no candidate ran successfully")
        return best, cands

    # -- output --------------------------------------------------------

    def write_results(self, best: Candidate, results_dir: Optional[str] = None) -> str:
        """Commit the results table and the tuned config atomically
        (tmp+rename — a kill mid-write leaves the previous files intact,
        ISSUE 14 satellite), sweeping any stale partials a previously
        killed writer left in the results dir."""
        results_dir = results_dir or (self.at.results_dir if self.at else "autotuning_results")
        os.makedirs(results_dir, exist_ok=True)
        sweep_stale_tmp(results_dir)
        table = [{
            "name": c.name, "status": c.status, "metric": None if c.metric_val != c.metric_val
            else c.metric_val, "est_gb": round(c.est_bytes / 1e9, 2),
            **c.as_config_patch(),
        } for c in self.results]
        atomic_write_json(
            os.path.join(results_dir, "autotuning_results.json"), table)
        tuned = _merge(self.base, best.as_config_patch())
        tuned.pop("train_batch_size", None)
        path = atomic_write_json(
            os.path.join(results_dir, "ds_config_optimal.json"), tuned)
        log_dist(f"autotuning: best = {best.name}; tuned config at {path}", ranks=[0])
        return path


def autotune(model, base_config: Dict[str, Any], batch_fn, **kw) -> Tuple[Dict[str, Any], Candidate]:
    """One-call API: returns (tuned_config_dict, best_candidate) and writes
    the results dir per the config's ``autotuning`` section. Trials journal
    into the results dir, so a killed tune rerun with the same config
    resumes instead of re-measuring (ISSUE 14)."""
    from ..config import SXConfig

    import jax

    world = kw.pop("world_size", len(jax.devices()))
    at = SXConfig.load(_merge(base_config, {"train_batch_size": base_config.get(
        "train_batch_size", world)}), world).autotuning
    kw.setdefault("journal_dir", at.results_dir)
    tuner = Autotuner(model, base_config, batch_fn, tuning_config=at,
                      world_size=world, **kw)
    best, _ = tuner.tune()
    tuner.write_results(best)
    return _merge(tuner.base, best.as_config_patch()), best
