"""``chip_smoke.py`` rehearsed without the chip (on-chip-measurement guide,
section 2, rehearsals 1 and 2, kept): the phase functions the chip run calls,
at ``tiny()`` size on the CPU with the fused kernels interpreted, the
four-chip phase body on four of the eight virtual devices, the compile-cache
rule, and the script's refusal to pass on a CPU. What these cannot show -
that the chip's compiler takes the real widths - is
``tests/test_mosaic_lowering.py``'s compile half; that the result is right on
silicon is the chip run itself."""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def meter():
    return chip_smoke.CompileMeter()


def _tiny_llama(**kw):
    from shuffle_exchange_tpu.models.transformer import tiny

    return tiny(vocab=256, d=64, layers=2, heads=4, seq=128,
                activation="swiglu", norm="rmsnorm", position="rope",
                n_kv_heads=2, tie_embeddings=False, **kw)


def test_trainer_phase_at_tiny_size(meter, monkeypatch, devices8, capsys):
    """One device, as on the one-chip machine (``sxt.initialize`` builds its
    mesh from ``jax.devices()``; the test hands it one of the eight)."""
    import jax

    from shuffle_exchange_tpu.models.transformer import tiny

    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices8[:1])
    out = chip_smoke.phase_trainer(
        meter, tiny(), model_name="tiny", seq=32, batch=4, steps=5,
        reduced={"everything": "tiny() on the CPU"})
    assert out["losses"][-1] < out["losses"][0]
    assert out["resume"]["old_engine"] == out["resume"]["fresh_engine"]
    assert out["zero_stage"] == 3 and out["programs_compiled"] > 0
    assert out["routes"] == {"attention": "reference"}
    # the phase leaves no SIGTERM hook behind, pointing into its deleted
    # checkpoint directory (it once did, and broke a later test's handler)
    import signal

    from shuffle_exchange_tpu.runtime import resilience

    assert resilience._PREEMPTION_SAVE is None
    assert signal.getsignal(signal.SIGTERM) is not resilience._preemption_handler
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "trainer" and line["ok"] is True


def test_server_phase_at_tiny_size_fused_kernels_interpreted(
        meter, monkeypatch, capsys):
    """float32, so greedy decoding is exact: every served token must BE the
    plain forward's argmax (gap 0), through the fused path end to end."""
    monkeypatch.setenv("SXT_FUSED_INTERPRET", "1")
    out = chip_smoke.phase_server(
        meter, _tiny_llama(), model_name="tiny llama-style",
        prompt_lengths=[5, 40, 9, 70, 12, 33, 8, 50],
        arrivals=[0, 0, 1, 3, 4, 8, 9, 12], max_new=8,
        inference={"dtype": "float32", "max_seq_len": 128,
                   "kv_block_size": 8, "num_kv_blocks": 129,
                   "decode_kernel": "pallas",
                   "serving": {"token_budget": 32, "max_running": 8,
                               "chunk_min": 8}},
        reduced={"everything": "tiny() on the CPU"}, gap_tol_sigma=0.0)
    assert out["routes"] == {"decode_kernel": "pallas", "fused_qkv": True,
                             "fused_mlp": True, "kv_append": "scatter"}
    assert out["dispatches"] == out["ticks"] > 8
    assert out["reference"]["exact_argmax"] == out["reference"]["tokens"] == 64
    assert out["repeat_serve"] == {"programs_compiled": 0,
                                   "new_program_shapes": 0,
                                   "same_tokens": True,
                                   "run_s": out["repeat_serve"]["run_s"]}
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "server" and line["ok"] is True


def test_server_phase_fails_on_a_wrong_token(meter, monkeypatch):
    """The reference check has teeth: corrupt one served token and the phase
    must refuse (a check that cannot fail proves nothing on the chip)."""
    from shuffle_exchange_tpu.inference import ContinuousBatchingScheduler

    real = ContinuousBatchingScheduler.serve

    def corrupt(self, *a, **kw):
        out = real(self, *a, **kw)
        first = out[min(out)]
        first[3] = (first[3] + 1) % 256
        return out

    monkeypatch.setattr(ContinuousBatchingScheduler, "serve", corrupt)
    with pytest.raises(chip_smoke.SmokeFailure, match="logit-sigmas"):
        chip_smoke.phase_server(
            meter, _tiny_llama(), model_name="tiny llama-style",
            prompt_lengths=[5, 40], arrivals=[0, 1], max_new=8,
            inference={"dtype": "float32", "max_seq_len": 128,
                       "kv_block_size": 8, "num_kv_blocks": 65,
                       "serving": {"token_budget": 32, "max_running": 8,
                                   "chunk_min": 8}},
            reduced={}, gap_tol_sigma=0.0)


def test_sharded_phase_on_four_of_the_eight_virtual_devices(
        meter, monkeypatch, devices8, capsys):
    """The --chips 4 body. ``sxt.initialize`` builds its mesh from
    ``jax.devices()``; the test, not an option of the program, hands it
    four."""
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices8[:4])
    out = chip_smoke.phase_sharded(
        meter, _tiny_llama(), model_name="tiny llama-style", seq=64, batch=8,
        steps=4, reduced={"everything": "tiny() on the CPU"},
        # XLA's CPU backend leaves the gradient reduction an all-reduce +
        # slice; the TPU compiler forms the reduce-scatter the chip run needs
        need=("all-gather", "all-reduce"))
    assert out["mesh"] == {"fsdp": 4} and len(out["devices"]) == 4
    assert out["bytes_source"] == "state_shards"     # no memory_stats on CPU
    assert out["max_dev_from_mean"] <= 0.25
    assert out["collectives"]["all-gather"] > 0
    assert out["losses"][-1] < out["losses"][0]
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "sharded_trainer" and line["ok"] is True


def test_compile_cache_is_placed_from_outside(monkeypatch):
    """Env set: JAX reads it and the helper sets no directory in code.
    Unset: <checkout>/.cache/jax, whatever the working directory."""
    import jax

    from shuffle_exchange_tpu.utils import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.enable_compile_cache() == "/some/dir"
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.chdir("/")
    want = os.path.join(_REPO, ".cache", "jax")
    assert compile_cache.enable_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]


@pytest.mark.parametrize("entry", [
    "__graft_entry__.py", "chip_smoke.py", "tests/conftest.py",
    "chipbench/harness.py", "scripts/chaos_drill.py",
    "shuffle_exchange_tpu/serving/worker.py"])
def test_entry_points_leave_the_cache_directory_to_the_helper(entry):
    with open(os.path.join(_REPO, entry)) as f:
        src = f.read()
    assert "jax_compilation_cache_dir" not in src, \
        f"{entry} sets a compile-cache directory in code"
    assert "enable_compile_cache" in src, f"{entry} never enables the cache"


def test_script_refuses_to_pass_on_the_cpu():
    """Run as the driver runs it, on a machine with no chip: non-zero exit,
    no result line, nothing run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, os.path.join(_REPO, "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout and res.stdout.strip() == ""
    assert "no accelerator" in res.stderr
