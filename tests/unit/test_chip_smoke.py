"""chip_smoke.py rehearsed without the chip: its phase functions at a tiny
GPT-2 on the CPU mesh (kernels in interpret mode), the --chips 4 legs on
four of the virtual devices, and the script as a command refusing to pass
on the CPU backend."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from deepspeed_tpu.models.gpt2 import GPT2Config  # noqa: E402


def _tiny(**kw):
    base = dict(vocab_size=97, n_positions=64, n_embd=32, n_layer=2,
                n_head=2, dtype=jnp.float32)
    base.update(kw)
    return GPT2Config(**base)


SERVE_TINY = dict(n_requests=4, prompt_range=(5, 20), new_tokens=4,
                  kv_block_size=4, prefill_chunk=8)


def test_kernels_phase_interpret_mode():
    out = chip_smoke.phase_kernels(
        causal_shape=(1, 1, 256, 64), bias_shape=(2, 2, 128, 64),
        sparse_shape=(2, 2, 256, 64), paged_shape=(4, 2, 8, 4, 8),
        mhc_shape=(40, 4, 32),
        window_shapes=((10, 2, 200, 384, 16, 8, 64, 70),
                       (8, 1, 136, 256, 16, 0, 96, 0)))
    assert set(out["rel_err"]) == {
        "window_prefill_w64", "window_prefill_w96",
        "mhc_mixes", "paged_decode_attn", "flash_causal", "flash_dropout",
        "flash_key_bias", "block_sparse", "block_sparse_key_bias"}


def test_train_phase_tiny_gpt2():
    out = chip_smoke.phase_train(
        cfg=_tiny(remat=True, scan_layers=True, loss_chunk_tokens=64),
        micro_batch=2, seq=32, steps=5, expect_flash=False)
    assert len(out["losses"]) == 5 and out["losses"][-1] < out["losses"][0]
    assert out["compilations_after_first_step"] == 0
    # the CPU backend takes the jnp attention path: no kernel in the HLO
    assert out["tpu_custom_calls_in_fused_step"] == 0


def test_train_phase_fails_without_flash_in_hlo():
    with pytest.raises(chip_smoke.SmokeFailure, match="tpu_custom_call"):
        chip_smoke.phase_train(cfg=_tiny(scan_layers=True), micro_batch=2,
                               seq=32, steps=3)


def test_serve_phase_tiny_gpt2():
    out = chip_smoke.phase_serve(cfg=_tiny(scan_layers=True), **SERVE_TINY)
    assert out["finished"] == 4 and out["compilations_after_warmup"] == 0
    agreement = out["tokens_vs_generate"]
    assert agreement["identical_to_reference"] == agreement["of"] == 4
    assert agreement["first_divergences"] == []
    assert agreement["worst_steps_below_best"] == 0.0


def test_a_wrong_served_token_fails_the_agreement_check():
    model, params, prompts = chip_smoke._serve_setup(
        0, _tiny(scan_layers=True), 2, (5, 9))
    good = chip_smoke._reference_tokens(model, params, prompts, 4)
    chip_smoke._token_agreement(model, params, good, good, prompts)
    bad = [g.copy() for g in good]
    bad[1][-2] = (bad[1][-2] + 1) % 97
    # neither a tie with the reference's token nor near the row's best
    with pytest.raises(chip_smoke.SmokeFailure) as err:
        chip_smoke._token_agreement(model, params, bad, good, prompts)
    assert "request 1: first differs from the reference at token " \
        f"{len(bad[1]) - 2}" in str(err.value)
    assert "request 1: served token" in str(err.value)
    assert "request 0" not in str(err.value)


@pytest.mark.parametrize("logit, step", [
    (1.0, 2.0 ** -7), (2.5, 2.0 ** -6), (-3.99, 2.0 ** -6), (4.0, 2.0 ** -5)])
def test_bf16_step_is_the_spacing_of_the_format(logit, step):
    assert chip_smoke._bf16_step(logit) == step
    x = jnp.asarray(logit, jnp.bfloat16)
    assert float(jnp.nextafter(x, jnp.asarray(jnp.inf, jnp.bfloat16))
                 - x) == step


def test_leg_zero2_data4_on_virtual_devices():
    out = chip_smoke.leg_zero2_data4(
        cfg=_tiny(n_embd=64, remat=True, scan_layers=True,
                  loss_chunk_tokens=64), micro_batch=2, seq=32)
    place = out["data4"]["shard_bytes_by_device"]
    assert len(place["optimizer_state"]) == 4
    assert len(place["grad_accumulator"]) == 4


def test_leg_pipeline_on_virtual_devices(monkeypatch):
    """At shapes the flash kernel takes and with the default backend
    answering 'tpu', as on the chip: PipelineEngine initialises its
    parameters through a forward on the host CPU, which must get the jnp
    attention path — the first four-chip run died there lowering the
    compiled kernel for the CPU."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    out = chip_smoke.leg_pipeline(
        cfg=_tiny(n_positions=128, n_embd=128, loss_chunk_tokens=0),
        micro_batch=2, seq=128, expect_flash=False)
    assert out["stage_devices"] == [[0, 1], [2, 3]]
    assert out["tpu_custom_calls_in_stage0_forward"] == 0


def test_leg_serve_shards_on_virtual_devices():
    out = chip_smoke.leg_serve_shards(cfg=_tiny(scan_layers=True),
                                      **SERVE_TINY)
    assert out["tokens_shards4_vs_shards1"]["identical_to_reference"] == 4
    assert len(out["pool_k_bytes_by_device"]["4"]) == 4
    # finding 6: both replicas on the default device
    assert all(r["params_devices"] == ["0"] and r["pool_devices"] == ["0"]
               for r in out["fleet_replica_placement"])


def test_command_refuses_the_cpu_backend():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 1
    last = json.loads(lines[-1])
    assert last["phase"] == "device" and last["ok"] is False
    assert "'cpu'" in last["error"]


@pytest.mark.parametrize("env_dir", [None, "/some/dir"])
def test_compile_cache_dir(env_dir, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: JAX's own setting, nothing done in
    code.  Unset: <checkout>/.jax_cache, a fixed path.  The tests keep the
    cache off, so the setting is put back."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = enable_compile_cache()
        if env_dir:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert got == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


def test_disable_persistent_compile_cache(tmp_path):
    """Off and loud when the cache is on; nothing to do when it is not."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from deepspeed_tpu.utils.compile_cache import \
        disable_persistent_compile_cache

    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_enable_compilation_cache)
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        assert disable_persistent_compile_cache("no cache set") is False
        assert jax.config.jax_enable_compilation_cache is before[1]
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_enable_compilation_cache", True)
        assert disable_persistent_compile_cache("a test") is True
        assert jax.config.jax_enable_compilation_cache is False
        assert disable_persistent_compile_cache("twice") is False
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_enable_compilation_cache", before[1])
        compilation_cache.reset_cache()


@pytest.mark.parametrize("stages, halts", [
    ([("tpu", 2), ("tpu", 2)], True),     # pipe 2 x model 2: chips 2 and 3
    ([("tpu", 4)], False),                # one stage holds device 0
    ([("tpu", 1)] * 4, False),            # one-chip stages ran from the cache
    ([("cpu", 2), ("cpu", 2)], False),    # the defect is the TPU runtime's
])
def test_which_pipeline_stages_cannot_come_from_the_cache(stages, halts):
    from types import SimpleNamespace as NS

    import numpy as np

    from deepspeed_tpu.runtime.pipe.engine import _cached_stage_programs_halt

    meshes = [NS(size=n, devices=np.array([NS(platform=p)] * n, object))
              for p, n in stages]
    assert _cached_stage_programs_halt(meshes) is halts
