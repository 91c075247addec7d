"""`chip_smoke.py` on the CPU: the phases are importable functions, so
tier-1 drives the same code at toy shapes (kernels declined — the CPU
never dispatches them) and pins the two contracts a chip-less machine
can check: the script refuses to run without a TPU, and the compile
cache lands where `JAX_COMPILATION_CACHE_DIR` says or at one fixed
in-checkout path."""
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import chip_smoke

REPO = Path(__file__).resolve().parents[1]
GPT = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2)
SERVE = dict(n_slots=4, max_len=96, page_size=8, prefill_chunk=16,
             n_short=3, short_len=8, long_len=40, n_tokens=8, int8_tokens=4)


def test_train_phase_toy():
    out = chip_smoke.phase_train(
        GPT, dict(T=32, batch=4, block=16, steps=3), kernels=False)
    assert len(out["losses"]) == 3 and out["losses"][2] < out["losses"][0]
    assert "flash_tile" not in out  # asserted only where kernels dispatch


def test_train_phase_demands_an_engaged_kernel_when_told_to():
    # on the CPU no flash tile can engage: with kernels=True the phase
    # must FAIL, not report a step that merely ran
    with pytest.raises(chip_smoke.SmokeFailure, match="no flash"):
        chip_smoke.phase_train(
            GPT, dict(T=32, batch=4, block=16, steps=2), kernels=True)


def test_lstm_phase_toy():
    out = chip_smoke.phase_lstm(
        dict(vocab=32, hidden=128, T=8, batch=16, steps=2), kernels=False)
    assert len(out["losses"]) == 2 and np.all(np.isfinite(out["losses"]))


def test_serve_phase_toy():
    out = chip_smoke.phase_serve(GPT, SERVE, kernels=False)
    assert out["requests"] == 4 and out["tokens"] == 4 * 8
    assert out["prefill_chunks"] >= 3  # the 40-token prompt, 16 at a time
    assert out["dispatches"]["decode_chunk"] >= 1
    # on the CPU both engines ARE the gather path: tokens identical
    assert out["agreement"]["common_prefix_tokens"] == [8] * 4
    assert out["agreement"]["tie_margins_nats"] == []
    json.dumps(out)  # the summary line must serialize


def test_multichip_phase_toy_on_the_virtual_mesh():
    gpt = dict(GPT, n_heads=4)  # tp=4 needs four heads
    train = dict(T=32, batch=4, block=16, steps=3)
    one_chip = chip_smoke.phase_train(gpt, train, kernels=False)
    out = chip_smoke.phase_multichip(gpt, train, dict(SERVE, max_len=128),
                                     one_chip["losses"][0])
    assert out["train"]["mesh"] == {"data": 2, "model": 2}
    assert out["train"]["losses"][0] == pytest.approx(
        one_chip["losses"][0], rel=1e-3)
    assert out["tp4"]["common_prefix_tokens"] == [4, 4, 4]
    # an in-process pool places nothing: four replicas, one device
    assert out["replica_devices"] == [[0]] * 4
    json.dumps(out)


def test_tie_margin_reads_the_nets_own_forward():
    net = chip_smoke._gpt_net(GPT, 128)  # the context pads to 128s
    prompt = np.arange(8, dtype=np.int32)
    a = np.asarray(net.output(prompt[None])).argmax(-1)[0, -1:]
    a = np.concatenate([a, [1, 2, 3]]).astype(np.int32)
    b = a.copy()
    b[0] = (a[0] + 1) % GPT["vocab_size"]  # disagree on the first token
    margin = chip_smoke._tie_margin(net, prompt, a, b)
    probs = np.asarray(net.output(prompt[None]), np.float64)[0, -1]
    assert margin == pytest.approx(
        abs(np.log(probs[a[0]]) - np.log(probs[b[0]])), rel=1e-3)
    assert margin > 0  # a is the argmax, b is not


def test_main_refuses_a_cpu_platform(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""  # no result line
    assert "no accelerator" in captured.err


class _FakeTpu:
    platform, device_kind, id = "tpu", "TPU v5 lite", 0

    def memory_stats(self):
        return {"bytes_in_use": 0}


def test_last_line_is_the_drivers_verdict(monkeypatch, capsys):
    """The driver reads the LAST stdout line and wants exactly
    {"ok", "device": {"platform", "kind", "count"}}; the rich summary
    (ending "claim": null) is the line before it."""
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpu()])
    monkeypatch.setattr(chip_smoke, "phase_lstm",
                        lambda shape, kernels: {"losses": [1.0]})
    saved = jax.config.jax_compilation_cache_dir
    try:
        assert chip_smoke.main(["lstm"]) == 0
        summary, verdict = capsys.readouterr().out.splitlines()[-2:]
        assert json.loads(verdict) == {"ok": True, "device": {
            "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
        assert summary.endswith('"claim": null}')
        assert json.loads(summary)["phases"]["lstm"]["ok"] is True

        def fails(shape, kernels):
            raise chip_smoke.SmokeFailure("wrong")

        monkeypatch.setattr(chip_smoke, "phase_lstm", fails)
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke.main(["lstm"])
        verdict = capsys.readouterr().out.splitlines()[-1]
        assert json.loads(verdict)["ok"] is False
        assert set(json.loads(verdict)) == {"ok", "device"}
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)


def test_script_fails_without_a_chip_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], cwd=str(REPO),
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no accelerator" in proc.stderr


def test_compile_cache_dir_rule(monkeypatch, tmp_path):
    import jax

    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache

    saved = jax.config.jax_compilation_cache_dir
    try:
        # unset: one fixed, git-ignored path inside the checkout
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        first, second = enable_compile_cache(), enable_compile_cache()
        assert first == second == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        ignored = (REPO / ".gitignore").read_text().split()
        assert ".jax_cache/" in ignored
        # set: that directory, and no other path set in code
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)


def test_bench_knows_its_peaks_and_refuses_the_cpu(monkeypatch):
    """A measuring entry point that finds no TPU fails; an MFU is only
    computed against a peak the table lists for the `device_kind`."""
    import bench

    assert bench._peak_flops("TPU v5 lite", bf16=True) == 197e12
    for kind in ("TPU v9 hypothetical", "cpu"):
        with pytest.raises(ValueError, match="no peak FLOP/s"):
            bench._peak_flops(kind, bf16=True)
    monkeypatch.setattr(sys, "argv", ["bench.py", "gpt"])
    with pytest.raises(SystemExit) as exit_info:
        bench.main()
    assert "found none" in str(exit_info.value)  # non-zero, names the gap


def test_supervisor_states_each_childs_platform(tmp_path, monkeypatch):
    """One process per chip: a replica child never inherits the parent's
    accelerator by accident — its platform is the CPU unless the
    caller's `env` names another."""
    from deeplearning4j_tpu.serving.remote_replica import ReplicaSupervisor

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")  # what a chip parent has
    sup = ReplicaSupervisor(tmp_path / "m.zip", 1, scratch_dir=tmp_path)
    try:
        assert sup.child_platform == "cpu"
        assert sup._env["JAX_PLATFORMS"] == "cpu"
    finally:
        sup.stop()
    sup = ReplicaSupervisor(tmp_path / "m.zip", 1, scratch_dir=tmp_path,
                            env={"JAX_PLATFORMS": "tpu"})
    try:
        assert sup.child_platform == "tpu"
    finally:
        sup.stop()
