"""`BENCHMARK.json` resolves, by name, to files that are there."""
import json
import subprocess
import sys

from perfbench.harness.manifest import ROOT, Manifest, check


def test_every_name_in_the_manifest_resolves():
    assert check(Manifest()) == []


def test_each_configuration_names_a_reference_beside_it():
    m = Manifest()
    for c in m.raw["configs"]:
        body = json.loads((ROOT / c["file"]).read_text())
        ref = ROOT / body["reference"]
        assert ref.parent == (ROOT / c["file"]).parent
        assert ref.name == f"{c['name']}_reference.py"
        for fn in ("logits_at", "train_steps", "block"):
            assert hasattr(m.reference(body), fn)


def test_a_reference_that_takes_from_the_program_is_named(tmp_path):
    from perfbench.harness.manifest import _names_the_program

    ref = ROOT / "perfbench/configs/cerebras-gpt-590m_reference.py"
    assert not _names_the_program(ROOT, ref)
    bad = tmp_path / "bad_reference.py"
    bad.write_text("from deeplearning4j_tpu.models import transformer\n")
    assert _names_the_program(ROOT, bad)


def test_the_check_names_what_is_missing(tmp_path):
    raw = json.loads((ROOT / "BENCHMARK.json").read_text())
    raw["workloads"][0]["traffic"] = "no-such-mix"
    raw["per_layer"][0]["moves"] = "no_such_metric"
    raw["per_layer"].append(dict(raw["per_layer"][1], name="bad name"))
    p = tmp_path / "BENCHMARK.json"
    p.write_text(json.dumps(raw))
    problems = "\n".join(check(Manifest(p, root=ROOT)))
    assert "no-such-mix" in problems and "no_such_metric" in problems
    assert "'bad name'" in problems


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    cmd = json.loads((ROOT / "BENCHMARK.json").read_text())["command"]
    w = Manifest().raw["workloads"][0]["name"]
    out = subprocess.run(
        [sys.executable, *cmd[1:], "--workload", w, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=ROOT, capture_output=True,
        text=True, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "only on a TPU" in out.stderr
