"""`BENCHMARK.json` and the files it names.

Everything that belongs to one configuration, one traffic mix, one cell or
one metric is a file of its own, found by its name:

    configuration <c>  ->  the `file` its entry gives, the reference it names
    traffic mix <t>    ->  <path>/traffic/<t>.json, whose `kind` <k> names
                           <path>/runners/<k>.py   (a `run(ctx)` function)
    cell <w>           ->  <path>/cells/<w>.json   (limits of its comparison)
    metric <m>         ->  <path>/metrics/<m>.py   (a `read(run)` function)

where <path> is any directory under `paths`. A later PR adds a model, a
mix, a cell or a metric by adding such files and the entries that name
them; nothing here lists them.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


class ManifestError(Exception):
    """`BENCHMARK.json` names something that is not there."""


class Manifest:
    def __init__(self, path: Path = ROOT / "BENCHMARK.json",
                 root: Path = None):
        self.path = Path(path)
        self.root = Path(root) if root else self.path.resolve().parent
        self.raw = json.loads(self.path.read_text())
        self.dirs = [self.root / p for p in self.raw["paths"]]

    def _find(self, rel: str) -> Path:
        for d in self.dirs:
            if (d / rel).is_file():
                return d / rel
        raise ManifestError(f"{rel} is under none of {self.raw['paths']}")

    def _json(self, rel: str) -> dict:
        return json.loads(self._find(rel).read_text())

    def workload(self, name: str) -> dict:
        for w in self.raw["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(f"no workload {name!r} in {self.path.name}")

    def config(self, name: str) -> dict:
        for c in self.raw["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise ManifestError(f"no configuration {name!r}")

    def traffic(self, name: str) -> dict:
        return self._json(f"traffic/{name}.json")

    def cell(self, name: str) -> dict:
        return self._json(f"cells/{name}.json")

    def module(self, path: Path):
        spec = importlib.util.spec_from_file_location(
            "perfbench_file_" + re.sub(r"\W", "_", path.stem), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def reader(self, metric: str):
        return self.module(self._find(f"metrics/{metric}.py")).read

    def runner(self, kind: str):
        return self.module(self._find(f"runners/{kind}.py")).run

    def reference(self, config: dict):
        return self.module(self.root / config["reference"])

    def family(self, config: dict):
        return self.module(self._find_family(config["family"]))

    def _find_family(self, family: str) -> Path:
        return self._find(f"families/{family}.py")

    def metrics_of(self, workload: str, section: str) -> list:
        """Entries of `end_to_end` or `per_layer` that this cell reports:
        those that list it, and those that list no cells and move (or
        are) a metric the cell reports."""
        e2e = [m for m in self.raw["end_to_end"]
               if workload in m.get("workloads", [workload])]
        if section == "end_to_end":
            return e2e
        mine = {m["name"] for m in e2e}
        return [m for m in self.raw["per_layer"]
                if (workload in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]


def _names_the_program(root: Path, ref: Path) -> bool:
    """Whether a reference file, or a file of the benchmark that it takes
    its functions from, mentions the program under test."""
    text = ref.read_text()
    for mod in re.findall(r"^from (perfbench[\w.]*) import", text, re.M):
        text += (root / (mod.replace(".", "/") + ".py")).read_text()
    return "deeplearning4j_tpu" in text


def check(manifest: Manifest) -> list:
    """What is wrong with the manifest, as sentences; empty when every
    name resolves and every rule the harness relies on holds."""
    raw, bad = manifest.raw, []

    def name_ok(kind, n):
        if not NAME.match(str(n)):
            bad.append(f"{kind} name {n!r} uses other characters than "
                       "letters, digits, '_', '.', '-'")

    configs = {c["name"] for c in raw["configs"]}
    for c in raw["configs"]:
        name_ok("configuration", c["name"])
        f = manifest.root / c["file"]
        if not f.is_file():
            bad.append(f"configuration {c['name']}: no file {c['file']}")
            continue
        body = json.loads(f.read_text())
        if body.get("source") != c["source"]:
            bad.append(f"configuration {c['name']}: its file gives another "
                       "source than its entry")
        ref = body.get("reference", "")
        if not ref or not (manifest.root / ref).is_file():
            bad.append(f"configuration {c['name']}: declares no reference "
                       "file that exists")
        elif (manifest.root / ref).parent != f.parent \
                or not Path(ref).name.startswith(f.stem):
            bad.append(f"configuration {c['name']}: its reference {ref} "
                       "is not beside its file, under its name")
        elif _names_the_program(manifest.root, manifest.root / ref):
            bad.append(f"configuration {c['name']}: its reference names "
                       "the program")
        try:
            manifest._find_family(body.get("family", ""))
        except ManifestError as e:
            bad.append(f"configuration {c['name']}: {e}")
        for k in c["reduced"]:
            name_ok("reduced key", k)
    used, cells = set(), {}
    for w in raw["workloads"]:
        name_ok("workload", w["name"])
        name_ok("traffic", w["traffic"])
        if w["config"] not in configs:
            bad.append(f"workload {w['name']}: no configuration "
                       f"{w['config']!r}")
        used.add(w["config"])
        for what, get in (
                ("traffic", lambda: manifest.traffic(w["traffic"])),
                ("runner", lambda: manifest.runner(
                    manifest.traffic(w["traffic"])["kind"])),
                ("cell", lambda: manifest.cell(w["name"]))):
            try:
                get()
            except ManifestError as e:
                bad.append(f"workload {w['name']}: {what}: {e}")
        cells[w["name"]] = {m["name"] for m in
                            manifest.metrics_of(w["name"], "end_to_end")}
    for c in configs - used:
        bad.append(f"configuration {c} is used by no workload")
    e2e = {m["name"] for m in raw["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("no end-to-end metric setup_s")
    for section in ("end_to_end", "per_layer"):
        for m in raw[section]:
            name_ok("metric", m["name"])
            if not UNIT.match(m["unit"]):
                bad.append(f"metric {m['name']}: unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"metric {m['name']}: better {m['better']!r}")
            if m["source"] not in SOURCES or (
                    section == "end_to_end"
                    and m["source"] not in ("host_clock", "device_trace")):
                bad.append(f"metric {m['name']}: source {m['source']!r}")
            for w in m.get("workloads", []):
                if w not in cells:
                    bad.append(f"metric {m['name']}: no workload {w!r}")
            try:
                manifest.reader(m["name"])
            except (ManifestError, AttributeError) as e:
                bad.append(f"metric {m['name']}: no reader: {e}")
    for m in raw["per_layer"]:
        if m["moves"] not in e2e:
            bad.append(f"metric {m['name']} moves {m['moves']!r}, which is "
                       "no end-to-end metric")
        for w in m.get("workloads", []):
            if w in cells and m["moves"] not in cells[w]:
                bad.append(f"metric {m['name']}: workload {w} does not "
                           f"report {m['moves']}")
    for w, reported in cells.items():
        if "setup_s" not in reported or len(reported) < 2:
            bad.append(f"workload {w} reports {sorted(reported)}: it needs "
                       "setup_s and one more end-to-end metric")
        if not manifest.metrics_of(w, "per_layer"):
            bad.append(f"workload {w} reports no per-layer metric")
    return bad
