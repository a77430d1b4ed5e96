"""`BENCHMARK.json` and the files its names lead to.

The harness holds no table of cells, configurations or metrics: a name
in the manifest leads to a file by the rules below, so a later PR adds a
cell by adding files and manifest entries and edits nothing here.

    configs[].file                      the configuration, as it is run;
                                        its ``family`` names the
                                        architecture
    chipbench/families/<family>.py      shape, reference, work counts,
                                        model objects, rehearsal widths
    chipbench/traffic/<traffic>.json    the traffic mix and the deployment
                                        it is offered to (`runner`,
                                        `generator`, `chips`, sizes)
    chipbench/generators/<name>.py      the generator a traffic file names
    chipbench/runners/<name>.py         the runner a traffic file names
    chipbench/layer_metrics/<metric>.py one reader per per-layer metric

`validate` checks the manifest against the driver's published rules
(names, units, layers, sources, the keys each entry may have), so the
refusal PR 22 met is a failing test and not a lost PR.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from typing import List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
ENTRY_KEYS = {
    "configs": ({"name", "source", "file", "reduced", "why"}, set()),
    "workloads": ({"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": ({"name", "unit", "better", "bound", "source"},
                   {"workloads"}),
    "per_layer": ({"name", "unit", "better", "source", "layer", "moves"},
                  {"workloads"}),
}
#: keys `reduced` may never name: widths
_WIDTH = re.compile(
    r"(hidden_size|intermediate_size|latent|state_size|d_state|proj|_dim$|"
    r"_rank$|head_size|expansion|experts_per_tok|^n_embd$|^n_inner$)")


class ManifestError(ValueError):
    pass


def load(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        return json.load(f)


def _line(text, what: str, errors: List[str]) -> None:
    if not (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text):
        errors.append(f"{what}: 1 to 200 characters on one line, no tab")


def validate(manifest: dict, root: str = ROOT) -> List[str]:
    """Every breach of the driver's rules found, as sentences; an empty
    list means the manifest would not be refused on form."""
    errors: List[str] = []
    if set(manifest) != TOP_KEYS:
        errors.append(f"top-level keys {sorted(manifest)} != "
                      f"{sorted(TOP_KEYS)}")
        return errors
    if len(json.dumps(manifest)) > 64 * 1024:
        errors.append("manifest larger than 64 KiB")
    cmd = manifest["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32):
        errors.append("command: a list of 1 to 32 strings")
    for word in cmd:
        _line(word, f"command word {word!r}", errors)
        if word.startswith("/") or ".." in word.split("/"):
            errors.append(f"command word {word!r} leads out of the repo")
    paths = manifest["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        errors.append("paths: 1 to 16 directories")
    for p in paths:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            errors.append(f"path {p!r}: relative, letters digits _ . - /")
    rs = manifest["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        errors.append("run_seconds: a whole number from 1 to 51")

    for section, (required, optional) in ENTRY_KEYS.items():
        entries = manifest[section]
        seen = set()
        for e in entries:
            keys = set(e)
            if not (required <= keys <= required | optional):
                errors.append(f"{section} entry {e.get('name')!r}: keys "
                              f"{sorted(keys)}")
                continue
            if not NAME.match(str(e["name"])):
                errors.append(f"{section} name {e['name']!r} breaks the "
                              f"name rule")
            if e["name"] in seen:
                errors.append(f"{section} name {e['name']!r} twice")
            seen.add(e["name"])

    def under_paths(file: str) -> bool:
        return any(file == p or file.startswith(p.rstrip("/") + "/")
                   for p in paths)

    configs = {c["name"]: c for c in manifest["configs"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if not 1 <= len(configs) <= 24:
        errors.append("configs: 1 to 24")
    if not 1 <= len(cells) <= 24:
        errors.append("workloads: 1 to 24")
    if not 1 <= len(e2e) <= 16:
        errors.append("end_to_end: 1 to 16")
    if not 1 <= len(manifest["per_layer"]) <= 128:
        errors.append("per_layer: 1 to 128")

    files = set()
    for c in manifest["configs"]:
        _line(c.get("source"), f"config {c['name']} source", errors)
        _line(c.get("why"), f"config {c['name']} why", errors)
        f = c.get("file", "")
        if not (PATH.match(f) and under_paths(f)):
            errors.append(f"config {c['name']}: file {f!r} not under paths")
        if f in files:
            errors.append(f"config file {f!r} used twice")
        files.add(f)
        if not os.path.isfile(os.path.join(root, f)):
            errors.append(f"config {c['name']}: file {f!r} missing")
        else:
            try:
                family = family_file(load_json(f, root), c["name"])
                if not os.path.isfile(os.path.join(root, family)):
                    errors.append(f"config {c['name']}: no family module "
                                  f"{family}")
            except ManifestError as e:
                errors.append(str(e))
        red = c.get("reduced", [])
        if not (isinstance(red, list) and len(red) <= 16):
            errors.append(f"config {c['name']}: reduced has at most 16 keys")
        for key in red:
            if not NAME.match(str(key)):
                errors.append(f"config {c['name']}: reduced key {key!r}")
            if _WIDTH.search(str(key)):
                errors.append(f"config {c['name']}: reduced names the "
                              f"width {key!r}")
        if c["name"] not in {w["config"] for w in manifest["workloads"]}:
            errors.append(f"config {c['name']} is used by no cell")

    pairs = set()
    for w in manifest["workloads"]:
        if w["config"] not in configs:
            errors.append(f"cell {w['name']}: unknown config {w['config']!r}")
        if not NAME.match(str(w["traffic"])):
            errors.append(f"cell {w['name']}: traffic {w['traffic']!r}")
        if w["chips"] not in (1, 4):
            errors.append(f"cell {w['name']}: chips must be 1 or 4")
        _line(w.get("why"), f"cell {w['name']} why", errors)
        pair = (w["config"], w["traffic"])
        if pair in pairs:
            errors.append(f"cell pair {pair} twice")
        pairs.add(pair)
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        errors.append(f"{four} four-chip cells of {len(cells)}")

    def listed_cells(metric: dict, what: str) -> List[str]:
        names = metric.get("workloads")
        if names is None:
            return list(cells)
        for n in names:
            if n not in cells:
                errors.append(f"{what} {metric['name']}: unknown cell {n!r}")
        return [n for n in names if n in cells]

    for m in manifest["end_to_end"] + manifest["per_layer"]:
        what = "metric"
        if not UNIT.match(str(m.get("unit", ""))):
            errors.append(f"{what} {m['name']}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            errors.append(f"{what} {m['name']}: better {m.get('better')!r}")
        if m.get("source") not in SOURCES:
            errors.append(f"{what} {m['name']}: source {m.get('source')!r}")
    if "setup_s" not in e2e:
        errors.append("end_to_end lacks setup_s")
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            errors.append(f"end-to-end {m['name']}: source {m['source']!r}")
        b = m.get("bound")
        if not (isinstance(b, (int, float)) and 0.01 <= b <= 0.1):
            errors.append(f"end-to-end {m['name']}: bound {b!r}")
        listed_cells(m, "end-to-end")
    names = set(e2e)
    for m in manifest["per_layer"]:
        if m["name"] in names:
            errors.append(f"metric name {m['name']!r} twice")
        names.add(m["name"])
        # the driver's words at PR 22: "layer must be 1 to 64 characters
        # from letters, digits, '_', '.' and '-', starting with a letter,
        # digit or '_'"
        if not NAME.match(str(m["layer"])):
            errors.append(f"per-layer {m['name']}: layer {m['layer']!r} "
                          f"must be one token")
        moved = e2e.get(m["moves"])
        if moved is None:
            errors.append(f"per-layer {m['name']}: moves {m['moves']!r}")
            continue
        reporting = set(listed_cells(moved, "end-to-end"))
        for cell in listed_cells(m, "per-layer"):
            if cell not in reporting:
                errors.append(f"per-layer {m['name']}: cell {cell} does "
                              f"not report {m['moves']}")
        if m["name"].endswith("_roofline") and m["unit"] != "%":
            errors.append(f"per-layer {m['name']}: a roofline is in %")
        reader = os.path.join(root, layer_metric_file(m["name"]))
        if not os.path.isfile(reader):
            errors.append(f"per-layer {m['name']}: no reader "
                          f"{layer_metric_file(m['name'])}")

    for cell in cells.values():
        mine_e2e = [m for m in manifest["end_to_end"]
                    if cell["name"] in (m.get("workloads") or cells)]
        mine_pl = [m for m in manifest["per_layer"]
                   if cell["name"] in (m.get("workloads") or cells)]
        if len(mine_e2e) < 2 or not any(m["name"] == "setup_s"
                                        for m in mine_e2e):
            errors.append(f"cell {cell['name']}: needs setup_s and one "
                          f"more end-to-end metric")
        if not mine_pl:
            errors.append(f"cell {cell['name']}: no per-layer metric")
        tf = os.path.join(root, traffic_file(cell["traffic"]))
        if not os.path.isfile(tf):
            errors.append(f"cell {cell['name']}: no traffic file "
                          f"{traffic_file(cell['traffic'])}")
    return errors


# ---------------------------------------------------------------------------
# names -> files
# ---------------------------------------------------------------------------

def traffic_file(traffic: str) -> str:
    return f"chipbench/traffic/{traffic}.json"


def family_file(config: dict, config_name: str) -> str:
    """The family module a configuration file's ``family`` names."""
    name = config.get("family")
    if not (isinstance(name, str) and NAME.match(name)):
        raise ManifestError(
            f"configuration {config_name}: its file names no family "
            f"(\"family\": \"<name>\" -> chipbench/families/<name>.py)")
    return f"chipbench/families/{name}.py"


def layer_metric_file(metric: str) -> str:
    return f"chipbench/layer_metrics/{metric}.py"


def load_json(rel: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def load_module(rel: str, root: str = ROOT):
    """Import a file by path (metric names hold dots, so not by name);
    one module object per file, however often it is asked for."""
    path = os.path.abspath(os.path.join(root, rel))
    if not os.path.isfile(path):
        raise ManifestError(f"{rel}: no such file")
    name = "chipbench_file_" + re.sub(r"\W", "_", path)
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        try:
            spec.loader.exec_module(module)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


class Cell:
    """One entry of `workloads`, with everything its names lead to."""

    def __init__(self, manifest: dict, name: str, root: str = ROOT):
        by_name = {w["name"]: w for w in manifest["workloads"]}
        if name not in by_name:
            raise ManifestError(
                f"no cell {name!r}; BENCHMARK.json has {sorted(by_name)}")
        self.root = root
        self.entry = by_name[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg = next(c for c in manifest["configs"]
                   if c["name"] == self.entry["config"])
        self.config_name = cfg["name"]
        self.config = load_json(cfg["file"], root)
        self.traffic_name = self.entry["traffic"]
        self.traffic = load_json(traffic_file(self.traffic_name), root)
        if int(self.traffic.get("chips", self.chips)) != self.chips:
            raise ManifestError(
                f"cell {name}: manifest says {self.chips} chips, traffic "
                f"file {self.traffic.get('chips')}")

        def mine(section):
            return [m for m in manifest[section]
                    if "workloads" not in m or name in m["workloads"]]
        self.end_to_end = mine("end_to_end")
        self.per_layer = mine("per_layer")

    def limits(self, rehearse: bool = False) -> dict:
        """The limit of each number this cell's check compares, from
        ``chipbench/limits/<cell>.json`` (its ``rehearse`` group where
        the tiny CPU sizes need other limits)."""
        data = load_json(f"chipbench/limits/{self.name}.json", self.root)
        out = dict(data["limits"])
        if rehearse:
            out.update(data.get("rehearse", {}))
        return out

    def family(self):
        """The module that holds whatever depends on the configuration's
        architecture (``"family"`` in the configuration file)."""
        return load_module(family_file(self.config, self.config_name),
                           self.root)

    def runner(self):
        return load_module(f"chipbench/runners/{self.traffic['runner']}.py",
                           self.root)

    def generator(self):
        return load_module(
            f"chipbench/generators/{self.traffic['generator']}.py",
            self.root)

    def reader(self, metric: str):
        return load_module(layer_metric_file(metric), self.root)
