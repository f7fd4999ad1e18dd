"""BENCHMARK.json and the data files it names.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: a workload entry names its ``config`` and ``traffic``, and every file
is found from those names (``cells/<workload>.json``, where a cell has one,
from the workload's own).  ``lint`` holds the manifest to the limits the
driver checks before any run (names, units, four-chip share, files), and
a share of a peak to the cells its count of operations was written for.
"""
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def load_json(path):
    with open(path) as f:
        return json.load(f)


def init_seed(config):
    """The draw of the weights a configuration is measured at: its
    ``deployment.init_seed``, a whole number.  A run's ``--seed`` makes the
    traffic and nothing else, so a configuration without the key is a
    refusal to run."""
    seed = config.get("deployment", {}).get("init_seed")
    if isinstance(seed, bool) or not isinstance(seed, int):
        raise SystemExit(
            f"benchmark: configuration {config.get('name')} states no "
            f"deployment.init_seed (a whole number: the draw of its "
            f"weights); has {seed!r}")
    return seed


class Manifest:
    def __init__(self, root=ROOT):
        self.root = root
        self.data = load_json(os.path.join(root, "BENCHMARK.json"))
        # the benchmark's own directory in this checkout (the first of
        # ``paths``); data files are looked up under it by name
        self.bench_dir = os.path.join(root, self.data["paths"][0])

    def path(self, *parts):
        return os.path.join(self.bench_dir, *parts)

    def workload(self, name):
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise SystemExit(f"benchmark: no workload named {name!r} in "
                         f"BENCHMARK.json")

    def config(self, name):
        for c in self.data["configs"]:
            if c["name"] == name:
                return {**load_json(os.path.join(self.root, c["file"])),
                        "name": name}
        raise SystemExit(f"benchmark: no configuration named {name!r}")

    def traffic(self, name):
        return {**load_json(self.path("traffic", name + ".json")),
                "name": name}

    def cell(self, name):
        """(workload entry, its configuration, its traffic mix).  The
        configuration's ``checks`` are what every cell of it is held to;
        ``cells/<workload>.json``, where there is one, replaces single
        keys of them for that pair of configuration and traffic."""
        cell = self.workload(name)
        config = self.config(cell["config"])
        own = self.path("cells", name + ".json")
        if os.path.isfile(own):
            config["checks"] = {**config["checks"], **load_json(own)}
        return cell, config, self.traffic(cell["traffic"])

    def metrics(self, group, workload):
        """The entries of ``end_to_end`` or ``per_layer`` that this cell
        reports: those with no ``workloads`` key, or that list it."""
        return [m for m in self.data[group]
                if workload in m.get("workloads", [workload])]

    def layer_metric(self, name):
        return load_json(self.path("layer_metrics", name + ".json"))


def lint(manifest):
    """Every rule of the contract that can be checked without a chip;
    returns a list of complaints (empty = clean)."""
    d, bad = manifest.data, []

    def name_ok(s, what):
        if not (isinstance(s, str) and NAME.match(s)):
            bad.append(f"{what}: bad name {s!r}")

    def line_ok(s, what):
        if not (isinstance(s, str) and 1 <= len(s) <= 200
                and "\n" not in s and "\t" not in s):
            bad.append(f"{what}: not 1..200 characters on one line")

    if set(d) != TOP_KEYS:
        bad.append(f"top-level keys {sorted(d)} != {sorted(TOP_KEYS)}")
    if not (1 <= len(d["paths"]) <= 16):
        bad.append("paths: 1 to 16 directories")
    for w in d["command"]:
        line_ok(w, "command")
        if w.startswith("/") or ".." in w.split("/"):
            bad.append(f"command word {w!r} leaves the repo")
    if not (isinstance(d["run_seconds"], int) and 1 <= d["run_seconds"] <= 51):
        bad.append("run_seconds: whole number 1..51")

    # own_count: the configurations that compute their mfu_pct with a
    # function of required_ops/ ("flops": {"train": "<file>:<function>"})
    files, own_count = set(), {}
    for c in d["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c.get('name')}: keys {sorted(c)}")
        name_ok(c["name"], "config")
        line_ok(c["source"], f"config {c['name']} source")
        line_ok(c["why"], f"config {c['name']} why")
        for k in c["reduced"]:
            name_ok(k, f"config {c['name']} reduced")
        if len(c["reduced"]) > 16:
            bad.append(f"config {c['name']}: more than 16 reduced keys")
        if not any(c["file"].startswith(p + "/") for p in d["paths"]):
            bad.append(f"config {c['name']}: file outside paths")
        if c["file"] in files:
            bad.append(f"config file {c['file']} used twice")
        files.add(c["file"])
        if not os.path.isfile(os.path.join(manifest.root, c["file"])):
            bad.append(f"config {c['name']}: {c['file']} missing")
            continue
        config = manifest.config(c["name"])
        try:
            init_seed(config)
        except SystemExit as refusal:
            bad.append(str(refusal))
        train = config.get("flops", {}).get("train", "")
        if ":" in train:
            own_count[c["name"]] = train

    configs = [c["name"] for c in d["configs"]]
    cells = d["workloads"]
    if not (2 <= len(cells) <= 24):
        bad.append("workloads: 2 to 24 cells")
    for w in cells:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload {w.get('name')}: keys {sorted(w)}")
        for k in ("name", "config", "traffic"):
            name_ok(w[k], f"workload {k}")
        line_ok(w["why"], f"workload {w['name']} why")
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips {w['chips']}")
        if w["config"] not in configs:
            bad.append(f"workload {w['name']}: unknown config")
        if not os.path.isfile(manifest.path("traffic",
                                            w["traffic"] + ".json")):
            bad.append(f"workload {w['name']}: traffic file missing")
    pairs = [(w["config"], w["traffic"]) for w in cells]
    if len(set(pairs)) != len(pairs):
        bad.append("a (config, traffic) pair appears twice")
    for c in configs:
        if c not in [w["config"] for w in cells]:
            bad.append(f"config {c}: used by no cell")
    four = sum(w["chips"] == 4 for w in cells)
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} four-chip cells of {len(cells)}: over 25%")

    cell_names = [w["name"] for w in cells]
    names = configs + cell_names
    e2e = [m["name"] for m in d["end_to_end"]]
    for group in ("end_to_end", "per_layer"):
        keys = {"name", "unit", "better", "source"} | (
            {"bound"} if group == "end_to_end" else {"layer", "moves"})
        for m in d[group]:
            if set(m) - {"workloads"} != keys:
                bad.append(f"{group} {m.get('name')}: keys {sorted(m)}")
            name_ok(m["name"], group)
            names.append(m["name"])
            if not UNIT.match(m["unit"]):
                bad.append(f"{m['name']}: bad unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"{m['name']}: better={m['better']!r}")
            if m["source"] not in SOURCES:
                bad.append(f"{m['name']}: source={m['source']!r}")
            for w in m.get("workloads", []):
                if w not in cell_names:
                    bad.append(f"{m['name']}: unknown workload {w}")
            if group == "end_to_end":
                if m["source"] not in ("host_clock", "device_trace"):
                    bad.append(f"{m['name']}: end-to-end source")
                if not (0 < m["bound"] <= 0.1):
                    bad.append(f"{m['name']}: bound {m['bound']}")
            else:
                line_ok(m["layer"], f"{m['name']} layer")
                if m["moves"] not in e2e:
                    bad.append(f"{m['name']}: moves unknown metric")
                if not os.path.isfile(manifest.path(
                        "layer_metrics", m["name"] + ".json")):
                    bad.append(f"{m['name']}: layer_metrics file missing")
                    continue
                if ("roofline" in m["name"] or "mfu" in m["name"]) \
                        and not m.get("workloads"):
                    bad.append(f"{m['name']}: no workloads list: a share "
                               f"of a peak is counted for named cells")
                counted_by = manifest.layer_metric(m["name"])["params"].get(
                    "flops")
                if counted_by is None or ":" in counted_by:
                    continue        # no count, or a family's own
                for w in cells:
                    own = own_count.get(w["config"])
                    if own and w["name"] in m.get("workloads", cell_names):
                        bad.append(
                            f"{m['name']}: {counted_by} of harness/flops.py "
                            f"is listed for {w['name']}, whose configuration "
                            f"counts its step with {own}: a family that "
                            f"needed its own count for the step needs it "
                            f"for the kernel too")
    if "setup_s" not in e2e:
        bad.append("end_to_end lacks setup_s")
    if len(set(names)) != len(names):
        bad.append("a name is used twice")
    for w in cell_names:
        if "setup_s" not in [m["name"]
                             for m in manifest.metrics("end_to_end", w)]:
            bad.append(f"cell {w} does not report setup_s")
        if len(manifest.metrics("end_to_end", w)) < 2:
            bad.append(f"cell {w}: needs a second end-to-end metric")
        if not manifest.metrics("per_layer", w):
            bad.append(f"cell {w}: no per-layer metric")
    size = os.path.getsize(os.path.join(manifest.root, "BENCHMARK.json"))
    if size > 64 * 1024:
        bad.append("BENCHMARK.json over 64 KiB")
    return bad
