"""Cross-repo string-registry inventory (DSL004's substrate and the
generator behind ``docs/reference/registries.md``).

The tree is full of string-keyed registries that drift silently when a
PR adds a use without a declaration (or deletes the last use and leaves
the declaration): fault-injection sites, ``DS_*`` env vars, dotted
``serving.*``/``telemetry.*``/``resilience.*`` config keys, metric
names, flight-recorder event kinds.  This module AST-scans the repo
(``deepspeed_tpu/``, ``scripts/``, ``bin/``) and collects every *use*
with its source location, and parses the *declaration* side:

- fault sites:     ``resilience/faults.py`` ``KNOWN_FAULT_SITES``
- flight kinds:    ``telemetry/flight_recorder.py`` ``KNOWN_EVENT_KINDS``
- config keys:     the pydantic-style models in ``runtime/config.py``
- env vars + metrics: the curated tables in ``registry_docs.py``

Everything is pure-AST — nothing from the repo is imported, so a
syntax-valid tree lints in milliseconds with no jax in sight.
"""
import ast
import os
import re

from .astutil import dotted as _dotted
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

#: roots scanned for USES (declarations have fixed paths)
SCAN_ROOTS = ("deepspeed_tpu", "scripts", "bin")

FAULTS_PATH = "deepspeed_tpu/resilience/faults.py"
FLIGHTREC_PATH = "deepspeed_tpu/telemetry/flight_recorder.py"
CONFIG_PATH = "deepspeed_tpu/runtime/config.py"
REGISTRIES_MD = "docs/reference/registries.md"

#: config section -> model class in runtime/config.py
SECTION_MODELS = {
    "serving": "ServingConfig",
    "telemetry": "TelemetryConfig",
    "resilience": "ResilienceConfig",
}

#: nested sub-config fields -> their model class.  ``dict_of`` entries
#: take one arbitrary segment (the user-chosen class name) before the
#: model's own fields apply (``serving.slo.classes.<name>.ttft_ms``).
SUBMODELS = {
    "serving.spec": "SpecDecodeConfig",
    "serving.prefix_cache": "PrefixCacheConfig",
    "serving.slo": "SLOConfig",
    "serving.chunked_prefill": "ChunkedPrefillConfig",
    "serving.fleet": "FleetConfig",
    "serving.kv_tiering": "KvTieringConfig",
    "serving.adapters": "AdaptersConfig",
    "resilience.retry": "RetryConfig",
    "resilience.offload": "OffloadIntegrityConfig",
    "telemetry.numerics": "NumericsConfig",
    "telemetry.comm": "CommConfig",
}
DICT_SUBMODELS = {
    "serving.slo.classes": "SLOClassConfig",
}

#: dotted-key extraction from string constants.  The lookbehind kills
#: module-path fragments (``deepspeed_tpu.serving.scheduler``); the
#: extension denylist kills filename mentions (``serving.md``).
_CONFIG_KEY_RE = re.compile(
    r"(?<![\w./-])(serving|telemetry|resilience)"
    r"((?:\.[a-z_][a-z0-9_]*)+)")
_NON_KEY_SUFFIXES = {"md", "py", "json", "jsonl", "yaml", "yml", "txt",
                     "log", "tmp", "html", "gz", "npz", "prom"}

_ENV_NAME_RE = re.compile(r"^DS_[A-Z][A-Z0-9_]*$")

#: registry-API method names whose first string arg is a metric name
_METRIC_WRITERS = {"inc", "set_gauge", "set_counter", "histogram"}
_REGISTRY_RE = re.compile(r"reg|metrics", re.IGNORECASE)

#: receivers that look like a FaultInjector (the repo idiom covers
#: self.injector / self.fault_injector / inj / NULL_INJECTOR) — both
#: alternatives are anchored to a name-segment boundary so receivers
#: merely *ending* in "fault" (self.default) don't match
_INJECTOR_RE = re.compile(
    r"(?:^|[._])(?:(?:fault_)?inj(?:ector)?|faults?)$", re.IGNORECASE)
_FAULT_METHODS = {"check", "deny", "truncate_bytes", "corrupt_bytes"}

_FLIGHT_RE = re.compile(r"flightrec|flight_recorder|recorder|(?:^|\.)rec$",
                        re.IGNORECASE)

_ENVIRON_RE = re.compile(r"(?:^|\.)(?:environ|env)$")
_ENV_METHODS = {"get", "getenv", "setdefault", "pop"}


@dataclass(frozen=True)
class Ref:
    """One use of a registry string: value + where."""
    value: str
    path: str
    line: int


def _add(d: Dict[str, List[Ref]], ref: Ref):
    d.setdefault(ref.value, []).append(ref)


@dataclass
class Inventory:
    repo_root: str = ""
    #: site -> uses (``injector.check("ckpt.save")`` and friends)
    fault_sites_fired: Dict[str, List[Ref]] = field(default_factory=dict)
    #: site -> description (KNOWN_FAULT_SITES)
    fault_sites_declared: Dict[str, str] = field(default_factory=dict)
    #: kind -> uses (``flightrec.record("req/admit", ...)``)
    flight_kinds_recorded: Dict[str, List[Ref]] = field(default_factory=dict)
    #: kind -> description (KNOWN_EVENT_KINDS; trailing ``/`` = prefix)
    flight_kinds_declared: Dict[str, str] = field(default_factory=dict)
    #: DS_* env var -> read sites
    env_reads: Dict[str, List[Ref]] = field(default_factory=dict)
    #: DS_* env var -> description (registry_docs.ENV_VARS)
    env_documented: Dict[str, str] = field(default_factory=dict)
    #: dotted config-key references found in code strings
    config_refs: List[Ref] = field(default_factory=list)
    #: model class -> field names (from runtime/config.py)
    config_fields: Dict[str, Set[str]] = field(default_factory=dict)
    #: metric name -> emission sites
    metrics_emitted: Dict[str, List[Ref]] = field(default_factory=dict)
    #: metric name -> description (registry_docs.METRICS)
    metrics_documented: Dict[str, str] = field(default_factory=dict)

    # ------------------------------------------------------------- build
    @classmethod
    def empty(cls) -> "Inventory":
        return cls()

    @classmethod
    def build(cls, repo_root: str, extra_files: Sequence[str] = (),
              parsed: Optional[Dict[str, ast.AST]] = None) -> "Inventory":
        """``parsed`` maps repo-relative path -> already-parsed tree
        (the lint driver's modules) so a full-tree run doesn't read and
        ast.parse every file twice."""
        from .core import collect_files
        from . import registry_docs
        inv = cls(repo_root=repo_root)
        inv.env_documented = dict(registry_docs.ENV_VARS)
        inv.metrics_documented = dict(registry_docs.METRICS)
        roots = [r for r in SCAN_ROOTS
                 if os.path.isdir(os.path.join(repo_root, r))]
        files = collect_files(roots, repo_root)
        files.extend(os.path.abspath(f) for f in extra_files)
        parsed = parsed or {}
        for path in files:
            rel = os.path.relpath(path, repo_root).replace(os.sep, "/")
            tree = parsed.get(rel)
            if tree is None:
                try:
                    with open(path, encoding="utf-8") as f:
                        source = f.read()
                    tree = ast.parse(source)
                except (OSError, SyntaxError, UnicodeDecodeError):
                    continue  # the core driver reports parse errors
            inv.scan_module(tree, rel)
            if rel == FAULTS_PATH:
                inv.fault_sites_declared = _literal_str_dict(
                    tree, "KNOWN_FAULT_SITES")
            if rel == FLIGHTREC_PATH:
                inv.flight_kinds_declared = _literal_str_dict(
                    tree, "KNOWN_EVENT_KINDS")
            if rel == CONFIG_PATH:
                inv.config_fields = _class_fields(tree)
        return inv

    # -------------------------------------------------------------- scan
    def scan_module(self, tree: ast.AST, rel: str):
        """Collect every registry use in one module (public so tests can
        feed synthetic snippets through the same extraction)."""
        consts = _module_str_constants(tree)
        # names that reach a registry by a variable, declared where they
        # are made: registry_docs.METRIC_NAME_TUPLES
        from . import registry_docs
        for node in getattr(tree, "body", []):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.Tuple) \
                    and registry_docs.METRIC_NAME_TUPLES.get(rel) \
                    == node.targets[0].id:
                for el in node.value.elts:
                    name = consts.get(el.id) if isinstance(el, ast.Name) \
                        else None
                    if name:
                        _add(self.metrics_emitted,
                             Ref(name, rel, node.lineno))
        # local aliases of the serving counter/gauge dicts — the repo
        # idiom `c = self.metrics.counters; c["x"] += 1`
        aliases = {"counters": "counters", "gauges": "gauges"}
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                src = _dotted(node.value)
                if src is not None:
                    for kind in ("counters", "gauges"):
                        if src == kind or src.endswith("." + kind):
                            aliases[node.targets[0].id] = kind
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                self._scan_call(node, rel, consts)
            elif isinstance(node, ast.Subscript):
                self._scan_subscript(node, rel, aliases)
            elif isinstance(node, ast.Constant) and isinstance(node.value,
                                                               str):
                self._scan_string(node, rel)

    def _scan_call(self, node: ast.Call, rel: str, consts: Dict[str, str]):
        func = node.func
        attr = func.attr if isinstance(func, ast.Attribute) else (
            func.id if isinstance(func, ast.Name) else None)
        if attr is None:
            return
        recv = None
        if isinstance(func, ast.Attribute):
            recv = _dotted(func.value)
            if recv is None and isinstance(func.value, ast.Call):
                # get_registry().inc(...) / get_flight_recorder().record
                recv = _dotted(func.value.func)
        arg0 = _str_arg(node, 0, consts)
        # fault sites: injector.check/deny/truncate_bytes("site")
        if (attr in _FAULT_METHODS and arg0 and recv
                and _INJECTOR_RE.search(recv)
                and rel != FAULTS_PATH):
            _add(self.fault_sites_fired, Ref(arg0, rel, node.lineno))
        # indirect firing through helpers: retry_call(...,
        # site="ckpt.manifest") — any call carrying a literal site= kw
        if rel != FAULTS_PATH:
            for kw in node.keywords:
                if kw.arg == "site" and isinstance(kw.value, ast.Constant) \
                        and isinstance(kw.value.value, str):
                    _add(self.fault_sites_fired,
                         Ref(kw.value.value, rel, node.lineno))
        # flight-recorder kinds: flightrec.record("kind", ...) — also
        # the conditional ('a' if x else 'b') and prefix-family
        # (f"anomaly/{kind}") arg shapes the tree actually uses
        # both the direct flightrec.record(...) form and the repo's
        # guard-helper idiom (``self._flight("kind", ...)`` delegating
        # to an optional recorder — kv_tiering, offload engine, the
        # adapter store)
        if (((attr == "record" and recv and _FLIGHT_RE.search(recv))
             or (attr == "_flight" and recv == "self"))
                and rel != FLIGHTREC_PATH):
            for kind in _kind_values(node.args[0] if node.args else None,
                                     consts):
                _add(self.flight_kinds_recorded,
                     Ref(kind, rel, node.lineno))
        # env reads: os.environ.get("DS_X") / os.getenv("DS_X") /
        # env.get(ENV_VAR) where ENV_VAR is a module constant
        if attr == "getenv" or (attr in _ENV_METHODS and recv
                                and _ENVIRON_RE.search(recv)):
            if arg0 and _ENV_NAME_RE.match(arg0):
                _add(self.env_reads, Ref(arg0, rel, node.lineno))
        # metrics: registry.inc/set_gauge/set_counter/histogram("name")
        # — receivers must look registry-shaped (reg / registry /
        # self.metrics...) so unrelated .inc()/.get() APIs don't count
        if (attr in _METRIC_WRITERS and arg0 and recv
                and _REGISTRY_RE.search(recv)):
            _add(self.metrics_emitted, Ref(arg0, rel, node.lineno))
        # serving counter/gauge dicts: metrics.gauges.update(name=...)
        if (attr == "update" and recv and
                (recv.endswith(".gauges") or recv.endswith(".counters"))):
            for kw in node.keywords:
                if kw.arg:
                    _add(self.metrics_emitted,
                         Ref(f"serving/{kw.arg}", rel, node.lineno))

    def _scan_subscript(self, node: ast.Subscript, rel: str,
                        aliases: Dict[str, str]):
        base = _dotted(node.value)
        sl = node.slice
        if base is None or not isinstance(sl, ast.Constant) \
                or not isinstance(sl.value, str):
            return
        # env reads through the mapping protocol: os.environ["DS_X"]
        if _ENVIRON_RE.search(base) and _ENV_NAME_RE.match(sl.value):
            _add(self.env_reads, Ref(sl.value, rel, node.lineno))
            return
        # serving counter/gauge dict writes:
        #   self.metrics.counters["preemptions"] += 1
        #   c = self.metrics.counters; c["x"] = ...   (aliased)
        # ServingMetrics.snapshot() exposes these as serving/<key>.
        # Reads (asserts, tests) don't count as emission.
        if not isinstance(node.ctx, (ast.Store, ast.Del)):
            return
        is_dict = (base.endswith(".counters") or base.endswith(".gauges")
                   or base in aliases)
        if is_dict:
            _add(self.metrics_emitted,
                 Ref(f"serving/{sl.value}", rel, node.lineno))

    def _scan_string(self, node: ast.Constant, rel: str):
        for m in _CONFIG_KEY_RE.finditer(node.value):
            dotted = m.group(1) + m.group(2)
            if dotted.rsplit(".", 1)[-1] in _NON_KEY_SUFFIXES:
                continue
            self.config_refs.append(Ref(dotted, rel, node.lineno))

    # --------------------------------------------------- config resolution
    def config_key_exists(self, key: str) -> bool:
        """Resolve a dotted key against the runtime/config.py models."""
        if not self.config_fields:
            return True  # no declarations scanned — don't false-positive
        parts = key.split(".")
        model = SECTION_MODELS.get(parts[0])
        if model is None:
            return False
        prefix = parts[0]
        i = 1
        while i < len(parts):
            seg = parts[i]
            fields = self.config_fields.get(model, set())
            if seg not in fields:
                return False
            prefix = f"{prefix}.{seg}"
            i += 1
            if prefix in SUBMODELS:
                model = SUBMODELS[prefix]
                continue
            if prefix in DICT_SUBMODELS:
                # one arbitrary segment (the class/user-chosen name)
                model = DICT_SUBMODELS[prefix]
                if i < len(parts):
                    prefix = f"{prefix}.{parts[i]}"
                    i += 1
                continue
            # plain leaf: nothing may follow it
            return i == len(parts)
        return True

    def flight_kind_known(self, kind: str) -> bool:
        if kind in self.flight_kinds_declared:
            return True
        return any(d.endswith("/") and kind.startswith(d)
                   for d in self.flight_kinds_declared)


# ------------------------------------------------------------- ast utils
def _str_arg(node: ast.Call, idx: int,
             consts: Dict[str, str]) -> Optional[str]:
    if len(node.args) <= idx:
        return None
    arg = node.args[idx]
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    if isinstance(arg, ast.Name):
        return consts.get(arg.id)
    return None


def _kind_values(arg, consts: Dict[str, str]) -> List[str]:
    """Flight-event kind(s) named by a ``record()`` first argument:
    plain literal, module constant, either branch of a conditional, or
    the literal prefix of an f-string (``f"anomaly/{kind}"`` records
    the ``anomaly/*`` family)."""
    if arg is None:
        return []
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return [arg.value]
    if isinstance(arg, ast.Name) and arg.id in consts:
        return [consts[arg.id]]
    if isinstance(arg, ast.IfExp):
        return _kind_values(arg.body, consts) + _kind_values(arg.orelse,
                                                             consts)
    if isinstance(arg, ast.JoinedStr) and arg.values:
        first = arg.values[0]
        if isinstance(first, ast.Constant) and isinstance(first.value,
                                                          str) \
                and first.value.endswith("/"):
            return [first.value + "*"]
    return []


def _module_str_constants(tree: ast.AST) -> Dict[str, str]:
    """Module-level NAME = "literal" bindings (``ENV_VAR = "DS_FAULTS"``
    is how faults.py names its env var — resolve reads through it)."""
    out: Dict[str, str] = {}
    for node in getattr(tree, "body", []):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Constant) \
                and isinstance(node.value.value, str):
            out[node.targets[0].id] = node.value.value
    return out


def _literal_str_dict(tree: ast.AST, name: str) -> Dict[str, str]:
    """Parse ``NAME = {"k": "v", ...}`` at module level."""
    for node in getattr(tree, "body", []):
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
        for t in targets:
            if isinstance(t, ast.Name) and t.id == name:
                try:
                    val = ast.literal_eval(node.value)
                except (ValueError, SyntaxError):
                    return {}
                if isinstance(val, dict):
                    return {str(k): str(v) for k, v in val.items()}
                if isinstance(val, (list, tuple, set)):
                    return {str(k): "" for k in val}
    return {}


def _class_fields(tree: ast.AST) -> Dict[str, Set[str]]:
    """Model class -> declared field names, from annotated assignments
    and plain assignments in the class body."""
    out: Dict[str, Set[str]] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        fields: Set[str] = set()
        for stmt in node.body:
            if isinstance(stmt, ast.AnnAssign) and isinstance(
                    stmt.target, ast.Name):
                fields.add(stmt.target.id)
            elif isinstance(stmt, ast.Assign):
                for t in stmt.targets:
                    if isinstance(t, ast.Name) and not t.id.startswith("_") \
                            and t.id.islower():
                        fields.add(t.id)
        out[node.name] = fields
    return out


# --------------------------------------------------------- doc generation
def _sites_cell(refs: List[Ref]) -> str:
    paths = sorted({r.path for r in refs})
    return ", ".join(f"`{p}`" for p in paths)


def generate_registries_md(inv: Inventory) -> str:
    """The authoritative cross-registry reference
    (``docs/reference/registries.md``) — generated, then committed;
    DSL004 flags the file when it drifts from this content.  Regenerate
    with ``python scripts/dslint.py --write-registries``."""
    L: List[str] = []
    L.append("# String-registry reference")
    L.append("")
    L.append("<!-- GENERATED FILE — do not edit by hand. -->")
    L.append("<!-- Regenerate: python scripts/dslint.py "
             "--write-registries -->")
    L.append("")
    L.append("One authoritative table per string-keyed registry in the "
             "tree, generated from the dslint DSL004 inventory "
             "(`deepspeed_tpu/tools/dslint/inventory.py`). The lint "
             "pass fails when code and these tables drift — see "
             "[the static-analysis tutorial](../tutorials/"
             "static-analysis.md).")
    L.append("")

    L.append("## Fault-injection sites")
    L.append("")
    L.append("Declared in `deepspeed_tpu/resilience/faults.py` "
             "(`KNOWN_FAULT_SITES`); armed via the `DS_FAULTS` env var "
             "or the `resilience.faults` config key (see "
             "[resilience](../tutorials/resilience.md)).")
    L.append("")
    L.append("| Site | Description | Fired from |")
    L.append("|---|---|---|")
    for site, desc in sorted(inv.fault_sites_declared.items()):
        L.append(f"| `{site}` | {desc} | "
                 f"{_sites_cell(inv.fault_sites_fired.get(site, []))} |")
    L.append("")

    L.append("## DS_* environment variables")
    L.append("")
    L.append("Documented in `deepspeed_tpu/tools/dslint/registry_docs.py`"
             " (`ENV_VARS`); dslint fails on a `DS_*` read that has no "
             "entry here.")
    L.append("")
    L.append("| Variable | Description | Read from |")
    L.append("|---|---|---|")
    for name, desc in sorted(inv.env_documented.items()):
        L.append(f"| `{name}` | {desc} | "
                 f"{_sites_cell(inv.env_reads.get(name, []))} |")
    L.append("")

    L.append("## Config keys (`serving.*`, `telemetry.*`, "
             "`resilience.*`)")
    L.append("")
    L.append("Declared by the models in "
             "`deepspeed_tpu/runtime/config.py`; every dotted key "
             "referenced anywhere in the tree must resolve against "
             "them.")
    L.append("")
    L.append("| Key | Declared by |")
    L.append("|---|---|")
    for key, model in sorted(_enumerate_config_keys(inv)):
        L.append(f"| `{key}` | `{model}` |")
    L.append("")

    L.append("## Metric names")
    L.append("")
    L.append("Documented in `deepspeed_tpu/tools/dslint/registry_docs.py`"
             " (`METRICS`); each is exposed through the shared "
             "Prometheus exposition (`/metrics` on `ds_serve` and the "
             "training `telemetry.metrics_port` endpoint — see "
             "[monitoring & profiling](../tutorials/"
             "monitoring-profiling.md)).")
    L.append("")
    L.append("| Metric | Description | Emitted from |")
    L.append("|---|---|---|")
    for name, desc in sorted(inv.metrics_documented.items()):
        L.append(f"| `{name}` | {desc} | "
                 f"{_sites_cell(inv.metrics_emitted.get(name, []))} |")
    L.append("")

    L.append("## Flight-recorder event kinds")
    L.append("")
    L.append("Declared in `deepspeed_tpu/telemetry/flight_recorder.py` "
             "(`KNOWN_EVENT_KINDS`); a trailing `/` declares a prefix "
             "family (`anomaly/<kind>`).")
    L.append("")
    L.append("| Kind | Description | Recorded from |")
    L.append("|---|---|---|")
    for kind, desc in sorted(inv.flight_kinds_declared.items()):
        refs = [r for k, rs in inv.flight_kinds_recorded.items()
                for r in rs
                if k == kind or (kind.endswith("/")
                                 and k.startswith(kind))]
        L.append(f"| `{kind}` | {desc} | {_sites_cell(refs)} |")
    L.append("")
    return "\n".join(L)


def _enumerate_config_keys(inv: Inventory) -> List[Tuple[str, str]]:
    """Flatten the declared config tree into (dotted key, model) rows."""
    out: List[Tuple[str, str]] = []

    def walk(prefix: str, model: str, depth: int = 0):
        if depth > 4:
            return
        for f in sorted(inv.config_fields.get(model, ())):
            key = f"{prefix}.{f}"
            out.append((key, model))
            if key in SUBMODELS:
                walk(key, SUBMODELS[key], depth + 1)
            elif key in DICT_SUBMODELS:
                walk(key + ".<class>", DICT_SUBMODELS[key], depth + 1)

    for section, model in sorted(SECTION_MODELS.items()):
        walk(section, model)
    return out
