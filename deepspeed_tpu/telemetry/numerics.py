"""Training-health observatory (ISSUE 15 tentpole).

The perf observatory (ISSUE 13) prices compute and the memory
observatory (ISSUE 14) prices bytes; nothing in the stack watches
training *health*: until now a non-finite step was a single lazily
banked ``grad_norm``/``overflow`` scalar pair with no attribution, no
timeline, and no forensic record.  This module is that layer:

- **in-graph stats** — per-leaf-group grad norms, a per-group
  non-finite count bitmap, and the update/param norm ratio are computed
  ON DEVICE inside the fused train step (:func:`group_stats`, wired in
  ``step_programs.apply_grads``) and banked as device scalars exactly like
  the overflow flag (:class:`NumericsState`), so the hot path pays ZERO
  extra host syncs; one lazy ``resolve()`` fetches the whole backlog in
  a single transfer and a non-finite step names the **first offending
  leaf group** (NaN provenance) instead of just being skipped;
- **detection** — resolved grad-norm / loss / update-ratio streams feed
  the PR 7 :class:`~deepspeed_tpu.telemetry.anomaly.AnomalyMonitor`
  (``anomaly/num_grad_norm`` / ``num_loss`` / ``num_update_ratio``
  instants carrying the step's corr id), and an unexpected (non-
  overflow) non-finite step emits a ``num/nonfinite`` flight event, an
  ``anomaly/num_nonfinite`` trace instant, and a post-mortem bundle
  through the engine's callback;
- **determinism fingerprints** — :func:`state_fingerprint` digests a
  bounded, strided sample of every param leaf plus the rng chain (and
  optionally the loss) with blake2b; the engine records one every
  ``telemetry.numerics.fingerprint_interval`` steps as a
  ``num/fingerprint`` flight event and stamps one into each checkpoint
  manifest, so restore==uninterrupted and DP==TP parity become
  runtime-auditable claims (``scripts/numerics_report.py --diff``);
- **read surfaces** — ``num/*`` gauges on both /metrics front doors,
  the ``/debug/numerics`` endpoint
  (:func:`deepspeed_tpu.telemetry.debug.numerics_payload`), and
  ``numerics.json`` in post-mortem bundles.

Resolution order (the repo's env-wins convention): ``DS_NUMERICS`` env
> ``telemetry.numerics.enabled`` > on; ``DS_FINGERPRINT_INTERVAL`` env
> ``telemetry.numerics.fingerprint_interval`` > off.
"""
import collections
import hashlib
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

NUMERICS_ENV = "DS_NUMERICS"
FINGERPRINT_ENV = "DS_FINGERPRINT_INTERVAL"

#: provenance records kept per process.  Unlike the rolling memory
#: forensics ring this keeps the FIRST N records: once gradients go
#: non-finite every later step is non-finite too, and the record that
#: explains the incident is the earliest one — it must never age off.
DEFAULT_MAX_NONFINITE = 32

#: fingerprint stream entries retained in memory (each ~100 bytes)
DEFAULT_MAX_FINGERPRINTS = 4096

#: per-leaf element cap for :func:`state_fingerprint` — bounds the
#: device->host fetch on large models (evenly strided sample; a
#: perturbation of any sampled element flips the digest)
FINGERPRINT_MAX_ELEMS = 65536


def numerics_enabled(config_default: Optional[bool] = None) -> bool:
    """``DS_NUMERICS`` env > the ``telemetry.numerics.enabled`` value
    the caller passes > on."""
    env = os.environ.get(NUMERICS_ENV, "").strip()
    if env:
        return env not in ("0", "false", "off")
    if config_default is not None:
        return bool(config_default)
    return True


def resolve_fingerprint_interval(config_default: int = 0) -> int:
    """``DS_FINGERPRINT_INTERVAL`` env > config; 0 disables the
    periodic fingerprint (checkpoint stamping stays on while numerics
    is on — one digest per save is noise next to the save itself)."""
    env = os.environ.get(FINGERPRINT_ENV, "").strip()
    if env:
        try:
            return max(int(env), 0)
        except ValueError:
            return max(int(config_default or 0), 0)
    return max(int(config_default or 0), 0)


# ------------------------------------------------------------ leaf groups
def _fmt_key(k) -> str:
    for attr in ("key", "idx", "name"):
        v = getattr(k, attr, None)
        if v is not None:
            return str(v)
    return str(k)


def leaf_groups(tree, depth: int = 2) -> Tuple[List[str], List[int]]:
    """Group a param/grad pytree's leaves by the first ``depth`` path
    components -> (ordered group names, per-leaf group index in flatten
    order).  "blocks/attn_w" rather than one entry per stacked layer:
    the in-graph stats are O(G) scatter-adds, so G stays small and the
    group name is what a human greps for."""
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    names: List[str] = []
    order: Dict[str, int] = {}
    index: List[int] = []
    for path, _leaf in flat:
        name = "/".join(_fmt_key(k) for k in path[:depth]) or "<root>"
        if name not in order:
            order[name] = len(names)
            names.append(name)
        index.append(order[name])
    return names, index


def group_stats(grads, leaf_group_index: Sequence[int], num_groups: int):
    """In-graph per-group stats (traced inside the fused train step):
    ``(group_norms [G] f32, nonfinite_counts [G] i32)``.  A group whose
    gradients contain NaN/Inf reports a non-finite norm AND a positive
    count — the count is the provenance bitmap, the norm keeps the
    per-group timeline meaningful on healthy steps."""
    import jax
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_leaves(grads)
    if len(leaves) != len(leaf_group_index):
        return None
    return group_stats_of(
        [jnp.sum(jnp.square(leaf.astype(jnp.float32))) for leaf in leaves],
        [jnp.sum(jnp.logical_not(jnp.isfinite(leaf))).astype(jnp.int32)
         for leaf in leaves], leaf_group_index, num_groups)


def group_stats_of(grad_sq, nonfinite, leaf_group_index: Sequence[int],
                   num_groups: int):
    """:func:`group_stats` from each leaf's sum of squares (float32) and
    non-finite count (int32), however they were formed (the fused
    optimizer's kernel leaves them beside the update)."""
    import jax.numpy as jnp
    if len(grad_sq) != len(leaf_group_index):
        return None
    sq = jnp.zeros((num_groups,), jnp.float32)
    nf = jnp.zeros((num_groups,), jnp.int32)
    for leaf_sq, leaf_nf, g in zip(grad_sq, nonfinite, leaf_group_index):
        sq = sq.at[g].add(leaf_sq)
        nf = nf.at[g].add(leaf_nf)
    return jnp.sqrt(sq), nf


def inject_nonfinite(grads, leaf_group_index: Sequence[int], group: int):
    """Chaos hook for the ``train.nonfinite`` fault site: NaN-poison
    the FIRST leaf of the chosen group (trace-time static choice — the
    engine compiles one step variant per injected group).  Provenance
    then must name exactly that group."""
    import jax
    import jax.numpy as jnp
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    target = None
    for i, g in enumerate(leaf_group_index[:len(leaves)]):
        if g == group:
            target = i
            break
    if target is not None:
        leaf = leaves[target]
        leaves[target] = leaf + jnp.asarray(jnp.nan, leaf.dtype)
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ------------------------------------------------------------ fingerprints
def _leaf_sample(leaf, max_elems: int):
    """Evenly strided 1-D sample of a leaf (whole leaf when small)."""
    size = int(leaf.size)
    flat = leaf.reshape(-1)
    if size <= max_elems:
        return flat
    stride = size // max_elems
    return flat[::stride][:max_elems]


def state_fingerprint(params, rng_key, step: int, loss=None,
                      max_elems: int = FINGERPRINT_MAX_ELEMS) -> str:
    """blake2b digest of (strided param-leaf samples, rng chain, step,
    loss) — the determinism fingerprint.  Two runs that agree bitwise
    on the sampled state produce identical digests; restore-vs-
    uninterrupted and DP-vs-TP drift flips them.  One bounded
    device->host transfer; callers pay it only at the fingerprint
    interval / at checkpoint boundaries."""
    import jax
    import numpy as np
    leaves = jax.tree_util.tree_leaves(params)
    samples = jax.device_get([_leaf_sample(l, max_elems) for l in leaves])
    h = hashlib.blake2b(digest_size=16)
    for leaf, s in zip(leaves, samples):
        arr = np.asarray(s)
        h.update(str(tuple(leaf.shape)).encode())
        h.update(str(arr.dtype).encode())
        h.update(arr.tobytes())
    h.update(np.asarray(rng_key).tobytes())
    h.update(str(int(step)).encode())
    if loss is not None:
        h.update(np.asarray(jax.device_get(loss),
                            dtype=np.float64).tobytes())
    return h.hexdigest()


# ------------------------------------------------------------- the bank
class NumericsState:
    """Lazily banked training-health state (the overflow-banking idiom
    generalized): the engine appends one record of DEVICE scalars per
    step (``bank`` is a lock acquire + list append — no transfer), and
    ``resolve()`` fetches the whole backlog in ONE ``jax.device_get``
    before processing it host-side.  Readers (``/debug/numerics``,
    ``numerics.json``) resolve on demand; the hot path never does.

    Writers take only this object's own lock — never a scheduler or
    engine lock — so the debug endpoint answers while a step is wedged
    (the PR 7/13/14 lock contract)."""

    def __init__(self, group_names: Sequence[str], history: int = 512,
                 registry=None, anomaly=None, flightrec=None,
                 on_nonfinite=None,
                 max_nonfinite: int = DEFAULT_MAX_NONFINITE,
                 max_fingerprints: int = DEFAULT_MAX_FINGERPRINTS):
        self.group_names = list(group_names)
        self.registry = registry
        self.anomaly = anomaly
        self.flightrec = flightrec
        self.on_nonfinite = on_nonfinite
        self._lock = threading.Lock()
        #: serializes whole resolve() passes (swap -> fetch -> process
        #: -> publish) so a concurrent /debug reader and the engine's
        #: report-boundary resolve can't interleave out-of-order
        #: entries or publish stale gauges.  RLock: a resolve-triggered
        #: post-mortem drains numerics_payload -> snapshot -> resolve
        #: on the SAME thread (the inner pass sees an empty backlog).
        self._resolve_lock = threading.RLock()
        self._pending: List[Tuple[int, Dict[str, Any]]] = []
        self._history: collections.deque = collections.deque(
            maxlen=max(int(history), 16))
        #: first-N UNEXPECTED provenance records (see
        #: DEFAULT_MAX_NONFINITE).  Loss-scaler-handled overflow skips
        #: are routine in a healthy fp16 run and live in their own
        #: rolling tail — they must never consume the incident ring.
        self._nonfinite: List[Dict[str, Any]] = []
        self._nonfinite_handled: collections.deque = collections.deque(
            maxlen=8)
        self._max_nonfinite = max(int(max_nonfinite), 1)
        self.nonfinite_steps = 0          #: unexpected (non-overflow)
        self.nonfinite_overflow_steps = 0  #: loss-scaler-handled
        self.fingerprints: collections.deque = collections.deque(
            maxlen=max(int(max_fingerprints), 16))
        self.restore_audits: List[Dict[str, Any]] = []
        #: resolve()/fetch accounting — the chaos acceptance test
        #: asserts the per-step host-sync count is unchanged by reading
        #: these (resolves stay 0 across a training loop)
        self.resolves = 0
        self.records_resolved = 0

    # ------------------------------------------------------------ writers
    def bank(self, step: int, **record):
        """Append one step's device-side record; no transfer, no sync."""
        with self._lock:
            self._pending.append((int(step), record))

    def pending_count(self) -> int:
        return len(self._pending)

    def record_fingerprint(self, step: int, digest: str,
                           source: str = "interval"):
        entry = {"step": int(step), "digest": digest, "source": source,
                 "ts": round(time.time(), 3)}
        with self._lock:
            self.fingerprints.append(entry)
        if self.registry is not None:
            self.registry.inc("num/fingerprints")
        if self.flightrec is not None:
            self.flightrec.record("num/fingerprint",
                                  corr=f"train-step-{int(step)}",
                                  step=int(step), digest=digest,
                                  source=source)
        return entry

    def record_restore_audit(self, step: int, expected: str,
                             actual: str) -> bool:
        """Restore-time fingerprint check (the manifest-stamped digest
        vs one recomputed from the restored state).  A mismatch is a
        perturbed/corrupted restore: counted, flight-recorded, and kept
        in the audit list the debug payload exposes."""
        ok = bool(expected == actual)
        entry = {"step": int(step), "ok": ok, "expected": expected,
                 "actual": actual, "ts": round(time.time(), 3)}
        with self._lock:
            self.restore_audits.append(entry)
        if self.registry is not None:
            if not ok:
                self.registry.inc("num/fingerprint_mismatch")
        if self.flightrec is not None:
            self.flightrec.record("num/fingerprint",
                                  corr=f"train-step-{int(step)}",
                                  step=int(step), source="restore",
                                  ok=ok, digest=actual)
        return ok

    # ------------------------------------------------------------ resolve
    def resolve(self, emit_postmortem: bool = True) -> List[Dict[str, Any]]:
        """Fetch and process every banked record (ONE device->host
        transfer for the whole backlog).  Feeds the anomaly detectors,
        publishes the ``num/*`` gauges, and turns non-finite steps into
        provenance records + ``num/nonfinite`` events.  Returns the
        resolved history entries."""
        with self._resolve_lock:
            with self._lock:
                batch, self._pending = self._pending, []
            if not batch:
                return []
            import jax
            values = jax.device_get([rec for _, rec in batch])
            self.resolves += 1
            self.records_resolved += len(batch)
            out = []
            for (step, _), rec in zip(batch, values):
                out.append(self._process(step, rec, emit_postmortem))
            if self.registry is not None and out:
                self._publish(out[-1])
            return out

    @staticmethod
    def _f(rec, key) -> Optional[float]:
        v = rec.get(key)
        if v is None:
            return None
        try:
            return float(v)
        except (TypeError, ValueError):
            return None

    @staticmethod
    def _json_safe(entry: Dict[str, Any]) -> Dict[str, Any]:
        """History/provenance copy with non-finite floats mapped to
        None (JSON null): ``json.dumps(float('nan'))`` emits the
        spec-invalid bare token ``NaN``, which would make the
        /debug/numerics body unreadable by jq/browsers/strict parsers
        at exactly the incident the endpoint exists for.  A
        ``nonfinite: true`` flag keeps the incident visible."""
        import math
        out: Dict[str, Any] = {}
        bad = False
        for k, v in entry.items():
            if isinstance(v, float) and not math.isfinite(v):
                out[k] = None
                bad = True
            elif isinstance(v, list):
                vals = [None if isinstance(x, float)
                        and not math.isfinite(x) else x for x in v]
                bad = bad or any(x is None for x in vals)
                out[k] = vals
            else:
                out[k] = v
        if bad:
            out["nonfinite"] = True
        return out

    def _process(self, step: int, rec: Dict[str, Any],
                 emit_postmortem: bool) -> Dict[str, Any]:
        import numpy as np
        entry: Dict[str, Any] = {"step": step}
        for key in ("loss", "grad_norm", "loss_scale", "update_ratio"):
            v = self._f(rec, key)
            if v is not None:
                entry[key] = v
        overflow = bool(np.asarray(rec.get("overflow", False)))
        entry["overflow"] = overflow
        norms = rec.get("group_norms")
        counts = rec.get("nonfinite")
        if norms is not None:
            entry["group_norms"] = [float(v) for v in np.asarray(norms)]
        with self._lock:
            self._history.append(self._json_safe(entry))
        corr = f"train-step-{step}"
        if self.anomaly is not None:
            for kind, key in (("num_grad_norm", "grad_norm"),
                              ("num_loss", "loss"),
                              ("num_update_ratio", "update_ratio")):
                v = entry.get(key)
                if v is not None and np.isfinite(v):
                    self.anomaly.observe(kind, v, corr=corr)
        nf_counts = (np.asarray(counts, dtype=np.int64)
                     if counts is not None else None)
        gn = entry.get("grad_norm")
        nonfinite = bool(
            (nf_counts is not None and int(nf_counts.sum()) > 0)
            or (gn is not None and not np.isfinite(gn)))
        if nonfinite:
            self._record_nonfinite(step, entry, nf_counts, overflow,
                                   emit_postmortem)
        return entry

    def _record_nonfinite(self, step: int, entry: Dict[str, Any],
                          nf_counts, overflow: bool,
                          emit_postmortem: bool):
        groups: Dict[str, int] = {}
        first_group = None
        if nf_counts is not None:
            for i, c in enumerate(nf_counts):
                if c > 0 and i < len(self.group_names):
                    name = self.group_names[i]
                    groups[name] = int(c)
                    if first_group is None:
                        first_group = name
        if first_group is None:
            # no bitmap (stats disabled / shape mismatch) but the global
            # norm is non-finite — provenance degrades to the whole tree
            first_group = "<global>"
        prov = self._json_safe(
            {"step": step, "first_group": first_group,
             "groups": groups, "overflow": overflow,
             "handled": overflow,
             "loss": entry.get("loss"),
             "loss_scale": entry.get("loss_scale"),
             "ts": round(time.time(), 3)})
        with self._lock:
            if overflow:
                # routine fp16 scale-backoff skips: rolling tail only —
                # they must never fill the first-N incident ring
                self.nonfinite_overflow_steps += 1
                self._nonfinite_handled.append(prov)
            else:
                self.nonfinite_steps += 1
                if len(self._nonfinite) < self._max_nonfinite:
                    self._nonfinite.append(prov)
        corr = f"train-step-{step}"
        if self.registry is not None:
            self.registry.inc("num/nonfinite_steps",
                              handled="overflow" if overflow
                              else "unexpected")
        if self.flightrec is not None:
            self.flightrec.record("num/nonfinite", corr=corr, step=step,
                                  first_group=first_group,
                                  handled=overflow)
        if not overflow:
            # trace instant with the detector-field shape
            # trace_validate --check-anomalies asserts (value/median/
            # score + the step corr) — a non-finite step is the
            # definitive numerics anomaly even without a MAD window
            from deepspeed_tpu.telemetry.tracing import get_tracer
            total = int(sum(groups.values())) if groups else 1
            get_tracer().instant(
                "anomaly/num_nonfinite", cat="anomaly", corr=corr,
                args={"value": float(total), "median": 0.0, "mad": 0.0,
                      "score": float(total),
                      "first_group": first_group})
            if emit_postmortem and self.on_nonfinite is not None:
                try:
                    self.on_nonfinite(prov)
                except Exception as e:  # forensics must not fail training
                    from deepspeed_tpu.utils.logging import logger
                    logger.warning(
                        f"numerics: nonfinite callback failed ({e})")

    def _publish(self, last: Dict[str, Any]):
        import math
        reg = self.registry

        def finite(v):
            return v if math.isfinite(v) else -1.0

        if last.get("grad_norm") is not None:
            reg.set_gauge("num/grad_norm", finite(last["grad_norm"]))
        if last.get("loss") is not None:
            reg.set_gauge("num/loss", finite(last["loss"]))
        if last.get("loss_scale") is not None:
            reg.set_gauge("num/loss_scale", finite(last["loss_scale"]))
        if last.get("update_ratio") is not None:
            reg.set_gauge("num/update_ratio",
                          finite(last["update_ratio"]))
        for name, v in zip(self.group_names,
                           last.get("group_norms") or ()):
            reg.set_gauge("num/group_grad_norm", finite(v), group=name)

    # ------------------------------------------------------------ readers
    def last_nonfinite(self) -> Optional[Dict[str, Any]]:
        """Most recent UNEXPECTED provenance record (the sanitize
        raise names its group; handled overflow skips never shadow a
        real incident here)."""
        with self._lock:
            return dict(self._nonfinite[-1]) if self._nonfinite else None

    def nonfinite_records(self) -> List[Dict[str, Any]]:
        """The first-N unexpected provenance records."""
        with self._lock:
            return [dict(r) for r in self._nonfinite]

    def handled_nonfinite_records(self) -> List[Dict[str, Any]]:
        """Rolling tail of loss-scaler-handled overflow skips."""
        with self._lock:
            return [dict(r) for r in self._nonfinite_handled]

    def history(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(e) for e in self._history]

    def fingerprint_stream(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(e) for e in self.fingerprints]

    def snapshot(self) -> Dict[str, Any]:
        """The ``/debug/numerics`` / ``numerics.json`` body.  Resolving
        the banked backlog IS the read path (lazy banking by design);
        it takes only the bank's own lock plus one device fetch — never
        a scheduler/engine lock."""
        self.resolve()
        hist = self.history()
        return {
            "ts": round(time.time(), 3),
            "groups": list(self.group_names),
            "history": hist,
            "last": hist[-1] if hist else None,
            "nonfinite": {
                "unexpected_steps": self.nonfinite_steps,
                "overflow_steps": self.nonfinite_overflow_steps,
                "records": self.nonfinite_records(),
                "handled_records": self.handled_nonfinite_records(),
            },
            "fingerprints": self.fingerprint_stream(),
            "restore_audits": list(self.restore_audits),
            "banked_pending": self.pending_count(),
            "resolves": self.resolves,
            "records_resolved": self.records_resolved,
        }


# ------------------------------------------------- process-wide state
_GLOBAL_LOCK = threading.Lock()
_GLOBAL: Optional[NumericsState] = None


def configure_numerics(group_names: Sequence[str], **kwargs
                       ) -> NumericsState:
    """(Re)build the process-wide numerics state (engine init).  The
    latest engine wins — matching the moe metrics tap semantics."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        _GLOBAL = NumericsState(group_names, **kwargs)
        return _GLOBAL


def peek_numerics() -> Optional[NumericsState]:
    """The existing process-wide state, or None — never creates one (a
    read-only debug GET must not arm telemetry; the iostat peek
    contract)."""
    return _GLOBAL


def reset_numerics():
    """Tests: drop the process-wide state."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        _GLOBAL = None
