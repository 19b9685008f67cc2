"""graftnum — streaming numerics observatory (per-layer grad/update
telemetry, NaN provenance, quantization-error tracking).

The other observability layers watch *around* the model (spans, MFU,
phase windows, fleet skew); graftnum watches *inside* it. Armed by
``train.graftnum`` (or ``TRLX_TPU_GRAFTNUM=1``), off by default, and the
disarmed hooks are one module-global load — the serial path stays
byte-identical (same contract as spans/graftfleet):

- **Per-subtree training telemetry** — ``train_step_stats`` folds
  per-top-level-param-subtree grad norm, param norm, and update/param
  ratio into the jitted train step's ``stats`` dict (reductions only, the
  objective is untouched): ``num/grad_norm/<subtree>``,
  ``num/param_norm/<subtree>``, ``num/update_ratio/<subtree>`` and the
  global ``num/grad_global_norm``, all riding the existing Tracker →
  MetricsExporter → report plumbing. The gate is resolved at train-step
  BUILD time, so a disarmed program compiles to the pre-graftnum jaxpr.
- **NaN provenance** — when the non-finite guard trips,
  ``nonfinite_census`` names every non-finite leaf of the (recomputed)
  gradient tree by path with NaN/Inf counts, and ``bisect_forward`` runs
  ONE eval-only instrumented re-forward on the offending microbatch
  through the probe taps ``models/lm.py`` registers at block boundaries
  (``embed`` → ``block_<i>`` → ``ln_f``), naming the FIRST layer whose
  activations go non-finite. Both land in the incident bundle as
  ``incidents/<step>/numerics.json``. The census half also runs with
  graftnum disarmed whenever ``train.nonfinite_guard`` has an incident
  path armed — the default-on guard finally names its culprit.
- **Quantization-error telemetry** — ``record_weight_quant`` /
  ``record_kv_quant`` drive the optional error probes grown by
  ``quantize_weights`` / ``quantize_kv`` at each weight-version handoff
  (engine ``update_weights``, W8A16 snapshot/refresh), emitting
  ``num/quant_err_max/<class>``, ``num/quant_err_rms/<class>``,
  ``num/quant_snr_db/<class>`` and ``num/quant_weight_version`` so int8
  drift is visible per weight version.
- **Health integration** — ``GradNormSpikeDetector`` (rolling-p50 spike
  gate over the global grad norm) and ``UpdateRatioDetector`` (per-subtree
  band violations) ride the PR 9 hysteresis state machine; when the health
  monitor is armed they register through ``register_detector``, otherwise
  CRIT still escalates through the ``register_emergency`` incident hook.

The probe taps are trace-transparent: disarmed (or under a live jit
trace) they return their input unchanged, so the hot-step jaxpr never
contains them; armed taps only run inside the bisector's EAGER forward.

See RUNBOOK.md §15 for knobs, the gauge glossary, and the triage
playbook; drill with ``TRLX_TPU_FAULTS=nan_layer@N``.
"""

import json
import math
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax

from trlx_tpu.observability.health import CRIT, OK, WARN, HysteresisDetector

__all__ = [
    "armed",
    "configure",
    "shutdown",
    "enabled",
    "instance",
    "train_step_stats",
    "param_subtrees",
    "probe_tap",
    "bisect_forward",
    "latch_injection",
    "consume_injection",
    "nonfinite_census",
    "record_weight_quant",
    "record_kv_quant",
    "record_weight_handoff",
    "write_incident",
    "GradNormSpikeDetector",
    "UpdateRatioDetector",
    "NUMERICS_FILENAME",
]

NUMERICS_FILENAME = "numerics.json"

# Cap on census entries written to the incident bundle: a fully-NaN tree
# has one entry per leaf — name the first K by path and summarize the rest.
CENSUS_MAX_LEAVES = 32


def armed(train_cfg) -> bool:
    """Config-or-env arming, resolved at trainer/train-step build time —
    the same convention as every other observability knob."""
    return bool(getattr(train_cfg, "graftnum", False)) or os.environ.get(
        "TRLX_TPU_GRAFTNUM", ""
    ) not in ("", "0")


# ------------------------------------------------- per-subtree reductions


def _is_mapping(node) -> bool:
    return hasattr(node, "items") and not hasattr(node, "shape")


def param_subtrees(tree) -> dict:
    """Named subtrees of a param/grad tree, one map level below the
    top-level groups — ``{"policy/h_0": ..., "policy/wte": ...}`` — so the
    gauges resolve to per-layer granularity without per-leaf key spam.
    Non-mapping children stay under their group's own name."""
    if not _is_mapping(tree):
        return {"all": tree}
    out = {}
    for group, sub in tree.items():
        if _is_mapping(sub) and sub:
            for child, v in sub.items():
                out[f"{group}/{child}"] = v
        else:
            out[str(group)] = sub
    return out


def train_step_stats(grads, params, new_params) -> dict:
    """Jit-safe numerics reductions for the train step's ``stats`` dict:
    per-subtree grad/param norms and the REALIZED update/param ratio
    (``new - old`` over ``old`` — exactly zero on guard-skipped steps, a
    signal in itself). Device scalars only; the trainer fetches them with
    the rest of the stats at log boundaries."""
    out = {"num/grad_global_norm": optax.global_norm(grads)}
    gsub = param_subtrees(grads)
    psub = param_subtrees(params)
    nsub = param_subtrees(new_params)
    for name in gsub:
        pn = optax.global_norm(psub[name])
        dn = optax.global_norm(
            jax.tree_util.tree_map(lambda a, b: a - b, nsub[name], psub[name])
        )
        out[f"num/grad_norm/{name}"] = optax.global_norm(gsub[name])
        out[f"num/param_norm/{name}"] = pn
        out[f"num/update_ratio/{name}"] = dn / (pn + 1e-12)
    return out


# ------------------------------------------------------------- probe taps

_TAP_LOCK = threading.Lock()
_TAP_SESSION = None  # armed ONLY inside bisect_forward's eager re-forward
_PENDING_INJECTION = None  # tap name latched by the nan_layer drill


def probe_tap(name: str, x):
    """Activation tap at a model block boundary (models/lm.py). Disarmed —
    the permanent state in every jitted forward — this is one global load
    returning ``x`` unchanged, so the traced program is identical to a
    tap-free model. Armed (inside ``bisect_forward`` only) it records the
    tap's non-finite count and applies the drill injection."""
    session = _TAP_SESSION
    if session is None:
        return x
    return session.tap(name, x)


def latch_injection(tap_name: str):
    """Arm the ``nan_layer`` drill: the NEXT ``bisect_forward`` poisons the
    named tap's activations, giving the bisector a ground-truth target."""
    global _PENDING_INJECTION
    _PENDING_INJECTION = str(tap_name)


def consume_injection():
    global _PENDING_INJECTION
    target, _PENDING_INJECTION = _PENDING_INJECTION, None
    return target


class _TapSession:
    def __init__(self, inject=None):
        self.inject = inject
        self.records = []
        self.first_nonfinite = None

    def tap(self, name, x):
        if isinstance(x, jax.core.Tracer):
            # A concurrent trace on another thread (producer retrace) must
            # never capture an armed tap into a compiled program.
            return x
        if self.inject is not None and name == self.inject:
            x = x * jnp.asarray(float("nan"), dtype=x.dtype)
        arr = np.asarray(jax.device_get(x))
        nan = int(np.isnan(arr).sum()) if np.issubdtype(arr.dtype, np.inexact) else 0
        inf = int(np.isinf(arr).sum()) if np.issubdtype(arr.dtype, np.inexact) else 0
        self.records.append(
            {"tap": name, "nan": nan, "inf": inf, "size": int(arr.size)}
        )
        if nan + inf and self.first_nonfinite is None:
            self.first_nonfinite = name
        return x

    def result(self) -> dict:
        return {
            "first_nonfinite": self.first_nonfinite,
            "injected": self.inject,
            "taps": self.records,
        }


def bisect_forward(forward, inject=None) -> dict:
    """One-shot instrumented re-forward: run ``forward()`` (an EAGER model
    apply on the offending microbatch) with the probe taps armed, and
    return which tap first produced NaN/Inf. Never raises — the bisector
    runs on the incident path and must not take the training loop down."""
    global _TAP_SESSION
    session = _TapSession(inject=inject)
    with _TAP_LOCK:
        _TAP_SESSION = session
        try:
            forward()
        except Exception as e:  # a NaN-tripped assert mid-forward is fine
            session.records.append({"tap": "<error>", "error": repr(e)})
        finally:
            _TAP_SESSION = None
    return session.result()


# --------------------------------------------------------------- census


def _path_str(path) -> str:
    parts = []
    for p in path:
        key = getattr(p, "key", None)
        if key is None:
            key = getattr(p, "name", None)
        if key is None:
            key = getattr(p, "idx", None)
        parts.append(str(key))
    return "/".join(parts)


def nonfinite_census(tree, max_leaves: int = CENSUS_MAX_LEAVES) -> dict:
    """Host-side walk of a (snapshot, undonated) tree naming every
    non-finite leaf by path with NaN/Inf counts. One ``device_get`` of the
    whole tree — incident-path only, never the hot loop."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    named, total = [], 0
    for path, leaf in leaves:
        arr = np.asarray(jax.device_get(leaf))
        if not np.issubdtype(arr.dtype, np.inexact):
            continue
        nan = int(np.isnan(arr).sum())
        inf = int(np.isinf(arr).sum())
        if nan + inf == 0:
            continue
        total += 1
        if len(named) < max_leaves:
            named.append(
                {
                    "path": _path_str(path),
                    "nan": nan,
                    "inf": inf,
                    "size": int(arr.size),
                }
            )
    return {"nonfinite_leaves": named, "total_nonfinite_leaves": total}


# ------------------------------------------------------ quantization error


def _quant_gauges(probe: dict, version=None) -> dict:
    gauges = {}
    for cls in sorted(probe):
        max_err, sq_err, sq_sig, count = probe[cls]
        max_err = float(jax.device_get(max_err))
        sq_err = float(jax.device_get(sq_err))
        sq_sig = float(jax.device_get(sq_sig))
        count = int(count)
        gauges[f"num/quant_err_max/{cls}"] = max_err
        gauges[f"num/quant_err_rms/{cls}"] = math.sqrt(sq_err / max(count, 1))
        # SNR in dB; a bit-exact round trip (sq_err == 0) caps at 200 so
        # the gauge stays finite for the exporter.
        gauges[f"num/quant_snr_db/{cls}"] = (
            10.0 * math.log10(sq_sig / sq_err) if sq_err > 0 and sq_sig > 0 else 200.0
        )
    if gauges and version is not None:
        gauges["num/quant_weight_version"] = float(version)
    return gauges


def record_weight_quant(params, version=None) -> dict:
    """int8 round-trip error of every quantizable trunk kernel, per tensor
    class (c_qkv / c_proj / c_fc / lm_head / ...), recorded as gauges on
    the armed observatory. Best-effort: the handoff path must never fail
    because of telemetry."""
    state = _STATE
    if state is None:
        return {}
    try:
        from trlx_tpu.models.lm import quantize_weights

        probe = {}
        quantize_weights(params, probe=probe)
        gauges = _quant_gauges(probe, version=version)
    except Exception:
        return {}
    state.update_gauges(gauges)
    return gauges


def record_kv_quant(x, label: str = "kv") -> dict:
    """int8 KV round-trip error over an activation tensor (or, at weight
    handoffs where no activation exists, an embedding-derived proxy — see
    ``record_weight_handoff``)."""
    state = _STATE
    if state is None:
        return {}
    try:
        from trlx_tpu.models.lm import quantize_kv

        probe = {}
        quantize_kv(x, probe=probe, probe_class=label)
        gauges = _quant_gauges(probe)
    except Exception:
        return {}
    state.update_gauges(gauges)
    return gauges


def _embedding_proxy(params, rows: int = 64):
    """A [1, rows, 1, d_model] pseudo-activation sliced from the token
    embedding table — a deterministic stand-in for KV-cache content at
    weight handoffs (real activations only exist mid-decode). The absolute
    SNR is approximate; the per-version TREND is the signal."""

    def find_wte(node):
        if not _is_mapping(node):
            return None
        for k, v in node.items():
            if k == "wte" and _is_mapping(v) and "embedding" in v:
                return v["embedding"]
            hit = find_wte(v) if _is_mapping(v) else None
            if hit is not None:
                return hit
        return None

    emb = find_wte(params)
    if emb is None or getattr(emb, "ndim", 0) != 2:
        return None
    take = min(rows, int(emb.shape[0]))
    return jnp.asarray(emb[:take]).reshape(1, take, 1, int(emb.shape[1]))


# Versions seen at recent weight handoffs, newest-last. With in-flight
# updates (engine.update_weights mid-decode) a phase's episodes can span
# SEVERAL versions — the per-version quant gauges above only tag the
# latest, so this window is what says how many versions are concurrently
# "live" in decode output (the span-form companion of the PR 15 scalar
# telemetry). Sized to comfortably cover one experience phase.
_HANDOFF_VERSIONS: list = []
_HANDOFF_WINDOW = 8


def record_weight_handoff(variables, version=None) -> dict:
    """Quant-error probe at a versioned weight handoff (engine
    ``update_weights`` / W8A16 snapshot): weight round-trip error per
    kernel class plus the embedding-proxy KV error, plus the count of
    distinct versions across the recent handoff window
    (``num/quant_versions_in_flight``). No-op when disarmed."""
    if _STATE is None or not isinstance(variables, dict):
        return {}
    params = variables.get("params")
    if params is None:
        return {}
    gauges = dict(record_weight_quant(params, version=version))
    proxy = _embedding_proxy(params)
    if proxy is not None:
        gauges.update(record_kv_quant(proxy))
    if version is not None:
        _HANDOFF_VERSIONS.append(int(version))
        del _HANDOFF_VERSIONS[:-_HANDOFF_WINDOW]
        inflight = {"num/quant_versions_in_flight": float(len(set(_HANDOFF_VERSIONS)))}
        _STATE.update_gauges(inflight)
        gauges.update(inflight)
    return gauges


# ------------------------------------------------------------- detectors


class GradNormSpikeDetector(HysteresisDetector):
    """Global grad norm vs its own rolling p50: WARN past ``warn_factor`` ×
    p50, CRIT past ``crit_factor`` × p50. The spike is judged BEFORE it
    enters the window, so a blow-up cannot inflate its own baseline."""

    name = "grad_norm_spike"

    def __init__(
        self,
        warn_factor: float = 3.0,
        crit_factor: float = 10.0,
        window: int = 64,
        warmup: int = 5,
        **streaks,
    ):
        super().__init__(**streaks)
        self.warn_factor = float(warn_factor)
        self.crit_factor = float(crit_factor)
        self.window = int(window)
        self.warmup = int(warmup)
        self.value = 0.0
        self._history = []

    def p50(self) -> float:
        return float(np.median(self._history)) if self._history else 0.0

    def severity(self, obs) -> int:
        g = float(obs)
        self.value = g
        baseline = self.p50()
        seeded = len(self._history) >= self.warmup
        sev = 0
        if not math.isfinite(g):
            sev = 2
        elif seeded and baseline > 0:
            if g > self.crit_factor * baseline:
                sev = 2
            elif g > self.warn_factor * baseline:
                sev = 1
        if sev == 0 and math.isfinite(g):
            # Only clean observations feed the baseline.
            self._history.append(g)
            if len(self._history) > self.window:
                self._history.pop(0)
        return sev


class UpdateRatioDetector(HysteresisDetector):
    """Per-subtree update/param ratio band: the realized step size should
    sit inside [lo, hi] per update. Ratios ABOVE the band mean the
    optimizer is rewriting a subtree (LR too hot for it); a WHOLLY stalled
    step (every ratio 0 — the guard skipping, or a dead schedule) reads as
    a violation too. Severity scales with the violating fraction."""

    name = "update_ratio"

    def __init__(
        self,
        lo: float = 1e-8,
        hi: float = 1e-1,
        warmup: int = 5,
        **streaks,
    ):
        super().__init__(**streaks)
        self.lo = float(lo)
        self.hi = float(hi)
        self.warmup = int(warmup)
        self.seen = 0
        self.violating = 0
        self.total = 0

    def severity(self, obs) -> int:
        ratios = {k: float(v) for k, v in dict(obs).items()}
        self.total = len(ratios)
        self.seen += 1
        if not ratios:
            return 0
        bad = sum(
            1
            for r in ratios.values()
            if not math.isfinite(r) or r > self.hi or (0.0 < r < self.lo)
        )
        stalled = all(r == 0.0 for r in ratios.values())
        self.violating = bad + (self.total if stalled else 0)
        if self.seen <= self.warmup:
            return 0
        extreme = any(
            not math.isfinite(r) or r > 10.0 * self.hi for r in ratios.values()
        )
        if extreme or self.violating >= max(1, self.total // 2 + self.total % 2):
            return 2 if self.violating else 0
        return 1 if self.violating else 0


def escalate(detector, obs):
    """CRIT escalation when no HealthMonitor is armed to adopt the
    detectors: the same ``register_emergency`` incident hook, the same
    ``health_<name>`` reason the monitor's own escalation uses, so the
    report's cross-links work either way."""
    from trlx_tpu.observability.anomaly import emergency_capture

    detail = {"detector": detector.name, "severity": int(detector.last_severity)}
    if isinstance(obs, dict):
        detail.update({k: v for k, v in obs.items() if isinstance(v, (int, float))})
    else:
        try:
            detail["observation"] = float(obs)
        except (TypeError, ValueError):
            pass
    emergency_capture(f"health_{detector.name}", detail=detail)


# -------------------------------------------------------- module instance


class _Numerics:
    """Process-global armed state: the two detectors plus the latest
    quant-error gauges (updated from handoff sites, drained into the
    log-boundary stats by the trainer)."""

    def __init__(self):
        self.grad_detector = GradNormSpikeDetector()
        self.ratio_detector = UpdateRatioDetector()
        self.detectors = (self.grad_detector, self.ratio_detector)
        self._gauges = {}
        self._lock = threading.Lock()

    def update_gauges(self, gauges: dict):
        if not gauges:
            return
        with self._lock:
            self._gauges.update(gauges)

    def observe_train(self, stats_host: dict):
        """Log-boundary feed from the synced stats dict (the owner-feeds
        contract of ``register_detector``)."""
        g = stats_host.get("num/grad_global_norm")
        if g is not None:
            self.grad_detector.observe(float(g))
        prefix = "num/update_ratio/"
        ratios = {
            k[len(prefix):]: v for k, v in stats_host.items() if k.startswith(prefix)
        }
        if ratios:
            self.ratio_detector.observe(ratios)

    def gauges(self, include_states: bool = False) -> dict:
        """Latest quant-error gauges (+ detector states when no armed
        HealthMonitor is emitting them already)."""
        with self._lock:
            out = dict(self._gauges)
        if include_states:
            level = {OK: 0.0, WARN: 1.0, CRIT: 2.0}
            for d in self.detectors:
                out[f"health/{d.name}_state"] = level[d.state]
        return out


_STATE = None


def configure() -> _Numerics:
    """Arm the process-global observatory (trainer construction owns it,
    like the span tracer: a prior armed trainer's gauges must not leak
    into this run)."""
    global _STATE
    _STATE = _Numerics()
    # A prior run's handoff-version window must not inflate this run's
    # versions-in-flight gauge.
    del _HANDOFF_VERSIONS[:]
    return _STATE


def shutdown():
    global _STATE, _PENDING_INJECTION
    _STATE = None
    _PENDING_INJECTION = None


def enabled() -> bool:
    return _STATE is not None


def instance():
    return _STATE


# -------------------------------------------------------- incident writer


def write_incident(bundle_dir: str, payload: dict):
    """Attach the numerics forensics to an incident bundle (best-effort —
    the incident path must never raise into the training loop). Returns
    the written path or None."""
    if not bundle_dir:
        return None
    try:
        path = os.path.join(bundle_dir, NUMERICS_FILENAME)
        with open(path, "w") as f:
            json.dump(payload, f, indent=2, default=str)
        return path
    except (OSError, TypeError, ValueError):
        return None
