"""Unified observability layer (PR 8) + training-health monitor (PR 9)
+ graftfleet cross-host federation (PR 14)
+ graftnum streaming numerics observatory (PR 15).

Eight parts, all off-hot-path and, but for the flight recorder for stalls
(``spans``' ring and ``anomaly``'s detector, counters and ``stalls.jsonl``:
on in every run), off by default:

- ``spans``     — the one way host work is timed: ``with trace_span(name)``
                  is always a profiler annotation and feeds the ``time/*``
                  keys; armed (``train.trace_spans`` / ``TRLX_TPU_SPANS=1``)
                  it also writes Chrome trace events to
                  ``<ckpt_dir>/spans.jsonl``;
- ``devicemon`` — compiled-cost capture (``cost_analysis`` /
                  ``memory_analysis``) for every jitted program, real-FLOPs
                  MFU gauges, kernel-routing + device-memory gauges
                  (``train.device_telemetry`` / ``TRLX_TPU_DEVICE_TELEMETRY=1``);
- ``anomaly``   — the flight recorder (always on: a rolling-median detector
                  over every step and rollout at 1.5x, process
                  counters, the ticker, ``<ckpt_dir>/stalls.jsonl``) +
                  one-shot incident bundles under
                  ``<ckpt_dir>/incidents/<step>/`` on the same median
                  (``train.anomaly_factor`` / ``TRLX_TPU_ANOMALY_FACTOR``);
- ``health``    — streaming RLHF health detectors (reward drift, KL
                  controller, entropy collapse, value EV, rollout sentinels)
                  with OK/WARN/CRIT hysteresis, ``health/*`` gauges, and
                  per-chunk lineage records (``train.health_monitor`` /
                  ``TRLX_TPU_HEALTH=1``);
- ``export``    — live Prometheus-text ``/metrics`` + JSON ``/healthz``
                  endpoint from process 0 (``train.metrics_port`` /
                  ``TRLX_TPU_METRICS_PORT``);
- ``report``    — ``python -m trlx_tpu.observability.report <ckpt_dir>``
                  renders everything as one markdown performance report;
- ``fleet``     — graftfleet cross-host federation: per-host span lanes
                  merged under a barrier-estimated clock alignment,
                  per-collective straggler attribution from guarded-
                  collective arrival records, fleet health rollup on
                  ``/healthz``, and cross-host incident bundles
                  (``train.graftfleet`` / ``TRLX_TPU_GRAFTFLEET=1``);
- ``numerics``  — graftnum streaming numerics observatory: per-subtree
                  grad/update-ratio telemetry folded into the jitted step
                  at build time (``num/*`` gauges), NaN provenance (leaf
                  census + first-NaN layer bisect) attached to guard-skip
                  incident bundles, quantization-error tracking at weight
                  handoffs, and grad-spike / update-ratio health detectors
                  (``train.graftnum`` / ``TRLX_TPU_GRAFTNUM=1``).

See RUNBOOK.md §8 (performance), §9 (training health), §14 (fleet
observability) and §15 (numerics observability) for knobs and triage.
"""

import os

from trlx_tpu.observability import fleet  # noqa: F401 — canonical import point
from trlx_tpu.observability import numerics  # noqa: F401 — canonical import point
from trlx_tpu.observability import spans  # noqa: F401 — canonical import point
from trlx_tpu.observability.anomaly import AnomalyDetector, IncidentCapture  # noqa: F401
from trlx_tpu.observability.devicemon import DeviceMonitor  # noqa: F401
from trlx_tpu.observability.health import HealthMonitor, LineageRecord  # noqa: F401
from trlx_tpu.observability.spans import instant, trace_span  # noqa: F401


def env_flag(name: str) -> bool:
    """True when the env var is set to anything but '' / '0' (the same
    convention as TRLX_TPU_DISABLE_TRACKER)."""
    return os.environ.get(name, "") not in ("", "0")
