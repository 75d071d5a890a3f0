"""In-process metrics: a stdlib-only registry + /metrics + /healthz.

graftscope's event stream answers "what happened"; a fleet router (and a
human with a Grafana tab) needs "what is true RIGHT NOW" — queue depth,
occupancy, SLO attainment, step cadence — scrapeable without touching the
stream files.  This module is that surface:

* :class:`MetricsRegistry` — counters / gauges / histograms keyed by
  (name, labels).  Fed two ways: **directly** (the serve scheduler sets
  queue-depth/occupancy gauges as it schedules — works with telemetry
  off), and **from the emit path** (``Telemetry.attach_metrics`` routes
  every event through :meth:`MetricsRegistry.observe_event`, deriving
  step gauges and ckpt/fault/alert counters — no second instrumentation
  pass).  Detached, the cost is one attribute check per event: the same
  free-when-off contract as ``GRAFT_TELEMETRY=0``.
* :class:`MetricsServer` — a ``ThreadingHTTPServer`` on a daemon thread
  serving ``/metrics`` (Prometheus text exposition v0.0.4) and
  ``/healthz`` (JSON liveness a supervisor curls).  The render path is
  bounded in tests: a 1k-series scrape must stay under 50 ms.

Stdlib-only like the rest of ``obs``: the endpoint must keep answering
while the step loop is hung in a device call — that is when the operator
is staring at the dashboard hardest.
"""
from __future__ import annotations

import http.server
import json
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from ..utils import locks

# default histogram buckets: serve latencies span ~ms..minute
DEFAULT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                   1.0, 2.5, 5.0, 10.0, 30.0, 60.0)

_LabelKey = Tuple[Tuple[str, str], ...]


def _label_key(labels: Dict[str, str]) -> _LabelKey:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _render_labels(key: _LabelKey, extra: Tuple[Tuple[str, str], ...] = ()
                   ) -> str:
    items = key + extra
    if not items:
        return ""
    body = ",".join(
        '%s="%s"' % (k, v.replace("\\", "\\\\").replace('"', '\\"')
                     .replace("\n", "\\n"))
        for k, v in items)
    return "{" + body + "}"


class Counter:
    """Monotonically increasing float (one labeled series)."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def inc(self, v: float = 1.0) -> None:
        self.value += v


class Gauge:
    """Set-to-current-value float (one labeled series)."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)

    def inc(self, v: float = 1.0) -> None:
        self.value += v


class Histogram:
    """Cumulative-bucket histogram (one labeled series)."""

    __slots__ = ("buckets", "counts", "sum", "count", "_lock")

    def __init__(self, buckets: Tuple[float, ...] = DEFAULT_BUCKETS):
        self.buckets = tuple(sorted(buckets))
        self.counts = [0] * len(self.buckets)
        self.sum = 0.0
        self.count = 0
        self._lock = locks.TracedLock("metrics.histogram")

    def observe(self, v: float) -> None:
        with self._lock:
            self.sum += v
            self.count += 1
            # counts are per-bucket; render() accumulates into the
            # cumulative le-series the exposition format wants
            for i, le in enumerate(self.buckets):
                if v <= le:
                    self.counts[i] += 1
                    break


class MetricsRegistry:
    """Thread-safe instrument registry with Prometheus text rendering.

    ``counter/gauge/histogram`` are get-or-create on (name, labels), so
    hot paths call them inline without holding references; creation takes
    the registry lock, subsequent lookups hit a dict."""

    def __init__(self):
        self._lock = locks.TracedLock("metrics.registry")
        # name -> (kind, help, {label_key -> instrument})
        self._families: Dict[str, Tuple[str, str, Dict[_LabelKey, object]]] \
            = {}
        self.created_at = time.monotonic()

    def _get(self, kind: str, name: str, help_: str, labels: Dict[str, str],
             factory: Callable[[], object]):
        key = _label_key(labels)
        # lock-free fast path: after first creation every hot-path call is
        # two dict gets (CPython dict reads are atomic; a racing creation
        # falls through to the locked slow path and setdefault wins once)
        fam = self._families.get(name)  # graftrace: unguarded (hot-path read; a miss or torn view only falls through to the locked setdefault below)
        if fam is not None:
            inst = fam[2].get(key)
            if inst is not None:
                return inst
        with self._lock:
            fam = self._families.setdefault(name, (kind, help_, {}))
            if fam[0] != kind:
                raise ValueError(
                    f"metric {name!r} already registered as {fam[0]}")
            return fam[2].setdefault(key, factory())  # graftrace: allow=T4 (factory is one of our instrument constructors — Counter/Gauge/Histogram — never caller code, so it cannot re-enter the registry)

    def counter(self, name: str, help: str = "", **labels) -> Counter:
        return self._get("counter", name, help, labels, Counter)

    def gauge(self, name: str, help: str = "", **labels) -> Gauge:
        return self._get("gauge", name, help, labels, Gauge)

    def histogram(self, name: str, help: str = "",
                  buckets: Tuple[float, ...] = DEFAULT_BUCKETS,
                  **labels) -> Histogram:
        return self._get("histogram", name, help, labels,
                         lambda: Histogram(buckets))

    @property
    def series_count(self) -> int:
        with self._lock:
            return sum(len(fam[2]) for fam in self._families.values())

    def render(self) -> str:
        """Prometheus text exposition format v0.0.4.  The family/series
        tables are snapshotted under the registry lock — the /metrics
        scrape thread renders while hot paths register new series, and
        iterating the live dicts would die with "dict changed size during
        iteration".  Instrument values are read lock-free (atomic
        attribute reads; a scrape sees each counter at some recent
        point)."""
        with self._lock:
            families = {name: (fam[0], fam[1], dict(fam[2]))
                        for name, fam in self._families.items()}
        lines: List[str] = []
        for name in sorted(families):
            kind, help_, series = families[name]
            if help_:
                lines.append(f"# HELP {name} {help_}")
            lines.append(f"# TYPE {name} {kind}")
            for key in sorted(series):
                inst = series[key]
                if kind == "histogram":
                    cum = 0
                    for le, n in zip(inst.buckets, inst.counts):
                        cum += n
                        lines.append(
                            f"{name}_bucket"
                            f"{_render_labels(key, (('le', repr(le)),))}"
                            f" {cum}")
                    lines.append(
                        f"{name}_bucket"
                        f"{_render_labels(key, (('le', '+Inf'),))}"
                        f" {inst.count}")
                    lines.append(
                        f"{name}_sum{_render_labels(key)} {inst.sum}")
                    lines.append(
                        f"{name}_count{_render_labels(key)} {inst.count}")
                else:
                    lines.append(
                        f"{name}{_render_labels(key)} {inst.value}")
        return "\n".join(lines) + "\n"

    # --- the emit-path feed (Telemetry.attach_metrics) --------------------

    def observe_event(self, rec: dict) -> None:
        """Derive series from one telemetry record.  Step gauges and
        ckpt/fault/quarantine/health/alert counters live here; serve
        series are DIRECT-instrumented by the scheduler (they must work
        with telemetry off, and deriving them here too would double
        count)."""
        kind = rec.get("kind", "?")
        self.counter("graft_events_total",
                     "telemetry records by kind", kind=kind).inc()
        if kind == "step" and "ph" not in rec:
            self.counter("graft_steps_total", "training steps logged").inc()
            if rec.get("step") is not None:
                self.gauge("graft_step", "last logged global step").set(
                    float(rec["step"]))
            for field, metric, help_ in (
                    ("loss", "graft_step_loss", "last logged loss"),
                    ("step_time_s", "graft_step_time_seconds",
                     "step-time EMA"),
                    ("mfu", "graft_step_mfu", "model FLOPs utilization"),
                    ("loader_stall_frac", "graft_loader_stall_frac",
                     "loader stall fraction of step time")):
                if rec.get(field) is not None:
                    self.gauge(metric, help_).set(float(rec[field]))
        elif kind == "ckpt":
            name = rec.get("name", "?")
            if name == "publish":
                self.counter("graft_ckpt_publishes_total",
                             "committed checkpoint manifests").inc()
            elif name in ("save_failed", "fallback_skip", "save_retry"):
                self.counter("graft_ckpt_incidents_total",
                             "checkpoint retries/failures/fallbacks",
                             incident=name).inc()
        elif kind == "fault":
            self.counter("graft_faults_total", "injected faults fired",
                         site=rec.get("name", "?")).inc()
        elif kind == "data" and str(rec.get("name", "")).endswith(
                "quarantine"):
            self.counter("graft_quarantines_total", "quarantined inputs",
                         what=rec.get("name", "?")).inc()
        elif kind == "health" and rec.get("name") not in (None, "ok"):
            self.counter("graft_health_verdicts_total",
                         "non-ok health verdicts",
                         verdict=rec.get("name", "?")).inc()
        elif kind == "alert":
            self.counter("graft_alerts_total", "alert rules fired",
                         rule=rec.get("name", "?")).inc()
        elif kind == "prof" and rec.get("name") == "predicted":
            # the roofline ceiling the perf ledger predicts for this
            # config — scrape beside graft_step_mfu for the
            # predicted-vs-measured panel
            if rec.get("mfu") is not None:
                self.gauge("graft_predicted_mfu",
                           "roofline-predicted MFU ceiling "
                           "(PERF_LEDGER.json)").set(float(rec["mfu"]))
        elif kind == "mem" and rec.get("name") == "watermark":
            # MemTracker phase-boundary polls (obs/mem.py) — the HBM
            # panel the hbm_headroom alert watches
            if rec.get("used_bytes") is not None:
                self.gauge("graft_hbm_used_bytes",
                           "device memory in use at the last "
                           "mem.watermark").set(float(rec["used_bytes"]))
            if rec.get("peak_bytes") is not None:
                self.gauge("graft_hbm_peak_bytes",
                           "high-watermark device memory").set(
                    float(rec["peak_bytes"]))
            if rec.get("headroom_bytes") is not None:
                self.gauge("graft_hbm_headroom_bytes",
                           "bytes of HBM left before the limit").set(
                    float(rec["headroom_bytes"]))


class _Handler(http.server.BaseHTTPRequestHandler):
    # the server instance carries .registry / .health_fn / .started_at

    def do_GET(self):  # noqa: N802 (http.server API)
        if self.path.split("?")[0] == "/metrics":
            body = self.server.registry.render().encode()
            ctype = "text/plain; version=0.0.4; charset=utf-8"
        elif self.path.split("?")[0] == "/healthz":
            payload = {"ok": True,
                       "uptime_s": round(
                           time.monotonic() - self.server.started_at, 3),
                       "series": self.server.registry.series_count}
            if self.server.health_fn is not None:
                try:
                    payload.update(self.server.health_fn())
                # graftlint: disable=EXC001 (liveness must answer even when the health callback is broken; the error is reported in-band)
                except Exception as e:
                    payload.update(ok=False, error=repr(e))
            body = (json.dumps(payload) + "\n").encode()
            ctype = "application/json"
        else:
            self.send_error(404)
            return
        self.send_response(200)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *args):  # silence per-request stderr noise
        pass


class MetricsServer:
    """/metrics + /healthz on a daemon thread.  ``port=0`` binds an
    ephemeral port (tests); the bound port is ``self.port``."""

    def __init__(self, port: int, registry: MetricsRegistry, *,
                 health_fn: Optional[Callable[[], dict]] = None,
                 host: str = "0.0.0.0"):
        self.registry = registry
        self._httpd = http.server.ThreadingHTTPServer((host, port), _Handler)
        self._httpd.daemon_threads = True
        self._httpd.registry = registry
        self._httpd.health_fn = health_fn
        self._httpd.started_at = time.monotonic()
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="graft-metrics",
            daemon=True)
        self._thread.start()

    def close(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5.0)


# --- module singleton: how the serve scheduler participates ----------------

_active_registry: Optional[MetricsRegistry] = None


def init(registry: Optional[MetricsRegistry] = None) -> MetricsRegistry:
    """Install the process-wide registry (the serve scheduler and anything
    else that direct-instruments looks it up via :func:`active`)."""
    global _active_registry
    _active_registry = registry if registry is not None else MetricsRegistry()
    return _active_registry


def active() -> Optional[MetricsRegistry]:
    """The installed registry, or None — direct-instrumentation sites
    guard with ``if reg is not None`` so the detached path is one module
    attribute read."""
    return _active_registry


def shutdown() -> None:
    global _active_registry
    _active_registry = None


def serve(port: int, registry: Optional[MetricsRegistry] = None, *,
          health_fn: Optional[Callable[[], dict]] = None) -> MetricsServer:
    """Start the endpoint over ``registry`` (default: the installed one,
    installing a fresh one if none)."""
    reg = registry or active() or init()
    return MetricsServer(port, reg, health_fn=health_fn)
