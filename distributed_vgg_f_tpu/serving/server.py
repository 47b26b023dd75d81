"""Always-on predict server — the u8-wire HTTP front end over the dynamic
batcher (r17; ROADMAP item 1, the serving half of arXiv 1605.08695's
training/serving split).

Request contract (deliberately the thinnest thing that carries the u8
wire over HTTP — the wire IS the payload format, HTTP adds routing only):

    POST /v1/predict/<model>        body: raw uint8 pixels, C-order,
                                    exactly image_size*image_size*3 bytes
                                    (1 B/px off the network; the device-
                                    finish prologue normalizes on device)
    → 200 {"model", "top_k": [{"class", "prob"}...], "bucket",
           "latency_ms"}            prob at FULL precision: the bitwise
                                    parity gate vs offline run_predict
                                    needs exact values, not display
                                    rounding
    → 400 {"error": "bad_request", ...}      wrong size/model
    → 503 {"error": "overloaded", "kind": "shed"|"draining",
           "queue_depth", "queue_limit", "retry_after_ms"}
                                    + Retry-After header — the typed shed
                                    payload; the queue is bounded and the
                                    server NEVER converts overload into
                                    unbounded latency
    → 504 {"error": "timeout"}      batcher answered nothing within
                                    serving.request_timeout_s
    GET  /v1/models                 the routing table (one row per
                                    registered engine, descriptor receipt
                                    included)

Observability is the EXISTING plane, extended, not a parallel one:
`serving/*` counters + latency-quantile gauges land in the process
registry (scraped at /metrics), the housekeeping loop heartbeats the
process exporter so `/healthz` is a real LB health check for the serving
process (the heartbeat means "the serve loop is alive", so an idle server
stays healthy), per-window summaries ride the flight recorder's ring (a
crash dumps the same black box a trainer crash does), and `/servingz`
serves the live admission state through the provider-registration pattern
(`telemetry/exporter.set_serving_source` — telemetry never imports this
package).

One server fronts the whole zoo: `add_engine` registers one
`PredictEngine` per model (each with its own batcher + admission
controller), routed by URL path over the `IngestDescriptor` table's
names.

Latency tiers (r23): the routing key is (model, TIER). A request picks
its tier with `?tier=fp32|bf16|int8|student` (unknown values are a typed
400 naming the ladder); absent the parameter it gets the configured
`serving.tier_default`. Every tier is a full engine with its own batcher
— batches never mix tiers, so the per-tier bitwise parity contract and
the per-tier latency quantiles (`serving/tier_latency_*`) are both
meaningful. The whole surface sits behind the kill switch
`serving.tiers.enabled` (default OFF): disabled, `add_engine` refuses
non-fp32 engines, the query parameter is ignored exactly as r22 ignored
it, and the server lowers and routes precisely the r22 fp32-only plane.
"""

from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, Optional

import numpy as np

from distributed_vgg_f_tpu import telemetry
from distributed_vgg_f_tpu.config import SERVING_TIERS
from distributed_vgg_f_tpu.serving.batcher import DynamicBatcher, OverloadShed
from distributed_vgg_f_tpu.serving.controller import AdmissionController
from distributed_vgg_f_tpu.serving.engine import PredictEngine


class _HTTPServer(ThreadingHTTPServer):
    daemon_threads = True
    #: listen() backlog. The stdlib default (5) refuses connections the
    #: moment an open-loop burst arrives faster than accept() turns —
    #: overload must reach the ADMISSION queue and shed with a typed 503,
    #: not die as TCP connection resets three layers below it.
    request_queue_size = 512

#: Counters/gauges pre-created at server start (the r11 discipline: a
#: visible zero reads as "instrumented, nothing happened" — and the README
#: counter-table drift guard scans these literals).
def _precreate(reg) -> None:
    reg.counter("serving/requests")
    reg.counter("serving/admitted")
    reg.counter("serving/shed")
    reg.counter("serving/errors")
    reg.counter("serving/batches")
    reg.counter("serving/batch_images")
    reg.counter("serving/padded_images")
    reg.counter("serving/controller_actuations")
    reg.set_gauge("serving/queue_depth", 0)
    reg.set_gauge("serving/models", 0)
    reg.set_gauge("serving/shed_rate", 0.0)
    reg.set_gauge("serving/window_ms", 0)
    # quantile gauges pre-created literally (the drift guard scans
    # literals); the housekeeping loop refreshes them per window
    reg.set_gauge("serving/latency_p50_ms", 0.0)
    reg.set_gauge("serving/latency_p95_ms", 0.0)
    reg.set_gauge("serving/latency_p99_ms", 0.0)
    # per-tier request counters + latency quantiles (r23) — one literal
    # per (tier, metric): the drift guard scans call literals, so a loop
    # over SERVING_TIERS here would hide the names from the lint
    reg.counter("serving/tier_requests_fp32")
    reg.counter("serving/tier_requests_bf16")
    reg.counter("serving/tier_requests_int8")
    reg.counter("serving/tier_requests_student")
    reg.set_gauge("serving/tier_latency_p50_ms_fp32", 0.0)
    reg.set_gauge("serving/tier_latency_p50_ms_bf16", 0.0)
    reg.set_gauge("serving/tier_latency_p50_ms_int8", 0.0)
    reg.set_gauge("serving/tier_latency_p50_ms_student", 0.0)
    reg.set_gauge("serving/tier_latency_p99_ms_fp32", 0.0)
    reg.set_gauge("serving/tier_latency_p99_ms_bf16", 0.0)
    reg.set_gauge("serving/tier_latency_p99_ms_int8", 0.0)
    reg.set_gauge("serving/tier_latency_p99_ms_student", 0.0)


class PredictServer:
    """HTTP front end + model router + housekeeping loop."""

    def __init__(self, serving_cfg, *, registry=None, flight=None):
        self.cfg = serving_cfg
        self._reg = registry if registry is not None \
            else telemetry.get_registry()
        if flight is None:
            from distributed_vgg_f_tpu.telemetry.flight import get_flight
            flight = get_flight()
        self._flight = flight
        _precreate(self._reg)
        # routing key: (model, tier) — one engine + one batcher per pair,
        # so batches never mix tiers (r23)
        self._engines: Dict[tuple, PredictEngine] = {}
        self._batchers: Dict[tuple, DynamicBatcher] = {}
        self._controllers: Dict[tuple, AdmissionController] = {}
        self._lock = threading.Lock()
        self._server: Optional[ThreadingHTTPServer] = None
        self._serve_thread: Optional[threading.Thread] = None
        self._house_thread: Optional[threading.Thread] = None
        self._closed = threading.Event()
        self._windows = 0
        self._started_mono = time.monotonic()
        # ONE bound-method object for register AND compare-and-clear:
        # `self.servingz_payload` is a fresh object per attribute access,
        # so clearing with a second access would never match `is`
        self._servingz_source = self.servingz_payload

    # --------------------------------------------------------------- routing
    def _tiers_enabled(self) -> bool:
        tiers = getattr(self.cfg, "tiers", None)
        return bool(tiers is not None and tiers.enabled)

    def add_engine(self, engine: PredictEngine) -> None:
        """Register one (model, tier) engine — its own batcher and (when
        configured) admission controller; the URL path routes by
        `engine.model_name`, the `?tier=` query by `engine.tier`. With
        `serving.tiers.enabled` false (the kill switch) only fp32 engines
        register: the disabled server cannot even HOLD a tier ladder, so
        its lowered surface is structurally the r22 one."""
        tier = str(getattr(engine, "tier", "fp32"))
        if tier != "fp32" and not self._tiers_enabled():
            raise ValueError(
                f"engine ({engine.model_name!r}, tier={tier!r}) refused: "
                "serving.tiers.enabled is false — the kill switch pins "
                "this server to the fp32-only surface")
        key = (engine.model_name, tier)
        with self._lock:
            if key in self._engines:
                raise ValueError(f"model {engine.model_name!r} tier "
                                 f"{tier!r} already registered")
            batcher = DynamicBatcher(
                engine, max_batch=self.cfg.max_batch,
                window_ms=self.cfg.max_latency_ms,
                queue_limit=self.cfg.queue_limit,
                # queue entries older than the request timeout are
                # expired, never run: their handlers already replied 504
                reap_after_s=self.cfg.request_timeout_s,
                registry=self._reg)
            self._engines[key] = engine
            self._batchers[key] = batcher
            if self.cfg.controller:
                self._controllers[key] = AdmissionController(
                    self.cfg, batcher, registry=self._reg,
                    flight=self._flight)
            # the gauge keeps its r22 meaning: distinct MODELS, not engines
            self._reg.set_gauge(
                "serving/models", len({m for m, _ in self._engines}))
        if self.cfg.warmup:
            engine.warmup()

    def engine(self, model: str,
               tier: str = "fp32") -> Optional[PredictEngine]:
        with self._lock:
            return self._engines.get((model, tier))

    def _model_tiers(self, model: str):
        """Registered tiers for one model, ladder order."""
        with self._lock:
            mine = {t for m, t in self._engines if m == model}
        return [t for t in SERVING_TIERS if t in mine]

    # ------------------------------------------------------------- lifecycle
    @property
    def port(self) -> Optional[int]:
        return self._server.server_address[1] if self._server else None

    @property
    def endpoint(self) -> str:
        return f"{self.cfg.host}:{self.port}"

    def start(self) -> int:
        """Bind + serve + start housekeeping; returns the BOUND port (the
        port-0 contract every server in this repo follows)."""
        if self._server is not None:
            return self.port
        srv = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):  # noqa: N802 — quiet
                pass

            def do_POST(self):  # noqa: N802
                srv._handle_post(self)

            def do_GET(self):  # noqa: N802
                srv._handle_get(self)

        self._server = _HTTPServer(
            (self.cfg.host, int(self.cfg.port)), Handler)
        self._started_mono = time.monotonic()
        self._serve_thread = threading.Thread(
            target=self._server.serve_forever, name="serving-http",
            daemon=True)
        self._serve_thread.start()
        self._house_thread = threading.Thread(
            target=self._housekeeping, name="serving-housekeeping",
            daemon=True)
        self._house_thread.start()
        from distributed_vgg_f_tpu.telemetry import exporter as _exp
        _exp.set_serving_source(self._servingz_source)
        return self.port

    def wait(self) -> None:
        """Block the caller (the CLI serve mode) until close()."""
        self._closed.wait()

    def close(self) -> None:
        """Drain, don't drop: stop admission + the listener, answer every
        in-flight request, then tear the threads down."""
        if self._closed.is_set():
            return
        self._closed.set()
        from distributed_vgg_f_tpu.telemetry import exporter as _exp
        _exp.clear_serving_source(self._servingz_source)
        server, self._server = self._server, None
        if server is not None:
            server.shutdown()
            server.server_close()
        with self._lock:
            batchers = list(self._batchers.values())
        for b in batchers:
            b.close()
        for t in (self._serve_thread, self._house_thread):
            if t is not None:
                t.join(timeout=10)

    # ---------------------------------------------------------- housekeeping
    def _housekeeping(self) -> None:
        """The serve loop's pulse: per interval, feed each model's
        controller its window evidence, refresh the latency-quantile
        gauges, append a window to the flight ring, and heartbeat the
        process exporter (the serving heartbeat /healthz reads — ticked
        whether or not traffic arrives, so an idle server is healthy and a
        stuck one goes 503)."""
        interval = max(0.01, float(self.cfg.controller_interval_s))
        while not self._closed.wait(interval):
            self._windows += 1
            # the whole window body is receipts: an exception here must
            # never kill the loop — a dead housekeeping thread silences
            # the heartbeat and an LB would drain a server that is still
            # answering requests
            try:
                self._housekeeping_window(interval)
            except Exception:  # noqa: BLE001 — receipts never kill serving
                self._reg.inc("serving/errors")
            from distributed_vgg_f_tpu.telemetry import exporter as _exp
            exp = _exp.get_exporter()
            if exp is not None:
                exp.heartbeat(self._windows)

    def _housekeeping_window(self, interval: float) -> None:
        lat_all = []
        lat_by_tier: Dict[str, list] = {}
        shed = admitted = 0
        depth_total = 0
        window_max = 0
        verdicts = {}
        with self._lock:
            items = list(self._batchers.items())
            controllers = dict(self._controllers)
        for key, batcher in items:
            stats = batcher.window_stats()
            lat_all.extend(stats["latencies_ms"])
            lat_by_tier.setdefault(key[1], []).extend(
                stats["latencies_ms"])
            shed += stats["shed"]
            admitted += stats["admitted"]
            depth_total += stats["queue_depth"]
            window_max = max(window_max, batcher.window_ms)
            ctrl = controllers.get(key)
            if ctrl is not None:
                verdicts[key] = ctrl.observe_window(stats)[
                    "serving_verdict"]
            else:
                verdicts[key] = "steady"
        # process-global gauges AGGREGATE across models (sum of depths,
        # widest live window) — per-model detail lives on /servingz; two
        # batchers writing one gauge would be last-writer-wins garbage
        self._reg.set_gauge("serving/queue_depth", depth_total)
        self._reg.set_gauge("serving/window_ms", window_max)
        total = shed + admitted
        self._reg.set_gauge("serving/shed_rate",
                            round(shed / total, 4) if total else 0.0)
        quantiles = _quantiles(lat_all)
        for key, value in quantiles.items():
            self._reg.set_gauge(f"serving/latency_{key}_ms", value)
        # per-tier quantiles (precreated literally in _precreate; refreshed
        # dynamically here — the drift guard scans literals, not refreshes)
        for tier, lats in lat_by_tier.items():
            tq = _quantiles(lats)
            if tq:
                self._reg.set_gauge(
                    f"serving/tier_latency_p50_ms_{tier}", tq["p50"])
                self._reg.set_gauge(
                    f"serving/tier_latency_p99_ms_{tier}", tq["p99"])
        # the worst per-model verdict labels the window in the ring
        verdict = "queue_pressure" if "queue_pressure" in \
            verdicts.values() else "steady"
        self._flight.record_window(
            step=self._windows,
            wall_s=interval,
            stall={"verdict": verdict,
                   "shed": shed, "admitted": admitted,
                   **({"p99_ms": quantiles["p99"]}
                      if quantiles else {})},
            counters={"serving/shed": shed,
                      "serving/admitted": admitted})

    # -------------------------------------------------------------- handling
    def _handle_post(self, req: BaseHTTPRequestHandler) -> None:
        self._reg.inc("serving/requests")
        t0 = time.monotonic()
        try:
            path = req.path.split("?", 1)[0]
            query = req.path.partition("?")[2]
            if not path.startswith("/v1/predict/"):
                _reply(req, 404, {"error": "not found",
                                  "endpoints": ["/v1/predict/<model>",
                                                "/v1/models"]})
                return
            model = path[len("/v1/predict/"):].strip("/")
            tiers_on = self._tiers_enabled()
            requested = _tier_from_query(query) if tiers_on else None
            if requested is not None and requested not in SERVING_TIERS:
                # the typed tier 400: names the offending value AND the
                # ladder, so a client can self-correct without docs
                _reply(req, 400, {"error": "bad_request",
                                  "detail": f"unknown tier {requested!r}",
                                  "tier": requested,
                                  "tiers": list(SERVING_TIERS)})
                return
            tier = requested if requested is not None else (
                self.cfg.tier_default if tiers_on else "fp32")
            engine = self.engine(model, tier)
            if engine is None and requested is None and tier != "fp32":
                # the model never registered the configured default tier —
                # an implicit default degrades to fp32; an EXPLICIT ask
                # never silently substitutes (400 below instead)
                tier = "fp32"
                engine = self.engine(model, tier)
            if engine is None:
                registered = self._model_tiers(model)
                if registered:
                    _reply(req, 400, {
                        "error": "bad_request",
                        "detail": f"model {model!r} does not serve tier "
                                  f"{tier!r}",
                        "tier": tier, "tiers": registered})
                    return
                with self._lock:
                    known = sorted({m for m, _ in self._engines})
                _reply(req, 400, {"error": "bad_request",
                                  "detail": f"unknown model {model!r}",
                                  "models": known})
                return
            self._reg.inc(f"serving/tier_requests_{tier}")
            length = int(req.headers.get("Content-Length") or 0)
            expect = engine.image_size * engine.image_size * 3
            if length != expect:
                _reply(req, 400, {
                    "error": "bad_request",
                    "detail": f"payload must be exactly {expect} bytes of "
                              f"raw uint8 pixels "
                              f"({engine.image_size}x{engine.image_size}"
                              f"x3, the u8 wire), got {length}"})
                return
            body = req.rfile.read(length)
            if len(body) != length:
                # truncated upload: a CLIENT fault (400), not a server
                # error — serving/errors is the counter ops alert on
                _reply(req, 400, {
                    "error": "bad_request",
                    "detail": f"body truncated: declared {length} bytes, "
                              f"received {len(body)}"})
                return
            image = np.frombuffer(body, np.uint8).reshape(
                engine.image_size, engine.image_size, 3)
            with self._lock:
                batcher = self._batchers[(model, tier)]
            # client-supplied correlation id (optional header): tags this
            # request's span AND the engine-flush span that carries it, so
            # telemetry/stitch.py can draw the request→flush flow arrow
            trace_id = str(req.headers.get("X-DVGGF-Trace-Id") or "") or None
            t0_ns = time.monotonic_ns()
            try:
                pending = batcher.submit(image, trace_id=trace_id)
            except OverloadShed as shed:
                # the header is SECOND-granular (RFC 9110): round the ms
                # hint UP so a compliant client never retries early; the
                # JSON field carries the precise hint
                retry_s = -(-int(self.cfg.shed_retry_after_ms) // 1000) or 1
                _reply(req, 503, {
                    "error": "overloaded", "kind": shed.kind,
                    "model": model,
                    "queue_depth": shed.queue_depth,
                    "queue_limit": shed.queue_limit,
                    "retry_after_ms": int(self.cfg.shed_retry_after_ms),
                }, headers={"Retry-After": str(retry_s)})
                return
            if not pending.event.wait(float(self.cfg.request_timeout_s)):
                self._reg.inc("serving/errors")
                _reply(req, 504, {"error": "timeout", "model": model,
                                  "timeout_s": self.cfg.request_timeout_s})
                return
            if pending.error is not None:
                self._reg.inc("serving/errors")
                if isinstance(pending.error, TimeoutError):
                    # reaped from the queue past the request deadline —
                    # same class as the handler's own wait timeout
                    _reply(req, 504, {"error": "timeout", "model": model,
                                      "detail": str(pending.error)})
                    return
                _reply(req, 500, {"error": "predict_failed",
                                  "detail": repr(pending.error)})
                return
            if trace_id:
                telemetry.record(
                    "serving_request", "serving", t0_ns,
                    time.monotonic_ns() - t0_ns,
                    {"trace_id": trace_id, "flow": "out", "model": model})
            k = _top_k_from_query(query, engine.num_classes)
            from distributed_vgg_f_tpu.train.predict import top_k_records
            _reply(req, 200, {
                "model": model,
                # the answering tier rides the payload only when the tier
                # plane is on — disabled, the response body is r22's
                **({"tier": tier} if tiers_on else {}),
                "top_k": top_k_records(pending.probs, k,
                                       full_precision=True),
                "bucket": pending.bucket,
                "latency_ms": round((time.monotonic() - t0) * 1e3, 3),
            })
        except (BrokenPipeError, ConnectionError):
            pass  # client hung up — its problem
        except Exception as e:  # noqa: BLE001 — a request must never kill
            self._reg.inc("serving/errors")
            try:
                _reply(req, 500, {"error": "internal", "detail": repr(e)})
            except (BrokenPipeError, ConnectionError, OSError):
                pass

    def _handle_get(self, req: BaseHTTPRequestHandler) -> None:
        self._reg.inc("serving/requests")
        path = req.path.split("?", 1)[0].rstrip("/")
        if path == "/v1/models":
            tiers_on = self._tiers_enabled()
            with self._lock:
                engines = dict(self._engines)
            rows: Dict[str, dict] = {}
            for (name, tier), eng in engines.items():
                # the row keeps its r22 shape — the fp32 engine's receipt
                # — and the tier ladder rides a "tiers" sub-table when the
                # plane is enabled
                if tier == "fp32":
                    base = dict(eng.describe())
                    base.update(rows.get(name) or {})
                    rows[name] = base
                if tiers_on:
                    rows.setdefault(name, {}).setdefault(
                        "tiers", {})[tier] = eng.describe()
            _reply(req, 200, {"models": rows})
            return
        _reply(req, 404, {"error": "not found",
                          "endpoints": ["/v1/predict/<model>",
                                        "/v1/models"]})

    # -------------------------------------------------------------- receipts
    def servingz_payload(self) -> dict:
        """The /servingz provider payload: live queue depth, bucket
        occupancy, shed rate, window state, controller receipts — plus,
        with tiers enabled, each model's ladder (per-tier engine/admission
        rows) and the ladder BUILD receipt (per-bucket compile seconds +
        the HBM residency estimate, satellite 6: warmup cost used to be
        invisible to the flight recorder)."""
        tiers_on = self._tiers_enabled()
        with self._lock:
            keys = sorted(self._engines)
            models: Dict[str, dict] = {}
            for key in keys:
                name, tier = key
                row = {"engine": self._engines[key].describe(),
                       "admission": self._batchers[key].describe()}
                ctrl = self._controllers.get(key)
                if ctrl is not None:
                    row["controller"] = ctrl.describe()
                if tier == "fp32":
                    models.setdefault(name, {}).update(row)
                if tiers_on:
                    models.setdefault(name, {}).setdefault(
                        "tiers", {})[tier] = row
        payload = {"enabled": True,
                   "endpoint": self.endpoint if self._server else None,
                   "uptime_s": round(
                       time.monotonic() - self._started_mono, 3),
                   "windows": self._windows,
                   "shed_rate": self._reg.gauge("serving/shed_rate", 0.0),
                   "latency_ms": {
                       q: self._reg.gauge(f"serving/latency_{q}_ms")
                       for q in ("p50", "p95", "p99")},
                   "models": models}
        if tiers_on:
            payload["tier_default"] = self.cfg.tier_default
            payload["ladder"] = self.ladder_receipt()
        return payload

    def ladder_receipt(self) -> dict:
        """Per (model, tier) build cost: bucket→compile seconds + the HBM
        residency estimate — the start-record / /servingz ladder receipt."""
        with self._lock:
            engines = dict(self._engines)
        out: Dict[str, dict] = {}
        for (name, tier), eng in sorted(engines.items()):
            out.setdefault(name, {})[tier] = {
                "served_by": getattr(eng, "served_by", name),
                "compile_s": {str(b): s for b, s in
                              sorted(getattr(eng, "compile_log",
                                             {}).items())},
                "hbm_estimate_bytes": int(getattr(
                    eng, "hbm_estimate_bytes", 0))}
        return out


def _quantiles(latencies_ms) -> dict:
    if not latencies_ms:
        return {}
    arr = np.asarray(latencies_ms, np.float64)
    return {"p50": round(float(np.percentile(arr, 50)), 3),
            "p95": round(float(np.percentile(arr, 95)), 3),
            "p99": round(float(np.percentile(arr, 99)), 3)}


def _tier_from_query(query: str) -> Optional[str]:
    """The `?tier=` value, verbatim (validation is the caller's: an
    unknown value must 400 with the ladder, not silently default)."""
    for part in (query or "").split("&"):
        key, sep, value = part.partition("=")
        if sep and key == "tier":
            return value
    return None


def _top_k_from_query(query: str, num_classes: int, default: int = 5) -> int:
    k = default
    for part in (query or "").split("&"):
        key, sep, value = part.partition("=")
        if sep and key == "k":
            try:
                k = int(value)
            except ValueError:
                pass
    return max(1, min(k, num_classes))


def _reply(req: BaseHTTPRequestHandler, status: int, payload: dict,
           headers: Optional[dict] = None) -> None:
    body = json.dumps(payload).encode()
    req.send_response(status)
    req.send_header("Content-Type", "application/json")
    req.send_header("Content-Length", str(len(body)))
    for key, value in (headers or {}).items():
        req.send_header(key, value)
    req.end_headers()
    req.wfile.write(body)


def serve_from_trainer(trainer, *, start: bool = True) -> PredictServer:
    """The `--mode serve` entry: one engine over the trainer's latest
    checkpoint (run_predict's restore path), routed under the configured
    model's name. With `serving.tiers.enabled` the derivable tiers (bf16,
    int8 for the vggf family) are built over that base engine; the student
    tier needs its own distilled weights (train/distill.py) and is added
    programmatically. Zoo composition likewise: build more engines with
    `PredictEngine.from_trainer` (one trainer per checkpoint) and
    `add_engine` them onto the same server."""
    cfg = trainer.cfg
    server = PredictServer(cfg.serving)
    base = PredictEngine.from_trainer(trainer)
    server.add_engine(base)
    if getattr(cfg.serving, "tiers", None) is not None \
            and cfg.serving.tiers.enabled:
        from distributed_vgg_f_tpu.serving.tiers import build_tier_engines
        tiers = ["bf16"]
        # int8 quantizes the CNN-F head stack — vggf family only
        if cfg.model.name.startswith("vggf"):
            tiers.append("int8")
        for eng in build_tier_engines(base, cfg.serving.tiers,
                                      tiers=tiers).values():
            server.add_engine(eng)
    if start:
        server.start()
    # the ladder build receipt lands in the run log as a start-class
    # record: per-tier compile seconds + HBM estimate (satellite 6)
    logger = getattr(trainer, "logger", None)
    if logger is not None:
        logger.log("serving_start", {
            "endpoint": server.endpoint if start else None,
            "tiers_enabled": server._tiers_enabled(),
            "ladder": server.ladder_receipt()})
    return server
