"""Placement-as-a-service: a persistent deployment server with plan caching.

Every ``deploy_model`` call used to rebuild topology tables and run a cold
search. The paper's setting is the opposite: one long-lived near-storage
system, many SNN models repeatedly (re)deployed onto it. This module is the
serving layer that amortizes the search. It answers each request one of
three ways:

* **exact hits** — requests are canonical :class:`~repro.deploy.request.
  DeployRequest` values; a repeat of the same key (model-spec hash, topology
  ``cache_key``, objective, method/backend/budget/seed/method-kwargs) is
  answered straight from the :class:`~repro.deploy.plancache.PlanCache` —
  legitimate because a seeded search is deterministic and the key captures
  every input. The cache is JSON on disk, so hits survive server restarts.
* **warm starts** — a *near miss* (same model/topology/partition ``warm_key``,
  different objective/budget/seed) reuses the cached placement as the
  search's ``init=`` at a fraction of the full budget, escalating like
  :func:`repro.deploy.runtime.run_scenario` until the warm cost is within
  ``warm_threshold`` of the donor's. The init-seeded searches keep the best
  candidate seen — warm results never regress below the donor.
* **cold searches** — anything else runs
  :func:`repro.deploy.engine.execute_request`, the same call as a solo
  ``deploy_model``.

A batch (one micro-batch window, or one ``/deploy_batch`` body) is answered
row by row, each row against the cache as the batch found it: an answer
depends on its request and that cache, never on the other rows.

:class:`PlacementService` is the in-process core (usable directly in tests
and benchmarks); :func:`make_server` wraps it in a stdlib
``ThreadingHTTPServer`` whose ``POST /deploy`` handler funnels concurrent
connections through a :class:`repro.launch.serve.MicroBatchQueue` — the same
continuous-batching idiom as the token server. Per-request latencies land in
the service :class:`repro.obs.Recorder` as ``service.latency_s`` histograms
(p50/p99 via ``/stats``), and hit/miss/warm/compile counts, a
cold search's own work counts (``ppo.sample.programs``) and the logical
graphs its plans were made on (``deploy.graphs``, ``deploy.graph.nodes``,
``.edges``, ``.branch_edges``) as counters; the recorder stores no spans.
The served path's spans (``http.deploy``, ``queue.window``,
``service.batch``, ``service.deploy``, the engine's ``deploy.*`` stages and
the searches' phases) are profiler annotations only. Each answer carries
its own ``latency_s``, ``queue_s``, ``report["search_phases_s"]`` and
``report["compiles"]``; its
``report["stage_times_s"]`` is the cached plan's (a hit repeats the stage
times of the search that made the plan).

HTTP surface: ``POST /deploy`` (one request JSON -> DeployResponse JSON,
micro-batched), ``POST /deploy_batch`` (``{"requests": [...]}`` -> one
batch), ``GET /plan/<cache_key>``, ``GET /stats``, ``GET /healthz``.
"""
from __future__ import annotations

import dataclasses
import json
import threading
import time
from contextlib import contextmanager

import jax
import numpy as np

from ..launch.serve import MicroBatchQueue
from ..obs import Recorder, maybe_span
from .engine import count_graph, execute_request
from .plancache import PlanCache, _obj_blob
from .request import DeployRequest

#: methods whose searches accept an ``init=`` warm start and keep the best
#: candidate seen (so warm-start cost can never regress below the donor's)
_WARM_METHODS = frozenset({"random_search", "simulated_annealing", "genetic",
                           "population_random_search",
                           "population_simulated_annealing"})

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = [0]             # backend compiles in this process since listening
_listener_lock = threading.Lock()
_listening = False


def _on_duration(event: str, duration: float, **kw) -> None:
    if event == _COMPILE_EVENT:
        _compiles[0] += 1


def _listen_for_compiles() -> None:
    """Register, once per process, the jax listener that counts compiles."""
    global _listening
    with _listener_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _listening = True


@dataclasses.dataclass
class DeployResponse:
    """One service answer: where the plan came from and what it is.

    ``status`` is ``"hit"`` (served from cache), ``"warm"`` (near-miss
    warm-started from ``warm_from``'s placement) or ``"miss"`` (cold
    search). ``latency_s`` is the service-side wall time of this request,
    from the start of its batch (its donor lookup included) to its answer.
    ``queue_s`` is the time the request waited in the HTTP micro-batch queue
    (0.0 for in-process calls). ``report["compiles"]`` counts the jax
    backend compiles in the process while the request ran, programs loaded
    from the persistent compile cache included; ``report["search_phases_s"]``
    holds the phase times of this request's own search, summed over a warm
    start's attempts (empty for a hit or a method that times none).
    ``request`` + ``placement`` are enough to
    re-materialize a live plan via
    :func:`repro.deploy.engine.instantiate_plan`.
    """
    status: str
    cache_key: str
    request: dict
    placement: list
    objective_cost: float
    comm_cost: float
    report: dict
    latency_s: float
    warm_from: str | None = None
    attempts: int = 1
    queue_s: float = 0.0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "DeployResponse":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})


class PlacementService:
    """The in-process placement service (cache + warm starts).

    ``cache`` defaults to a fresh in-memory :class:`PlanCache` (load one from
    disk for restart persistence). ``recorder`` collects the service metrics
    (a private one is created when omitted): counters and latency
    histograms, never spans or events, so a long-lived server's recorder
    stays bounded. The deployment engine runs with no recorder; its spans
    are profiler annotations and its stage and search-phase times come back
    in each answer's report.

    Warm-start control mirrors ``run_scenario``: the first attempt runs at
    ``warm_budget_frac`` of the full budget seeded with the donor placement;
    while the cost is above ``(1 + warm_threshold) x`` the donor's (only
    comparable for same-objective donors) the budget escalates ``x
    escalation`` up to ``max_retries`` extra attempts (never beyond the full
    budget).
    """

    def __init__(self, cache: PlanCache | None = None, recorder=None,
                 warm_budget_frac: float = 0.4, warm_threshold: float = 0.05,
                 escalation: float = 2.0, max_retries: int = 1):
        self.cache = cache if cache is not None else PlanCache()
        self.recorder = recorder if recorder is not None else Recorder()
        self.warm_budget_frac = float(warm_budget_frac)
        self.warm_threshold = float(warm_threshold)
        self.escalation = float(escalation)
        self.max_retries = int(max_retries)
        self._topologies: dict = {}     # topology key tuple -> live Topology
        self._models: dict = {}         # model spec tuple -> live model
        self._lock = threading.RLock()
        _listen_for_compiles()

    # ---- public API --------------------------------------------------------
    def submit(self, request: DeployRequest) -> DeployResponse:
        """Answer one request (a batch of one): cache hit, warm start, or
        cold search."""
        return self.submit_batch([request])[0]

    def submit_batch(self, requests) -> list:
        """Answer several requests in order, each against the cache as the
        batch found it: warm donors are looked up before any row runs, so
        no row warm-starts from another. A row repeating an earlier row's
        exact key is a hit. Each answer's ``latency_s`` runs from the start
        of the batch."""
        with self._serving(), maybe_span(None, "service.batch"):
            t0 = time.perf_counter()
            rows = [(r, r.cache_key()) for r in requests]
            donors = [None if ck in self.cache else self._donor(r)
                      for r, ck in rows]
            return [self._submit(r, ck, d, t0)
                    for (r, ck), d in zip(rows, donors)]

    @contextmanager
    def _serving(self):
        """Hold the service lock; count the compiles made meanwhile."""
        with self._lock:
            c0 = _compiles[0]
            try:
                yield
            finally:
                self.recorder.count("service.compiles", _compiles[0] - c0)

    def stats(self) -> dict:
        """Cache size + service counters + latency histogram summaries."""
        with self._lock:
            return {"cache_entries": len(self.cache),
                    "counters": self.recorder.counters,
                    "latency": self.recorder.histogram_summaries()}

    # ---- request handling --------------------------------------------------
    def _submit(self, request: DeployRequest, ck: str, donor: dict | None,
                t0: float) -> DeployResponse:
        c0 = _compiles[0]
        rec = self.recorder
        rec.count("service.requests")
        entry = self.cache.get(ck)
        if entry is not None:
            rec.count("service.hits")
            return self._finish(entry, "hit", t0, c0)
        if donor is not None:
            try:
                with maybe_span(None, "service.deploy"):
                    plan, attempts, phases = self._deploy_warm(request,
                                                               donor)
            except ValueError:
                donor = None            # incompatible donor: run cold
            else:
                rec.count("service.warm_starts")
                entry = self.cache.put(request, plan)
                count_graph(rec, entry["report"]["graph"])
                return self._finish(entry, "warm", t0, c0, phases,
                                    warm_from=donor["cache_key"],
                                    attempts=attempts)
        with maybe_span(None, "service.deploy"):
            model, noc = self._materialize(request)
            plan = execute_request(request, model=model, noc=noc)
        rec.count("service.misses")
        for name, n in plan.placement.counters.items():
            rec.count(name, n)
        entry = self.cache.put(request, plan)
        count_graph(rec, entry["report"]["graph"])
        return self._finish(entry, "miss", t0, c0, plan.placement.phases_s)

    def _finish(self, entry: dict, status: str, t0: float, c0: int,
                phases: dict | None = None, warm_from: str | None = None,
                attempts: int = 1) -> DeployResponse:
        """The answer to one request. Its report is the cached plan's, plus
        what this request alone did: ``compiles`` since ``c0`` and
        ``search_phases_s``, the phase times of its own search (``phases``;
        empty for a hit)."""
        dt = time.perf_counter() - t0
        self.recorder.observe("service.latency_s", dt)
        self.recorder.observe(f"service.latency_s.{status}", dt)
        return DeployResponse(
            status=status, cache_key=entry["cache_key"],
            request=dict(entry["request"]),
            placement=list(entry["placement"]),
            objective_cost=float(entry["objective_cost"]),
            comm_cost=float(entry["comm_cost"]),
            report={**entry["report"], "compiles": _compiles[0] - c0,
                    "search_phases_s": dict(phases or {})},
            latency_s=dt, warm_from=warm_from, attempts=attempts)

    def _materialize(self, request: DeployRequest):
        """Live (model, topology) for a request — memoized per spec, so a
        long-lived server rebuilds a DegradedTopology's BFS tables once."""
        noc = self._topologies.get(request.topology)
        if noc is None:
            noc = request.materialize_topology()
            self._topologies[request.topology] = noc
        model = self._models.get(request.model)
        if model is None:
            model = request.materialize_model()
            self._models[request.model] = model
        return model, noc

    # ---- warm starts -------------------------------------------------------
    def _donor(self, request: DeployRequest) -> dict | None:
        """The cache's warm-start donor for ``request`` now, or None."""
        if not self._warm_startable(request):
            return None
        return self.cache.find_warm(request)

    def _warm_startable(self, request: DeployRequest) -> bool:
        return (request.method in _WARM_METHODS
                and request.copartition_iters == 0
                and "init" not in dict(request.method_kw))

    def _full_budget(self, request: DeployRequest):
        """(override-kwarg-name, full budget) — explicit ``iters`` wins over
        ``budget`` in the searches, so the warm fraction must scale whichever
        the request actually drives."""
        # core.placement imports deploy, so it is imported when first needed
        from ..core.placement.optimizer import DEFAULT_BUDGET

        mk = request.materialize_method_kw()
        if mk.get("iters"):
            return "iters", int(mk["iters"])
        if request.budget:
            return "budget", int(request.budget)
        return "budget", DEFAULT_BUDGET[request.method]

    def _deploy_warm(self, request: DeployRequest, donor: dict):
        """Warm-started search: ``(best plan, attempts, phase times summed
        over the attempts)``."""
        model, noc = self._materialize(request)
        init = np.asarray(donor["placement"], dtype=int)
        kind, full = self._full_budget(request)
        same_obj = (_obj_blob(donor["request"]["objective"])
                    == _obj_blob(request.objective))
        target = (1.0 + self.warm_threshold) * float(donor["objective_cost"])
        b = max(1, int(round(self.warm_budget_frac * full)))
        attempts, best, phases = 0, None, {}
        while True:
            attempts += 1
            plan = execute_request(request, model=model, noc=noc,
                                   init=init, **{kind: b})
            for k, v in plan.placement.phases_s.items():
                phases[k] = phases.get(k, 0.0) + v
            if best is None or (plan.placement.objective_cost
                                < best.placement.objective_cost):
                best = plan
            if not same_obj or best.placement.objective_cost <= target:
                break
            if attempts > self.max_retries or b >= full:
                break
            b = min(full, max(b + 1, int(round(b * self.escalation))))
        return best, attempts, phases


# ---------------------------------------------------------------------------
# HTTP layer (stdlib only)
# ---------------------------------------------------------------------------

def make_server(service: PlacementService, host: str = "127.0.0.1",
                port: int = 0, max_batch: int = 8, window_s: float = 0.01):
    """A ``ThreadingHTTPServer`` serving ``service``. ``POST /deploy``
    requests from concurrent connections funnel through one
    :class:`MicroBatchQueue` (requests landing within ``window_s`` go to
    one ``submit_batch``). The queue is at ``server.queue`` — call
    ``server.queue.close()`` after ``server.shutdown()``."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    queue = MicroBatchQueue(service.submit_batch, max_batch=max_batch,
                            window_s=window_s)

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):   # quiet: metrics live in /stats
            pass

        def _json(self, code: int, obj) -> None:
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path == "/healthz":
                return self._json(200, {"ok": True})
            if self.path == "/stats":
                return self._json(200, service.stats())
            if self.path.startswith("/plan/"):
                key = self.path[len("/plan/"):]
                with service._lock:
                    entry = service.cache.get(key)
                if entry is None:
                    return self._json(404, {"error": f"no plan {key!r}"})
                return self._json(200, {
                    k: entry[k] for k in ("cache_key", "request", "placement",
                                          "objective_cost", "comm_cost",
                                          "report")})
            return self._json(404, {"error": f"unknown path {self.path!r}"})

        def do_POST(self):
            with maybe_span(None, "http.deploy"):
                return self._post()

        def _post(self):
            length = int(self.headers.get("Content-Length") or 0)
            try:
                body = json.loads(self.rfile.read(length) or b"{}")
            except json.JSONDecodeError as e:
                return self._json(400, {"error": f"bad JSON: {e}"})
            try:
                if self.path == "/deploy":
                    req = DeployRequest.from_json(body)
                    resp, wait_s = queue.submit_timed(req)
                    resp.queue_s = wait_s
                    return self._json(200, resp.to_dict())
                if self.path == "/deploy_batch":
                    reqs = [DeployRequest.from_json(d)
                            for d in body["requests"]]
                    resps = service.submit_batch(reqs)
                    return self._json(200,
                                      {"responses": [r.to_dict()
                                                     for r in resps]})
            except (TypeError, ValueError, KeyError) as e:
                return self._json(400, {"error": f"{type(e).__name__}: {e}"})
            return self._json(404, {"error": f"unknown path {self.path!r}"})

    return ThreadingHTTPServer((host, port), Handler), queue


def request_over_http(url: str, request: DeployRequest,
                      timeout: float = 300.0) -> DeployResponse:
    """Client helper: POST one request to a running server's ``/deploy``."""
    import urllib.request

    data = json.dumps(request.to_json()).encode()
    http_req = urllib.request.Request(
        url.rstrip("/") + "/deploy", data=data,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(http_req, timeout=timeout) as resp:
        return DeployResponse.from_dict(json.loads(resp.read()))


def fetch_plan(src: str, timeout: float = 60.0) -> dict:
    """A cached-plan dict (``request`` + ``placement`` + ``report``) from a
    JSON file or a server URL (``http://host:port/plan/<cache_key>``, or any
    endpoint returning a saved DeployResponse/plan entry)."""
    if src.startswith(("http://", "https://")):
        import urllib.request

        with urllib.request.urlopen(src, timeout=timeout) as resp:
            return json.loads(resp.read())
    with open(src) as f:
        return json.load(f)
