"""Runnable serving driver: SISO semantic cache in front of a zoo model.

Three modes (``--mode``, DESIGN.md §16.3):

* ``batch`` — the original one-shot driver: bootstrap from a synthetic
  history, run the analytic SLO study, then push a real request stream
  through the reduced model with continuous batching.
* ``http`` — a thin stdlib HTTP front end over one ``ServingGateway``:
  ``POST /v1/query`` with ``{"tokens": [...]}`` answers inline on a
  cache hit or drives the engine to completion on a miss, tagging every
  response with ``X-Cache: HIT|MISS`` and ``X-Cache-Region`` headers
  (the drop-in proxy shape); ``GET /healthz`` reports serving state.
  SIGTERM drains gracefully: in-flight work completes, new queries get
  503, then the listener stops.
* ``replica`` — the same front end over N gateways in a
  :class:`ReplicaGroup` exchanging replication deltas (DESIGN.md §16),
  requests routed per-user across replicas. With ``--transport socket``
  each replica runs in its **own process** with its own engine, deltas
  flow over TCP loopback (DESIGN.md §17), and the parent becomes a thin
  router: ``/v1/query`` proxies to the routed worker, ``/healthz``
  aggregates per-worker replication/transport stats (outbox depth,
  retries, backoffs, last-applied seqs, reconcile counts) so replication
  lag is visible without reading logs.

  PYTHONPATH=src python -m repro.launch.serve --mode batch --requests 200
  PYTHONPATH=src python -m repro.launch.serve --mode http --port 8080
  PYTHONPATH=src python -m repro.launch.serve --mode replica --replicas 3
  PYTHONPATH=src python -m repro.launch.serve --mode replica \
      --transport socket --replicas 3   # one process per replica
  PYTHONPATH=src python -m repro.launch.serve --mode http \
      --arch minicpm3-4b --dim 768 --no-reduced   # published widths

Models are built at toy width unless ``--no-reduced`` is given. On a TPU
host ``--transport socket`` is refused: only one process may hold a chip,
so the replicas run in one process with ``--transport inproc``. Every mode
keeps JAX's persistent compilation cache (``enable_compile_cache``).

Port layout in socket mode (base = ``--port``): the router listens on
base, worker i's HTTP front end on base+1+i, worker i's replication
transport on base+1000+i.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
import zlib
from functools import partial
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional, Sequence

import numpy as np

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]

# region int8 -> header tag (LookupResult.region, DESIGN.md §13/§14)
REGION_NAMES = {-1: "miss", 0: "centroid", 1: "spill", 2: "warm",
                3: "cold", 4: "overlay"}


def user_key(user) -> Optional[int]:
    """Stable int key for user-sticky routing and the gateway's repeat
    escape: ints pass through, anything else hashes (crc32 — stable
    across router and worker processes, unlike ``hash()``)."""
    if user is None:
        return None
    try:
        return int(user)
    except (TypeError, ValueError):
        return zlib.crc32(str(user).encode()) & 0x7FFFFFFF


def enable_compile_cache() -> str:
    """Keep JAX's persistent compilation cache for an entry point and
    return its directory. ``JAX_COMPILATION_CACHE_DIR``, where set, is
    what JAX already uses, and nothing else is set; otherwise the cache is
    ``<repo>/.jax_cache``, a fixed path in the checkout, so repeated runs
    of the same checkout find their compiled programs again. Entry points
    call this; library modules and tests never do."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def tpu_attached() -> bool:
    """Whether JAX would get a TPU here, decided without initialising a
    backend: a process that initialises one holds the chip, and a child
    that needs it then fails. An explicit ``JAX_PLATFORMS`` without
    ``tpu`` rules it out; otherwise the PCI bus is scanned for TPU chips
    the way JAX's own TPU start-up does."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return False
    from jax._src import hardware_utils
    return hardware_utils.num_available_tpu_chips_and_device_id()[0] > 0


def hash_embed_fn(dim: int):
    """Deterministic token-sequence embedder for the HTTP modes: crc32 of
    the token bytes seeds a unit vector, so identical queries map to
    identical cache keys without a learned embedder in the loop."""
    def fn(token_lists: Sequence[np.ndarray]) -> np.ndarray:
        out = np.zeros((len(token_lists), dim), np.float32)
        for i, toks in enumerate(token_lists):
            seed = zlib.crc32(np.asarray(toks, np.int64).tobytes())
            v = np.random.default_rng(seed).normal(size=dim)
            out[i] = (v / np.linalg.norm(v)).astype(np.float32)
        return out
    return fn


class CacheHTTPServer(ThreadingHTTPServer):
    """stdlib HTTP front end over one or more gateways (DESIGN.md §16.3).

    ``targets`` are submit-capable objects — bare ``ServingGateway``s or
    ``Replica`` wrappers (whose ``submit`` additionally publishes
    replication deltas). One lock serializes the serving path: the
    gateway pipeline is single-threaded by design, and the front end is
    a demo form factor, not a throughput claim.
    """

    daemon_threads = True
    allow_reuse_address = True

    def __init__(self, addr, targets: Sequence, names: Sequence[str],
                 clock=None):
        super().__init__(addr, _Handler)
        self.targets = list(targets)
        self.names = list(names)
        self.lock = threading.Lock()
        self.clock = clock or time.perf_counter
        self.draining = False
        self._rid = 0
        self._rr = 0

    @staticmethod
    def _gw(target):
        return target.gw if hasattr(target, "gw") else target

    def route(self, user: Optional[int]) -> int:
        """Replica index for a request: per-user sticky hash (the load-
        balancer shape), round-robin for anonymous traffic."""
        if user is not None:
            return user % len(self.targets)
        self._rr += 1
        return (self._rr - 1) % len(self.targets)

    def serve_query(self, body: dict) -> tuple[int, dict, dict]:
        """The whole request path under the lock; returns
        (http_status, response_json, extra_headers)."""
        toks = np.asarray(body.get("tokens", []), np.int32)
        if toks.size == 0:
            return 400, {"error": "body needs a non-empty 'tokens' list"}, {}
        user = user_key(body.get("user"))
        with self.lock:
            if self.draining:
                return 503, {"error": "draining"}, {"Retry-After": "1"}
            ix = self.route(user)
            target = self.targets[ix]
            gw = self._gw(target)
            rid = self._rid
            self._rid += 1
            from repro.serving.gateway import GatewayRequest
            req = GatewayRequest(
                rid=rid, model_tokens=toks,
                user_id=user,
                tenant=body.get("tenant"),
                max_new=int(body.get("max_new", 16)))
            done0 = len(gw.done)    # a hit lands right after this index
            hit = bool(target.submit([req], now=self.clock())[0])
            res = gw.last_result
            out = self._await(gw, rid, done0)
            if not hit and hasattr(target, "publish"):
                # the miss's answer was recorded while _await drove the
                # engine — publish it now so a repeat routed to a peer
                # replica hits instead of waiting for the next submit
                target.publish(self.clock())
        region = int(res.region[0])
        resp = {"rid": rid, "hit": hit, "replica": self.names[ix],
                "region": REGION_NAMES.get(region, str(region)),
                "sim": float(res.sim[0]),
                "served_by": out.served_by if out is not None else None,
                "tokens_out": (np.asarray(out.out).tolist()
                               if out is not None and out.out is not None
                               else None)}
        headers = {"X-Cache": "HIT" if hit else "MISS",
                   "X-Cache-Region": resp["region"],
                   "X-Replica": self.names[ix]}
        return 200, resp, headers

    @staticmethod
    def _await(gw, rid: int, done0: int, max_ticks: int = 10_000):
        """Drive the engine until this rid completes (hits are already in
        the done list from admit_resolved)."""
        for _ in range(max_ticks):
            for r in gw.done[done0:]:
                if r.rid == rid:
                    return r
            if not gw.sched.active and not gw.sched.queue:
                break
            gw.step()
        for r in gw.done[done0:]:
            if r.rid == rid:
                return r
        return None

    def health(self) -> dict:
        reports = {}
        for name, t in zip(self.names, self.targets):
            gw = self._gw(t)
            entry = {"submitted": gw.stats.submitted,
                     "epoch": int(getattr(gw.frontend,
                                          "refresh_epoch", 0))}
            if hasattr(t, "report"):
                # Replica wrapper: replication + transport observability
                # (pending outbox depth, retries, backoffs, last-applied
                # seqs, reconcile counts — DESIGN.md §17)
                entry["replication"] = t.report()
            reports[name] = entry
        return {"status": "draining" if self.draining else "serving",
                "replicas": reports}

    def begin_drain(self) -> None:
        """Graceful drain (SIGTERM): refuse new queries, complete queued
        engine work, fold pending replication records, snapshot if
        persistence is attached."""
        with self.lock:
            self.draining = True
            for t in self.targets:
                if hasattr(t, "drain"):     # Replica wrapper
                    t.drain()
                else:
                    self._gw(t).drain()


class _Handler(BaseHTTPRequestHandler):
    server_version = "siso-serve/1.0"

    def log_message(self, fmt, *args):      # stay quiet under test
        pass

    def _send(self, status: int, payload: dict, headers: dict = ()) -> None:
        body = json.dumps(payload).encode()
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in dict(headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        if self.path == "/healthz":
            self._send(200, self.server.health())
        else:
            self._send(404, {"error": f"no route {self.path}"})

    def do_POST(self):
        if self.path != "/v1/query":
            self._send(404, {"error": f"no route {self.path}"})
            return
        try:
            n = int(self.headers.get("Content-Length", 0))
            body = json.loads(self.rfile.read(n) or b"{}")
        except (ValueError, json.JSONDecodeError):
            self._send(400, {"error": "malformed JSON body"})
            return
        status, payload, headers = self.server.serve_query(body)
        self._send(status, payload, headers)


class ReplicaRouter(ThreadingHTTPServer):
    """Parent-process front door for ``--transport socket``: proxies
    ``/v1/query`` to the routed worker (per-user sticky, round-robin for
    anonymous traffic) and aggregates every worker's ``/healthz`` —
    replication lag shows up here, not in worker logs."""

    daemon_threads = True
    allow_reuse_address = True

    def __init__(self, addr, worker_host: str, worker_ports: Sequence[int],
                 names: Sequence[str]):
        super().__init__(addr, _RouterHandler)
        self.worker_host = worker_host
        self.worker_ports = list(worker_ports)
        self.names = list(names)
        self.draining = False
        self._rr = 0
        self._rr_lock = threading.Lock()

    def route(self, user: Optional[int]) -> int:
        if user is not None:
            return user % len(self.worker_ports)
        with self._rr_lock:
            self._rr += 1
            return (self._rr - 1) % len(self.worker_ports)

    def forward_query(self, raw_body: bytes, user: Optional[int]
                      ) -> tuple[int, dict, dict]:
        if self.draining:
            return 503, {"error": "draining"}, {"Retry-After": "1"}
        ix = self.route(user)
        url = (f"http://{self.worker_host}:{self.worker_ports[ix]}"
               f"/v1/query")
        req = urllib.request.Request(
            url, data=raw_body, method="POST",
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=120.0) as resp:
                payload = json.loads(resp.read())
                headers = {k: v for k, v in resp.headers.items()
                           if k.startswith("X-")}
                headers["X-Routed-To"] = self.names[ix]
                return resp.status, payload, headers
        except urllib.error.HTTPError as e:      # worker said 4xx/5xx
            try:
                payload = json.loads(e.read())
            except (ValueError, json.JSONDecodeError):
                payload = {"error": f"worker {self.names[ix]}: {e.code}"}
            return e.code, payload, {"X-Routed-To": self.names[ix]}
        except (urllib.error.URLError, OSError, TimeoutError):
            return 503, {"error": f"worker {self.names[ix]} unavailable"}, \
                {"Retry-After": "1"}

    def health(self) -> dict:
        replicas = {}
        statuses = []
        for name, port in zip(self.names, self.worker_ports):
            url = f"http://{self.worker_host}:{port}/healthz"
            try:
                with urllib.request.urlopen(url, timeout=5.0) as resp:
                    h = json.loads(resp.read())
                statuses.append(h.get("status", "unknown"))
                replicas[name] = h.get("replicas", {}).get(name, h)
            except (urllib.error.URLError, OSError, ValueError,
                    TimeoutError):
                statuses.append("unreachable")
                replicas[name] = {"status": "unreachable"}
        status = "draining" if self.draining else (
            "serving" if all(s == "serving" for s in statuses)
            else "degraded")
        return {"status": status, "transport": "socket",
                "replicas": replicas}


class _RouterHandler(BaseHTTPRequestHandler):
    server_version = "siso-router/1.0"

    def log_message(self, fmt, *args):
        pass

    _send = _Handler._send

    def do_GET(self):
        if self.path == "/healthz":
            self._send(200, self.server.health())
        else:
            self._send(404, {"error": f"no route {self.path}"})

    def do_POST(self):
        if self.path != "/v1/query":
            self._send(404, {"error": f"no route {self.path}"})
            return
        n = int(self.headers.get("Content-Length", 0))
        raw = self.rfile.read(n) or b"{}"
        try:
            user = user_key(json.loads(raw).get("user"))
        except (ValueError, json.JSONDecodeError):
            self._send(400, {"error": "malformed JSON body"})
            return
        status, payload, headers = self.server.forward_query(raw, user)
        self._send(status, payload, headers)


# ---------------------------------------------------------------------------
# mode drivers
# ---------------------------------------------------------------------------


def _serving_config(args) -> "ServingConfig":
    from repro.serving.config import (CacheConfig, RefreshConfig,
                                      ServingConfig)
    return ServingConfig(
        cache=CacheConfig(dim=args.dim, answer_dim=args.dim,
                          capacity=args.capacity,
                          dynamic_threshold=not args.no_dta),
        refresh=RefreshConfig(min=args.refresh_min),
        slo_latency=args.slo, llm_latency=args.slo / 1.3)


def _model_config(args):
    """The engine's architecture: published widths, or the toy-width
    variant with ``--reduced`` (the default, for CPU runs)."""
    from repro.configs.base import get_config
    cfg = get_config(args.arch)
    return (cfg.reduced() if args.reduced else cfg).replace(remat=False)


def init_weights(cfg, seed: int):
    """Random weights from ``seed``, drawn under jit: eagerly, every stacked
    leaf is first drawn in f32 over all layers before the cast — at
    published widths a multi-GB temporary per leaf."""
    import jax
    from repro.models import lm
    return jax.jit(partial(lm.init_params, cfg=cfg))(jax.random.PRNGKey(seed))


def _make_engine(args):
    from repro.serving.engine import ModelEngine
    cfg = _model_config(args)
    return ModelEngine(init_weights(cfg, args.seed), cfg,
                       n_slots=args.slots, max_len=128), cfg


def run_http(args) -> int:
    """--mode http / --mode replica: N gateways behind the front end."""
    from repro.distributed.replication import ReplicaGroup, ReplicationConfig
    from repro.serving.gateway import ServingGateway
    if args.mode == "replica" and args.transport == "socket":
        if args.worker_index >= 0:
            return _run_socket_worker(args)
        return _run_socket_parent(args)
    n = args.replicas if args.mode == "replica" else 1
    cfg = _serving_config(args)
    embed = hash_embed_fn(args.dim)
    engine, _ = _make_engine(args)
    # without an answer_fn the scheduler records nothing on completion
    # and repeat queries can never hit: embed the generated tokens with
    # the same hasher so the answer key is deterministic too
    answer_fn = lambda toks: embed([np.asarray(toks)])[0]
    gws = [ServingGateway.from_config(cfg, engine=engine, embed_fn=embed,
                                      answer_fn=answer_fn)
           for _ in range(n)]
    names = [f"r{i}" for i in range(n)]
    if n > 1:
        group = ReplicaGroup(cfg.replication or ReplicationConfig())
        targets = [group.add(name, gw) for name, gw in zip(names, gws)]
    else:
        targets = gws
    server = CacheHTTPServer((args.host, args.port), targets, names)
    host, port = server.server_address[:2]
    print(f"serving {n} replica(s) on http://{host}:{port} "
          f"(POST /v1/query, GET /healthz)")

    def _sigterm(signum, frame):
        print("SIGTERM: draining...")
        server.begin_drain()
        threading.Thread(target=server.shutdown, daemon=True).start()

    signal.signal(signal.SIGTERM, _sigterm)
    try:
        server.serve_forever(poll_interval=0.2)
    except KeyboardInterrupt:
        server.begin_drain()
    finally:
        server.server_close()
    return 0


def _run_socket_worker(args) -> int:
    """One replica process: its own engine + gateway + SocketTransport,
    full mesh to the other workers. Internal entry point — the parent
    spawns this via ``--worker-index``."""
    from repro.distributed.replication import Replica, ReplicationConfig
    from repro.distributed.transport import SocketTransport, TransportConfig
    from repro.serving.gateway import ServingGateway
    i, n = args.worker_index, args.replicas
    name = f"r{i}"
    cfg = _serving_config(args)
    embed = hash_embed_fn(args.dim)
    engine, _ = _make_engine(args)
    answer_fn = lambda toks: embed([np.asarray(toks)])[0]
    gw = ServingGateway.from_config(cfg, engine=engine, embed_fn=embed,
                                    answer_fn=answer_fn)
    tcfg = TransportConfig(kind="socket", host=args.host,
                           port=args.port + 1000 + i)
    transport = SocketTransport(name, tcfg)
    rep = Replica(name, gw, transport, ReplicationConfig(n_replicas=n))
    for j in range(n):
        if j != i:
            transport.connect(f"r{j}", (args.host, args.port + 1000 + j))
    server = CacheHTTPServer((args.host, args.port + 1 + i), [rep], [name])

    def _state_provider():
        # reconcile donor runs on a transport reader thread; serialize
        # against the serving path, bounded so a wedged lock surfaces as
        # a requester timeout instead of a deadlock
        if not server.lock.acquire(timeout=2.0):
            return None
        try:
            return rep._reconcile_payload(copy=False)
        finally:
            server.lock.release()

    transport.state_provider = _state_provider
    stop = threading.Event()

    def _ticker():
        # fold peer deltas even when no requests arrive (an idle worker
        # must still apply, ack, and reconcile)
        while not stop.wait(0.05):
            with server.lock:
                if not server.draining:
                    rep.apply_pending(rep.cfg.apply_budget)

    ticker = threading.Thread(target=_ticker, daemon=True)
    ticker.start()
    print(f"worker {name}: http={args.port + 1 + i} "
          f"transport={args.port + 1000 + i}")

    def _sigterm(signum, frame):
        server.begin_drain()       # finishes in-flight, folds, publishes
        transport.flush(5.0)
        threading.Thread(target=server.shutdown, daemon=True).start()

    signal.signal(signal.SIGTERM, _sigterm)
    try:
        server.serve_forever(poll_interval=0.2)
    except KeyboardInterrupt:
        server.begin_drain()
    finally:
        stop.set()
        ticker.join(timeout=2.0)
        rep.close()
        server.server_close()
    return 0


def _run_socket_parent(args) -> int:
    """Parent: spawn one worker process per replica, then route."""
    names = [f"r{i}" for i in range(args.replicas)]
    ports = [args.port + 1 + i for i in range(args.replicas)]
    base = [sys.executable, "-m", "repro.launch.serve",
            "--mode", "replica", "--transport", "socket",
            "--replicas", str(args.replicas),
            "--host", args.host, "--port", str(args.port),
            "--arch", args.arch, "--dim", str(args.dim),
            "--capacity", str(args.capacity), "--slots", str(args.slots),
            "--refresh-min", str(args.refresh_min),
            "--slo", str(args.slo), "--seed", str(args.seed),
            "--reduced" if args.reduced else "--no-reduced"]
    if args.no_dta:
        base.append("--no-dta")
    procs = [subprocess.Popen(base + ["--worker-index", str(i)])
             for i in range(args.replicas)]
    router = ReplicaRouter((args.host, args.port), args.host, ports, names)
    host, port = router.server_address[:2]
    print(f"routing {args.replicas} worker replica(s) on "
          f"http://{host}:{port} (POST /v1/query, GET /healthz)")

    def _sigterm(signum, frame):
        print("SIGTERM: draining workers...")
        router.draining = True
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        threading.Thread(target=router.shutdown, daemon=True).start()

    signal.signal(signal.SIGTERM, _sigterm)
    try:
        router.serve_forever(poll_interval=0.2)
    except KeyboardInterrupt:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
    finally:
        router.server_close()
        for p in procs:
            try:
                p.wait(timeout=30.0)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
    return 0


def run_batch(args) -> int:
    """The original one-shot driver (analytic study + real engine pass),
    constructed through the ServingConfig builders."""
    from repro.configs.base import get_config
    from repro.data.synth import SyntheticWorkload
    from repro.serving.engine import AnalyticEngine, EngineModel, ModelEngine
    from repro.serving.scheduler import ContinuousBatchScheduler, Request
    from repro.serving.simulator import (ServingSimulator, bootstrap_frontend,
                                         build_system)

    cfg = _model_config(args)
    wl = SyntheticWorkload(args.profile, dim=args.dim, n_clusters=500,
                           seed=args.seed)
    model = EngineModel.from_config(get_config(args.arch), n_chips=8)
    L = model.e2e(wl.profile.avg_tokens_in, wl.profile.avg_tokens_out)
    print(f"engine model: zero-load e2e = {L:.3f}s")

    # --- offline path: bootstrap the cache from history ---
    siso = build_system("siso-nodta" if args.no_dta else "siso",
                        dim=args.dim, capacity=args.capacity,
                        slo_latency=1.3 * L, llm_latency=L)
    hist = wl.sample(args.history, rps=100.0)
    t0 = time.time()
    stats = bootstrap_frontend(siso, hist)
    print(f"bootstrap: {stats.added} centroids added, "
          f"{stats.evicted} filtered, cache={len(siso.cache.centroids)} "
          f"({time.time() - t0:.1f}s)")

    # --- online path A: analytic engine (SLO study at the target scale) ---
    sim = ServingSimulator(AnalyticEngine(model, concurrency=args.slots),
                           siso)
    test = wl.sample(args.requests, rps=args.rps, cv=args.cv)
    r = sim.run(test, name="siso")
    print(f"[analytic] hit={r.hit_ratio:.3f} slo={r.slo_attainment:.3f} "
          f"e2e={r.mean_e2e:.3f}s quality={r.mean_quality:.3f} "
          f"theta_R(final)={r.theta_trace[-1] if r.theta_trace else None}")

    # --- online path B: the real model through continuous batching ---
    engine = ModelEngine(init_weights(cfg, args.seed), cfg,
                         n_slots=args.slots, max_len=128)
    sched = ContinuousBatchScheduler(engine, cache=siso)
    rng = np.random.default_rng(args.seed)
    n_real = min(args.requests, 32)
    reqs = wl.sample(n_real, rps=args.rps)
    t0 = time.time()
    for i in range(n_real):
        toks = rng.integers(0, cfg.vocab_size, size=rng.integers(4, 12))
        sched.submit(Request(rid=i, tokens=toks.astype(np.int32),
                             max_new=args.max_new,
                             vector=reqs.vectors[i]))
        sched.step()
    done = sched.drain()
    by = {"cache": 0, "engine": 0}
    for rq in done:
        by[rq.served_by] += 1
    print(f"[real engine] {len(done)} served in {time.time() - t0:.1f}s — "
          f"cache hits {by['cache']}, engine {by['engine']}; "
          f"sample output tokens: {done[-1].out[:8]}")
    return 0


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("batch", "http", "replica"),
                    default="batch")
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="toy-width model (default); --no-reduced builds "
                         "the architecture at its published widths")
    ap.add_argument("--profile", default="quora")
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--history", type=int, default=3000)
    ap.add_argument("--rps", type=float, default=20.0)
    ap.add_argument("--cv", type=float, default=1.0)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--capacity", type=int, default=256)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--no-dta", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    # http/replica mode
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--transport", choices=("inproc", "socket"),
                    default="inproc")
    ap.add_argument("--worker-index", type=int, default=-1,
                    help=argparse.SUPPRESS)   # internal: socket worker
    ap.add_argument("--refresh-min", type=int, default=32)
    ap.add_argument("--slo", type=float, default=1.0)
    return ap.parse_args(argv)


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = parse_args(argv)
    if args.mode == "replica" and args.transport == "socket" \
            and tpu_attached():
        raise SystemExit(
            "--transport socket starts one process per replica, and only "
            "one process may hold a TPU chip; use --transport inproc, whose "
            "replicas share one engine in this process")
    enable_compile_cache()
    if args.mode == "batch":
        return run_batch(args)
    return run_http(args)


if __name__ == "__main__":
    raise SystemExit(main())
