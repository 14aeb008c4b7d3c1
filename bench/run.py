"""Run one benchmark cell once, on the chip this process is started on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration (``bench/configs/<config>.json``), its traffic
mix (``bench/traffic/<mix>.json``) and its metrics (``bench/metrics/<name>.py``)
are found by name from ``BENCHMARK.json`` at the checkout's root. This
process holds the chip: it starts the placement server (``POST /deploy``
through the service's micro-batch queue), warms every program the window
will run, and then lets a client process, which never imports jax, drive the
window. After the window it checks every answer against the configuration's
plain reference (``bench/check.py``) and prints one JSON result line last.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# run as a script, this directory heads sys.path: drop it so no module here
# shadows a library module of the same name, and import through the package
if sys.path and Path(sys.path[0]).resolve() == BENCH:
    sys.path.pop(0)
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, xplane  # noqa: E402
from bench import traffic as traffic_gen  # noqa: E402
from bench.client import post  # noqa: E402

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
#: a part of the device SA search program's name in the trace
SA_PROGRAM = "_sa_chains"
#: tracing goes on this long after the traced window closes: the profiler
#: cuts short the event of a program still running when tracing stops, and
#: such a program must not read as one that ran wholly inside the window
TRACE_TAIL_S = 0.5


class BenchError(RuntimeError):
    """The run cannot be made as asked (no chip, unknown cell or device)."""


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(name: str):
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    config = json.loads(
        (BENCH / "configs" / f"{cell['config']}.json").read_text())
    mix = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return bm, cell, config, mix


def device_info(chips: int, require_chip: bool) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chip and (info["platform"] != "tpu" or len(devs) < chips):
        raise BenchError(f"needs {chips} TPU chip(s); JAX found "
                         f"{len(devs)} {info['platform']} device(s)")
    return info


def peaks_for(kind: str, require_chip: bool) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind in table:
        return table[kind]
    if require_chip:
        raise BenchError(f"device kind {kind!r} has no entry in "
                         "bench/peaks.json")
    return {}


class Run:
    """Everything one run measured, as the metric readers see it."""

    def __init__(self, cell, config, mix, seed, seconds):
        self.cell, self.config, self.mix = cell, config, mix
        self.seed, self.seconds = seed, seconds
        self.ref = load_module(BENCH / "reference" /
                               f"{config['reference']}.py", "bench_reference")
        self.bodies: list = []      # request JSON of each request drawn
        self.records: list = []     # {"id","t0","t1","req","response",
        self.rows: dict = {}        #  "in_window"}; rows: see check.evaluate
        self.sample: list = []      # record ids of the quality sample
        self.trace = None
        self.setup_s = self.window_s = 0.0
        self.window_compiles = 0
        self.exhausted = False
        self.setup_phases: dict = {}    # set-up step -> seconds since start
        self.peaks: dict = {}

    def answers(self):
        return self.records

    def window_records(self):
        return [r for r in self.records if r["in_window"]]

    def searched_stage_times(self):
        return [r["response"]["report"]["stage_times_s"]
                for r in self.window_records()
                if r["response"] is not None
                and r["response"]["status"] == "miss"]

    def _device_sa(self) -> bool:
        req = self.config["request"]
        return (req["method"] == "simulated_annealing"
                and req["backend"] == "device")

    def sa_step_s(self):
        """Device time of one SA scan step: the device time of the SA search
        programs that ran wholly inside the traced window over the steps
        they ran, each a cold search of the full budget (a warm start in the
        window is an error: its scan length is the service's to choose). A
        traced device-SA run whose trace holds none has lost its source:
        that is an error, not a metric left out."""
        if self.trace is None or not self._device_sa():
            return None
        status = [r["response"]["status"] for r in self.window_records()
                  if r["response"] is not None]
        if "warm" in status:
            raise BenchError("a warm-started search ran in the window")
        runs = [v for k, v in self.trace["modules_whole"].items()
                if SA_PROGRAM in k]
        searches = sum(n for n, _ in runs)
        if not searches:
            raise BenchError(f"no {SA_PROGRAM!r} program ran wholly inside "
                             f"the traced window; programs: "
                             f"{sorted(self.trace['modules_s'])}")
        steps = searches * int(self.config["request"]["budget"])
        return sum(t for _, t in runs) / steps

    def incident_degree(self) -> int:
        import numpy as np

        _, src, dst, _ = self.reference_graph()
        return int(np.bincount(np.concatenate([src, dst])).max())

    def chains(self) -> int:
        return int(self.config["request"]["method_kw"].get("restarts", 1))

    def reference_graph(self):
        req = self.config["request"]
        return self.ref.graph(self.config, {k: req[k] for k in (
            "batch", "spike_density", "training")})


def build_request_factory(config):
    """``make(overrides) -> DeployRequest`` for the configuration."""
    import repro.snn as snn
    from repro.core.partition import CoreSpec
    from repro.core.topology import parse_topology
    from repro.deploy import DeployRequest

    m = dict(config["model"])
    model = getattr(snn, m.pop("family"))(**m)
    noc = parse_topology(config["fabric"])
    core = CoreSpec(**config["core"])

    def make(overrides):
        fields = {**config["request"], **overrides}
        method_kw = dict(fields.pop("method_kw"))
        return DeployRequest.from_call(model, noc, core=core,
                                       method_kw=method_kw, **fields)
    return make


def warm_up(make, mix):
    """Run, on a service of its own, the program shapes the window will use:
    one cold search on values no request of the window takes. Every run
    warms the same set, so after a cell's first run every program is in
    the compile cache."""
    from repro.deploy import PlacementService

    PlacementService().submit(make(traffic_gen.warmup_fields(mix)))


def start_server(config):
    import jax
    from repro.deploy import PlacementService
    from repro.deploy.service import make_server

    svc = PlacementService()
    inner = svc.submit_batch

    def submit_batch(requests):
        with jax.profiler.TraceAnnotation(xplane.BATCH):
            return inner(requests)

    svc.submit_batch = submit_batch
    server, queue = make_server(svc, "127.0.0.1", 0,
                                max_batch=config["server"]["max_batch"],
                                window_s=config["server"]["window_s"])
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    url = f"http://127.0.0.1:{server.server_address[1]}"

    def stop():
        server.shutdown()
        server.server_close()
        queue.close()
        thread.join(timeout=30)
    return url, stop


def parse_answer(code, text):
    if code != 200:
        return None
    try:
        return json.loads(text)
    except json.JSONDecodeError:
        return None


def run_cell(args, require_chip: bool = True) -> dict:
    """One run of one cell; returns the result line's object."""
    bm, cell, config, mix = cell_spec(args.workload)
    device = device_info(int(cell["chips"]), require_chip)
    phases = {"chip": time.perf_counter() - T_START}
    peaks = peaks_for(device["kind"], require_chip)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = [0]

    def on_duration(event, duration, **kw):
        if event == BACKEND_COMPILE:
            compiles[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    run = Run(cell, config, mix, args.seed, args.seconds)
    run.peaks = peaks
    stream = traffic_gen.draw(mix, args.seed)
    make = build_request_factory(config)
    run.bodies = bodies = [json.loads(json.dumps(make(f).to_json()))
                           for f in stream]
    phases["requests"] = time.perf_counter() - T_START
    warm_up(make, mix)
    phases["warm-up"] = time.perf_counter() - T_START
    url, stop_server = start_server(config)
    client = trace_dir = None
    try:
        client = subprocess.Popen(
            [sys.executable, str(BENCH / "client.py")], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True)
        client.stdin.write(json.dumps({
            "url": url, "clients": mix["clients"], "seconds": args.seconds,
            "bodies": [json.dumps(b) for b in bodies]}) + "\n")
        client.stdin.flush()
        if client.stdout.readline().strip() != "ready":
            raise BenchError("the client process did not start")
        run.setup_s = time.perf_counter() - T_START
        phases["server and client"] = run.setup_s
        run.setup_phases = phases
        n_compiles = compiles[0]
        if args.trace:
            # the first trace_seconds of the window: a scanned search puts
            # every step's operations in the trace, some 10 MB a request
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            window = jax.profiler.TraceAnnotation(xplane.WINDOW)
            window.__enter__()
        client.stdin.write("go\n")
        client.stdin.flush()
        if args.trace:
            time.sleep(min(float(mix["trace_seconds"]), args.seconds))
            window.__exit__(None, None, None)
            time.sleep(TRACE_TAIL_S)
            jax.profiler.stop_trace()
        summary = None
        for line in client.stdout:
            msg = json.loads(line)
            if "window" in msg:
                summary = msg
                break
            run.records.append({
                "id": len(run.records), "t0": msg["t0"], "t1": msg["t1"],
                "req": msg["i"],
                "response": parse_answer(msg["code"], msg["body"]),
                "in_window": True})
        run.window_compiles = compiles[0] - n_compiles
        if summary is None:
            raise BenchError("the client process ended without a window")
        run.window_s = summary["window"]
        run.exhausted = summary["exhausted"]
        client.stdin.close()
        client.wait(timeout=60)
        memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                          for d in jax.devices())
        # the fixed quality sample: the first searched requests of the stream,
        # answered after the window where the window did not reach them
        for f in range(min(int(mix["quality_sample"]), len(stream))):
            got = [r for r in run.records
                   if r["req"] == f and r["response"] is not None]
            if not got:
                code, text = post(url + "/deploy",
                                  json.dumps(bodies[f]).encode())
                got = [{"id": len(run.records), "t0": 0.0, "t1": 0.0,
                        "req": f, "response": parse_answer(code, text),
                        "in_window": False}]
                run.records.append(got[0])
            run.sample.append(got[0]["id"])
    finally:
        stop_server()
        if client is not None and client.poll() is None:
            client.kill()
            client.wait()
    if trace_dir:
        try:
            run.trace = xplane.reduce_file(xplane.find_xplane(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    device["memory_peak_bytes"] = int(memory_peak)
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]

    numbers = check.evaluate(run, run.ref)
    correct, table = check.judge(numbers, config["limits"])

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in bm[kind]:
        if cell["name"] not in m.get("workloads", [cell["name"]]):
            continue
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             f"bench_metric_{len(metrics)}")
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    window = run.window_records()
    out = {"correct": bool(correct), "attempted": len(window),
           "failed": sum(1 for r in window if r["response"] is None),
           "metrics": metrics, "device": device}
    if run.trace is not None:
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["setup_phases"] = run.setup_phases
    out["window_compiles"] = run.window_compiles
    out["stream_exhausted"] = run.exhausted
    out["checks"] = table
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print("set-up, seconds from process start: " + ", ".join(
        f"{k} {v:.3f}" for k, v in out["setup_phases"].items()),
        file=sys.stderr)
    print(f"window: {out['attempted']} requests, {out['failed']} failed, "
          f"{out['window_compiles']} compiles in the window, stream "
          f"{'exhausted' if out['stream_exhausted'] else 'not exhausted'}",
          file=sys.stderr)
    for name, v in out["checks"].items():
        print(f"check {name}: {v['value']!r} <= {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
