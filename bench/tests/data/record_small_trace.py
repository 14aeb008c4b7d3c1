"""Record the small TPU trace ``test_xplane.py`` reads: one short device-SA
search of the devsa configuration inside the harness's ``bench.window`` and
``bench.batch`` annotations, on the chip this runs on.

    python bench/tests/data/record_small_trace.py <out_dir>

It copies the trace's ``.xplane.pb`` to ``bench/tests/data/small.xplane.pb``
under ``out_dir`` and prints the reduction.
"""
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out_dir: str) -> int:
    import jax

    from bench import xplane
    from bench.run import build_request_factory
    from repro.deploy import PlacementService

    config = json.loads((ROOT / "bench" / "configs" /
                         "sresnet50-mesh8x8-devsa.json").read_text())
    make = build_request_factory(config)
    svc = PlacementService()
    svc.submit(make({"budget": 20}))                     # compile first
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation(xplane.WINDOW):
        with jax.profiler.TraceAnnotation(xplane.BATCH):
            svc.submit(make({"budget": 20, "spike_density": 0.12}))
    jax.profiler.stop_trace()
    dest = Path(out_dir) / "bench" / "tests" / "data" / "small.xplane.pb"
    dest.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(xplane.find_xplane(tmp), dest)
    shutil.rmtree(tmp, ignore_errors=True)
    r = xplane.reduce_file(str(dest))
    print(json.dumps({k: r[k] for k in ("window_s", "busy_s", "chips")}),
          sorted(r["modules_s"]), dest.stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
