#!/usr/bin/env python3
"""Bring-up check of the deployment path on one TPU chip.

    python chip_smoke.py

Runs, in this one process, what a user of the system runs, at full size:

A. the paper's method: ``deploy_model`` of Spike-ResNet18 onto the paper's
   32-core NoC with PPO (a few training steps of the GCN actor-critic);
B. device search: S-VGG16 on the 2x2-chip ``hier:2x2:4x4`` fabric with
   ``backend="device"`` SA (64 restarts; with the Pallas delta kernel and
   with plain gathers) and GA;
C. the served path: one ``PlacementService`` answering a cold request, its
   exact repeat (hit) and a near miss (warm), plus one HTTP round trip;
D. the Pallas link-traffic scorer on a [256, n] population.

Every plan is checked against the plain reference (float64
``Topology.evaluate`` on the host) and against the zigzag constructor; the
kernels are checked against numpy and must appear compiled
(``tpu_custom_call``) in the programs that ran. The script exits non-zero,
before its last line, when there is no TPU or any check fails. Its last line
is one JSON object: ``{"ok": true, "device": {...}}``.
"""
import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# full-size settings of each phase
PPO_CFG = dict(batch_size=32, iterations=12, ppo_epochs=4)
RESTARTS = 64
SA_BUDGET = 5000            # device-SA iterations (the method's default)
SERVICE_BUDGET = 5000
SCORER_POP = 256
DELTA_SWAPS = 4096
RTOL = 1e-5                 # float32 against float64


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(cond, msg: str):
    if not cond:
        fail(msg)


def close(got, ref, what: str, rtol: float = RTOL):
    """float32 ``got`` against a float64 ``ref``, relative to ref's scale."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    scale = max(float(np.abs(ref).max(initial=0.0)), 1e-30)
    err = float(np.abs(got - ref).max(initial=0.0)) / scale
    check(err <= rtol, f"{what}: max error {err:.3e} of scale exceeds {rtol}")
    return err


def kernel_compiled(lowered_text: str, what: str):
    check("tpu_custom_call" in lowered_text,
          f"{what}: no tpu_custom_call in the lowered program (the Pallas "
          "kernel was not compiled by Mosaic)")


def device_info():
    devs = jax.devices()
    if not devs or jax.default_backend() != "tpu":
        fail(f"no TPU: default backend is {jax.default_backend()!r} "
             f"with devices {devs}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def check_plan(noc, graph, placement, reported_comm, what: str,
               beat_zigzag: bool = True) -> float:
    """Valid, injective, host float64 cost equal to the reported one and (for
    the searches) no worse than zigzag. Returns the host cost."""
    from repro.core.placement import zigzag
    p = np.asarray(placement, dtype=np.int64)
    check(p.shape == (graph.n,), f"{what}: placement shape {p.shape}, "
          f"expected ({graph.n},)")
    check(len(set(p.tolist())) == graph.n, f"{what}: placement not injective")
    check(p.min() >= 0 and p.max() < noc.n_cores,
          f"{what}: placement outside [0, {noc.n_cores})")
    host = float(noc.evaluate(graph, p).comm_cost)
    check(host == float(reported_comm),
          f"{what}: host comm cost {host!r} != reported {reported_comm!r}")
    zz = float(noc.evaluate(graph, zigzag(graph.n, noc)).comm_cost)
    check(host <= zz or not beat_zigzag,
          f"{what}: comm cost {host:.6e} above zigzag {zz:.6e}")
    print(f"  {what}: comm_cost={host:.6e} zigzag={zz:.6e} "
          f"({100 * (1 - host / zz):.1f}% below)")
    return host


def last_event(recorder, name: str) -> dict:
    evs = [e["attrs"] for e in recorder.events if e["name"] == name]
    check(evs, f"no {name} event recorded")
    return evs[-1]


def vgg_graph(hier, vgg):
    """The partitioned S-VGG16 graph every phase-B-shape request places."""
    from repro.deploy import deploy_model
    return deploy_model(vgg, hier, method="zigzag", schedule="none").graph


def phase_a(common):
    from repro.core import NoC
    from repro.core.noc_batch import evaluate_batch
    from repro.core.placement.ppo import PPOConfig
    from repro.deploy import deploy_model

    noc = NoC(4, 8, link_bw=common.LINK_BW, core_flops=common.CORE_FLOPS,
              hop_latency=common.HOP_LAT)
    plan = deploy_model(common.SPIKE_MODELS["S-ResNet18"](), noc,
                        method="ppo", cfg=PPOConfig(**PPO_CFG),
                        schedule="fpdeep")
    r, graph = plan.placement, plan.graph
    # 12 iterations of 32 rollouts do not reliably beat zigzag (on the CPU,
    # seeds 0-3 land 2% above to 12% below it), so PPO is held to beat the
    # best of as many uniformly random placements as it sampled
    host = check_plan(noc, graph, r.placement, r.comm_cost,
                      "S-ResNet18 / mesh 4x8 / ppo", beat_zigzag=False)
    n_samples = PPO_CFG["batch_size"] * PPO_CFG["iterations"]
    rng = np.random.default_rng(0)
    P = np.stack([rng.permutation(noc.n_cores)[:graph.n]
                  for _ in range(n_samples)])
    rand_best = float(evaluate_batch(noc, graph, P).comm_cost.min())
    check(host <= rand_best, f"ppo: comm cost {host:.6e} above the best of "
          f"{n_samples} random placements {rand_best:.6e}")
    print(f"  ppo vs best of {n_samples} random placements: "
          f"{100 * (1 - host / rand_best):.1f}% below")
    check(r.history and len(r.history) == PPO_CFG["iterations"],
          "ppo: missing per-iteration history")
    close(r.history[-1]["best_cost"], host, "ppo: best rollout cost")
    check(plan.schedule is not None and plan.schedule.makespan > 0,
          "ppo: no fpdeep schedule")


def phase_b(hier, vgg):
    from repro.core.noc_batch import build_incident_tables, delta_comm_cost
    from repro.core.placement.device_search import (_sa_chains, _sa_inputs,
                                                    _swap_delta)
    from repro.deploy import deploy_model
    from repro.obs import Recorder

    graph = None
    for use_pallas in (None, False):        # None: the TPU default, kernel on
        rec = Recorder()
        plan = deploy_model(vgg, hier, method="sa", backend="device",
                            restarts=RESTARTS, use_pallas=use_pallas,
                            budget=SA_BUDGET, schedule="none", recorder=rec)
        summary = last_event(rec, "sa.device")
        want = use_pallas is None
        check(summary["use_pallas"] is want,
              f"device SA ran use_pallas={summary['use_pallas']}, "
              f"expected {want}")
        graph, r = plan.graph, plan.placement
        what = f"S-VGG16 / hier:2x2:4x4 / device SA use_pallas={want}"
        host = check_plan(hier, graph, r.placement, r.comm_cost, what)
        close(summary["best_cost"], host, f"{what}: device best cost")

    rec = Recorder()
    plan = deploy_model(vgg, hier, method="ga", backend="device",
                        schedule="none", recorder=rec)
    r = plan.placement
    host = check_plan(hier, plan.graph, r.placement, r.comm_cost,
                      "S-VGG16 / hier:2x2:4x4 / device GA")
    close(last_event(rec, "ga.gen")["best_cost"], host,
          "device GA: device best cost")

    # the device-SA program that ran holds the compiled delta kernel
    args, static = _sa_inputs(graph, hier, SA_BUDGET, 0.05, 1e-3, 0, None,
                              RESTARTS, 1.0, None, 256)
    kernel_compiled(_sa_chains.lower(*args, **static).as_text(),
                    "device-SA scan")

    # the delta kernel against the float64 numpy reference on random swaps
    rng = np.random.default_rng(0)
    S = hier.n_cores
    slots = np.stack([rng.permutation(S) for _ in range(DELTA_SWAPS)])
    i = rng.integers(0, S, DELTA_SWAPS)
    j = rng.integers(0, S, DELTA_SWAPS)
    hops_f, inc_other, inc_vol, inc_src = args[7], args[4], args[5], args[6]
    swap = jax.jit(_swap_delta, static_argnums=(7, 8, 9))
    got = np.asarray(swap(slots.astype(np.int32), i, j, hops_f, inc_other,
                          inc_vol, inc_src, graph.n, True, static["interpret"]))
    tbl = build_incident_tables(graph)
    ref = np.array([delta_comm_cost(hier, graph, slots[k], int(i[k]),
                                    int(j[k]), tbl)
                    for k in range(DELTA_SWAPS)])
    err = close(got, ref, "delta_cost_pallas vs numpy delta")
    print(f"  delta kernel: {DELTA_SWAPS} swaps, max error {err:.2e} of scale")


def phase_c(hier, vgg):
    from repro.deploy import DeployRequest, PlacementService
    from repro.deploy.service import make_server, request_over_http

    svc = PlacementService()
    req = DeployRequest.from_call(vgg, hier, method="sa", backend="device",
                                  schedule="none", budget=SERVICE_BUDGET,
                                  seed=0, method_kw={"restarts": RESTARTS})
    near = DeployRequest.from_call(vgg, hier, method="sa", backend="device",
                                   schedule="none", budget=SERVICE_BUDGET,
                                   seed=1, method_kw={"restarts": RESTARTS})
    graph = vgg_graph(hier, vgg)
    for request, want in ((req, "miss"), (req, "hit"), (near, "warm")):
        resp = svc.submit(request)
        check(resp.status == want, f"service: got {resp.status}, "
              f"expected {want}")
        check(want != "warm" or resp.warm_from == req.cache_key(),
              "service: the warm start did not come from the cold plan")
        check_plan(hier, graph, resp.placement, resp.comm_cost,
                   f"service {want}")
        print(f"  service {want}: latency_s={resp.latency_s:.3f}")

    server, queue = make_server(svc, "127.0.0.1", 0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        url = f"http://127.0.0.1:{server.server_address[1]}"
        resp = request_over_http(url, req, timeout=600)
    finally:
        server.shutdown()
        server.server_close()
        queue.close()
        thread.join()
    check(resp.status == "hit", f"http: got {resp.status}, expected hit")
    check_plan(hier, graph, resp.placement, resp.comm_cost, "http hit")


def phase_d(hier, vgg):
    from repro.core.noc_batch import batched_noc, evaluate_batch

    graph = vgg_graph(hier, vgg)
    rng = np.random.default_rng(1)
    P = np.stack([rng.permutation(hier.n_cores)[:graph.n]
                  for _ in range(SCORER_POP)])
    got = evaluate_batch(hier, graph, P, backend="pallas")
    ref = evaluate_batch(hier, graph, P, backend="numpy")
    err = close(got.link_traffic, ref.link_traffic, "pallas link traffic")
    close(got.comm_cost, ref.comm_cost, "pallas-scorer comm cost")
    close(got.core_traffic, ref.core_traffic, "pallas-scorer core traffic")
    close(got.max_link, ref.max_link, "pallas-scorer max link")
    print(f"  pallas scorer: [{SCORER_POP}, {graph.n}] population, "
          f"link traffic max error {err:.2e} of scale")

    bn = batched_noc(hier)
    src, dst, vol, compute = bn.edge_arrays(graph)
    lowered = bn._get_jax_fn("full_pallas").lower(
        jnp.asarray(P), jnp.asarray(src), jnp.asarray(dst),
        jnp.asarray(vol, jnp.float32),
        jnp.asarray(compute / hier.core_flops, jnp.float32))
    kernel_compiled(lowered.as_text(), "pallas scorer")


def main() -> int:
    t_start = time.perf_counter()
    device = device_info()
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)

    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1
    jax.monitoring.register_event_listener(on_event)

    from benchmarks import common
    from repro.core.topology import parse_topology
    hier = parse_topology("hier:2x2:4x4", link_bw=common.LINK_BW,
                          core_flops=common.CORE_FLOPS,
                          hop_latency=common.HOP_LAT)
    vgg = common.SPIKE_MODELS["S-VGG16"]()

    phases = (("A ppo S-ResNet18", lambda: phase_a(common)),
              ("B device search S-VGG16", lambda: phase_b(hier, vgg)),
              ("C service", lambda: phase_c(hier, vgg)),
              ("D pallas scorer", lambda: phase_d(hier, vgg)))
    for name, run in phases:
        print(f"phase {name}", flush=True)
        t0 = time.perf_counter()
        run()
        print(f"phase {name}: ok, wall {time.perf_counter() - t0:.2f} s "
              "(compile included)", flush=True)
    print(f"compile cache: dir={cache_dir} hits={cache_events['hits']} "
          f"misses={cache_events['misses']}")
    print(f"total wall {time.perf_counter() - t_start:.2f} s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
