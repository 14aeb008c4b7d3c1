"""Fully device-resident placement search: whole-search-in-one-dispatch SA/GA.

The host searches (:mod:`.baselines`, :mod:`.population`) pay one Python
round-trip per iteration — at BENCH_deploy_e2e shapes (~38 µs/step) that
round-trip *is* the wall time. This module compiles the entire search into a
single ``jax.jit``-ed ``lax.scan`` dispatch:

* :func:`simulated_annealing_device` — pairwise-swap SA whose carried state is
  ``(slots, cost, best, temperature, key)``, advanced ``iters`` steps on
  device with **O(degree) incremental delta costs**: a swap of two slots only
  perturbs the edges incident to the (at most two) moved nodes, gathered from
  :class:`repro.core.noc_batch.IncidentTables` (the numpy reference is
  :func:`repro.core.noc_batch.delta_comm_cost`, bit-exact on integer-volume
  graphs). ``restarts=R`` runs R independent chains batched along the leading
  axis — the vmap-style multi-start where 64 restarts cost roughly one — and
  returns the best chain. Chain ``c`` draws from ``fold_in(key(seed), c)``,
  so chain 0 is bit-identical whatever ``restarts`` is (more restarts can
  only improve the returned best). The per-swap delta is evaluated either by
  plain jax hop-matrix gathers (CPU default) or by the tiled Pallas one-hot
  matmul kernel :func:`repro.kernels.delta_cost.delta_cost_pallas`
  (``use_pallas=True``; interpret mode on CPU, Mosaic on TPU — the default on
  TPU hosts, where dynamic gathers lower poorly, for fabrics whose hop matrix
  fits the kernel's VMEM block: :data:`PALLAS_DELTA_MAX_CORES`). Float32 drift of the
  accumulated cost is bounded by an exact full re-evaluation every
  ``refresh_every`` steps (``lax.cond``, still on device).
* :func:`genetic_device` — the OX1-crossover evolutionary search as a scanned
  generation loop over a device-resident population: stable-argsort elitism,
  tournament selection, vectorized order crossover (membership scatter +
  cumsum-rank fill) and geometric pairwise-swap mutation, the whole
  population scored per generation inside the same dispatch.

Both emit the same recorder trajectory semantics as their host counterparts
(``sa.iter`` / ``ga.gen``, one event per step/generation) by replaying the
scan's stacked per-step outputs host-side *after* the single dispatch — no
per-step host sync. The trajectory arrays are always computed on device;
attaching a recorder only fetches them, so results are bit-identical with the
recorder on or off.

The device path anneals in float32 and draws its own (jax) RNG streams, so it
is a distinct method variant — the host backends (``batch``/``numpy``/
``jax``/``pallas``/``reference``) stay seed-for-seed bit-identical to before.
Only ``objective="comm_cost"`` is supported: the O(degree) delta
decomposition is a property of the edge-separable comm cost (use the host
backends for ``max_link``/``energy``/composite objectives).
"""
from __future__ import annotations

import functools

import numpy as np

from ...deploy.objective import as_objective
from ..noc_batch import (batched_noc, build_incident_tables,
                         validate_placements)
from .baselines import core_pool, sigmate, zigzag

import jax
import jax.numpy as jnp

from ...kernels.delta_cost import delta_cost_pallas


#: Largest fabric (cores) whose delta kernel runs on TPU by default: the
#: kernel holds the whole hop matrix, padded to 128-multiples, as one VMEM
#: block, double-buffered. At 1024 cores that is 2 x 4 MiB and compiles for
#: v5e; at 2048 cores, 2 x 16 MiB, it runs out of VMEM. Larger fabrics take
#: the plain-jax gather path.
PALLAS_DELTA_MAX_CORES = 1024


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pool_array(noc) -> np.ndarray:
    pool = core_pool(noc)
    return np.arange(pool) if isinstance(pool, int) else np.asarray(pool)


def _check_objective(objective) -> None:
    if as_objective(objective if objective is not None
                    else "comm_cost").name != "comm_cost":
        raise ValueError(
            "backend='device' supports objective='comm_cost' only (the "
            "O(degree) delta decomposition needs an edge-separable cost); "
            "use the host backends for other objectives")


# ---------------------------------------------------------------------------
# Shared device pieces
# ---------------------------------------------------------------------------

def _full_cost(slots, hops_f, e_src, e_dst, e_vol, n: int):
    """Exact (up to f32 summation) comm cost of each row's placement: [R]."""
    p = slots[:, :n]
    return jnp.sum(e_vol * hops_f[p[:, e_src], p[:, e_dst]], axis=1)


def _swap_delta(slots, i, j, hops_f, inc_other, inc_vol, inc_src, n: int,
                use_pallas: bool, interpret: bool):
    """O(degree) comm-cost delta of swapping ``slots[r, i[r]]``/``slots[r, j[r]]``.

    Device transcription of :func:`repro.core.noc_batch.delta_comm_cost`,
    batched over the chain axis. Free-slot indices resolve to the all-padding
    sentinel row ``n`` of the incident tables, so no branching is needed.
    """
    R = slots.shape[0]
    rows = jnp.arange(R)
    ci, cj = slots[rows, i], slots[rows, j]
    a = jnp.where(i < n, i, n).astype(jnp.int32)   # node id or sentinel n
    b = jnp.where(j < n, j, n).astype(jnp.int32)
    p_pad = jnp.concatenate(
        [slots[:, :n], jnp.zeros((R, 1), slots.dtype)], axis=1)
    # both halves (node a's edges, node b's edges) in one batched gather —
    # inside a CPU scan, per-op dispatch dominates, so fewer/wider ops win
    nodes = jnp.stack([a, b], axis=1)               # [R, 2]
    a3, b3 = a[:, None, None], b[:, None, None]
    ci3, cj3 = ci[:, None, None], cj[:, None, None]
    oth = inc_other[nodes]                          # [R, 2, D]
    # zero a–b edges in node b's half so they are not counted twice; in node
    # a's own half ``oth == a`` only hits padding (already volume 0), so the
    # mask needs no per-half gating
    vol = jnp.where(oth == a3, 0.0, inc_vol[nodes])
    is_s = inc_src[nodes]
    # flat take instead of 2-axis advanced indexing: XLA lowers it to a
    # plain 1-D gather, measurably cheaper per step at wide R
    oc_b = jnp.take(p_pad, rows[:, None, None] * p_pad.shape[1] + oth)
    # the other endpoint moves too when it is the swap's partner node
    oc_a = jnp.where(oth == a3, cj3, jnp.where(oth == b3, ci3, oc_b))
    cu_before = jnp.stack([ci, cj], axis=1)[..., None]   # [R, 2, 1]
    cu_after = jnp.stack([cj, ci], axis=1)[..., None]
    src_b = jnp.where(is_s, cu_before, oc_b)
    dst_b = jnp.where(is_s, oc_b, cu_before)
    src_a = jnp.where(is_s, cu_after, oc_a)
    dst_a = jnp.where(is_s, oc_a, cu_after)
    if use_pallas:
        D2 = 2 * oth.shape[2]
        return delta_cost_pallas(
            src_b.reshape(R, D2), dst_b.reshape(R, D2),
            src_a.reshape(R, D2), dst_a.reshape(R, D2),
            vol.reshape(R, D2), hops_f, interpret=interpret)
    C = hops_f.shape[0]
    flat = jnp.concatenate([src_a * C + dst_a, src_b * C + dst_b], axis=1)
    h = jnp.take(hops_f, flat)                      # [R, 4, D]
    return jnp.sum(vol * (h[:, :2] - h[:, 2:]), axis=(1, 2))


# ---------------------------------------------------------------------------
# Simulated annealing: R restart chains, one dispatch
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "iters", "n", "refresh_every", "use_pallas", "interpret"))
def _sa_chains(slots0, keys0, t0_vec, cooling, inc_other, inc_vol, inc_src,
               hops_f, e_src, e_dst, e_vol, *, iters: int, n: int,
               refresh_every: int, use_pallas: bool, interpret: bool):
    R, S = slots0.shape
    cost0 = _full_cost(slots0, hops_f, e_src, e_dst, e_vol, n)
    t_init = jnp.maximum(t0_vec * jnp.maximum(cost0, 1.0), 1e-9)
    rows = jnp.arange(R)
    # draw every chain's whole proposal stream up front (3 batched threefry
    # calls instead of 4 splits per step — per-step key management dominates
    # a CPU scan otherwise); chain c's stream is a function of keys0[c] only
    ks = jax.vmap(lambda k: jax.random.split(k, 3))(keys0)
    i_all = jax.vmap(
        lambda k: jax.random.randint(k, (iters,), 0, S))(ks[:, 0]).T
    j_all = jax.vmap(
        lambda k: jax.random.randint(k, (iters,), 0, S))(ks[:, 1]).T
    u_all = jax.vmap(
        lambda k: jax.random.uniform(k, (iters,)))(ks[:, 2]).T

    def step(carry, xs):
        slots, cost, best_slots, best_cost, t = carry
        it, i, j, u = xs
        degenerate = (i == j) | ((i >= n) & (j >= n))
        delta = _swap_delta(slots, i, j, hops_f, inc_other, inc_vol, inc_src,
                            n, use_pallas, interpret)
        accept = ~degenerate & (
            (delta <= 0)
            | (u < jnp.exp(jnp.minimum(-delta / jnp.maximum(t, 1e-9), 0.0))))
        # arithmetic swap instead of a scatter: two compares + selects over
        # [R, S] fuse into one elementwise kernel (XLA CPU scatters don't)
        si, sj = slots[rows, i], slots[rows, j]
        pos = jnp.arange(S)[None, :]
        swapped = jnp.where(pos == i[:, None], sj[:, None],
                            jnp.where(pos == j[:, None], si[:, None], slots))
        slots = jnp.where(accept[:, None], swapped, slots)
        cost = cost + jnp.where(accept, delta, 0.0)
        # bound float32 drift of the accumulated cost with a periodic exact
        # re-evaluation (still on device, amortized over refresh_every steps)
        cost = jax.lax.cond(
            (it + 1) % refresh_every == 0,
            lambda s, c: _full_cost(s, hops_f, e_src, e_dst, e_vol, n),
            lambda s, c: c, slots, cost)
        improved = cost < best_cost
        best_cost = jnp.where(improved, cost, best_cost)
        best_slots = jnp.where(improved[:, None], slots, best_slots)
        t = t * cooling          # unconditional decay (fixed SA schedule)
        ys = (cost, best_cost, t, accept, ~degenerate)
        return (slots, cost, best_slots, best_cost, t), ys

    carry0 = (slots0, cost0, slots0, cost0, t_init)
    # unroll amortizes the per-step dispatch overhead that dominates small
    # [R]-shaped ops on CPU; numerics are identical (same ops, same order)
    (slots, cost, best_slots, best_cost, t), traj = jax.lax.scan(
        step, carry0, (jnp.arange(iters), i_all, j_all, u_all), unroll=8)
    return best_slots, best_cost, traj


def _sa_inputs(graph, noc, iters, t0, t_end_frac, seed, init, restarts,
               t0_spread, use_pallas, refresh_every):
    """``(args, static)`` such that ``_sa_chains(*args, **static)`` is the
    device-SA dispatch of :func:`simulated_annealing_device` — also what a
    caller lowers to inspect or compile that program."""
    rng = np.random.default_rng(seed)
    pool_arr = _pool_array(noc)
    n = graph.n
    base = np.asarray(init if init is not None else zigzag(n, noc), dtype=int)
    validate_placements(noc, base, n)
    free = np.setdiff1d(pool_arr, base)
    slots0 = np.empty((restarts, pool_arr.size), dtype=np.int32)
    slots0[0] = np.concatenate([base, free])
    pool = core_pool(noc)
    for r in range(1, restarts):
        slots0[r] = rng.permutation(pool)

    bn = batched_noc(noc)
    inc = build_incident_tables(graph)
    e_src, e_dst, e_vol, _ = bn.edge_arrays(graph)
    if use_pallas is None:
        use_pallas = _on_tpu() and bn.tables.n_cores <= PALLAS_DELTA_MAX_CORES
    spread = (t0_spread ** (np.arange(restarts) / max(restarts - 1, 1))
              if restarts > 1 else np.ones(1))
    args = (jnp.asarray(slots0), _chain_keys(seed, restarts),
            jnp.asarray(t0 * spread, jnp.float32),
            jnp.float32(t_end_frac ** (1.0 / max(iters, 1))),
            jnp.asarray(inc.other), jnp.asarray(inc.vol, jnp.float32),
            jnp.asarray(inc.is_src),
            jnp.asarray(bn.tables.hops, jnp.float32),
            jnp.asarray(e_src, jnp.int32), jnp.asarray(e_dst, jnp.int32),
            jnp.asarray(e_vol, jnp.float32))
    static = dict(iters=iters, n=n, refresh_every=refresh_every,
                  use_pallas=bool(use_pallas), interpret=not _on_tpu())
    return args, static


def simulated_annealing_device(graph, noc, iters: int = 5000,
                               t0: float = 0.05, t_end_frac: float = 1e-3,
                               seed: int = 0, init=None, restarts: int = 1,
                               t0_spread: float = 1.0,
                               objective="comm_cost", use_pallas=None,
                               refresh_every: int = 256,
                               recorder=None) -> np.ndarray:
    """Device-resident pairwise-swap SA, ``restarts`` parallel chains.

    One compiled dispatch advances all chains ``iters`` steps with O(degree)
    delta costs; the best placement across chains is returned. Chain 0 starts
    from ``init`` (zigzag by default), the others from random injective
    placements — the same multi-start convention as
    :func:`repro.core.placement.population.simulated_annealing_population`.
    ``t0_spread`` stretches the chains' initial temperatures geometrically
    from ``t0`` to ``t0 * t0_spread`` (1.0 = all equal), annealing restarts at
    different aggressiveness for free. ``use_pallas=None`` picks the Pallas
    delta kernel on TPU for fabrics up to :data:`PALLAS_DELTA_MAX_CORES`
    cores and plain jax gathers otherwise (on CPU, interpret-mode Pallas is
    correct but slow); the choice is recorded in ``sa.device``.
    ``recorder`` replays one ``sa.iter`` event per step of the winning chain
    after the dispatch (identical schema to the host SA) plus one
    ``sa.device`` summary — results are bit-identical with
    or without it.
    """
    if restarts < 1:
        raise ValueError(f"restarts must be >= 1, got {restarts}")
    _check_objective(objective)
    args, static = _sa_inputs(graph, noc, iters, t0, t_end_frac, seed, init,
                              restarts, t0_spread, use_pallas, refresh_every)
    best_slots, best_cost, traj = _sa_chains(*args, **static)
    best_cost = np.asarray(best_cost)
    win = int(np.argmin(best_cost))
    if recorder is not None:
        cost_tr, best_tr, t_tr, acc_tr, prop_tr = (
            np.asarray(y) for y in traj)
        for it in range(iters):
            recorder.event("sa.iter", iter=it, cost=float(cost_tr[it, win]),
                           best_cost=float(best_tr[it, win]),
                           temperature=float(t_tr[it, win]),
                           accepted=bool(acc_tr[it, win]),
                           proposed=bool(prop_tr[it, win]))
        n_acc = int(acc_tr[:, win].sum())
        if n_acc:
            recorder.count("sa.accepted", n_acc)
        recorder.event("sa.device", restarts=restarts, iters=iters,
                       best_chain=win, best_cost=float(best_cost[win]),
                       chain_best_mean=float(best_cost.mean()),
                       use_pallas=static["use_pallas"],
                       refresh_every=refresh_every)
    return np.asarray(best_slots)[win, :graph.n].astype(np.int64)


@functools.partial(jax.jit, static_argnames=("seed", "restarts"))
def _chain_keys(seed: int, restarts: int):
    """Per-chain PRNG keys — chain c's stream is independent of ``restarts``.
    Jitted (both args static): the eager vmapped ``fold_in`` costs ~2 ms of
    per-call dispatch otherwise, a third of the whole device-SA wall time."""
    base = jax.random.PRNGKey(seed)
    return jax.vmap(lambda c: jax.random.fold_in(base, c))(
        jnp.arange(restarts))


# ---------------------------------------------------------------------------
# Genetic search: scanned generation loop over a device-resident population
# ---------------------------------------------------------------------------

def _ox_device(key, p1, p2, n_cores: int):
    """Vectorizable OX1 crossover (device transcription of
    ``population._ox_crossover``): keep ``p1[i:j)``, fill the rest with
    ``p2``'s cores in ``p2``'s order starting after the segment, wrapping."""
    S = p1.shape[0]
    ij = jax.random.randint(key, (2,), 0, S + 1)
    i, j = jnp.minimum(ij[0], ij[1]), jnp.maximum(ij[0], ij[1])
    pos = jnp.arange(S)
    in_seg = (pos >= i) & (pos < j)
    member = jnp.zeros(n_cores + 1, bool).at[
        jnp.where(in_seg, p1, n_cores)].set(True)
    take = ~member[p2]                       # p2 cores outside the segment
    dest = (j + jnp.cumsum(take) - 1) % S    # fill order: after segment, wrap
    child = jnp.zeros(S + 1, p1.dtype).at[
        jnp.where(take, dest, S)].set(p2)[:S]
    child = jnp.where(in_seg, p1, child)
    return jnp.where(i == j, p1, child)


def _mutate_device(key, child, rate, kmax: int):
    """Geometric pairwise-swap mutation, truncated at ``kmax`` swaps (the
    host draws a geometric number of swaps, ~1.5 expected at rate 0.6;
    P(>8) < 2%)."""
    ku, kidx = jax.random.split(key)
    gate = jnp.cumprod(
        jax.random.uniform(ku, (kmax,)) < rate)   # 1 while the coin says swap
    idx = jax.random.randint(kidx, (kmax, 2), 0, child.shape[0])

    def body(ch, args):
        g, ij = args
        va, vb = ch[ij[0]], ch[ij[1]]
        ch = (ch.at[ij[0]].set(jnp.where(g > 0, vb, va))
                .at[ij[1]].set(jnp.where(g > 0, va, vb)))
        return ch, None

    child, _ = jax.lax.scan(body, child, (gate, idx))
    return child


@functools.partial(jax.jit, static_argnames=(
    "generations", "n", "n_elite", "tournament", "kmax"))
def _ga_generations(slots0, key, hops_f, e_src, e_dst, e_vol,
                    crossover_rate, mutation_rate, *, generations: int,
                    n: int, n_elite: int, tournament: int, kmax: int):
    P, S = slots0.shape
    C = hops_f.shape[0]
    cost0 = _full_cost(slots0, hops_f, e_src, e_dst, e_vol, n)
    i0 = jnp.argmin(cost0)
    best0 = (slots0[i0], cost0[i0])
    init_stats = (cost0[i0], jnp.mean(cost0),
                  jnp.mean((slots0[:, :n] != slots0[i0, :n]).astype(
                      jnp.float32)))

    def gen_step(carry, _):
        slots, cost, best_slots, best_cost, key = carry
        key, kc, ku, kx, km = jax.random.split(key, 5)
        order = jnp.argsort(cost, stable=True)
        elite = slots[order[:n_elite]]
        n_child = P - n_elite
        cand = jax.random.randint(kc, (n_child, 2, tournament), 0, P)
        win = jnp.take_along_axis(
            cand, jnp.argmin(cost[cand], axis=2)[..., None], axis=2)[..., 0]
        p1, p2 = slots[win[:, 0]], slots[win[:, 1]]
        do_cx = jax.random.uniform(ku, (n_child,)) < crossover_rate
        children = jax.vmap(
            lambda k, a, b: _ox_device(k, a, b, C))(
                jax.random.split(kx, n_child), p1, p2)
        children = jnp.where(do_cx[:, None], children, p1)
        children = jax.vmap(
            lambda k, c: _mutate_device(k, c, mutation_rate, kmax))(
                jax.random.split(km, n_child), children)
        slots = jnp.concatenate([elite, children])
        cost = _full_cost(slots, hops_f, e_src, e_dst, e_vol, n)
        i1 = jnp.argmin(cost)
        improved = cost[i1] < best_cost
        best_cost = jnp.where(improved, cost[i1], best_cost)
        best_slots = jnp.where(improved, slots[i1], best_slots)
        ys = (best_cost, cost[i1], jnp.mean(cost),
              jnp.mean((slots[:, :n] != slots[i1, :n]).astype(jnp.float32)))
        return (slots, cost, best_slots, best_cost, key), ys

    carry0 = (slots0, cost0, best0[0], best0[1], key)
    (_, _, best_slots, best_cost, _), traj = jax.lax.scan(
        gen_step, carry0, None, length=generations)
    return best_slots, best_cost, init_stats, traj


def genetic_device(graph, noc, generations: int = 80, pop_size: int = 64,
                   elite_frac: float = 0.125, tournament: int = 3,
                   crossover_rate: float = 0.9, mutation_rate: float = 0.6,
                   seed: int = 0, init=None, objective="comm_cost",
                   recorder=None) -> np.ndarray:
    """Device-resident evolutionary search: all generations in one dispatch.

    Same operators and hyper-parameters as
    :func:`repro.core.placement.population.genetic_population` (stable-sort
    elitism, tournament selection, OX1 crossover, geometric pairwise-swap
    mutation — truncated at 8 swaps on device), with the whole population
    evolved and scored inside one scanned jit. RNG streams are jax-native, so
    it is a method variant, not a bit-replay of the host GA. ``recorder``
    replays one ``ga.gen`` event per generation (host schema, including the
    initial ``gen=-1``) after the dispatch.
    """
    if pop_size < 2:
        raise ValueError(f"pop_size must be >= 2, got {pop_size}")
    if tournament < 1:
        raise ValueError(f"tournament must be >= 1, got {tournament}")
    _check_objective(objective)
    rng = np.random.default_rng(seed)
    pool_arr = _pool_array(noc)
    n = graph.n

    def full_perm(placement):
        placement = np.asarray(placement, dtype=int)
        free = np.setdiff1d(pool_arr, placement)
        return np.concatenate([placement, free])

    slots0 = np.empty((pop_size, pool_arr.size), dtype=np.int32)
    if init is not None:
        validate_placements(noc, np.asarray(init, dtype=int), n)
        slots0[0] = full_perm(init)
    else:
        slots0[0] = full_perm(zigzag(n, noc))
    slots0[1] = full_perm(sigmate(n, noc))
    pool = core_pool(noc)
    for p in range(2, pop_size):
        slots0[p] = rng.permutation(pool)

    bn = batched_noc(noc)
    e_src, e_dst, e_vol, _ = bn.edge_arrays(graph)
    n_elite = max(1, int(round(elite_frac * pop_size)))
    best_slots, best_cost, init_stats, traj = _ga_generations(
        jnp.asarray(slots0), jax.random.PRNGKey(seed),
        jnp.asarray(bn.tables.hops, jnp.float32),
        jnp.asarray(e_src, jnp.int32), jnp.asarray(e_dst, jnp.int32),
        jnp.asarray(e_vol, jnp.float32),
        jnp.float32(crossover_rate), jnp.float32(mutation_rate),
        generations=generations, n=n, n_elite=n_elite,
        tournament=tournament, kmax=8)
    if recorder is not None:
        c0, mean0, div0 = (float(x) for x in init_stats)
        recorder.event("ga.gen", gen=-1, best_cost=c0, cur_min=c0,
                       cur_mean=mean0, diversity=div0)
        best_tr, min_tr, mean_tr, div_tr = (np.asarray(y) for y in traj)
        for gen in range(generations):
            recorder.event("ga.gen", gen=gen,
                           best_cost=float(best_tr[gen]),
                           cur_min=float(min_tr[gen]),
                           cur_mean=float(mean_tr[gen]),
                           diversity=float(div_tr[gen]))
    return np.asarray(best_slots)[:n].astype(np.int64)
