"""PPO-clip training of the placement policy (paper §4.3 "Weight Update", Eq. 5).

One-shot placement is a contextual bandit: every episode is a single action (a full
placement) followed by the simulator reward (Eq. 4). We therefore use PPO with a
state-value baseline from the critic, advantage normalization, reward scaling against
the Zigzag baseline, and reward clipping to [-10, 10] (paper's setting).

Paper hyperparameters (§5.1): gcn feature size 32, batch 256, lr 0.005,
ppo_epochs 10, clip 0.1–0.5, reward clip [-10, 10]. Defaults below mirror them but are
all overridable; tests use smaller batches.

The pipeline is batched end-to-end: rollouts are drawn by two compiled
programs (`_sample`, bit-exact vs the op-by-op actor forward and sampling),
discretized by the vectorized resolver (`discretize_batch`, bit-exact vs the
sequential spiral), scored in one `noc_batch` call, and all ``ppo_epochs`` inner
epochs run as a single jitted ``lax.scan`` dispatch (`_ppo_update_scan`) with
rollout tensors device-resident.
Benchmarked in ``benchmarks/ppo_pipeline.py``.

``noc`` is any grid :class:`repro.core.topology.Topology` (the continuous
actions discretize onto its ``rows × cols`` cell grid): flat ``NoC`` chips and
multi-chip ``HierarchicalMesh`` systems score through the same batched tables,
and the reward anchor (the Zigzag deployment under ``cfg.objective``) follows
the topology's per-link latency/energy models automatically.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ...obs import maybe_span
from ...train.optim import AdamWConfig, adamw_init, adamw_update
from ..noc_batch import make_scorer
from . import actor_critic as ac
from .discretize_batch import actions_to_placement_batch


@dataclasses.dataclass
class PPOConfig:
    batch_size: int = 256
    lr: float = 5e-3
    ppo_epochs: int = 10
    clip: float = 0.2           # paper reports 0.1 (range) and 0.5 (ppo_clip)
    entropy_coef: float = 1e-3
    reward_clip: float = 10.0
    iterations: int = 60
    d_gcn: int = 32             # paper: GCN feature size 32
    d_fc: int = 64
    freeze_gcn: bool = True     # paper: GCN pre-trained, not updated by PPO
    action_clip: float = 1.0
    seed: int = 0
    backend: str = "batch"      # rollout scoring: "batch"|"jax"|"pallas"|"reference"
    objective: object = "comm_cost"   # repro.deploy.objective spec (name|dict|Objective)
    device_discretize: bool = False   # opt-in jitted lax.scan collision resolver
    # (host float64 binning either way; the device resolver matches the numpy
    #  resolver exactly on integer cells, but stays off by default so the
    #  rollout pipeline of record is the bit-exact host path)


def _freeze_gcn_grads(grads):
    g = dict(grads)
    g["gcn"] = jax.tree_util.tree_map(jnp.zeros_like, grads["gcn"])
    return g


def _ppo_epoch(actor, critic, opt_a, opt_c, lap, feats, acts, logp_old, rewards,
               cfg_clip: float, cfg_ent: float, freeze_gcn: bool,
               adam_a: AdamWConfig, adam_c: AdamWConfig):
    value = ac.critic_apply(critic, lap, feats)
    adv = rewards - value
    adv = (adv - adv.mean()) / (adv.std() + 1e-8)

    def actor_loss(a_params):
        mu, log_std = ac.actor_apply(a_params, lap, feats)
        logp = ac.gaussian_logp(acts, mu, log_std)
        ratio = jnp.exp(logp - logp_old)
        unclipped = ratio * adv
        clipped = jnp.clip(ratio, 1 - cfg_clip, 1 + cfg_clip) * adv
        pg = -jnp.mean(jnp.minimum(unclipped, clipped))
        ent = ac.entropy(log_std)
        return pg - cfg_ent * ent

    def critic_loss(c_params):
        v = ac.critic_apply(c_params, lap, feats)
        return jnp.mean((rewards - v) ** 2)

    la, ga = jax.value_and_grad(actor_loss)(actor)
    if freeze_gcn:
        ga = _freeze_gcn_grads(ga)
    lc, gc = jax.value_and_grad(critic_loss)(critic)
    actor, opt_a = adamw_update(ga, opt_a, actor, adam_a)
    critic, opt_c = adamw_update(gc, opt_c, critic, adam_c)
    return actor, critic, opt_a, opt_c, la, lc


# Single-epoch jit (the seed-era update path; kept for benchmarks and as the
# reference the fused loop is validated against).
_ppo_update = partial(jax.jit, static_argnames=(
    "cfg_clip", "cfg_ent", "freeze_gcn", "adam_a", "adam_c"))(_ppo_epoch)


@partial(jax.jit, static_argnames=("n_epochs", "cfg_clip", "cfg_ent",
                                   "freeze_gcn", "adam_a", "adam_c"))
def _ppo_update_scan(actor, critic, opt_a, opt_c, lap, feats, acts, logp_old,
                     rewards, n_epochs: int, cfg_clip: float, cfg_ent: float,
                     freeze_gcn: bool, adam_a: AdamWConfig,
                     adam_c: AdamWConfig):
    """All ``ppo_epochs`` inner epochs fused into one jitted ``lax.scan`` —
    one dispatch per PPO iteration instead of ``ppo_epochs`` host round-trips.
    Per-epoch math is exactly :func:`_ppo_epoch`."""

    def body(carry, _):
        actor, critic, opt_a, opt_c = carry
        actor, critic, opt_a, opt_c, la, lc = _ppo_epoch(
            actor, critic, opt_a, opt_c, lap, feats, acts, logp_old, rewards,
            cfg_clip, cfg_ent, freeze_gcn, adam_a, adam_c)
        return (actor, critic, opt_a, opt_c), (la, lc)

    # rolled scan (unroll=1): unrolling is ~1.25x faster on CPU but lets XLA
    # fuse across epochs, perturbing last-ulp floats and breaking seed-for-seed
    # trajectory parity with the pre-fusion epoch loop — parity wins
    (actor, critic, opt_a, opt_c), (las, lcs) = jax.lax.scan(
        body, (actor, critic, opt_a, opt_c), None, length=n_epochs)
    return actor, critic, opt_a, opt_c, las[-1], lcs[-1]


@partial(jax.jit, static_argnames=("n_samples",))
def _sample_draw(key, actor, lap, feats, n_samples: int):
    """The sample phase up to the noise, as one program: the key split, the
    actor forward, ``std`` and the normal draw. Returns
    ``(key, mu, log_std, std, eps)``; ``key`` carries to the next iteration.

    The cut is set by float rounding, not by taste: one ulp in an action
    moves the discretized plan, and compiled, each piece here matches the
    eager ops bit for bit on the CPU and the TPU. ``mu + std * eps`` does
    not on the CPU (compiled, alone or fused with the actor, it rounds
    differently from the eager product and sum), so :func:`_sample` forms
    the actions eagerly between this program and :data:`_sample_logp`."""
    key, k_s = jax.random.split(key)
    mu, log_std = ac.actor_apply(actor, lap, feats)
    std = jnp.exp(log_std)
    eps = jax.random.normal(k_s, (n_samples,) + mu.shape)
    return key, mu, log_std, std, eps


#: the log-density of the sampled actions, one program; its ½·log 2π comes
#: in as an argument (see ``ac.gaussian_logp``)
_sample_logp = jax.jit(ac.gaussian_logp)

#: compiled programs :func:`_sample` dispatches (``_sample_draw`` and
#: ``_sample_logp``), counted per iteration in ``ppo.sample.programs``
SAMPLE_PROGRAMS = 2


def _sample(key, actor, lap, feats, n_samples: int, half_log_2pi):
    """One iteration's rollouts: ``(key, acts [B, n, 2], logp [B])``, bit for
    bit the eager split → ``actor_apply`` → ``sample_actions``, given
    ``half_log_2pi = 0.5 * jnp.log(2 * jnp.pi)`` computed op by op."""
    key, mu, log_std, std, eps = _sample_draw(key, actor, lap, feats,
                                              n_samples)
    acts = mu[None] + std[None] * eps         # eager: see _sample_draw
    return key, acts, _sample_logp(acts, mu, log_std, half_log_2pi)


#: the phases of one PPO iteration, each a span; ``PPOState.phases_s`` sums
#: each over the iterations
PPO_PHASES = ("ppo.sample", "ppo.discretize", "ppo.score", "ppo.update")


@dataclasses.dataclass
class PPOState:
    actor: dict
    critic: dict
    opt_a: dict
    opt_c: dict
    history: list
    best_cost: float
    best_placement: np.ndarray
    phases_s: dict = dataclasses.field(default_factory=dict)
    #: work counts of the search (``ppo.sample.programs``)
    counters: dict = dataclasses.field(default_factory=dict)


def run_ppo(graph, noc, cfg: PPOConfig = PPOConfig(), baseline_cost=None,
            priority=None, recorder=None) -> PPOState:
    """Optimize a placement of ``graph`` on ``noc`` with PPO. Returns best found.

    ``recorder`` (a :class:`repro.obs.Recorder`) emits one ``ppo.iter`` event
    per iteration — mean/min rollout cost, best-so-far, and the PPO policy /
    value losses — plus scoring dispatch counters; the training trajectory is
    bit-identical with or without it (no RNG or float path touched).

    Each iteration runs as four spans (:data:`PPO_PHASES`), each ending at a
    host sync the loop needs anyway: ``ppo.sample`` (actor forward and
    sampling, up to the actions on the host), ``ppo.discretize``,
    ``ppo.score`` (host scoring) and ``ppo.update`` (rewards, the fused
    update dispatch, up to the losses on the host). ``phases_s`` of the
    returned state sums each over the iterations; its ``counters`` hold
    ``ppo.sample.programs``, the compiled programs the sample phase
    dispatched (:data:`SAMPLE_PROGRAMS` an iteration), also counted on
    ``recorder``."""
    key = jax.random.PRNGKey(cfg.seed)
    lap = jnp.asarray(graph.laplacian(), jnp.float32)
    feats = jnp.asarray(graph.node_features(), jnp.float32)
    actor, critic = ac.init_actor_critic(key, feats.shape[1], cfg.d_gcn, cfg.d_fc)
    adam = AdamWConfig(lr=cfg.lr)
    opt_a, opt_c = adamw_init(actor, adam), adamw_init(critic, adam)

    if baseline_cost is None:
        from ...deploy.objective import as_objective
        from .baselines import zigzag
        # reward scale is anchored at the Zigzag deployment's score under the
        # *same* objective the rollouts are scored with (for the default
        # comm-cost objective this is bit-identical to the historical
        # noc.evaluate(...).comm_cost anchor)
        baseline_cost = as_objective(cfg.objective).from_metrics(
            noc.evaluate(graph, zigzag(graph.n, noc)), noc)
    baseline_cost = max(baseline_cost, 1e-12)

    score = make_scorer(noc, graph, cfg.backend, cfg.objective,
                        recorder=recorder)
    resolver = None
    if cfg.device_discretize:
        from .discretize_batch import (continuous_to_grid_batch,
                                       make_jax_resolver)
        resolver = make_jax_resolver(noc.rows, noc.cols, priority)
    best_cost, best_placement = np.inf, None
    history = []
    phases = dict.fromkeys(PPO_PHASES, 0.0)
    counters = {"ppo.sample.programs": 0}
    # on the device, as the op-by-op sampling computed it each iteration
    half_log_2pi = 0.5 * jnp.log(2 * jnp.pi)
    for it in range(cfg.iterations):
        with maybe_span(recorder, "ppo.sample") as sp:
            key, acts, logp_old = _sample(key, actor, lap, feats,
                                          cfg.batch_size, half_log_2pi)
            acts_np = np.asarray(acts, np.float64)
            counters["ppo.sample.programs"] += SAMPLE_PROGRAMS
            if recorder is not None:
                recorder.count("ppo.sample.programs", SAMPLE_PROGRAMS)
        phases["ppo.sample"] += sp.duration_s
        with maybe_span(recorder, "ppo.discretize") as sp:
            if resolver is not None:
                cells = continuous_to_grid_batch(acts_np, noc.rows, noc.cols,
                                                 cfg.action_clip)
                placements = np.asarray(resolver(cells), np.int64)
            else:
                placements = actions_to_placement_batch(
                    acts_np, noc.rows, noc.cols, cfg.action_clip, priority)
        phases["ppo.discretize"] += sp.duration_s
        with maybe_span(recorder, "ppo.score") as sp:
            costs = score(placements)    # whole rollout batch in one call
            b_min = int(costs.argmin())
            if costs[b_min] < best_cost:
                best_cost, best_placement = costs[b_min], placements[b_min]
        phases["ppo.score"] += sp.duration_s
        with maybe_span(recorder, "ppo.update") as sp:
            rewards = np.clip(
                cfg.reward_clip * (baseline_cost - costs) / baseline_cost,
                -cfg.reward_clip, cfg.reward_clip)
            rewards = jnp.asarray(rewards, jnp.float32)
            # acts/logp_old/rewards stay device-resident; all ppo_epochs run
            # in one fused dispatch (lax.scan) instead of ppo_epochs
            # round-trips.
            actor, critic, opt_a, opt_c, la, lc = _ppo_update_scan(
                actor, critic, opt_a, opt_c, lap, feats, acts, logp_old,
                rewards, cfg.ppo_epochs, cfg.clip, cfg.entropy_coef,
                cfg.freeze_gcn, adam, adam)
            actor_loss, critic_loss = float(la), float(lc)
        phases["ppo.update"] += sp.duration_s
        history.append({
            "iter": it,
            "mean_cost": float(costs.mean()),
            "min_cost": float(costs[b_min]),
            "best_cost": float(best_cost),
            "actor_loss": actor_loss,
            "critic_loss": critic_loss,
        })
        if recorder is not None:
            recorder.event("ppo.iter", **history[-1])
    return PPOState(actor, critic, opt_a, opt_c, history, float(best_cost),
                    best_placement, phases, counters)
