"""Unified deployment engine: profile -> partition -> place -> schedule.

``deploy_model`` runs the paper's whole flow in one call and returns a
:class:`DeploymentPlan`; :mod:`repro.deploy.objective` defines the pluggable
multi-objective cost model every placement optimizer scores against
(``objective="comm_cost"`` default, ``"max_link"``, ``"energy"``,
``"latency"``, or weighted combinations). ``python -m repro.deploy`` sweeps
models × methods × objectives from the command line.

Deployment-as-a-service lives on top: :class:`DeployRequest`
(:mod:`repro.deploy.request`) canonicalizes one deployment call into a
hashable, JSON-able value object; :class:`PlanCache` / :class:`PlacementService`
(:mod:`repro.deploy.plancache` / :mod:`repro.deploy.service`) serve cached
plans, warm-start near misses, and run a cold ``execute_request`` for
anything else. ``python -m repro.deploy serve`` runs the HTTP server;
``... request`` is the client.
"""
from .objective import (EnergyModel, MigrationSpec, Objective,  # noqa: F401
                        OBJECTIVES, as_objective, objective_scorer,
                        partition_interchip_bytes, with_migration)
from .engine import (DeploymentPlan, SCHEDULES, deploy_model,  # noqa: F401
                     execute_request, instantiate_plan)
from .request import (DeployRequest, RequestEncodeError,  # noqa: F401
                      topology_from_key)
from .plancache import PlanCache  # noqa: F401
from .service import DeployResponse, PlacementService  # noqa: F401
from .runtime import (Scenario, ScenarioEvent, ScenarioResult,  # noqa: F401
                      parse_scenario, run_scenario)
