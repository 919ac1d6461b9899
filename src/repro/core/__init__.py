"""The paper's contribution: multi-resource GPU/TPU interference
quantification and colocation scheduling. See DESIGN.md §1-2."""
from repro.core.backend import (SOLVER_BACKENDS, get_solver_backend,  # noqa: F401
                                set_solver_backend, solver_backend,
                                warmup_solver)
from repro.core.resources import (DEVICE_KINDS, DEVICES, H100,  # noqa: F401
                                  RTX3090, TPU_V5E, TPU_V5P, DeviceModel,
                                  device_model)
from repro.core.profile import KernelProfile, ProfileMatrix, WorkloadProfile  # noqa: F401
from repro.core.scenario import (CompiledScenarios, Scenario,  # noqa: F401
                                 compile_scenarios, group_victim_scenarios)
from repro.core.estimator import (FRACTION_FLOOR, BatchResult,  # noqa: F401
                                  ColocationResult, colocation_speedup,
                                  estimate, estimate_batch,
                                  pairwise_slowdown, solve_scenarios,
                                  workload_slowdown)
from repro.core.fracsearch import (DENSE_SEARCH, LEGACY_SEARCH,  # noqa: F401
                                   FractionSearchConfig, GroupFractions,
                                   search_group_fractions,
                                   simplex_candidates)
from repro.core.sensitivity import (SensitivityReport, cache_pollution_curve,  # noqa: F401
                                    partition_curve, sensitivity,
                                    sensitivity_batch, stressor)
from repro.core.scheduler import (ColocationScheduler, Plan, Placement,  # noqa: F401
                                  evaluate_group, evaluate_group_partitioned,
                                  evaluate_pair, evaluate_pair_partitioned,
                                  plan_colocation)
from repro.core.repair import (RepairPlanner, RepairRecord,  # noqa: F401
                               RepairResult, RepairScope)
from repro.core.fleet import (BEST_EFFORT, SLO, AdmissionDecision,  # noqa: F401
                              FleetConfig, FleetPlan, FleetScheduler)
