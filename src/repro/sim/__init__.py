"""Vectorized simulation + sweep subsystem.

Layers:
  scenarios — declarative catalog of platform/power scenarios and the
              struct-of-arrays :class:`ParamGrid` the batched layers consume.
  engine    — the Monte-Carlo trajectory loop as a fixed-shape ``jax.lax.scan``
              phase machine, vmapped over trials and parameter batches.
  sweep     — batched closed-form model + period solvers (AlgoT/AlgoE/Young/
              Daly/MSK) evaluated for a whole grid in a few jitted calls.
  dispatch  — the sharded, memory-bounded execution layer every grid entry
              point routes through: multi-device grid sharding (1-D sweep
              mesh), streaming chunker bounded by a device-memory budget,
              and bounded compiled-runner caches.  All knobs are pure
              performance knobs — a fixed seed's results never change.
  precision — per-backend :class:`PrecisionPolicy` (f64 oracle on CPU,
              compensated f32 on accelerators) with documented parity
              tolerances; resolved per call via ``resolve_precision``.
  cache     — persistent XLA compilation cache (cold-start compile paid
              once per cache directory, not once per process): in
              ``$JAX_COMPILATION_CACHE_DIR`` when set, else in the
              checkout's ``.jax_cache``.

The scalar ``repro.core.simulator.simulate_once`` remains the reference
oracle; ``tests/test_sim_engine.py`` pins the batched engine to it
trajectory-for-trajectory, and ``tests/test_dispatch.py`` pins the
sharded/chunked execution paths to the single-device single-chunk results
bit-for-bit.
"""
from .cache import enable_compile_cache
from .dispatch import (DispatchConfig, default_config, sweep_mesh,
                       cache_stats, reset_cache_stats,
                       BackendInfo, backend_info, resolve_precision)
from .precision import PrecisionPolicy, F64, COMPENSATED_F32
from .scenarios import (ParamGrid, Scenario, MultilevelParamGrid,
                        MultilevelScenario, get_scenario, list_scenarios,
                        register_scenario, mu_rho_grid, nodes_grid,
                        product_grid, arch_grid, grid_from_scenarios,
                        multilevel_grid_from_scenarios, buddy_ratio_grid,
                        multilevel_arch_grid, robustness_grid)
from .engine import (TrajectoryBatch, MultilevelTrajectoryBatch,
                     ScheduledRNG, simulate_trajectories,
                     simulate_candidates, simulate_grid,
                     simulate_trajectories_ml, simulate_grid_ml,
                     presample_gaps, presample_gaps_device,
                     fail_capacity_points, fail_capacity_points_ml,
                     step_budget_points)
from .sweep import (GridResult, MultilevelGridResult, RobustnessResult,
                    evaluate_grid, evaluate_multilevel_grid,
                    evaluate_robustness_grid, evaluate_periods_grid,
                    sweep_weibull_shapes,
                    golden_section_batched,
                    t_opt_time_batched, t_opt_energy_batched,
                    t_young_batched, t_daly_batched, t_msk_energy_batched,
                    time_final_batched, energy_final_batched,
                    ml_time_final_batched, ml_energy_final_batched,
                    sweep_rho_grid, sweep_mu_rho_grid, sweep_nodes_grid)

# Persistent compile cache, on before the first jitted call (sim/cache.py).
enable_compile_cache()
