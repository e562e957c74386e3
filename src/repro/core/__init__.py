"""Core: the paper's analytical checkpoint time/energy model."""
from .params import (CheckpointParams, MultilevelCheckpointParams,
                     MultilevelPowerParams, PowerParams,
                     EXASCALE_POWER_RHO55, EXASCALE_POWER_RHO7,
                     EXASCALE_ML_POWER, MU_IND_JAGUAR_MIN,
                     fig12_checkpoint, fig3_checkpoint)
from .failures import (FailureProcess, Exponential, Weibull, LogNormal,
                       TraceReplay, get_process, as_process,
                       level_loss_flags)
from .model import (time_final, time_fault_free, time_lost_per_failure,
                    phase_times, energy_final, energy_breakdown,
                    K_factor, K_dE_dT,
                    ml_time_final, ml_phase_times, ml_energy_final,
                    ml_energy_breakdown, ml_energy_final_prime,
                    ml_K_factor, ml_K_dE_dT)
from .optimal import (t_opt_time, t_opt_time_ex, PeriodResult,
                      t_opt_time_numeric, t_opt_energy,
                      t_opt_energy_numeric, t_young, t_daly, t_msk_energy,
                      energy_quadratic_coefficients,
                      paper_printed_coefficients, period_for, STRATEGIES,
                      golden_section,
                      MCSurrogate, t_opt_time_mc, t_opt_energy_mc,
                      mc_evaluate_periods,
                      t_opt_time_multilevel, t_opt_energy_multilevel,
                      ml_energy_quadratic_coefficients, DEFAULT_M_MAX)
from .tradeoff import (TradeoffPoint, MultilevelTradeoffPoint,
                       RobustnessPoint, evaluate, evaluate_multilevel,
                       evaluate_robustness, sweep_rho, sweep_mu_rho,
                       sweep_nodes, sweep_buddy_ratio)
from .simulator import (simulate, simulate_once, SimResult,
                        simulate_once_ml, MultilevelSimResult)
from .policy import CheckpointPolicy, PolicyConfig
