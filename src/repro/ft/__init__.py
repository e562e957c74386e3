from ..sim import cache as _cache
from .failures import FailureInjector, FailureModel
from .watchdog import StepTimeWatchdog, WatchdogConfig
from .elastic import ElasticPlan, plan_reshard, build_mesh, reshard_tree
from .trainer import FaultTolerantTrainer, TrainerConfig
from .tracker import (Tracker, NullTracker, MemoryTracker, StdoutTracker,
                      JsonlTracker, CompositeTracker)
from .run import RunSpec, execute as execute_run

# The train step's programs go to the persistent compile cache too.
_cache.enable_compile_cache()
