from .chaos import FaultInjector, InjectedFault
from .compile_cache import enable_compile_cache
from .fault import FaultTolerantLoop, StragglerMonitor, ElasticPlan, plan_remesh
