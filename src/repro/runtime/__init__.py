from repro.runtime.compile_cache import enable_compile_cache  # noqa: F401
from repro.runtime.watchdog import StragglerWatchdog, StepStats  # noqa: F401
from repro.runtime.elastic import (  # noqa: F401
    ElasticController, ZOElasticController)
from repro.runtime.trace import Recorder  # noqa: F401
