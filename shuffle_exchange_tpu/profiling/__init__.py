"""Profiling: the flops profiler (reference ``profiling/flops_profiler/``) and
the program's tracer (``trace``: host spans, named scopes, step annotations,
compile events; the external-profiler/NVTX analog, SURVEY §5.1)."""

from . import trace  # noqa: F401
from .trace import xla_trace  # noqa: F401
from .flops_profiler import (FlopsProfiler, compiled_flops, count_params,
                             flops_to_string, get_model_profile, number_to_string,
                             params_breakdown, params_to_string)

__all__ = ["FlopsProfiler", "compiled_flops", "count_params", "flops_to_string",
           "get_model_profile", "number_to_string", "params_breakdown",
           "params_to_string", "trace", "xla_trace"]
