"""Runtime services: checkpointing, profiling, perf DB, memory analysis.

TPU mappings of the reference's aux subsystems (SURVEY.md §5): the C++
CUPTI tracer becomes `jax.profiler` + XLA cost analysis; the custom CUDA
allocator's planning role becomes donation/remat + XLA's allocator; the perf
pickle DB keeps its shape.
"""

from .checkpoint import save_checkpoint, load_checkpoint, latest_step  # noqa: F401
from .perfdb import PerfDB  # noqa: F401
from .profiler import (op_cost_analysis, memory_analysis,  # noqa: F401
                       serving_history, measure_collective_overlap)
from .elastic import run_training, multihost_setup  # noqa: F401
from .data import TokenLoader  # noqa: F401
from .calibrate import (calibrate, apply_calibration,  # noqa: F401
                        apply_device_constants, calibrate_overlap,
                        detect_device_constants)
