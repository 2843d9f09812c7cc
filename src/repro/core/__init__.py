"""VTA core: the paper's contribution (template, ISA, runtime, simulator,
scheduler, program-level JIT) as a composable package."""
from . import autotune, backend, chaos, compiler, conv, driver  # noqa: F401
from . import hwspec, isa, layout, microop, pipeline_model, program  # noqa: F401
from . import quantize, runtime, sched, scheduler, serve, spans  # noqa: F401
from . import simulator, workloads  # noqa: F401
from .autotune import TuningCache, TuningRecord  # noqa: F401
from .chaos import Fault, FaultPlan  # noqa: F401
from .backend import (CrossBackendChecker, ExecutionBackend,  # noqa: F401
                      PallasBackend, SimulatorBackend, assert_fast_path,
                      decode_cache_info, resolve_backend,
                      set_decode_cache_cap)
from .conv import ConvShape, select_conv_lowering  # noqa: F401
from .hwspec import HardwareSpec, pynq, pynq_batch2, tpu_like  # noqa: F401
from .program import (CompiledProgram, Program, TensorRef,  # noqa: F401
                      compile_multi)
from .runtime import Runtime  # noqa: F401
from .sched import (DeadlineExpired, QueueFull, SchedConfig,  # noqa: F401
                    SchedFuture, Scheduler, Shed, auto_gang_width)
from .scheduler import Epilogue, SramPartition  # noqa: F401
from .serve import (BatchServer, DevicePool, IntegrityError,  # noqa: F401
                    PoolFuture, SessionStats, SlotDied, WaitTimeout,
                    WatchdogConfig, WatchdogTimeout, serve_batch)
