"""Core utilities layer (capability parity with reference ``include/dmlc/``, SURVEY §2.1)."""

from .logging import (  # noqa: F401
    DMLCError, ParamError, IdOverflowError,
    check, check_eq, check_ne, check_lt, check_le, check_gt, check_ge,
    check_notnull, log_info, log_warning, log_error, log_fatal,
    set_log_sink, set_log_context, get_logger, PeriodicLogger,
)
from .registry import Registry, RegistryEntry  # noqa: F401
from .parameter import Parameter, field, FieldEntry, get_env  # noqa: F401
from .config import Config  # noqa: F401
from .threaded_iter import ThreadedIter  # noqa: F401
from .timer import get_time, Timer  # noqa: F401
from . import serializer  # noqa: F401
from .concurrency import (  # noqa: F401
    ConcurrentBlockingQueue, Spinlock, ThreadLocalStore, ObjectPool,
)
from .memory_io import MemoryFixedSizeStream, MemoryStringStream  # noqa: F401
from .common import split, hash_combine, byteswap  # noqa: F401
from .checkpoint import (  # noqa: F401
    Serializable, CheckpointManager, save_pytree, load_pytree, fast_forward,
    load_for_inference,
)
from .orbax_compat import save_orbax, restore_orbax  # noqa: F401
from .metrics import (  # noqa: F401
    Counter, Gauge, Histogram, ThroughputMeter, StageTimer, MetricsRegistry,
    metrics,
)
from .retry import (  # noqa: F401
    Deadline, DeadlineExpired, RetryPolicy, RetriesExhausted,
    CircuitBreaker, CircuitOpen,
)
from .faults import (  # noqa: F401
    FaultInjected, FaultSpecError, fault_point, install_faults,
    clear_faults, inject_faults,
)
from .json import (  # noqa: F401
    JSONReader, JSONWriter, JSONObjectReadHelper, AnyValue,
    register_any_type, read_any, json_dumps, json_loads,
)
