"""Host-side utilities (platform selection, timing helpers, fault
injection)."""
from .faults import FaultInjected, fault
from .jaxplatform import force_cpu

__all__ = ["force_cpu", "fault", "FaultInjected"]
