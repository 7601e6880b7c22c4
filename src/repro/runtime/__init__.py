"""Runtime fault tolerance: preemption and stragglers."""
from .ft import PreemptionHandler, StragglerDetector

__all__ = ["PreemptionHandler", "StragglerDetector"]
