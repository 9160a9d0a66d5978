from .modes import get_mode
from .script import Burst, ModeSegment, ScenarioScript, SensorDropout

__all__ = ["Burst", "ModeSegment", "ScenarioScript", "SensorDropout", "get_mode"]
