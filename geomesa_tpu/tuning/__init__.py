"""Self-tuning controller tier (docs/tuning.md): the loop that turns
the store's existing telemetry — estimate-accuracy windows, live
histograms and counters, SLO burn rates — into bounded online
decisions. ``DataStore.attach_tuning()`` is the entry
point; ``geomesa.tuning.enabled`` arms it; disarmed behavior is
bit-identical to a store without this package."""

from geomesa_tpu.tuning.burnshed import BurnShed
from geomesa_tpu.tuning.controllers import (
    CONTROLLER_SPECS,
    ControllerSpec,
    KnobController,
)
from geomesa_tpu.tuning.manager import TuningManager
from geomesa_tpu.tuning.primitives import (
    DEFAULT_ALPHA,
    CostEwma,
    ProbeGate,
    ewma_step,
)
from geomesa_tpu.tuning.reweight import IndexReweighter

__all__ = [
    "BurnShed",
    "CONTROLLER_SPECS",
    "ControllerSpec",
    "CostEwma",
    "DEFAULT_ALPHA",
    "IndexReweighter",
    "KnobController",
    "ProbeGate",
    "TuningManager",
    "ewma_step",
]
