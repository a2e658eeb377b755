"""Self-driving serving: the feedback control plane.

The sensor planes PRs 11-18 built (goodput ledger, reqtrace stage
histograms, admission gauges, speculative accept stats) become ACTUATION
inputs here: a single controller thread reads them, windowed, and drives
three narrow public setters —

  * SLO-aware admission depth overrides (``AdmissionController``);
  * replica drain/undrain/restart (``EngineReplica``);
  * per-replica speculative K / tree-width (``set_spec_params``).

Layering: ``decisions.py`` (the decision log every actuation goes
through) <- ``policies.py`` (sensors in, proposals out) <-
``controller.py`` (the loop, the flap budget, the ONLY sanctioned
actuator call sites). Configured by ``serving.gateway.control``; absent
block = none of these objects exist (the zero-overhead-off contract).
"""

from .controller import ServingController
from .decisions import DecisionLog
from .policies import (AdmissionPolicy, ScalingPolicy, SpeculationPolicy,
                       build_policies)

__all__ = ["ServingController", "DecisionLog", "AdmissionPolicy",
           "ScalingPolicy", "SpeculationPolicy",
           "build_policies"]
