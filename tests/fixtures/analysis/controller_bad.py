"""Must-fail fixture for controller-registry (docs/analysis.md).

One spec trips every checked direction at once: a name CONTROLLERS
never registers, a knob conf.py never declares, inverted bounds, and
an objective metric no instrument site emits.
"""

from geomesa_tpu.tuning.controllers import ControllerSpec

BAD = ControllerSpec(
    name="bogus_controller",
    knob="geomesa.bogus.knob",  # lint: ignore[knob-undeclared]
    lo=10.0,
    hi=1.0,
    objective="geomesa.bogus.metric",  # lint: ignore[knob-undeclared]
    objective_kind="counter",
    higher_is_better=True,
    step=0.5,
    policy="hill",
    doc="fixture",
)

# the disciplined twin: registered name, declared knob, ordered literal
# bounds, emitted objective — zero controller-registry findings
GOOD = ControllerSpec(
    name="fold_slice_rows",
    knob="geomesa.stream.fold.slice.rows",
    lo=8192.0,
    hi=262144.0,
    objective="geomesa.stream.fold.slice",
    objective_kind="quantile",
    higher_is_better=False,
    step=0.25,
    policy="hill",
    integral=True,
    doc="fixture twin of the shipped fold-slice controller",
)
