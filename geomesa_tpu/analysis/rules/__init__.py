"""Rule assembly: the shipped rule set, in deterministic order.

Adding a rule (docs/analysis.md "Adding a rule"): subclass
:class:`geomesa_tpu.analysis.core.Rule` in one of these modules (or a
new one), give it a unique kebab-case ``id``, a one-line
``description`` and a ``fix_hint``, append an instance here, document
the id in docs/analysis.md (tests/test_docs.py enforces that), and add
known-bad/known-good fixtures under tests/fixtures/analysis/.
"""

from geomesa_tpu.analysis.rules.concurrency import (
    BlockingUnderLockRule,
    CheckThenActRule,
    GuardedEscapeRule,
    LockOrderRule,
)
from geomesa_tpu.analysis.rules.faults import FaultPointRule
from geomesa_tpu.analysis.rules.fused import FusedVariantKeyRule
from geomesa_tpu.analysis.rules.kernels import (
    KernelDynamicShapeRule,
    KernelTracedCoercionRule,
    WarmupCoverageRule,
)
from geomesa_tpu.analysis.rules.knobs import (
    DocUnknownNameRule,
    KnobUndeclaredRule,
    KnobUndocumentedRule,
    KnobUnreadRule,
    UserDataUnusedRule,
)
from geomesa_tpu.analysis.rules.locks import LockDisciplineRule
from geomesa_tpu.analysis.rules.metrics import (
    MetricConventionRule,
    MetricTypeConflictRule,
)
from geomesa_tpu.analysis.rules.scripts import ScriptDocstringRule

ALL_RULES = [
    KnobUndeclaredRule(),
    KnobUnreadRule(),
    KnobUndocumentedRule(),
    UserDataUnusedRule(),
    DocUnknownNameRule(),
    MetricConventionRule(),
    MetricTypeConflictRule(),
    FaultPointRule(),
    FusedVariantKeyRule(),
    LockDisciplineRule(),
    LockOrderRule(),
    CheckThenActRule(),
    BlockingUnderLockRule(),
    GuardedEscapeRule(),
    KernelTracedCoercionRule(),
    KernelDynamicShapeRule(),
    WarmupCoverageRule(),
    ScriptDocstringRule(),
]
