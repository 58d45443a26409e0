"""Hand-built streaming query plans (the "model zoo" of this framework).

Until the SQL frontend's planner/fragmenter lands, these builders are the
canonical executable plans for the headline Nexmark queries, used by
the e2e tests.
"""

from risingwave_tpu.models.nexmark import (  # noqa: F401
    Pipeline, build_q1, build_q7, build_q8,
)
