# The paper's primary contribution: GENIE generic inverted-index similarity
# search (match-count model, c-PQ selection, LSH/SA transforms, distributed
# merge).  Engine dispatch lives in the MatchModel registry (core/engines.py);
# query execution is the unified plan->execute pipeline (core/plan.py): every
# search path builds a QueryPlan and delegates to the one executor that calls
# match kernels, pad masks, select_topk, and the merge buffers.
from repro.core import (  # noqa: F401
    cpq, distributed, engines, index, match, merge, plan, postings, routing,
    segments, select, spq,
)
from repro.core.engines import MatchModel  # noqa: F401
from repro.core.index import GenieIndex  # noqa: F401
from repro.core.plan import Layout, QueryPlan, execute, plan_search  # noqa: F401
from repro.core.routing import Router, Routing, SegmentSummary  # noqa: F401
from repro.core.segments import SegmentedIndex  # noqa: F401
from repro.core.select import select_topk  # noqa: F401
from repro.core.types import Engine, SearchParams, TopKMethod, TopKResult  # noqa: F401
