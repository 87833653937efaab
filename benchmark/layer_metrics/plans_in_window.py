"""Plan layer: distinct physical plans a statement shape ran under
among the window's statements (the statement roots' ``plan_fp``, grouped
by ``shape_fp``), the largest over shapes.  1 where every template kept
one plan through the window; more says the planner re-ordered a
template between statements - each new plan asks XLA for programs and
changes what the statement costs.  None where the program's roots carry
no plan fingerprint."""

from benchmark.span_facts import window_statements


def read(run):
    statements = window_statements(run)
    if statements is None:
        return None
    plans = {}
    for root, _, _, _ in statements:
        if "plan_fp" in root["attrs"]:
            plans.setdefault(root["attrs"].get("shape_fp"), set()).add(
                root["attrs"]["plan_fp"])
    return max(map(len, plans.values())) if plans else None
